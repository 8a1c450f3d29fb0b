"""Plain reference of one EF-PowerSGD training cell.

The model's loss and gradients, PowerSGD's rank-r compression and the
error-feedback momentum step, written in straightforward ``jax.numpy`` from
the published descriptions (PowerSGD, Vogels et al. 2019, Algorithms 1 and
2).  The model is the configuration's architecture module,
``bench/archs/<arch>.py`` (:class:`Model`); this file knows no layer of it.
It imports nothing of the program under test and takes nothing the program
has made: it draws its own weights and warm-start factors from the seed, by
the same rule the program's ``init_state`` uses (the module's
``init_params``, :func:`init_factors`), and follows the same first steps on
the same batches.

Everything is float32 under ``highest`` matmul precision unless a lower
precision is asked for (the control).  The ``W`` workers of a data mesh
each live on their own device, so the reference fits on the cell's own
chips at the timed sizes.
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Model:
    """A configuration's model: its architecture module and what the
    module's ``from_config`` reads from the configuration file.

    The module gives ``from_config(config)``, ``init_params(key, arch)`` (by
    the program's own init rule), ``loss(params, tokens, labels, arch,
    mask)`` returning ``(total, lm_loss)`` and ``flops_per_token(config,
    seq)``; optionally ``matrix_dims(name, shape)``, where its compressed
    leaves differ from :func:`matrix_dims`, and ``PROGRAM_FIELDS``."""

    module: Any
    arch: Any

    @classmethod
    def of(cls, module, config: dict) -> "Model":
        return cls(module, module.from_config(config))

    def init_params(self, key):
        return self.module.init_params(key, self.arch)

    def loss(self, params, tokens, labels, mask=None):
        return self.module.loss(params, tokens, labels, self.arch, mask)

    def matrix_dims(self, name: str, shape) -> Optional[int]:
        return getattr(self.module, "matrix_dims", matrix_dims)(name, shape)


@dataclasses.dataclass(frozen=True)
class Optim:
    """EF-SGD with post-compression (Nesterov) momentum, coupled weight
    decay on compressed leaves, and a linear warm-up of the learning rate."""

    lr: float
    momentum: float
    weight_decay: float
    warmup_steps: int
    warmup_start_frac: float
    rank: int
    compressor: str            # "powersgd" | "identity"

    @classmethod
    def from_traffic(cls, t: dict) -> "Optim":
        h = t["hyper"]
        return cls(lr=h["lr"], momentum=h["momentum"],
                   weight_decay=h["weight_decay"],
                   warmup_steps=h["warmup_steps"],
                   warmup_start_frac=t["schedule"]["warmup_start_frac"],
                   rank=h["rank"], compressor=t["compressor"])

    def lr_at(self, step: int) -> float:
        frac = min(max(step / max(self.warmup_steps, 1), 0.0), 1.0)
        return self.lr * (self.warmup_start_frac
                          + (1.0 - self.warmup_start_frac) * frac)


# ---------------------------------------------------------------------------
# weights and warm-start factors from the seed
# ---------------------------------------------------------------------------

def path_name(path) -> str:
    return "".join(f"[{k.key!r}]" for k in path)


def matrix_dims(name: str, shape) -> Optional[int]:
    """Leading batch dims of a compressed leaf, or None for a vector.

    Every weight matrix is compressed as the matrix it is (rows = its first
    trailing dim, the rest flattened into columns); the stacked layer and
    expert dims are batch dims; norm scales are vectors and go
    uncompressed (the paper's bias rule)."""
    per_layer = len(shape) - (1 if name.startswith("['blocks']") else 0)
    if per_layer < 2:
        return None
    return len(shape) - 2


def init_factors(key, params, rank: int, dims=matrix_dims):
    """PowerSGD's warm-start Q per leaf that ``dims`` compresses: i.i.d.
    N(0, 1), keyed by the leaf's tree path."""

    def leaf(path, p):
        name = path_name(path)
        b = dims(name, p.shape)
        if b is None:
            return None
        h = hashlib.sha256(name.encode()).digest()
        k = jax.random.fold_in(key, int.from_bytes(h[:4], "little"))
        return jax.random.normal(k, p.shape[:b] + (p.shape[-1], rank),
                                 jnp.float32)

    return jax.tree_util.tree_map_with_path(leaf, params)


def init_state(key, model: Model, rank: int):
    kp, kc = jax.random.split(key)
    params = model.init_params(kp)
    return params, init_factors(kc, params, rank, model.matrix_dims)


# ---------------------------------------------------------------------------
# EF-PowerSGD
# ---------------------------------------------------------------------------

def _t(x):
    return jnp.swapaxes(x, -1, -2)


def powersgd(m, q):
    """One warm-started power-iteration step on a (..., n, m) matrix:
    P = M Q, P̂ = an orthonormal basis of P's columns, Q' = Mᵀ P̂; the
    aggregate is P̂ Q'ᵀ (the projection of M onto span P)."""
    p_hat, _ = jnp.linalg.qr((m @ q).astype(jnp.float32))
    p_hat = p_hat.astype(m.dtype)
    q_new = _t(m) @ p_hat
    return p_hat @ _t(q_new), q_new


@dataclasses.dataclass
class Readings:
    """What a check compares: the loss of each step (mean over workers), the
    norm of each leaf of the first gradient as the optimizer gets it (mean
    over workers, weight decay included), and the norm of each leaf's change
    over the steps."""

    losses: list
    grad_norms: dict
    change_norms: dict


def leaf_norms(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {path_name(p): float(n) for (p, _), n in zip(flat, norms)}


FAULTS = ("half_batch", "no_exchange", "double_head")


def _unzip(tree):
    is_pair = lambda x: isinstance(x, tuple)
    return (jax.tree_util.tree_map(lambda t: t[0], tree, is_leaf=is_pair),
            jax.tree_util.tree_map(lambda t: t[1], tree, is_leaf=is_pair))


def train(model: Model, o: Optim, key, batches: Sequence[Sequence], devices,
          *, dtype=jnp.float32, precision: str = "highest",
          fault: Optional[str] = None) -> Readings:
    """Follow ``len(batches)`` EF-SGD steps from the seed's initial state.

    ``batches[t][w]`` is worker ``w``'s ``(tokens, labels)`` at step ``t``
    (numpy, ``(b, S)``); worker ``w`` runs on ``devices[w]``.  ``dtype`` /
    ``precision`` select the control (bfloat16, default precision).
    ``fault`` plants one of :data:`FAULTS` where the step produces it: half
    of each worker's tokens left out of its loss; no exchange (each worker
    compresses and applies its own update; worker 0 is read); the
    gradient of the top-level leaf ``head`` (the output projection, which
    every architecture module names so) counted twice."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    workers = len(batches[0])
    devs = list(devices)[:workers]
    if len(devs) < workers:
        raise ValueError(f"{workers} workers need {workers} devices")
    is_none = lambda x: x is None

    with jax.default_matmul_precision(precision):
        def start(k):
            params, factors = init_state(k, model, o.rank)
            return jax.tree_util.tree_map(lambda x: x.astype(dtype),
                                          params), factors

        start = jax.jit(start)
        key = jax.device_put(key, devs[0])
        params, factors = start(key)
        compressed = jax.tree_util.tree_map_with_path(
            lambda p, x: model.matrix_dims(path_name(p), x.shape) is not None,
            params)

        @jax.jit
        def grad_fn(p, tokens, labels, mask):
            (_, lm), g = jax.value_and_grad(
                lambda p_: model.loss(p_, tokens, labels, mask),
                has_aux=True)(p)
            return lm, g

        def token_mask(shape):
            n = math.prod(shape)
            keep = n // 2 if fault == "half_batch" else n
            return (np.arange(n) < keep).reshape(shape).astype(np.float32)

        @jax.jit
        def delta_fn(g, p, e):
            if fault == "double_head":
                g = dict(g, head=2 * g["head"])
            # Δ_w = g_w (+ λ·x on compressed leaves) + e_w
            d = jax.tree_util.tree_map(
                lambda gl, pl, c: gl + o.weight_decay * pl if c else gl,
                g, p, compressed)
            return d if e is None else jax.tree_util.tree_map(jnp.add, d, e)

        @jax.jit
        def compress_fn(mbar, q):
            if o.compressor == "identity":
                return mbar, q
            return _unzip(jax.tree_util.tree_map(
                lambda qq, m: (m, None) if qq is None
                else powersgd(m, qq.astype(m.dtype)),
                q, mbar, is_leaf=is_none))

        @jax.jit
        def error_fn(delta, agg):
            # e_w = Δ_w − recon; vectors are sent whole, so their error is 0
            return jax.tree_util.tree_map(
                lambda d, g, c: d - g if c else jnp.zeros_like(d),
                delta, agg, compressed)

        @jax.jit
        def apply_fn(p, m, agg, lr):
            # m ← μ m + Δ';  x ← x − γ (Δ' + m)
            m = jax.tree_util.tree_map(lambda mm, g: o.momentum * mm + g,
                                       m, agg)
            p = jax.tree_util.tree_map(
                lambda x, g, mm: x - lr.astype(x.dtype) * (g + mm), p, agg, m)
            return p, m

        tree_add = lambda x, y: jax.tree_util.tree_map(jnp.add, x, y)
        first_add = jax.jit(tree_add)
        add = jax.jit(tree_add, donate_argnums=0)
        scale = jax.jit(lambda x, c: jax.tree_util.tree_map(
            lambda v: (v * c).astype(v.dtype), x))

        def mean_on_first(trees):
            if workers == 1:
                return trees[0]
            acc = first_add(trees[0], jax.device_put(trees[1], devs[0]))
            for t in trees[2:]:
                acc = add(acc, jax.device_put(t, devs[0]))
            return scale(acc, 1.0 / workers)

        theta = [params] + [jax.device_put(params, d) for d in devs[1:]]
        mom = [jax.tree_util.tree_map(jnp.zeros_like, t) for t in theta]
        err = [None] * workers
        qs = ([jax.device_put(factors, d) for d in devs]
              if fault == "no_exchange" else [factors])
        losses, grad_norms = [], None
        for step, batch in enumerate(batches):
            deltas, lms = [], []
            for w, (tokens, labels) in enumerate(batch):
                lm, g = grad_fn(theta[w], *jax.device_put(
                    (tokens, labels, token_mask(tokens.shape)), devs[w]))
                deltas.append(delta_fn(g, theta[w], err[w]))
                lms.append(lm)
                err[w] = None
                del g
            losses.append(float(np.mean([float(x) for x in lms])))
            lr = jnp.float32(o.lr_at(step))
            if step == 0 and fault != "no_exchange":
                grad_norms = leaf_norms(mean_on_first(deltas))
            if fault == "no_exchange":
                aggs = []
                for w in range(workers):
                    agg, qs[w] = compress_fn(deltas[w], qs[w])
                    aggs.append(agg)
            else:
                agg, qs[0] = compress_fn(mean_on_first(deltas), qs[0])
                aggs = [agg] + [jax.device_put(agg, d) for d in devs[1:]]
            for w in range(workers):
                err[w] = error_fn(deltas[w], aggs[w])
                theta[w], mom[w] = apply_fn(theta[w], mom[w], aggs[w], lr)
            if step == 0 and fault == "no_exchange":
                # what the program's state then says: mean error + momentum
                grad_norms = leaf_norms(first_add(mean_on_first(err), mom[0]))
            del deltas, aggs, agg
        final = theta[0]
        del theta, mom, err, qs
        theta0, _ = start(key)
        change = jax.jit(lambda x, y: jax.tree_util.tree_map(
            lambda u, v: u.astype(jnp.float32) - v.astype(jnp.float32),
            x, y))(final, theta0)
        del final, theta0
        change_norms = leaf_norms(change)
    return Readings(losses=losses, grad_norms=grad_norms,
                    change_norms=change_norms)
