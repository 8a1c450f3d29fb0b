"""Plain reference of one EF-PowerSGD training cell.

The decoder's loss and gradients, PowerSGD's rank-r compression and the
error-feedback momentum step, written in straightforward ``jax.numpy`` from
the published descriptions (Qwen3 / OLMoE decoder blocks; PowerSGD,
Vogels et al. 2019, Algorithms 1 and 2).  It imports nothing of the program
under test and takes nothing the program has made: it draws its own
weights and warm-start factors from the seed, by the same rule the
program's ``init_state`` uses (``init_params`` / ``init_factors``), and
follows the same first steps on the same batches.

Everything is float32 under ``highest`` matmul precision unless a lower
precision is asked for (the control).  Memory: every layer and every
attention head group is rematerialized, experts run in blocks, and the
``W`` workers of a data mesh each live on their own device, so the
reference fits on the cell's own chips at the timed sizes.
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 8        # experts evaluated together in the dense MoE
#                         (the expert count is a multiple of it)
NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class Arch:
    """The decoder, as the configuration file states it (as run)."""

    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    layers: int
    vocab: int
    rope_theta: float
    rms_eps: float
    qk_norm: bool
    experts: int = 0
    top_k: int = 0
    norm_topk: bool = True
    capacity_factor: float = 0.0
    aux_weight: float = 0.0

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        return cls(d_model=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], layers=c["num_hidden_layers"],
                   vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
                   rms_eps=float(c["rms_norm_eps"]), qk_norm=bool(c["qk_norm"]),
                   experts=c.get("num_experts", 0),
                   top_k=c.get("num_experts_per_tok", 0),
                   norm_topk=bool(c.get("norm_topk_prob", True)),
                   capacity_factor=float(c.get("capacity_factor", 0.0)),
                   aux_weight=float(c.get("router_aux_loss_coef", 0.0)))


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

def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _layer(key, a: Arch):
    (k_slot,) = jax.random.split(key, 1)
    km, kf = jax.random.split(k_slot)
    kq, kk, kv, ko = jax.random.split(km, 4)
    d, hd = a.d_model, a.head_dim
    mixer = {
        "wq": _normal(kq, (d, a.heads * hd), 1 / math.sqrt(d)),
        "wk": _normal(kk, (d, a.kv_heads * hd), 1 / math.sqrt(d)),
        "wv": _normal(kv, (d, a.kv_heads * hd), 1 / math.sqrt(d)),
        "wo": _normal(ko, (a.heads * hd, d), 1 / math.sqrt(a.heads * hd)),
    }
    if a.qk_norm:
        mixer["q_norm"] = jnp.ones((hd,), jnp.float32)
        mixer["k_norm"] = jnp.ones((hd,), jnp.float32)
    if a.experts:
        kr, kg, ku, kd = jax.random.split(kf, 4)
        e = a.experts
        ffn = {"router": _normal(kr, (d, e), 1 / math.sqrt(d)),
               "w_gate": _normal(kg, (e, d, a.d_ff), 1 / math.sqrt(d)),
               "w_up": _normal(ku, (e, d, a.d_ff), 1 / math.sqrt(d)),
               "w_down": _normal(kd, (e, a.d_ff, d), 1 / math.sqrt(a.d_ff))}
    else:
        kg, ku, kd = jax.random.split(kf, 3)
        ffn = {"w_gate": _normal(kg, (d, a.d_ff), 1 / math.sqrt(d)),
               "w_up": _normal(ku, (d, a.d_ff), 1 / math.sqrt(d)),
               "w_down": _normal(kd, (a.d_ff, d), 1 / math.sqrt(a.d_ff))}
    return {"ffn": ffn, "mixer": mixer,
            "norm1": jnp.ones((d,), jnp.float32),
            "norm2": jnp.ones((d,), jnp.float32)}


def init_params(key, a: Arch):
    """Weights: N(0, 1/fan_in) matrices, N(0, 0.02²) embedding, unit norm
    scales; the key is split embed / blocks / head, and blocks per layer."""
    ke, kb, kh, _ = jax.random.split(key, 4)
    layers = [_layer(k, a) for k in jax.random.split(kb, a.layers)]
    blocks = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    return {"blocks": {"slot0": blocks},
            "embed": _normal(ke, (a.vocab, a.d_model), 0.02),
            "final_norm": jnp.ones((a.d_model,), jnp.float32),
            "head": _normal(kh, (a.d_model, a.vocab), 1 / math.sqrt(a.d_model))}


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


def init_factors(key, params, rank: int):
    """PowerSGD's warm-start Q per compressed leaf: i.i.d. N(0, 1), keyed by
    the leaf's tree path."""

    def leaf(path, p):
        name = path_name(path)
        b = matrix_dims(name, p.shape)
        if b is None:
            return None
        h = hashlib.sha256(name.encode()).digest()
        k = jax.random.fold_in(key, int.from_bytes(h[:4], "little"))
        return jax.random.normal(k, p.shape[:b] + (p.shape[-1], rank),
                                 jnp.float32)

    return jax.tree_util.tree_map_with_path(leaf, params)


def init_state(key, a: Arch, rank: int):
    kp, kc = jax.random.split(key)
    params = init_params(kp, a)
    return params, init_factors(kc, params, rank)


# ---------------------------------------------------------------------------
# the decoder
# ---------------------------------------------------------------------------

def rmsnorm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope(x, theta):
    """Rotary embedding, rotate-half form; x: (B, S, heads, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _group_attention(q, k, v):
    """Causal softmax attention of one kv head's group of q heads.
    q: (B, S, G, hd); k, v: (B, S, hd)."""
    s = q.shape[1]
    scores = jnp.einsum("bqgd,bkd->bgqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bgqk,bkd->bqgd", probs, v)


def attention(p, z, a: Arch):
    b, s, _ = z.shape
    q = (z @ p["wq"]).reshape(b, s, a.heads, a.head_dim)
    k = (z @ p["wk"]).reshape(b, s, a.kv_heads, a.head_dim)
    v = (z @ p["wv"]).reshape(b, s, a.kv_heads, a.head_dim)
    if a.qk_norm:
        q = rmsnorm(q, p["q_norm"], a.rms_eps)
        k = rmsnorm(k, p["k_norm"], a.rms_eps)
    q, k = rope(q, a.rope_theta), rope(k, a.rope_theta)
    g = a.heads // a.kv_heads
    outs = [jax.checkpoint(_group_attention)(q[:, :, i * g:(i + 1) * g],
                                             k[:, :, i], v[:, :, i])
            for i in range(a.kv_heads)]
    out = jnp.concatenate(outs, axis=2).reshape(b, s, a.heads * a.head_dim)
    return out @ p["wo"]


def mlp(p, z):
    return (jax.nn.silu(z @ p["w_gate"]) * (z @ p["w_up"])) @ p["w_down"]


def _expert_block(x, wg, wu, wd, weights):
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, wg))
    h = h * jnp.einsum("td,edf->etf", x, wu)
    y = jnp.einsum("etf,efd->etd", h, wd)
    return jnp.einsum("te,etd->td", weights, y)


def moe(p, z, a: Arch):
    """Top-k routed experts with per-expert capacity.

    Gates are the softmax probabilities of the k chosen experts (rescaled
    to sum to one where ``norm_topk``).  Each expert takes at most
    C = max(8, min(ceil(T·k/E·cf), T)) of its (token, choice) pairs, in
    token order; the rest are dropped and pass through the residual.  The
    load-balance loss is E·Σ_e f_e·p_e.  Experts are evaluated densely on
    every token and weighted by the (mostly zero) combine matrix."""
    b, s, d = z.shape
    t, e, k = b * s, a.experts, a.top_k
    x = z.reshape(t, d)
    logits = (x @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, chosen = jax.lax.top_k(probs, k)
    if a.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    flat = chosen.reshape(-1)
    onehot = jax.nn.one_hot(flat, e, dtype=jnp.int32)
    counts = onehot.sum(0)
    aux = e * jnp.sum(probs.mean(0) * counts / (t * k))
    cap = max(8, min(int(math.ceil(t * k / e * a.capacity_factor)), t))
    before = (jnp.cumsum(onehot, axis=0) - onehot)[jnp.arange(t * k), flat]
    keep = before < cap
    token = jnp.repeat(jnp.arange(t), k)
    combine = jnp.zeros((t, e), jnp.float32).at[token, flat].add(
        jnp.where(keep, gates.reshape(-1), 0.0)).astype(x.dtype)

    nb = e // EXPERT_BLOCK
    blocks = lambda w: w.reshape((nb, EXPERT_BLOCK) + w.shape[1:])

    def body(acc, blk):
        return acc + _expert_block(x, *blk), None

    out, _ = jax.lax.scan(
        jax.checkpoint(body), jnp.zeros((t, d), x.dtype),
        (blocks(p["w_gate"]), blocks(p["w_up"]), blocks(p["w_down"]),
         combine.reshape(t, nb, EXPERT_BLOCK).transpose(1, 0, 2)))
    return out.reshape(b, s, d), aux


def _layer_forward(h, lp, a: Arch):
    h = h + attention(lp["mixer"], rmsnorm(h, lp["norm1"], a.rms_eps), a)
    z = rmsnorm(h, lp["norm2"], a.rms_eps)
    if a.experts:
        y, aux = moe(lp["ffn"], z, a)
    else:
        y, aux = mlp(lp["ffn"], z), jnp.zeros((), jnp.float32)
    return h + y, aux


def loss(params, tokens, labels, a: Arch, mask=None):
    """Mean next-token cross-entropy over the tokens ``mask`` keeps (all by
    default), plus the weighted load-balance loss.  Returns (total, lm_loss)."""
    h = params["embed"][tokens]
    aux_total = jnp.zeros((), jnp.float32)
    blocks = params["blocks"]["slot0"]
    for i in range(a.layers):
        lp = jax.tree_util.tree_map(lambda x: x[i], blocks)
        h, aux = jax.checkpoint(_layer_forward, static_argnums=2)(h, lp, a)
        aux_total = aux_total + aux
    h = rmsnorm(h, params["final_norm"], a.rms_eps)
    logits = (h @ params["head"]).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = jnp.ones(labels.shape, jnp.float32) if mask is None else mask
    lm = jnp.sum((lse - picked) * mask) / jnp.sum(mask)
    return lm + a.aux_weight * aux_total, lm


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


def train(a: Arch, o: Optim, key, batches: Sequence[Sequence], devices,
          *, dtype=jnp.float32, precision: str = "highest",
          fault: Optional[str] = None) -> Readings:
    """Follow ``len(batches)`` EF-SGD steps from the seed's initial state.

    ``batches[t][w]`` is worker ``w``'s ``(tokens, labels)`` at step ``t``
    (numpy, ``(b, S)``); worker ``w`` runs on ``devices[w]``.  ``dtype`` /
    ``precision`` select the control (bfloat16, default precision).
    ``fault`` plants one of :data:`FAULTS` where the step produces it: half
    of each worker's tokens left out of its loss; no exchange (each worker
    compresses and applies its own update; worker 0 is read); the head's
    gradient counted twice."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    workers = len(batches[0])
    devs = list(devices)[:workers]
    if len(devs) < workers:
        raise ValueError(f"{workers} workers need {workers} devices")
    is_none = lambda x: x is None

    with jax.default_matmul_precision(precision):
        def start(k):
            params, factors = init_state(k, a, o.rank)
            return jax.tree_util.tree_map(lambda x: x.astype(dtype),
                                          params), factors

        start = jax.jit(start)
        key = jax.device_put(key, devs[0])
        params, factors = start(key)
        compressed = jax.tree_util.tree_map_with_path(
            lambda p, x: matrix_dims(path_name(p), x.shape) is not None,
            params)

        @jax.jit
        def grad_fn(p, tokens, labels, mask):
            (_, lm), g = jax.value_and_grad(
                lambda p_: loss(p_, tokens, labels, a, mask),
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
