"""Distributed error-feedback SGD with post-compression momentum (Alg. 2).

    Δ_w   ← g_w + e_w                      (feedback)
    C(Δ)  ← compress+aggregate(Δ_1..Δ_W)   (the compressor's job)
    e_w   ← Δ_w − recon                    (memorize local error)
    Δ'    ← decompress(C(Δ))
    m     ← λ m + Δ'
    x     ← x − γ (Δ' + m)

``start_compress_step`` delays compression, as in the PyTorch DDP PowerSGD
hook: for the first k steps the deltas are aggregated *dense* (one fused
flat all-reduce through the transport engine) and the reconstruction is the
delta itself, so the error buffers stay exactly zero and the trajectory is
bit-identical to the identity compressor's.  Compression — and error
feedback — kick in at step k against gradients whose statistics have
stabilised, which is what makes warm-started low-rank compression safe at
the very start of training.

The error buffer ``e_w`` is per-worker state: in the distributed train step it
is carried with a leading data-parallel dim sharded over the data axes, so
each rank owns a distinct buffer.  This module itself is shape-agnostic — it
operates on whatever (local) tree it is given.  Under the in-process
W-worker simulator (:mod:`repro.core.simmesh`, ``make_sim_train_step``) the
same code runs per logical worker under ``vmap``: ``e_w`` carries a stacked
leading worker dim and the compressor's collectives become exact means over
it.  A worker dropped from a round (scenario weight 0) still updates its
error from its own ``Δ_w`` as usual (against the round's reconstruction:
the worker's own back-projection under ``error_mode="local"``, the
aggregated one under the default ``"global"``) — Algorithm 2's per-worker
state is local by construction, only the aggregation is weighted.

Weight decay follows the paper's recipe (§5): coupled, added to the gradient
*before* compression, and disabled for uncompressed (norm/bias) parameters.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import matrixize, scopes
from repro.core.compressors import Compressor
from repro.core.dist import MeshCtx, SINGLE


@dataclasses.dataclass
class EFState:
    """The optimizer's full cross-step state.

    Every field is *algorithm state* in the fault-tolerance sense — the
    trajectory is a function of all four, so a checkpoint that drops any of
    them does not resume the same algorithm: zeroed ``error`` discards the
    compression error Algorithm 1's EF loop was about to feed back, and a
    re-randomized ``comp`` restarts the warm-start power iteration from
    scratch (§3 ablation).  ``repro.checkpoint.train_state`` serializes the
    whole thing; the measured cost of dropping each piece is in
    ``docs/paper_map.md`` (resume design note).
    """

    error: Any        # per-worker error buffers e_w (tree like params)
    momentum: Any     # post-compression momentum m (tree like params)
    comp: Any         # compressor state (e.g. PowerSGD Q factors)
    step: jax.Array   # int32 step counter
    # One-step-stale pipeline only (``staleness="one_step"``): the aggregated
    # update Δ'_{t-1} produced at the previous step but not yet applied —
    # the in-flight half of the double-buffered schedule.  ``None`` under the
    # synchronous default, so existing 4-field constructions keep their exact
    # tree structure and numerics.
    inflight: Any = None


jax.tree_util.register_dataclass(
    EFState, data_fields=["error", "momentum", "comp", "step", "inflight"],
    meta_fields=[])


def init_state(compressor: Compressor, params, specs, key: jax.Array,
               *, staleness: str = "none") -> EFState:
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    shapes = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params)
    return EFState(
        error=zeros,
        momentum=jax.tree_util.tree_map(jnp.zeros_like, params),
        comp=compressor.init(shapes, specs, key),
        step=jnp.zeros((), jnp.int32),
        inflight=(jax.tree_util.tree_map(jnp.zeros_like, params)
                  if staleness == "one_step" else None),
    )


def rescale_path(w_old: int, w_new: int) -> str:
    """Which :func:`rescale_error_buffers` branch a ``w_old → w_new``
    rescale takes: ``"identity"`` / ``"grow"`` / ``"shrink"`` /
    ``"coprime-mean"``.  Pure — the checkpoint layer records it into the
    restore ``meta`` (``meta["ef_rescale"]``) so post-resume trajectory
    deltas are attributable to the rescale semantics actually applied."""
    if w_new == w_old:
        return "identity"
    if w_new % w_old == 0:
        return "grow"
    if w_old % w_new == 0:
        return "shrink"
    return "coprime-mean"


def rescale_error_buffers(error, workers: int):
    """Re-shard a stacked per-worker error-buffer tree to a new worker count.

    ``error`` carries a leading worker dim ``W_old`` on every leaf (the
    SimMesh stacked layout, or the distributed step's global
    ``(dp_total, ...)`` buffers pulled to host).  The elastic-resume
    contract is about the quantity Algorithm 2 actually aggregates — the
    *worker-mean* of ``Δ_w = g_w + e_w`` — so the rescale preserves the
    worker-mean of the buffers (Lemma 3's linearity then carries the
    trajectory):

    * ``W_new == W_old`` — identity, bit-exact.
    * ``W_new % W_old == 0`` (grow, e.g. 1→4): each original buffer is
      duplicated to its ``W_new/W_old`` successor workers.  Every new
      buffer equals an original bit-exactly, and the worker-mean is the
      original multiset mean unchanged.
    * ``W_old % W_new == 0`` (shrink, e.g. 4→1): each new buffer is the
      mean of the ``W_old/W_new`` buffers it absorbs — the global mean is
      preserved up to one float32 reassociation.
    * otherwise: every new buffer is the global worker-mean (the documented
      fallback for coprime rescales).

    Only the *mean* is an invariant: per-worker identity is necessarily
    lost when W changes, so a rescaled resume is trajectory-preserving in
    the Lemma-3 sense, not bit-exact (``tests/sim/test_resume.py`` pins
    both sides of that line).
    """
    leaves = jax.tree_util.tree_leaves(error)
    if not leaves:
        return error
    w_old = leaves[0].shape[0]
    for l in leaves:
        assert l.shape[0] == w_old, (l.shape, w_old)
    path = rescale_path(w_old, workers)
    if path == "identity":
        return error
    if path == "coprime-mean":
        warnings.warn(
            f"coprime EF rescale {w_old} -> {workers}: every new buffer is "
            f"the global worker-mean (per-worker identity lost; mean "
            f"preserved)", stacklevel=2)

    def leaf(e):
        if path == "grow":
            return jnp.repeat(e, workers // w_old, axis=0)
        if path == "shrink":
            k = w_old // workers
            return jnp.mean(e.reshape((workers, k) + e.shape[1:]), axis=1)
        mean = jnp.mean(e, axis=0, keepdims=True)
        return jnp.broadcast_to(mean, (workers,) + e.shape[1:])

    return jax.tree_util.tree_map(leaf, error)


def replace_comp(state: EFState, comp) -> EFState:
    """``state`` with a new compressor state — the rank-transition hook.

    A :class:`~repro.core.powersgd.RankSchedule` switch replaces only the
    warm-start factors; error buffers, momentum and the step counter pass
    through bit-exactly (``tests/sim/test_rank_transitions.py`` pins this)."""
    return EFState(error=state.error, momentum=state.momentum, comp=comp,
                   step=state.step, inflight=state.inflight)


@scopes.scoped(scopes.EF_APPLY)
def apply_updates(
    compressor: Compressor,
    params,
    grads,                      # per-worker local gradients g_w
    state: EFState,
    specs,
    *,
    lr,                         # scalar or traced schedule value
    momentum: float = 0.9,
    weight_decay: float = 0.0,
    ctx: MeshCtx = SINGLE,
    key: Optional[jax.Array] = None,
    use_pallas_apply: bool = False,
    start_compress_step: int = 0,
    staleness: str = "none",
):
    """One EF-SGD step.  Returns (new_params, new_state, aux).

    ``start_compress_step=k`` aggregates the first k steps dense (see module
    docstring); with the default 0 every step compresses.

    ``staleness="one_step"`` turns on the delayed-parameter-update pipeline
    (the DPU/ACCO pattern): the update *applied* at step t is the aggregate
    Δ'_{t-1} carried in ``state.inflight``, and this step's fresh aggregate
    Δ'_t is parked as the next ``inflight`` — so the fused collectives that
    produce Δ'_t never sit between the gradient computation and the
    parameter write of the same step.  The error buffers are untouched by
    the delay: ``e_w = Δ_w − recon_t`` memorizes exactly what step t's
    compression dropped, regardless of *when* the aggregate is applied, so
    Alg. 2's EF guarantee absorbs the one-step shift like any other bounded
    perturbation.  Step 0 applies the zero aggregate (the pipeline bubble).
    ``state.inflight`` must be a params-shaped tree (see :func:`init_state`).
    """
    if staleness not in ("none", "one_step"):
        raise ValueError(f"unknown staleness mode {staleness!r}")
    if staleness == "one_step" and state.inflight is None:
        raise ValueError(
            "staleness='one_step' needs EFState.inflight initialized "
            "(init_state(..., staleness='one_step'))")
    if key is not None:
        key = jax.random.fold_in(key, state.step)

    if weight_decay:
        def add_wd(g, p, spec):
            return g + weight_decay * p if spec.is_compressed() else g
        grads = jax.tree_util.tree_map(add_wd, grads, params, specs)

    # Δ_w = g_w + e_w
    deltas = jax.tree_util.tree_map(jnp.add, grads, state.error)

    with jax.named_scope(scopes.COMPRESS):
        if start_compress_step:
            out = _warmup_or_compress(compressor, deltas, state.comp, specs,
                                      ctx, key, state.step,
                                      start_compress_step)
        else:
            out = compressor.step(deltas, state.comp, specs, ctx=ctx,
                                  key=key)

    # e_w = Δ_w − recon
    new_error = jax.tree_util.tree_map(jnp.subtract, deltas, out.recon)

    # Synchronous: apply this step's aggregate.  One-step-stale: apply the
    # in-flight aggregate from step t−1 and park this step's for step t+1.
    if staleness == "one_step":
        applied, new_inflight = state.inflight, out.agg
    else:
        applied, new_inflight = out.agg, state.inflight

    if use_pallas_apply:
        from repro.kernels import ops

        new_params, new_momentum = ops.ef_apply_tree(
            params, applied, state.momentum, lr=lr, momentum=momentum)
    else:
        # m ← λ m + Δ' ;  x ← x − γ (Δ' + m)
        new_momentum = jax.tree_util.tree_map(
            lambda m, d: momentum * m + d, state.momentum, applied)
        new_params = jax.tree_util.tree_map(
            lambda x, d, m: x - lr * (d + m), params, applied, new_momentum)

    new_state = EFState(
        error=new_error,
        momentum=new_momentum,
        comp=out.state,
        step=state.step + 1,
        inflight=new_inflight,
    )
    aux = {"bits_per_worker": out.bits_per_worker}
    if getattr(out, "metrics", None):
        # compressor observability (e.g. PowerSGD residual-energy ratios
        # when track_residual is on) — host-side RankControllers read these
        aux.update(out.metrics)
    return new_params, new_state, aux


def _warmup_or_compress(compressor, deltas, comp_state, specs, ctx, key,
                        step, k):
    """Dense fused all-reduce for ``step < k``, the compressor afterwards.

    Both branches run under ``lax.cond`` (a jittable, traced-step-compatible
    switch), so the compressor's state must pass through the dense branch
    unchanged — which it does by construction: warm-start factors only start
    evolving once compression starts.  The dense reconstruction is the delta
    itself, keeping the error buffers exactly zero through the warmup.

    Note for :class:`~repro.core.dist.CollectiveStats` users: recording is
    trace-time, and ``cond`` traces both branches, so a warmup-enabled step
    records the dense collective *and* the compressor's — gate on
    ``start_compress_step=0`` when asserting collective budgets.
    """
    from repro.core.engine import CompressOut

    wire_dtype = getattr(compressor, "wire_dtype", "auto")
    max_chunk = getattr(compressor, "max_chunk_bytes", None)
    dense_bits = sum(matrixize.uncompressed_floats(g.shape) * 32
                     for g in jax.tree_util.tree_leaves(deltas))
    comp_bits = [dense_bits]

    def dense(args):
        deltas, comp_state = args
        leaves, treedef = jax.tree_util.tree_flatten(deltas)
        agg = jax.tree_util.tree_unflatten(
            treedef, ctx.pmean_flat(leaves, wire_dtype=wire_dtype,
                                    max_chunk_bytes=max_chunk))
        return agg, deltas, comp_state

    def compress(args):
        deltas, comp_state = args
        out = compressor.step(deltas, comp_state, specs, ctx=ctx, key=key)
        comp_bits[0] = out.bits_per_worker  # captured at trace time
        return out.agg, out.recon, out.state

    agg, recon, new_comp = lax.cond(
        step < k, dense, compress, (deltas, comp_state))
    bits = jnp.where(step < k, dense_bits, comp_bits[0])
    return CompressOut(agg=agg, recon=recon, state=new_comp,
                       bits_per_worker=bits)
