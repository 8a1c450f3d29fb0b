"""Distributed training step: one ``shard_map`` over the full mesh with
manual Megatron-style TP collectives and PowerSGD gradient aggregation over
the data axes (the paper's Algorithm 1+2, composed with tensor parallelism).

Also provides a CLI driver (``python -m repro.launch.train``) that trains a
reduced model end-to-end on the host devices, with full-state fault-tolerant
checkpointing: ``--ckpt-every`` writes periodic
:class:`repro.checkpoint.TrainState` envelopes (params, EF buffers,
warm-start factors, rank controller, PRNG stream, data cursor) and
``--resume`` continues a killed run bit-exactly (``docs/checkpoint.md``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import error_feedback, matrixize, scopes
from repro.core.compressors import Compressor, PowerSGDCompressor
from repro.core.dist import MeshCtx
from repro.core.error_feedback import EFState
from repro.configs.base import InputShape, ModelConfig
from repro.models import model
from repro.launch import mesh as mesh_lib
from repro.launch import specs as specs_lib


@dataclasses.dataclass(frozen=True)
class TrainHyper:
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 1e-4
    warmup_steps: int = 200
    rank: int = 2
    q_chunk: int = 512              # query rows per block of the XLA
    #   attention path; the TPU's flash kernels (full causal attention at
    #   aligned shapes, see models/attention.py) size their own blocks
    window: int = 0                 # sliding-window attention (0 = full)
    remat: bool = True
    unroll: int = 1                 # scan unroll (dry-run cost accounting)
    orthogonalizer: str = "gram_schmidt"
    use_pallas: bool = False
    bucketing: str = "auto"         # "auto"/"on" = batched engine, "off" = per-leaf
    wire_dtype: str = "auto"        # fused-collective wire policy
    #                                 ("auto"|"float32"|"bfloat16"|"int8"|"int4")
    start_compress_step: int = 0    # dense warmup steps before compression kicks in
    rank_schedule: Optional[str] = None  # adaptive-rank spec ("4@0,2@60",
    #   "residual:min=1,max=8", ...; see repro.core.powersgd.parse_schedule).
    #   The schedule is *driven by the host loop* (rank = factor shape, so a
    #   switch retraces the jitted step): build a RankController from the
    #   compressor and transition ef.comp between steps — see main() below.
    track_residual: bool = False    # emit residual_ratio in the step metrics
    staleness: str = "none"         # "one_step" = delayed-parameter-update
    #   pipeline (ISSUE 8): apply step t−1's aggregated update while step t's
    #   gradients are computed, the in-flight aggregate carried in
    #   EFState.inflight and the engine on the double-buffered
    #   PipelinedTransport; error feedback absorbs the one-step delay.
    #   "none" (default) is the synchronous path, bit-identical to pre-ISSUE-8.
    sync_mode: str = "allreduce"    # "broadcast" = replica-deterministic
    #   data-axis aggregation (canonical reduction order + rank-0 broadcast;
    #   see repro.core.dist.MeshCtx.sync_mode) — bit-identical replicas on
    #   substrates whose all-reduce is rank-dependent at ULP level
    track_drift: bool = False       # emit drift_{params,momentum,error,q}
    #   metrics: max abs cross-data-rank divergence of the step's outputs
    tp_grad_sync: bool = True       # model-axis psum on backward cotangents
    #   at replicated→sharded boundaries (common.grad_synced).  False is a
    #   debug switch reproducing the legacy per-rank partial gradients whose
    #   cross-model drift docs/checkpoint.md once misread as all-reduce
    #   nondeterminism — pinned by tests/sim/test_drift.py.


def _schedule(hyper: TrainHyper, step):
    from repro.optim import schedules

    return schedules.linear_warmup(step, hyper.lr, hyper.warmup_steps, 0.1)


def replica_drift(ctx: MeshCtx, tree) -> jax.Array:
    """Max abs divergence of ``tree``'s float leaves across the data ranks.

    The drift probe behind ``TrainHyper.track_drift``: every rank compares
    its copy against rank 0's (delivered by the backend's masked-psum
    broadcast — called on the backend directly, so the probe never perturbs
    :class:`~repro.core.dist.CollectiveStats` budgets) and the worst
    divergence is ``pmax``-reduced back to every rank.  Exactly ``0.0``
    certifies bit-identical replicas for these leaves this step; under
    ``sync_mode="allreduce"`` on rank-dependent substrates it exposes the
    ULP-seeded divergence documented in ``docs/checkpoint.md``.  Works
    unchanged under ``shard_map`` and SimMesh.  Observability only.
    """
    drifts = []
    idx = ctx.data_index()
    for x in jax.tree_util.tree_leaves(tree):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            continue
        x = x.astype(jnp.float32)
        ref = ctx.backend.broadcast0(x, ctx.data_axes, idx)
        drifts.append(jnp.max(jnp.abs(x - ref)))
    if not drifts:
        return jnp.zeros((), jnp.float32)
    return ctx.backend.pmax(jnp.max(jnp.stack(drifts)), ctx.data_axes)


def make_train_step(cfg: ModelConfig, mesh, hyper: TrainHyper,
                    compressor: Optional[Compressor] = None):
    """Returns (jitted_step, abstract_state_fn).

    jitted_step(params, ef_state, batch, key) → (params, ef_state, metrics)
    """
    dp_axes = mesh_lib.data_axes(mesh)
    maxis = mesh_lib.model_axis(mesh)
    model_shards = mesh.shape[maxis]
    ctx = MeshCtx(data_axes=dp_axes, model_axis=maxis,
                  sync_mode=hyper.sync_mode,
                  tp_grad_sync=hyper.tp_grad_sync)
    all_axes = tuple(mesh.axis_names)

    if compressor is None:
        compressor = PowerSGDCompressor(
            rank=hyper.rank, orthogonalizer=hyper.orthogonalizer,
            use_pallas=hyper.use_pallas, bucketing=hyper.bucketing,
            wire_dtype=hyper.wire_dtype, rank_schedule=hyper.rank_schedule,
            track_residual=hyper.track_residual,
            pipeline=hyper.staleness == "one_step")

    param_ps = model.pspecs(cfg)
    mspec_tree = model.mspecs(cfg)
    # per-leaf StatePartition: the dims specs for shard_map, plus the
    # model-relation (replicated / sharded / LOCAL) the engine and the
    # checkpoint layer need (model-LOCAL Q factors must not be treated as
    # replicated — see docs/checkpoint.md "state pspecs")
    state_parts = specs_lib.ef_partition(param_ps, mspec_tree, dp_axes,
                                         compressor=compressor,
                                         stateful=compressor.stateful,
                                         staleness=hyper.staleness)
    # the in-flight aggregate (staleness="one_step") is classified inside
    # the partition tree like any other leaf — params-shaped, data-
    # replicated, model-sharded exactly like the params it is applied to
    ef_ps = specs_lib.partition_specs(state_parts)
    if hasattr(compressor, "bind_state_partition"):
        compressor.bind_state_partition(state_parts.comp)

    def local_step(params, ef_state, batch, key):
        # error buffers arrive with a leading local dp dim of 1 — unwrap
        error_local = jax.tree_util.tree_map(lambda e: e[0], ef_state.error)
        state = EFState(error=error_local, momentum=ef_state.momentum,
                        comp=ef_state.comp, step=ef_state.step,
                        inflight=ef_state.inflight)

        def loss_fn(p):
            return model.loss_fn(p, batch, cfg, ctx, window=hyper.window,
                                 q_chunk=hyper.q_chunk, remat=hyper.remat,
                                 unroll=hyper.unroll)

        with jax.named_scope(scopes.LOSS_GRAD):
            grads, metrics = jax.grad(loss_fn, has_aux=True)(params)

        lr = _schedule(hyper, state.step)
        new_params, new_state, aux = error_feedback.apply_updates(
            compressor, params, grads, state, mspec_tree,
            lr=lr, momentum=hyper.momentum, weight_decay=hyper.weight_decay,
            ctx=ctx, key=key, use_pallas_apply=hyper.use_pallas,
            start_compress_step=hyper.start_compress_step,
            staleness=hyper.staleness)

        new_state = EFState(
            error=jax.tree_util.tree_map(lambda e: e[None], new_state.error),
            momentum=new_state.momentum, comp=new_state.comp,
            step=new_state.step, inflight=new_state.inflight)
        if "residual_ratio" in aux:  # host-side RankControllers read this
            metrics["residual_ratio"] = aux["residual_ratio"]
        metrics = {k: lax.pmean(v, all_axes) for k, v in metrics.items()}
        if hyper.track_drift and dp_axes:
            # added after the metrics pmean: already cross-rank reduced
            # (pmax over data, then over all axes so the output replicates)
            for name, tree in (("params", new_params),
                               ("momentum", new_state.momentum),
                               ("error", new_state.error),
                               ("q", new_state.comp)):
                metrics[f"drift_{name}"] = lax.pmax(
                    replica_drift(ctx, tree), all_axes)
        metrics["lr"] = lr
        return new_params, new_state, metrics

    batch_ps = specs_lib.batch_pspecs(
        cfg, InputShape("x", 0, 2, "train"), dp_axes)

    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(param_ps, _ef_in_specs(ef_ps), batch_ps, P()),
        out_specs=(param_ps, _ef_in_specs(ef_ps), P()),
        check_vma=False,
    )
    step_fn = jax.jit(sharded, donate_argnums=(0, 1))

    def abstract_state(key=None):
        """Abstract (SDS) params + EF state with shardings, for the dry-run."""
        k = jax.random.key(0) if key is None else key
        params_sds = jax.eval_shape(lambda: model.init(k, cfg, model_shards))
        dp_total = specs_lib.axis_sizes(mesh, dp_axes)

        def err_leaf(p):
            return jax.ShapeDtypeStruct((dp_total,) + tuple(p.shape), p.dtype)

        comp_sds = jax.eval_shape(
            lambda: compressor.init(params_sds, mspec_tree, k))
        ef_sds = EFState(
            error=jax.tree_util.tree_map(err_leaf, params_sds),
            momentum=params_sds,
            comp=comp_sds,
            step=jax.ShapeDtypeStruct((), jnp.int32),
            inflight=(params_sds if hyper.staleness == "one_step" else None),
        )
        params_sds = specs_lib.with_sharding(params_sds, param_ps, mesh)
        ef_sds = specs_lib.with_sharding(ef_sds, ef_ps, mesh)
        return params_sds, ef_sds

    def init_state(key):
        """Concrete initialisation, built already laid out as the step takes
        it: one jitted program whose ``out_shardings`` come from
        :func:`abstract_state`, so each device materialises only its own
        shard (the per-data-rank error buffers never sit on one device)."""
        params_sds, ef_sds = abstract_state(key)
        shardings = jax.tree_util.tree_map(lambda s: s.sharding,
                                           (params_sds, ef_sds))
        return jax.jit(_init_state, out_shardings=shardings)(key)

    def _init_state(key):
        kp, kc = jax.random.split(key)
        params = model.init(kp, cfg, model_shards)
        dp_total = specs_lib.axis_sizes(mesh, dp_axes)
        comp = compressor.init(
            jax.tree_util.tree_map(
                lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params),
            mspec_tree, kc)
        ef = EFState(
            error=jax.tree_util.tree_map(
                lambda p: jnp.zeros((dp_total,) + tuple(p.shape), p.dtype), params),
            momentum=jax.tree_util.tree_map(jnp.zeros_like, params),
            comp=comp,
            step=jnp.zeros((), jnp.int32),
            inflight=(jax.tree_util.tree_map(jnp.zeros_like, params)
                      if hyper.staleness == "one_step" else None),
        )
        return params, ef

    return step_fn, abstract_state, init_state


def _ef_in_specs(ef_ps: EFState):
    return EFState(error=ef_ps.error, momentum=ef_ps.momentum,
                   comp=ef_ps.comp, step=ef_ps.step, inflight=ef_ps.inflight)


def train_state_partition(cfg: ModelConfig, mesh,
                          compressor: Optional[Compressor] = None,
                          staleness: str = "none") -> EFState:
    """The per-leaf :class:`~repro.core.engine.StatePartition` tree a
    driver hands to ``repro.checkpoint.canonicalize_mesh`` /
    ``replicate_mesh`` / ``stack_model_template`` — the same derivation
    :func:`make_train_step` binds into the engine, recomputed standalone so
    checkpoint tooling (and a restoring process that hasn't built a step
    yet) can classify leaves without tracing anything.  Pass the run's
    ``staleness`` so a one-step-stale state's ``inflight`` leaves are
    classified too (an EFState with more leaves than its partition tree
    fails gradlint's GL401)."""
    if compressor is None:
        compressor = PowerSGDCompressor()
    return specs_lib.ef_partition(
        model.pspecs(cfg), model.mspecs(cfg), mesh_lib.data_axes(mesh),
        compressor=compressor, stateful=compressor.stateful,
        staleness=staleness)


# ---------------------------------------------------------------------------
# SimMesh training step: W logical workers in one process (one device)
# ---------------------------------------------------------------------------

def make_sim_train_step(cfg: ModelConfig, sim, hyper: TrainHyper,
                        compressor: Optional[Compressor] = None,
                        stats=None):
    """W-worker EF-PowerSGD train step on a :class:`repro.core.simmesh.
    SimMesh` — same math as the ``shard_map`` step, no mesh required.

    Returns ``(step_fn, init_state)``:

    ``step_fn(params, ef_state, batch, key, weights=None)`` →
    ``(params, ef_state, metrics)`` where every tree carries a stacked
    leading worker dim of size ``sim.workers`` (``batch`` is per-worker
    shards ``(W, b_local, ...)``, see :meth:`SimMesh.shard`) and ``key`` is
    shared by all workers (compressors rely on shared seeds).  ``weights``
    is an optional ``(W,)`` per-worker contribution-weight vector for
    scenario injection — uniform means when omitted; ``0`` drops a worker
    from this round's aggregation (its per-worker EF memory still updates
    from its own ``Δ_w``, against the round's reconstruction per
    ``error_mode``); for heterogeneous batch sizes pass each worker's
    valid-token count.

    ``init_state(key)`` → ``(params, ef_state)``, replicated/zeroed with the
    worker dim attached.  Workers start bit-identical and — because every
    update is a function of all-reduced quantities only — must *stay*
    bit-identical (``sim.assert_replicated`` checks this invariant).
    """
    if compressor is None:
        compressor = PowerSGDCompressor(
            rank=hyper.rank, orthogonalizer=hyper.orthogonalizer,
            use_pallas=hyper.use_pallas, bucketing=hyper.bucketing,
            wire_dtype=hyper.wire_dtype, rank_schedule=hyper.rank_schedule,
            track_residual=hyper.track_residual,
            pipeline=hyper.staleness == "one_step")
    mspec_tree = model.mspecs(cfg)

    def worker_step(params, ef_state, batch, key, weight):
        # ctx is built inside the mapped function so the traced per-worker
        # weight binds to this trace
        ctx = sim.ctx(weight=weight, stats=stats, sync_mode=hyper.sync_mode)

        def loss_fn(p):
            return model.loss_fn(p, batch, cfg, ctx, window=hyper.window,
                                 q_chunk=hyper.q_chunk, remat=hyper.remat,
                                 unroll=hyper.unroll)

        with jax.named_scope(scopes.LOSS_GRAD):
            grads, metrics = jax.grad(loss_fn, has_aux=True)(params)

        lr = _schedule(hyper, ef_state.step)
        new_params, new_state, aux = error_feedback.apply_updates(
            compressor, params, grads, ef_state, mspec_tree,
            lr=lr, momentum=hyper.momentum, weight_decay=hyper.weight_decay,
            ctx=ctx, key=key, use_pallas_apply=hyper.use_pallas,
            start_compress_step=hyper.start_compress_step,
            staleness=hyper.staleness)

        # metrics aggregate through the backend directly: they are
        # observability, not gradient traffic, and must not perturb the
        # CollectiveStats 2-collectives-per-step invariant
        if "residual_ratio" in aux:  # host-side RankControllers read this
            metrics["residual_ratio"] = aux["residual_ratio"]
        metrics = {k: ctx.backend.pmean(v, ctx.data_axes)
                   for k, v in metrics.items()}
        if hyper.track_drift:
            for name, tree in (("params", new_params),
                               ("momentum", new_state.momentum),
                               ("error", new_state.error),
                               ("q", new_state.comp)):
                metrics[f"drift_{name}"] = replica_drift(ctx, tree)
        metrics["lr"] = lr
        return new_params, new_state, metrics

    mapped = sim.run(worker_step, in_axes=(0, 0, 0, None, 0))
    jitted = jax.jit(mapped, donate_argnums=(0, 1))

    def step_fn(params, ef_state, batch, key, weights=None):
        if weights is None:
            weights = jnp.ones((sim.workers,), jnp.float32)
        return jitted(params, ef_state, batch, key,
                      jnp.asarray(weights, jnp.float32))

    def init_state(key):
        kp, kc = jax.random.split(key)
        params = model.init(kp, cfg, model_shards=1)
        comp = compressor.init(
            jax.tree_util.tree_map(
                lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params),
            mspec_tree, kc)
        ef = EFState(
            error=jax.tree_util.tree_map(jnp.zeros_like, params),
            momentum=jax.tree_util.tree_map(jnp.zeros_like, params),
            comp=comp,
            step=jnp.zeros((), jnp.int32),
            inflight=(jax.tree_util.tree_map(jnp.zeros_like, params)
                      if hyper.staleness == "one_step" else None),
        )
        return sim.replicate(params), sim.replicate(ef)

    return step_fn, init_state


def check_wire_dtype_meta(meta: dict, wire_dtype: str) -> None:
    """Resume guard: the checkpoint's recorded wire policy must match.

    The wire dtype shapes the error-feedback trajectory — under a quantized
    wire every step's quantization error lands in the EF buffers, so the
    buffers in the envelope are only meaningful under the policy that
    produced them.  A mismatch is a config error, not something to adapt."""
    saved = meta.get("wire_dtype", "auto")
    if saved != wire_dtype:
        raise SystemExit(
            f"--wire-dtype {wire_dtype!r} does not match the checkpoint's "
            f"{saved!r} — the wire policy shapes the error-feedback "
            f"trajectory (quantization error is part of the algorithm "
            f"state); resume with the wire dtype the run was started with")


# ---------------------------------------------------------------------------
# CLI driver: end-to-end training of a reduced model on host devices
# ---------------------------------------------------------------------------

def main():
    import argparse
    import time

    from repro.checkpoint import (TrainState, canonicalize_mesh,
                                  replicate_mesh, restore_train_state,
                                  save_train_state, stack_model_template)
    from repro.configs.base import get_config
    from repro.data.synthetic import MarkovLM
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--rank", type=int, default=2)
    ap.add_argument("--rank-schedule", default=None,
                    help="adaptive-rank spec, e.g. '4@0,2@60,1@120' or "
                         "'residual:min=1,max=8,init=4' (see "
                         "repro.core.powersgd.parse_schedule)")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--sync-mode", default="allreduce",
                    choices=("allreduce", "broadcast"),
                    help="'broadcast' makes every data-axis aggregate "
                         "replica-deterministic (canonical reduction order "
                         "+ rank-0 broadcast; see docs/checkpoint.md)")
    ap.add_argument("--wire-dtype", default="auto",
                    choices=matrixize.WIRE_DTYPES,
                    help="fused-collective wire policy: 'auto' keeps each "
                         "part's dtype, float32/bfloat16 cast, int8/int4 "
                         "quantize float payloads symmetrically per slot "
                         "(int4 nibble-packed; see docs/tuning.md)")
    ap.add_argument("--staleness", default="none",
                    choices=("none", "one_step"),
                    help="'one_step' turns on the delayed-parameter-update "
                         "pipeline: apply step t-1's aggregated compressed "
                         "update while step t's gradients are computed "
                         "(error feedback absorbs the delay; see "
                         "docs/tuning.md)")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="the config's reduced preset (default); "
                         "--no-reduced trains at published widths")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save a full TrainState checkpoint every N steps "
                         "(0 = only at the end; needs --ckpt-dir)")
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="retention: keep the newest N checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir: "
                         "full algorithm state (EF buffers, warm-start "
                         "factors, rank controller, PRNG stream, data "
                         "cursor), bit-exact at the same worker count")
    args = ap.parse_args()
    if args.ckpt_every and not args.ckpt_dir:
        ap.error("--ckpt-every requires --ckpt-dir (no checkpoint would "
                 "ever be written)")

    cfg = get_config(args.arch, reduced=args.reduced)
    n_dev = len(jax.devices())
    if n_dev >= 4:
        m = mesh_lib.make_mesh((n_dev // 2, 2), ("data", "model"))
    elif n_dev >= 2:
        m = mesh_lib.make_mesh((n_dev, 1), ("data", "model"))
    else:
        m = mesh_lib.make_mesh((1, 1), ("data", "model"))

    hyper = TrainHyper(lr=args.lr, rank=args.rank, q_chunk=64,
                       warmup_steps=20, remat=False,
                       rank_schedule=args.rank_schedule,
                       wire_dtype=args.wire_dtype,
                       sync_mode=args.sync_mode, staleness=args.staleness)
    compressor = PowerSGDCompressor(
        rank=args.rank, rank_schedule=args.rank_schedule,
        wire_dtype=args.wire_dtype,
        pipeline=args.staleness == "one_step")
    step_fn, _, init_state = make_train_step(cfg, m, hyper,
                                             compressor=compressor)
    controller = (compressor.controller()
                  if compressor.rank_schedule is not None else None)
    # per-leaf state partition: which checkpoint leaves are model-LOCAL
    # (per-model-rank Q factors) and must be gathered/re-sliced per rank
    parts = train_state_partition(cfg, m, compressor,
                                  staleness=args.staleness)
    model_size = int(m.shape["model"])

    key = jax.random.key(0)   # base key; per-step keys fold in the step index
    with jax.set_mesh(m):
        params, ef = init_state(key)
    data = MarkovLM(vocab=cfg.vocab_size, seed=0)

    start = 0
    residual = None
    if args.resume:
        if not args.ckpt_dir:
            ap.error("--resume requires --ckpt-dir")
        template = TrainState(
            params=params, ef=stack_model_template(ef, parts, model_size),
            key=key, data_step=jnp.zeros((), jnp.int32))
        state, meta = restore_train_state(args.ckpt_dir, template,
                                          model_axis_size=model_size)
        if meta.get("rank_schedule") != args.rank_schedule:
            raise SystemExit(
                f"--rank-schedule {args.rank_schedule!r} does not match the "
                f"checkpoint's {meta.get('rank_schedule')!r} — resume with "
                f"the schedule the run was started with")
        if meta.get("staleness", "none") != args.staleness:
            raise SystemExit(
                f"--staleness {args.staleness!r} does not match the "
                f"checkpoint's {meta.get('staleness', 'none')!r} — the "
                f"envelope does (not) carry an in-flight aggregate; resume "
                f"with the mode the run was started with")
        check_wire_dtype_meta(meta, args.wire_dtype)
        # re-slice stacked model-LOCAL leaves: every model rank gets its
        # own pre-save factors back (not rank-0's copy)
        with jax.set_mesh(m):
            params, ef = replicate_mesh(m, state.params, state.ef, parts)
        key = state.key
        start = int(state.ef.step)
        if int(state.data_step) != start:
            raise SystemExit(
                f"checkpoint data cursor {int(state.data_step)} does not "
                f"match its step counter {start} — this CLI keys batches "
                f"by step, so the envelope was written by a different "
                f"driver; resume it with that driver")
        if controller is not None and meta.get("controller"):
            controller.load_state_dict(meta["controller"])
        residual = meta.get("last_residual")
        print(f"resumed from step {start} in {args.ckpt_dir} "
              f"(saved at {meta.get('workers')} worker(s), rank "
              f"{controller.rank if controller else args.rank})")

    def save_ckpt():
        # params/ef/key/residual are read at call time: the state *after*
        # the step that just completed, i.e. "about to run step ef.step".
        # canonicalize_mesh gathers model-LOCAL leaves host-side into the
        # stacked per-model-rank layout (no collectives)
        p_c, ef_c = canonicalize_mesh(m, params, ef, parts)
        path = save_train_state(
            args.ckpt_dir,
            TrainState(params=p_c, ef=ef_c, key=key,
                       data_step=jnp.asarray(int(ef.step), jnp.int32)),
            controller=controller, keep=args.ckpt_keep,
            model_axis_size=model_size,
            mesh_shape={a: int(m.shape[a]) for a in m.axis_names},
            extra_meta={"rank_schedule": args.rank_schedule,
                        "arch": args.arch, "last_residual": residual,
                        "staleness": args.staleness,
                        "wire_dtype": args.wire_dtype})
        return path

    t0 = time.time()
    metrics = {}
    for i in range(start, args.steps):
        if controller is not None:
            # host-level rank transition: a switch changes the factor
            # shapes, and the jitted step simply retraces
            new_comp, changed = controller.update(ef.comp, i, residual)
            if changed:
                ef = error_feedback.replace_comp(ef, new_comp)
                print(f"step {i:4d} rank -> {controller.rank}")
        # the data cursor IS the step index: batch i is sample(step=i),
        # so a resumed run rejoins the stream exactly where it left off
        toks = data.sample(args.batch, args.seq, step=i)
        batch = {"tokens": jnp.asarray(toks[:, :-1]),
                 "labels": jnp.asarray(toks[:, 1:].copy())}
        step_key = jax.random.fold_in(key, i)
        with jax.set_mesh(m):
            params, ef, metrics = step_fn(params, ef, batch, step_key)
        if "residual_ratio" in metrics:
            residual = float(metrics["residual_ratio"])
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss={float(metrics['lm_loss']):.4f} "
                  f"lr={float(metrics['lr']):.4f} ({time.time()-t0:.1f}s)")
        if args.ckpt_dir and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
            print(f"step {i:4d} checkpoint -> {save_ckpt()}")
    if args.ckpt_dir and start < args.steps:
        print(f"final checkpoint -> {save_ckpt()}")
    if metrics:
        # full-precision hex so the CI resume smoke can compare bit-for-bit
        print(f"final lm_loss={float(metrics['lm_loss']):.6f} "
              f"hex={float(metrics['lm_loss']).hex()}")


if __name__ == "__main__":
    main()
