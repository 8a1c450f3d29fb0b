"""Device-free step tracing and jaxpr inspection for gradlint.

Everything here runs with ``jax.make_jaxpr`` under an ``axis_env`` — no
devices, no executions, no shard_map.  The named-axis collectives the
transport engine emits (:class:`repro.core.dist.AxisBackend`) trace exactly
as they would inside shard_map, and :class:`repro.core.dist.CollectiveStats`
records at *Python trace time*, so one ``make_jaxpr`` call yields both
accounting paths (the jaxpr and the stats trace) for free.

Attribution: every collective equation carries a source-info traceback; the
innermost frames inside ``src/repro`` identify which ``dist.py`` entry point
emitted it (``pmean_flat``, ``allgather_flat``, ``broadcast0``,
``_canonical_reduce``, ...).  That chain is the finding provenance and the
key for classifying each primitive into the *logical* collective ledger
(e.g. a quantized gather's float32 scale sidecar is a second ``all_gather``
primitive but the same logical collective — see
:meth:`repro.core.dist.MeshCtx.allgather_flat`).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import dist
from repro.core.dist import (COLLECTIVE_PRIMITIVES, COLLECTIVE_SITES,
                             CollectiveStats, MeshCtx)

DATA_AXIS = "data"
DEFAULT_WORKERS = 4


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def iter_eqns(jaxpr):
    """Yield every equation of ``jaxpr`` and of all sub-jaxprs (pjit, scan,
    while, cond branches, custom_jvp/vjp calls, remat, ...) recursively."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from iter_eqns(sub)


def _eqn_axes(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def provenance_chain(eqn, package: str = "/repro/") -> Tuple[Tuple[str, str, int], ...]:
    """(file, function, line) frames of the eqn's traceback that live inside
    ``package``, innermost first.  Empty when the collective was issued
    outside the repro tree (a hand-rolled collective — GL103).

    ``function`` is the bare name: tracebacks report the qualified name
    (``MeshCtx.pmean_flat.<locals>.issue``), and the attribution contract
    (:data:`repro.core.dist.COLLECTIVE_SITES`) is keyed by the last part."""
    src = getattr(eqn, "source_info", None)
    tb = getattr(src, "traceback", None)
    if tb is None:
        return ()
    chain = []
    for fr in tb.frames:
        if package in fr.file_name.replace("\\", "/"):
            name = fr.file_name.replace("\\", "/").rsplit(package, 1)[-1]
            func = fr.function_name.rsplit(".", 1)[-1]
            chain.append((name, func, fr.line_num))
    return tuple(chain)


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    """One collective primitive in a traced step, with attribution."""

    primitive: str                 # "psum" | "all_gather" | "ppermute" | ...
    axes: Tuple[str, ...]
    dtype: str                     # operand dtype on the wire
    size: int                      # operand element count
    chain: Tuple[Tuple[str, str, int], ...]  # repro frames, innermost first

    @property
    def entry(self) -> Optional[str]:
        """The dist.py entry-point function this collective belongs to, or
        None when the call chain never passes through core/dist.py."""
        for _file, func, _line in self.chain:
            if _file.endswith("core/dist.py") and func in COLLECTIVE_SITES:
                return func
        return None

    @property
    def kind(self) -> Optional[str]:
        """'reduce' | 'gather' | 'broadcast' per the dist entry point."""
        entry = self.entry
        return None if entry is None else COLLECTIVE_SITES[entry]

    @property
    def is_scale_sidecar(self) -> bool:
        """True for the float32 scale all_gather that rides a quantized
        payload gather — the same *logical* collective (its bytes are the
        stats record's overhead, not a new record)."""
        if self.primitive != "all_gather" or self.entry != "allgather_flat":
            return False
        sidecar_line = dist.quant_sidecar_line()
        return any(_file.endswith("core/dist.py")
                   and func == "allgather_flat" and line == sidecar_line
                   for _file, func, line in self.chain)

    def provenance(self) -> str:
        inner = " <- ".join(f"{f}:{fn}:{ln}" for f, fn, ln in self.chain[:4])
        return f"{self.primitive}[{','.join(self.axes)}] {inner or '<outside repro>'}"


def collect_collectives(closed_jaxpr,
                        data_axes: Sequence[str] = (DATA_AXIS,)) -> List[CollectiveSite]:
    """All data-axis collective primitives in trace order."""
    sites = []
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name not in COLLECTIVE_PRIMITIVES:
            continue
        axes = _eqn_axes(eqn)
        if not any(a in data_axes for a in axes):
            continue
        aval = eqn.invars[0].aval
        sites.append(CollectiveSite(
            primitive=eqn.primitive.name,
            axes=axes,
            dtype=str(aval.dtype),
            size=int(aval.size),
            chain=provenance_chain(eqn)))
    return sites


def logical_collectives(sites: Sequence[CollectiveSite]) -> List[CollectiveSite]:
    """The logical ledger: scale sidecars fold into their payload gather."""
    return [s for s in sites if not s.is_scale_sidecar]


# ---------------------------------------------------------------------------
# tracing entry points
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TraceArtifact:
    """One traced step: the closed jaxpr, the trace-time stats, the
    extracted collective sites, and the declared config that produced it."""

    closed_jaxpr: Any
    stats: CollectiveStats
    sites: Tuple[CollectiveSite, ...]
    label: str = ""
    sync_mode: str = "allreduce"

    def logical(self) -> List[CollectiveSite]:
        return logical_collectives(self.sites)


def trace_fn(fn: Callable, example_args: Sequence[Any], *,
             workers: int = DEFAULT_WORKERS,
             data_axis: str = DATA_AXIS, label: str = "",
             sync_mode: str = "allreduce",
             stats: Optional[CollectiveStats] = None) -> TraceArtifact:
    """Trace ``fn(*example_args)`` under a ``(data_axis, workers)`` axis env.

    ``example_args`` may be ShapeDtypeStructs or concrete arrays — tracing
    never executes either way.  ``stats`` should be the CollectiveStats the
    ctx inside ``fn`` records into, so the artifact carries both ledgers.
    """
    avals = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))
        if not isinstance(x, jax.ShapeDtypeStruct) else x, tuple(example_args))
    if stats is None:
        stats = CollectiveStats()
    closed = jax.make_jaxpr(fn, axis_env=[(data_axis, workers)])(*avals)
    sites = tuple(collect_collectives(closed, (data_axis,)))
    return TraceArtifact(closed_jaxpr=closed, stats=stats, sites=sites,
                         label=label, sync_mode=sync_mode)


def trace_compress_step(compressor, grads, specs, *,
                        staleness: str = "none",
                        sync_mode: str = "allreduce",
                        workers: int = DEFAULT_WORKERS,
                        with_error_feedback: bool = True,
                        label: str = "") -> TraceArtifact:
    """Trace one error-feedback compress+aggregate step, device-free.

    This is the same path ``launch/train.py`` runs inside shard_map —
    :func:`repro.core.error_feedback.apply_updates` over the compressor —
    with the data axis supplied by ``axis_env`` instead of a mesh.
    ``staleness="one_step"`` carries the params-shaped in-flight buffer
    exactly like the pipeline (the collectives must be identical — PR 8's
    trace-identity contract, which the budget pass re-proves statically).
    """
    from repro.core import error_feedback

    stats = CollectiveStats()
    ctx = MeshCtx(data_axes=(DATA_AXIS,), stats=stats, sync_mode=sync_mode)
    grads_sds = jax.tree_util.tree_map(
        lambda g: jax.ShapeDtypeStruct(jnp.shape(g), jnp.result_type(g)),
        grads)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    comp_state = jax.eval_shape(
        lambda: compressor.init(grads_sds, specs, jax.random.key(0)))

    if not with_error_feedback:
        def fn(g, state, key):
            out = compressor.step(g, state, specs, ctx=ctx, key=key)
            return out.agg
        return trace_fn(fn, (grads_sds, comp_state, key), workers=workers,
                        label=label, sync_mode=sync_mode, stats=stats)

    state = error_feedback.EFState(
        error=grads_sds,
        momentum=grads_sds,
        comp=comp_state,
        step=jax.ShapeDtypeStruct((), jnp.int32),
        inflight=(grads_sds if staleness == "one_step" else None))

    def fn(params, g, state, key):
        new_params, new_state, _aux = error_feedback.apply_updates(
            compressor, params, g, state, specs, lr=0.1, ctx=ctx, key=key,
            staleness=staleness)
        return new_params, new_state

    return trace_fn(fn, (grads_sds, grads_sds, state, key), workers=workers,
                    label=label, sync_mode=sync_mode, stats=stats)


# ---------------------------------------------------------------------------
# stable jaxpr hashing (retrace-stability pass)
# ---------------------------------------------------------------------------


def jaxpr_hash(closed_jaxpr) -> str:
    """Stable content hash of a closed jaxpr.

    The pretty-printer assigns canonical single-letter names in program
    order, so two structurally identical traces print identically; source
    line info is not part of the rendering.  Constants are hashed by
    shape/dtype (not value) — a retrace with different constant *values*
    but identical structure is the same program shape, which is what
    retrace-stability is about.
    """
    text = str(closed_jaxpr.jaxpr)
    consts = ",".join(
        f"{jnp.shape(c)}:{jnp.result_type(c)}" for c in closed_jaxpr.consts)
    return hashlib.sha256(f"{text}||{consts}".encode()).hexdigest()
