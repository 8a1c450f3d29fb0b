"""The gradlint jaxpr passes: collective-budget, wire-dtype, determinism.

Each pass is a function ``(artifact: TraceArtifact, ...) -> List[Finding]``
over one traced step (:func:`repro.analysis.tracing.trace_compress_step`).
They never execute anything — all evidence comes from the closed jaxpr, the
equation source provenance, and the trace-time ``CollectiveStats`` records.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Set, Tuple

import jax.extend.core as jax_core
import jax.numpy as jnp

from repro.analysis.findings import Finding
from repro.analysis.tracing import (CollectiveSite, TraceArtifact, iter_eqns)

# pack-path primitives: ops that merely move/reshape payload bytes between a
# producer and the wire.  The wire-dtype pass slices backwards from each
# collective operand through exactly these (plus convert_element_type,
# which it inspects) — anything else ends the slice.
_PACK_OPS = frozenset({
    "concatenate", "reshape", "broadcast_in_dim", "squeeze", "transpose",
    "pad", "slice", "dynamic_slice", "rev", "copy", "expand_dims",
    "convert_element_type", "pjit",
})

_FLOAT_WIDTHS = {"bfloat16": 2, "float16": 2, "float32": 4, "float64": 8}


# ---------------------------------------------------------------------------
# 1. collective-budget
# ---------------------------------------------------------------------------


def check_budget(artifact: TraceArtifact,
                 budget: Tuple[int, int, int],
                 scheme: str = "") -> List[Finding]:
    """Statically verify the documented per-scheme collective budget and
    cross-check the jaxpr ledger against the CollectiveStats ledger.

    ``budget`` is the scheme's declared ``(total, reduce, gather)``
    (:meth:`repro.core.compressors.Compressor.declared_budget`).  Neither
    accounting path is trusted alone: the jaxpr count proves what the
    compiled program will actually execute; the stats count is what the
    byte/bandwidth models and the runtime budget guards consume — if either
    rots, GL102 fires.
    """
    findings: List[Finding] = []
    label = artifact.label or scheme

    # -- attribution: every data-axis collective must come from dist.py ----
    logical: List[CollectiveSite] = []
    for site in artifact.sites:
        if site.entry is None:
            findings.append(Finding(
                rule="GL103", pass_name="budget",
                message=f"{label}: data-axis {site.primitive} issued outside "
                        "the repro.core.dist entry points — hand-rolled "
                        "collectives escape budget and byte accounting",
                provenance=site.provenance()))
        elif not site.is_scale_sidecar:
            logical.append(site)

    n_reduce = sum(1 for s in logical if s.kind == "reduce")
    n_gather = sum(1 for s in logical if s.kind == "gather")
    n_bcast = sum(1 for s in logical if s.kind == "broadcast")
    total, max_reduce, max_gather = budget

    # -- the documented budget (the paper's O(1) claim, statically) --------
    # Under sync_mode="broadcast" every reduce records one extra broadcast
    # accounting leg (or one fused end-of-step broadcast) that is not part
    # of the scheme's algorithmic budget; the budget is checked on the
    # allreduce trace where collectives and budget are 1:1.
    if artifact.sync_mode == "allreduce":
        if n_reduce + n_gather > total or n_reduce > max_reduce \
                or n_gather > max_gather:
            findings.append(Finding(
                rule="GL101", pass_name="budget",
                message=f"{label}: traced step issues {n_reduce} reduce + "
                        f"{n_gather} gather fused collectives, documented "
                        f"budget is {max_reduce}+{max_gather} "
                        f"(total {total})",
                provenance="; ".join(s.provenance() for s in logical)))
        elif n_reduce + n_gather < total:
            findings.append(Finding(
                rule="GL104", pass_name="budget",
                message=f"{label}: traced step issues only "
                        f"{n_reduce}+{n_gather} collectives against a "
                        f"documented budget of {max_reduce}+{max_gather} — "
                        "scheme and budget table have diverged",
                provenance="; ".join(s.provenance() for s in logical)))

    # -- static-vs-stats cross-check ---------------------------------------
    stats = artifact.stats
    stat_reduce = sum(1 for k in stats.kinds if k == "reduce")
    stat_gather = sum(1 for k in stats.kinds if k == "gather")
    stat_bcast = sum(1 for k in stats.kinds if k == "broadcast")
    # Under sync_mode="broadcast" a reduce's broadcast *accounting* leg
    # (recorded so wire-cost models price the one-to-all delivery) shares
    # the canonical reduce's single all_gather primitive — the jaxpr holds
    # no extra collective for it.  Standalone broadcast_flat legs do lower
    # to a masked psum each, and those the jaxpr must show.
    expect_bcast = stat_bcast if artifact.sync_mode == "allreduce" else \
        sum(1 for s in logical if s.kind == "broadcast")
    if (n_reduce, n_gather, n_bcast) != (stat_reduce, stat_gather,
                                         expect_bcast):
        findings.append(Finding(
            rule="GL102", pass_name="budget",
            message=f"{label}: jaxpr ledger (reduce={n_reduce}, "
                    f"gather={n_gather}, broadcast={n_bcast}) disagrees "
                    f"with CollectiveStats (reduce={stat_reduce}, "
                    f"gather={stat_gather}, broadcast={stat_bcast}, "
                    f"sync_mode={artifact.sync_mode})",
            provenance="; ".join(s.provenance() for s in logical)))
    return findings


# ---------------------------------------------------------------------------
# 2. wire-dtype discipline
# ---------------------------------------------------------------------------


def _collect_pack_slice(jaxpr, wire_vars: Set) -> Tuple[List, Set]:
    """Backward slice from collective operands through the pack whitelist.

    Returns the equations on the pack path (producers of payload bytes)
    and the set of variables on it.  The walk is over the flat equation
    list of each (sub)jaxpr in reverse program order — cheap and exact
    enough for straight-line pack/quantize code.
    """
    eqns = list(iter_eqns(jaxpr))
    on_path = set(wire_vars)
    sliced = []
    for eqn in reversed(eqns):
        if not any(v in on_path for v in eqn.outvars):
            continue
        if eqn.primitive.name not in _PACK_OPS:
            continue
        sliced.append(eqn)
        for v in eqn.invars:
            if not isinstance(v, jax_core.Literal):
                on_path.add(v)
    return sliced, on_path


def check_wire_dtypes(artifact: TraceArtifact,
                      scheme: str = "") -> List[Finding]:
    """Wire-dtype discipline on the payload pack paths.

    * **GL201** — a float→wider-float ``convert_element_type`` on the pack
      path feeding a collective: the PR 3 bug class, where one float32
      straggler silently promoted a whole bfloat16 payload to a 4-byte
      wire.  Integer→float converts are exempt: that is the *sanctioned*
      widened accumulator of the quantized reduce path
      (``MeshCtx.pmean_flat``: quantize → dequantize to float32 → plain
      all-reduce).
    * **GL202** — an integer-dtype buffer as a data-axis ``psum`` operand:
      int8/int4 slots must never reach a reduce unwidened (integer
      overflow wraps silently at W ≥ 2).
    """
    findings: List[Finding] = []
    label = artifact.label or scheme

    psum_wire_vars = set()
    gather_wire_vars = set()
    for eqn in iter_eqns(artifact.closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        if name not in ("psum", "all_gather"):
            continue
        for v in eqn.invars:
            if isinstance(v, jax_core.Literal):
                continue
            aval = v.aval
            if name == "psum":
                psum_wire_vars.add(v)
                if jnp.issubdtype(aval.dtype, jnp.integer) or \
                        jnp.issubdtype(aval.dtype, jnp.bool_):
                    findings.append(Finding(
                        rule="GL202", pass_name="wire-dtype",
                        message=f"{label}: {aval.dtype} buffer reaches a "
                                "data-axis psum unwidened — quantized "
                                "payloads must dequantize into a float "
                                "accumulator before any reduce",
                        provenance=CollectiveSite(
                            primitive=name, axes=(), dtype=str(aval.dtype),
                            size=int(aval.size),
                            chain=_chain_of(eqn)).provenance()))
            else:
                gather_wire_vars.add(v)

    sliced, _ = _collect_pack_slice(
        artifact.closed_jaxpr.jaxpr, psum_wire_vars | gather_wire_vars)
    for eqn in sliced:
        if eqn.primitive.name != "convert_element_type":
            continue
        src = eqn.invars[0].aval.dtype
        dst = eqn.outvars[0].aval.dtype
        src_w = _FLOAT_WIDTHS.get(str(src))
        dst_w = _FLOAT_WIDTHS.get(str(dst))
        if src_w is not None and dst_w is not None and dst_w > src_w:
            findings.append(Finding(
                rule="GL201", pass_name="wire-dtype",
                message=f"{label}: {src} payload widened to {dst} on the "
                        "pack path before a collective — a narrower part "
                        "is riding a wider wire (the mixed-dtype upcast "
                        "footgun)",
                provenance=CollectiveSite(
                    primitive="convert_element_type", axes=(),
                    dtype=f"{src}->{dst}", size=int(eqn.outvars[0].aval.size),
                    chain=_chain_of(eqn)).provenance()))
    return findings


def _chain_of(eqn):
    from repro.analysis.tracing import provenance_chain
    return provenance_chain(eqn)


# ---------------------------------------------------------------------------
# 3. determinism
# ---------------------------------------------------------------------------

_SEED_PRIMS = frozenset({"random_seed", "threefry2x32_seed", "rng_bit_generator"})


def check_determinism(artifact: TraceArtifact,
                      scheme: str = "") -> List[Finding]:
    """Replica-determinism discipline in the traced step.

    * **GL301** — a PRNG key constructed from a constant inside the trace
      (``random_seed`` on a literal/constant operand).  Keys must enter as
      step arguments and derive via ``fold_in`` (``random_fold_in``) — an
      in-trace constant seed makes every step draw the same stream, and a
      rank-dependent one desynchronizes replicas on retrace.
    * **GL302** — under ``sync_mode="broadcast"`` a data-axis ``psum``
      whose call chain is not the masked ``broadcast0`` delivery.  The PR 6
      drift class: a raw psum's reduction order is substrate-defined, so
      replicas (and SimMesh-vs-shard_map reruns) may disagree in the last
      ULP; certified reductions lower to the canonical all_gather +
      pairwise-tree replay (``_canonical_reduce``) instead.
    """
    findings: List[Finding] = []
    label = artifact.label or scheme

    # variables produced from the jaxpr's own arguments (a key that *enters*
    # the trace is fine; one seeded inside it is not)
    for eqn in iter_eqns(artifact.closed_jaxpr.jaxpr):
        if eqn.primitive.name in _SEED_PRIMS:
            chain = _chain_of(eqn)
            findings.append(Finding(
                rule="GL301", pass_name="determinism",
                message=f"{label}: PRNG key seeded inside the traced step "
                        f"({eqn.primitive.name}) — pass keys in as "
                        "arguments and derive per-step keys with fold_in",
                provenance=CollectiveSite(
                    primitive=eqn.primitive.name, axes=(), dtype="key",
                    size=0, chain=chain).provenance()))

    if artifact.sync_mode == "broadcast":
        for site in artifact.sites:
            if site.primitive != "psum":
                continue
            in_broadcast0 = any(
                func == "broadcast0" for _f, func, _l in site.chain)
            if not in_broadcast0:
                findings.append(Finding(
                    rule="GL302", pass_name="determinism",
                    message=f"{label}: raw data-axis psum under "
                            "sync_mode='broadcast' — reduction order is "
                            "substrate-defined; use the canonical "
                            "gather+tree-sum reduce or the masked "
                            "broadcast0 delivery",
                    provenance=site.provenance()))
    return findings


# ---------------------------------------------------------------------------
# convenience: the full jaxpr-pass pipeline over one artifact
# ---------------------------------------------------------------------------


def run_jaxpr_passes(artifact: TraceArtifact,
                     budget: Optional[Tuple[int, int, int]] = None,
                     scheme: str = "") -> List[Finding]:
    findings: List[Finding] = []
    if budget is not None:
        findings.extend(check_budget(artifact, budget, scheme))
    findings.extend(check_wire_dtypes(artifact, scheme))
    findings.extend(check_determinism(artifact, scheme))
    return findings
