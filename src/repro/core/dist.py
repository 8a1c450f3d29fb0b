"""Mesh/axis context threaded through the whole framework.

All model and compressor code is written against :class:`MeshCtx` instead of
hard-coding ``lax.psum(..., axis_name=...)`` calls.  Outside of a
``shard_map`` (single-device smoke tests, benchmarks) the context has no axis
names and every collective degenerates to the identity, so the *same* code
path runs on one CPU device and on a 512-chip mesh.

Collective dispatch
-------------------
``MeshCtx`` does not issue ``lax`` collectives directly; every collective
goes through a :class:`CollectiveBackend`.  Two backends exist:

* :data:`AXIS` (:class:`AxisBackend`) — the production backend: delegates to
  the ``lax`` named-axis collectives, which resolve against the enclosing
  ``shard_map`` (or ``vmap``) axis environment.  This is the default and is
  behaviourally identical to the pre-backend code.
* :class:`SimBackend` — the in-process W-worker simulation backend used by
  :class:`repro.core.simmesh.SimMesh`.  The worker axis is a ``jax.vmap``
  axis carried as a stacked leading dimension through the whole step, so
  collectives lower to *exact* sums/means over that stacked axis on a single
  device — no XLA collectives, bit-deterministic, and byte-for-byte the same
  compressor code path as production.  It additionally supports per-worker
  *weights* (heterogeneous batch sizes, worker dropout, stragglers): with a
  weight ``w_i`` attached, ``pmean`` becomes ``Σ w_i x_i / Σ w_i`` and
  ``psum`` becomes ``Σ w_i x_i``.

``CollectiveStats`` recording and ``pmean_flat`` fusion live in ``MeshCtx``
itself and therefore work unchanged under either backend.

Which collective carries which payload
--------------------------------------
The transport engine (:mod:`repro.core.engine`, see its worked TopK
example) maps every compressor's wire traffic onto exactly three ``MeshCtx``
entry points:

* :meth:`MeshCtx.pmean_flat` — the fused all-reduce.  Carries every
  *linear* payload (PowerSGD's P and Q factor slabs — one call per
  power-iteration phase — identity/random-k/random-block values, the
  ``exact_rank_k`` oracle's dense gradient) and ALL uncompressed
  bias/norm leaves, which ride the first reduce of the step whatever the
  scheme.  One ``pmean`` per wire chunk; bytes flat in W.
* :meth:`MeshCtx.allgather_flat` — the fused all-gather.  Carries
  *non-linear* payloads (sign_norm's int8 signs + f32 norms, top_k's f32
  values + i32 indices, spectral_atomo's (P, V) triplets); every part
  returns with a leading worker dim of ``data_size()`` and is decoded
  per worker.  Bytes scale with W (``CollectiveStats`` fanout).
* :meth:`MeshCtx.gather_data_weight` — the scenario side channel: the
  per-worker contribution weights a gather-pattern combine needs on the
  receiver (one tiny all-gather, only under a weighted ``SimBackend``).

``pmean_data``/``psum_data`` remain the unfused per-tensor path (the
``transport="per_leaf"`` / ``bucketing="off"`` reference engines).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import scopes


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _psum_identity_bwd(x, axes):
    """``lax.psum`` forward, *identity* backward (Megatron's *f* operator).

    ``lax.psum``'s own transpose is ``psum`` — the right adjoint when every
    rank's output is a distinct loss contribution, but a ×W overcount under
    this codebase's convention that the loss is *replicated* over the model
    axis (every rank redundantly computes the same scalar).  A row-parallel
    output reduce must then pass the (already-full, replicated) cotangent
    straight through; the matching backward ``psum`` lives at the
    replicated→sharded *entry* instead (:func:`repro.models.common.
    grad_synced`)."""
    return lax.psum(x, axes)


def _psum_identity_bwd_fwd(x, axes):
    return lax.psum(x, axes), None


def _psum_identity_bwd_bwd(axes, _, ct):
    return (ct,)


_psum_identity_bwd.defvjp(_psum_identity_bwd_fwd, _psum_identity_bwd_bwd)


@dataclasses.dataclass(eq=False)
class CollectiveStats:
    """Trace-time counter of *data-axis* collectives.

    Attach one to a :class:`MeshCtx` (``MeshCtx(..., stats=CollectiveStats())``)
    and every ``psum_data`` / ``pmean_data`` / ``pmean_flat`` /
    ``allgather_flat`` call records the logical collective it issues — the
    count a real mesh would see.  Recording happens at Python trace time, so
    counts are exact for an eagerly executed step and count one trace for a
    jitted one.  Collectives that degenerate to the identity (empty
    ``data_axes``) are still recorded: the *would-be* communication pattern is
    what the benchmarks compare.

    Each record carries its transport ``kind``:

    * ``"reduce"`` — all-reduce pattern (``psum``/``pmean``): every worker
      contributes and receives ``size`` elements; traffic does not grow
      with the number of workers W (the paper's §3 scalability argument).
    * ``"gather"`` — all-gather pattern: every worker contributes ``size``
      elements and *receives* ``fanout·size`` (fanout = W), so wire bytes
      scale with the data-parallel world size.
    * ``"broadcast"`` — one-to-all pattern (``sync_mode="broadcast"``): the
      root contributes ``size`` elements and every worker receives ``size``;
      like a reduce, wire bytes are flat in W (a tree broadcast moves
      ``(W−1)/W·size`` per link), so it is recorded at face value with
      ``fanout=1``.

    ``itemsizes`` records the *actual* wire itemsize of each buffer (e.g. 2
    for a bfloat16 chunk, 1 for int8 sign payloads, fractional 0.5 for
    nibble-packed int4) — not a blanket float32 assumption — and
    ``overheads`` the per-collective sidecar bytes (the float32 scale per
    quantized slot), so ``bytes_per_collective`` is honest about the wire
    dtype, sub-byte packing, sidecars and the reduce-vs-gather scaling.
    """

    data_collectives: int = 0
    data_floats: int = 0
    sizes: List[int] = dataclasses.field(default_factory=list)
    itemsizes: List[float] = dataclasses.field(default_factory=list)
    kinds: List[str] = dataclasses.field(default_factory=list)
    fanouts: List[int] = dataclasses.field(default_factory=list)
    overheads: List[int] = dataclasses.field(default_factory=list)

    def record(self, n_elems: int, itemsize: float = 4, kind: str = "reduce",
               fanout: int = 1, overhead: int = 0) -> None:
        assert kind in ("reduce", "gather", "broadcast"), kind
        self.data_collectives += 1
        self.data_floats += int(n_elems)
        self.sizes.append(int(n_elems))
        i = float(itemsize)
        self.itemsizes.append(int(i) if i.is_integer() else i)
        self.kinds.append(kind)
        self.fanouts.append(int(fanout))
        self.overheads.append(int(overhead))

    def reset(self) -> None:
        self.data_collectives = 0
        self.data_floats = 0
        self.sizes.clear()
        self.itemsizes.clear()
        self.kinds.clear()
        self.fanouts.clear()
        self.overheads.clear()

    @property
    def reduce_collectives(self) -> int:
        return sum(1 for k in self.kinds if k == "reduce")

    @property
    def gather_collectives(self) -> int:
        return sum(1 for k in self.kinds if k == "gather")

    @property
    def broadcast_collectives(self) -> int:
        return sum(1 for k in self.kinds if k == "broadcast")

    def bytes_per_collective(self) -> List[float]:
        """Wire bytes per collective: ``size·itemsize + overhead``, using
        each buffer's recorded (possibly fractional) itemsize and its scale
        sidecar.  Integral entries come back as ints.

        Gather-pattern entries are scaled by their fanout (the data-parallel
        world size W): each worker receives every other worker's payload, so
        the bytes crossing a worker's NIC are W× the per-worker payload —
        the cost the paper's all-reduce argument avoids.
        """
        out = []
        for s, i, k, f, o in zip(self.sizes, self.itemsizes, self.kinds,
                                 self.fanouts, self.overheads):
            b = (s * i + o) * (f if k == "gather" else 1)
            out.append(int(b) if float(b).is_integer() else b)
        return out


# ---------------------------------------------------------------------------
# collective backends
# ---------------------------------------------------------------------------

class CollectiveBackend:
    """The primitive collectives :class:`MeshCtx` dispatches through.

    ``axes`` arguments are tuples of axis names (or a single name for the
    single-axis collectives) that are guaranteed non-empty by the caller —
    ``MeshCtx`` short-circuits empty axis sets to the identity before
    dispatching.
    """

    def psum(self, x, axes):
        raise NotImplementedError

    def pmean(self, x, axes):
        raise NotImplementedError

    def pmax(self, x, axes):
        raise NotImplementedError

    def all_gather(self, x, axis, *, gather_axis: int, tiled: bool):
        raise NotImplementedError

    def ppermute(self, x, axis, perm):
        raise NotImplementedError

    def all_to_all(self, x, axis, *, split_axis: int, concat_axis: int):
        raise NotImplementedError

    def axis_size(self, axes) -> int:
        raise NotImplementedError

    def axis_index(self, axis):
        raise NotImplementedError

    def broadcast0(self, x, axes, index):
        """Deliver rank 0's value to every rank along ``axes``.

        Implemented as a masked *unweighted* ``psum`` (every non-root
        contributes exact zeros), the standard one-to-all lowering on
        all-reduce-only transports.  Deliberately NOT overridden by
        :class:`SimBackend`: a broadcast is a control-plane replica sync,
        not a data aggregation, so scenario weights never apply — a
        weight-0 (dropped) root would otherwise destroy the payload.
        Bit-stability note: summing one value with W−1 exact ``+0.0``
        terms is exact in any association order, so this is bit-identical
        across substrates and reduction orders (modulo ``−0.0 → +0.0``,
        which both substrates flip identically).
        """
        return lax.psum(jnp.where(index == 0, x, jnp.zeros_like(x)), axes)


class AxisBackend(CollectiveBackend):
    """Named-axis collectives against the enclosing shard_map/vmap env."""

    def psum(self, x, axes):
        return lax.psum(x, axes)

    def pmean(self, x, axes):
        return lax.pmean(x, axes)

    def pmax(self, x, axes):
        return lax.pmax(x, axes)

    def all_gather(self, x, axis, *, gather_axis: int, tiled: bool):
        return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)

    def ppermute(self, x, axis, perm):
        return lax.ppermute(x, axis, perm)

    def all_to_all(self, x, axis, *, split_axis: int, concat_axis: int):
        return lax.all_to_all(x, axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)

    def axis_size(self, axes) -> int:
        n = 1
        for a in (axes if isinstance(axes, (tuple, list)) else (axes,)):
            n *= lax.axis_size(a)
        return n

    def axis_index(self, axis):
        return lax.axis_index(axis)


AXIS = AxisBackend()  # stateless — one shared instance


def _tree_sum(stacked: jax.Array) -> jax.Array:
    """Fixed pairwise-tree sum over the leading axis.

    The canonical reduction order behind ``sync_mode="broadcast"``: every
    rank gathers all W contributions in rank order and replays this exact
    expression tree, so the result is bit-identical across ranks *by
    construction* — and, because the tree is plain elementwise adds (which
    XLA does not reassociate), bit-identical between the ``shard_map`` and
    SimMesh substrates too.  This is the deterministic-allreduce recipe
    (reduce in a fixed order at a root, broadcast the result) executed
    redundantly on every rank instead of shipping the result separately.
    """
    n = stacked.shape[0]
    while n > 1:
        half = n // 2
        paired = stacked[0:2 * half:2] + stacked[1:2 * half:2]
        if n % 2:
            paired = jnp.concatenate([paired, stacked[2 * half:]], axis=0)
        stacked = paired
        n = stacked.shape[0]
    return stacked[0]


def weighted_mean(x, w, sum_fn):
    """``Σ w·x / Σ w`` with a guarded denominator, generic over how the sum
    is taken (``lax.psum`` over a named axis, ``jnp.sum`` over a stacked
    worker dim).  The single home of the weighted-aggregation semantics:
    :meth:`SimBackend.pmean` (wire-side weighting) and
    :meth:`repro.core.engine.Transport.combine_mean` (receiver-side
    weighting of gathered decodes) must stay exactly equal — the zoo
    conformance suite compares them bit-for-bit.

    The division happens in the weight dtype (f32): ``finfo.tiny`` would
    underflow to 0 if cast to a low-precision wire dtype, turning the
    all-dropped round into 0/0 = NaN instead of the documented exact zero.
    """
    total = sum_fn(w)
    numer = sum_fn(x * w.astype(x.dtype))
    denom = jnp.maximum(total, jnp.finfo(total.dtype).tiny)
    return (numer.astype(total.dtype) / denom).astype(x.dtype)


@dataclasses.dataclass(frozen=True, eq=False)
class SimBackend(AxisBackend):
    """W-logical-worker simulation backend (see :mod:`repro.core.simmesh`).

    Must run inside ``jax.vmap(..., axis_name=self.axis)`` over the stacked
    worker dimension; the named-axis collectives then lower to exact
    reductions over that stacked axis on one device.

    ``weight`` (optional) is this worker's scalar contribution weight — a
    traced value under ``vmap``, one scalar per worker.  It models
    heterogeneous per-worker batch sizes (weight ∝ local token count),
    worker dropout and straggler-skipped rounds (weight 0 for the affected
    round).  Weighted ``pmean`` is ``Σ w_i x_i / Σ w_i``; if every worker is
    dropped the aggregate degenerates to exactly zero (the denominator is
    guarded), i.e. the round becomes a no-op on the aggregated update.
    Weights apply to ``psum``/``pmean`` only — in simulation the context has
    no model/seq axes, so those are the data-parallel collectives.
    """

    axis: str
    size: int
    weight: Optional[jax.Array] = None

    def psum(self, x, axes):
        if self.weight is not None:
            x = x * self.weight.astype(x.dtype)
        return lax.psum(x, axes)

    def pmean(self, x, axes):
        if self.weight is None:
            return lax.pmean(x, axes)
        return weighted_mean(x, self.weight, lambda v: lax.psum(v, axes))

    def axis_size(self, axes) -> int:
        n = 1
        for a in (axes if isinstance(axes, (tuple, list)) else (axes,)):
            n *= self.size if a == self.axis else lax.axis_size(a)
        return n


@dataclasses.dataclass(frozen=True)
class MeshCtx:
    """Names of the mesh axes the current computation is mapped over.

    data_axes:  axes that carry data parallelism (gradient all-reduce),
                e.g. ``("pod", "data")`` or ``("data",)``.
    sync_mode:  how data-axis aggregates reach the ranks.  ``"allreduce"``
                (default) trusts the substrate's all-reduce to hand every
                rank the same value — true mathematically, but NOT at ULP
                level on real meshes (XLA's reduction order can be
                rank-dependent), which lets replicated state drift apart
                bit-wise over steps.  ``"broadcast"`` makes every data-axis
                aggregate replica-deterministic: contributions are gathered
                in rank order and reduced in one canonical pairwise-tree
                order (:func:`_tree_sum`) — logically a reduce-to-root
                followed by a rank-0 broadcast, and recorded in
                :class:`CollectiveStats` as those two legs (``"reduce"`` +
                ``"broadcast"``).  Fused transports can suppress the
                per-call broadcast leg (``sync=False``) and issue ONE real
                end-of-step rank-0 broadcast instead
                (:meth:`broadcast_flat`), keeping the collective budget at
                reduces + 1 broadcast per step.
    model_axis: axis carrying tensor/expert parallelism, e.g. ``"model"``.
    tp_grad_sync: whether :func:`repro.models.common.grad_synced` inserts
                the model-axis ``psum`` on backward cotangents at
                replicated→sharded boundaries.  ``True`` (default) is
                required for correct gradients whenever ``model_axis`` is
                set; ``False`` is a debug switch that reproduces the
                historical per-rank partial gradients (replicated params
                drift apart across model ranks — the divergence formerly
                misattributed to all-reduce nondeterminism in
                docs/checkpoint.md, pinned by tests/sim/test_drift.py).
    seq_axes:   axes over which a decode KV cache is sequence-sharded
                (flash-decode softmax merge): ``("model",)`` for decode_32k,
                ``("pod", "data", "model")`` for long_500k (batch=1).
    stats:      optional :class:`CollectiveStats` that records every data-axis
                collective issued through this context (excluded from eq/hash;
                purely observational).
    backend:    :class:`CollectiveBackend` the collectives dispatch through —
                :data:`AXIS` (production shard_map) by default, or a
                :class:`SimBackend` inside a :class:`~repro.core.simmesh.
                SimMesh` step (excluded from eq/hash: a ``SimBackend`` may
                hold traced per-worker weights).
    """

    data_axes: Tuple[str, ...] = ()
    model_axis: Optional[str] = None
    seq_axes: Tuple[str, ...] = ()
    sync_mode: str = "allreduce"
    tp_grad_sync: bool = True
    stats: Optional[CollectiveStats] = dataclasses.field(
        default=None, compare=False)
    backend: CollectiveBackend = dataclasses.field(
        default=AXIS, compare=False)

    def __post_init__(self):
        assert self.sync_mode in ("allreduce", "broadcast"), self.sync_mode

    def _record_data(self, x, kind: str = "reduce") -> None:
        if self.stats is not None:
            self.stats.record(
                x.size, jnp.dtype(x.dtype).itemsize, kind=kind,
                fanout=self.data_size() if kind == "gather" else 1)

    def _record_chunk(self, chunk, kind: str = "reduce") -> None:
        """Record a quantized wire chunk at its honest cost: fractional
        itemsize (0.5 for int4) plus the scale-sidecar overhead bytes."""
        if self.stats is not None:
            self.stats.record(
                chunk.size, chunk.wire_itemsize, kind=kind,
                fanout=self.data_size() if kind == "gather" else 1,
                overhead=chunk.overhead_bytes)

    @property
    def _synced(self) -> bool:
        return self.sync_mode == "broadcast" and bool(self.data_axes)

    def _canonical_reduce(self, x, *, mean: bool):
        """Replica-deterministic data-axis sum/mean (``sync_mode="broadcast"``).

        Gathers all W contributions in rank order and replays the fixed
        pairwise-tree reduction (:func:`_tree_sum`) identically on every
        rank — the result is bit-identical across ranks and across the
        shard_map/SimMesh substrates.  Honors a weighted :class:`SimBackend`
        with exactly :func:`weighted_mean`'s guarded-denominator semantics
        (the zoo conformance contract).
        """
        stacked = self.backend.all_gather(x, self.data_axes,
                                          gather_axis=0, tiled=False)
        weight = getattr(self.backend, "weight", None)
        if weight is None:
            total = _tree_sum(stacked)
            if not mean:
                return total
            return (total / self.data_size()).astype(x.dtype)
        wvec = self.backend.all_gather(jnp.reshape(weight, ()),
                                       self.data_axes,
                                       gather_axis=0, tiled=False)
        wb = wvec.reshape(wvec.shape + (1,) * x.ndim)
        numer = _tree_sum(stacked * wb.astype(x.dtype))
        if not mean:
            return numer
        total = _tree_sum(wvec)
        denom = jnp.maximum(total, jnp.finfo(total.dtype).tiny)
        return (numer.astype(total.dtype) / denom).astype(x.dtype)

    # -- data-parallel collectives (gradient aggregation) ------------------
    @scopes.scoped(scopes.EXCHANGE)
    def psum_data(self, x, *, sync: Optional[bool] = None):
        self._record_data(x)
        if not self.data_axes:
            return x
        if self._synced:
            if sync is not False:
                self._record_data(x, kind="broadcast")
            return self._canonical_reduce(x, mean=False)
        return self.backend.psum(x, self.data_axes)

    @scopes.scoped(scopes.EXCHANGE)
    def pmean_data(self, x, *, sync: Optional[bool] = None):
        self._record_data(x)
        if not self.data_axes:
            return x
        if self._synced:
            if sync is not False:
                self._record_data(x, kind="broadcast")
            return self._canonical_reduce(x, mean=True)
        return self.backend.pmean(x, self.data_axes)

    @scopes.scoped(scopes.EXCHANGE)
    def pmean_flat(self, parts: Sequence[jax.Array], *,
                   wire_dtype: str = "auto",
                   max_chunk_bytes: Optional[int] = None,
                   sync: Optional[bool] = None,
                   interleave: bool = False) -> List[jax.Array]:
        """Fused all-reduce-mean: O(1) collectives for a whole list of arrays.

        Ravels every part, concatenates into contiguous wire buffers (one per
        :class:`~repro.core.matrixize.FlatChunk` — see
        :func:`repro.core.matrixize.plan_flat` for the ``wire_dtype`` /
        ``max_chunk_bytes`` chunking policy), issues one ``pmean`` per chunk
        over the data axes, then splits back into the original shapes/dtypes.
        Because ``pmean`` is elementwise, this is numerically identical to
        per-part ``pmean_data`` calls (bit-identical when no wire cast
        applies) while replacing N latency-bound collectives with one
        bandwidth-bound one per chunk.

        ``wire_dtype="auto"`` keeps each part's own dtype (same-dtype parts
        share a chunk) — a mixed tree no longer silently upcasts a bfloat16
        payload because one float32 straggler rode along.  Each chunk's
        *actual* wire itemsize is recorded in :class:`CollectiveStats`.

        Under ``sync_mode="broadcast"`` each chunk reduces in the canonical
        deterministic order and records the extra ``"broadcast"`` leg;
        ``sync=False`` keeps the canonical order but suppresses that record
        — for multi-phase transports (PowerSGD's P/Q reduces) that issue
        one fused end-of-step :meth:`broadcast_flat` instead.

        ``wire_dtype="int8"``/``"int4"`` quantize each float chunk slot
        symmetrically before the reduce (integer parts keep their own
        chunks): values are snapped to the wire grid locally and the mean is
        taken over the dequantized float32 buffer — a widened accumulator,
        so the collective stays a plain all-reduce and error feedback sees
        the quantization error.  Stats record the honest quantized wire cost
        (1 byte/elem for int8, 0.5 for nibble-packed int4, + one float32
        scale per slot).

        ``interleave=True`` emits the double-buffered schedule instead of
        the serial one: the reduce for chunk b is issued *before* chunk b−1
        is unpacked, so no chunk's decompression sits between consecutive
        collectives in the dataflow graph and the runtime is free to overlap
        chunk b's wire time with chunk b−1's decode.  Chunks, wire bytes,
        reduction order and :class:`CollectiveStats` records (made at issue
        time) are identical to the serial schedule — only the unpack points
        move — so results are bit-identical and budget guards see the same
        trace.
        """
        from repro.core import matrixize  # local: dist must stay import-light

        parts = list(parts)
        if not parts:
            return []
        plan = matrixize.plan_flat(parts, wire_dtype=wire_dtype,
                                   max_chunk_bytes=max_chunk_bytes)

        def issue(chunk):
            if chunk.quant is not None:
                # quantize-before-reduce, widened accumulator: each worker
                # contributes exactly its wire-representable (dequantized)
                # values and the mean is taken in float32, so the transport
                # stays a plain all-reduce.  Recorded at the honest quantized
                # wire cost (fractional itemsize + scale sidecar).
                buf = matrixize.quant_dequant_flat(chunk, parts)
                self._record_chunk(chunk, "reduce")
            else:
                buf = matrixize.pack_flat(chunk, parts)
                self._record_data(buf)
            if self._synced:
                if sync is not False:
                    self._record_data(buf, kind="broadcast")
                return self._canonical_reduce(buf, mean=True)
            if self.data_axes:
                return self.backend.pmean(buf, self.data_axes)
            return buf

        out: dict = {}
        pending = None  # the in-flight (chunk, reduced buffer) pair
        for chunk in plan.chunks:
            buf = issue(chunk)
            if interleave:
                if pending is not None:
                    out.update(matrixize.unpack_flat(*pending))
                pending = (chunk, buf)
            else:
                out.update(matrixize.unpack_flat(chunk, buf))
        if pending is not None:
            out.update(matrixize.unpack_flat(*pending))
        return [out[i] for i in range(len(parts))]

    @scopes.scoped(scopes.EXCHANGE)
    def broadcast_flat(self, parts: Sequence[jax.Array], *,
                       wire_dtype: str = "auto",
                       max_chunk_bytes: Optional[int] = None) -> List[jax.Array]:
        """Fused rank-0 broadcast: every part replaced by rank 0's copy.

        The end-of-step replica-sync collective of ``sync_mode="broadcast"``:
        parts are packed into wire chunks exactly like :meth:`pmean_flat`
        and each chunk is delivered from rank 0 via the backend's masked
        unweighted psum (:meth:`CollectiveBackend.broadcast0`).  Recorded
        with ``kind="broadcast"``, bytes flat in W.  Outside any data axis
        (and on already replica-identical inputs) this is the identity.

        Quantized wire dtypes remap to ``"auto"`` here: the broadcast is a
        replica *sync* and must deliver rank 0's exact bits — lossy
        requantization of already-synced state would defeat its purpose.
        """
        from repro.core import matrixize

        if wire_dtype in matrixize.QUANT_WIRE_DTYPES:
            wire_dtype = "auto"
        parts = list(parts)
        if not parts:
            return []
        plan = matrixize.plan_flat(parts, wire_dtype=wire_dtype,
                                   max_chunk_bytes=max_chunk_bytes)
        idx = self.data_index()
        out: dict = {}
        for chunk in plan.chunks:
            buf = matrixize.pack_flat(chunk, parts)
            self._record_data(buf, kind="broadcast")
            if self.data_axes:
                buf = self.backend.broadcast0(buf, self.data_axes, idx)
            out.update(matrixize.unpack_flat(chunk, buf))
        return [out[i] for i in range(len(parts))]

    @scopes.scoped(scopes.EXCHANGE)
    def allgather_flat(self, parts: Sequence[jax.Array], *,
                       wire_dtype: str = "auto",
                       max_chunk_bytes: Optional[int] = None) -> List[jax.Array]:
        """Fused all-gather: O(1) collectives for a whole list of arrays.

        The gather-pattern sibling of :meth:`pmean_flat`, for compressed
        representations that are *not* linear (sign, top-K, sampled SVD
        triplets): the payloads themselves cannot be summed on the wire, so
        every worker must see every other worker's payload and decode all W
        of them.  Parts are fused into wire chunks exactly like
        :meth:`pmean_flat`; each chunk is gathered with ONE ``all_gather``
        over the data axes and each part comes back with a leading
        worker dimension of size ``data_size()`` (size 1 outside any data
        axis — same code path single-device and distributed).

        :class:`CollectiveStats` records these with ``kind="gather"`` and
        ``fanout=data_size()`` so ``bytes_per_collective`` reflects the
        W-scaled traffic — the cost the paper's all-reduce argument avoids.
        """
        from repro.core import matrixize

        parts = list(parts)
        if not parts:
            return []
        plan = matrixize.plan_flat(parts, wire_dtype=wire_dtype,
                                   max_chunk_bytes=max_chunk_bytes)
        w = self.data_size()
        out: dict = {}
        for chunk in plan.chunks:
            if chunk.quant is not None:
                # quantize-before-gather: the real integer payload crosses
                # the wire (nibble-packed for int4) with its per-slot scale
                # sidecar; every worker dequantizes all W payloads after the
                # gather.  One logical collective per chunk — the sidecar
                # rides it, counted as overhead bytes, not a new collective.
                payload, scales = matrixize.quant_pack_flat(chunk, parts)
                self._record_chunk(chunk, "gather")
                if self.data_axes:
                    payload = self.backend.all_gather(
                        payload, self.data_axes, gather_axis=0, tiled=False)
                    scales = self.backend.all_gather(
                        scales, self.data_axes, gather_axis=0, tiled=False)
                else:
                    payload, scales = payload[None], scales[None]
                out.update(matrixize.quant_unpack_flat(
                    chunk, payload, scales, leading=(w,)))
                continue
            buf = matrixize.pack_flat(chunk, parts)
            self._record_data(buf, kind="gather")
            if self.data_axes:
                buf = self.backend.all_gather(buf, self.data_axes,
                                              gather_axis=0, tiled=False)
            else:
                buf = buf[None]
            out.update(matrixize.unpack_flat(chunk, buf, leading=(w,)))
        return [out[i] for i in range(len(parts))]

    def gather_data_weight(self) -> Optional[jax.Array]:
        """All workers' contribution weights as a ``(data_size(),)`` vector,
        or ``None`` when the backend carries no per-worker weight (uniform).

        Gather-pattern aggregation averages *decoded* payloads on the
        receiver, so scenario weights (worker dropout, heterogeneous
        batches — :class:`SimBackend`) must travel with the payloads; the
        transport engine uses this to weight its combine step exactly like
        a weighted ``pmean``.
        """
        weight = getattr(self.backend, "weight", None)
        if weight is None:
            return None
        w = jnp.reshape(weight, ())
        if not self.data_axes:
            return w[None]
        return self.backend.all_gather(w[None], self.data_axes,
                                       gather_axis=0, tiled=True)

    # -- model-parallel collectives (tensor parallelism) --------------------
    def psum_model(self, x):
        if not self.model_axis:
            return x
        if self.tp_grad_sync and self.backend is AXIS:
            # Megatron f: reduce forward, identity backward — paired with the
            # backward psum grad_synced inserts at replicated→sharded entries
            return _psum_identity_bwd(x, self.model_axis)
        return self.backend.psum(x, self.model_axis)

    def pmean_model(self, x):
        return self.backend.pmean(x, self.model_axis) if self.model_axis else x

    def pmax_model(self, x):
        return self.backend.pmax(x, self.model_axis) if self.model_axis else x

    def all_gather_model(self, x, axis: int = -1, tiled: bool = True):
        if self.model_axis is None:
            return x
        return self.backend.all_gather(x, self.model_axis, gather_axis=axis,
                                       tiled=tiled)

    def ppermute_model(self, x, perm):
        if self.model_axis is None:
            return x
        return self.backend.ppermute(x, self.model_axis, perm)

    def all_to_all_model(self, x, split_axis: int, concat_axis: int):
        """Re-distribute: split ``split_axis`` over the model axis, gather
        ``concat_axis`` (e.g. column-sharded → row-sharded activations)."""
        if self.model_axis is None:
            return x
        return self.backend.all_to_all(x, self.model_axis,
                                       split_axis=split_axis,
                                       concat_axis=concat_axis)

    # -- sequence-shard collectives (flash-decode merge) ---------------------
    def psum_seq(self, x):
        return self.backend.psum(x, self.seq_axes) if self.seq_axes else x

    def pmax_seq(self, x):
        return self.backend.pmax(x, self.seq_axes) if self.seq_axes else x

    # -- sizes / indices ----------------------------------------------------
    def data_size(self) -> int:
        return self.backend.axis_size(self.data_axes) if self.data_axes else 1

    def model_size(self) -> int:
        return self.backend.axis_size(self.model_axis) if self.model_axis else 1

    def seq_size(self) -> int:
        return self.backend.axis_size(self.seq_axes) if self.seq_axes else 1

    def model_index(self):
        if self.model_axis is None:
            return 0
        return self.backend.axis_index(self.model_axis)

    def seq_index(self):
        """Linearised index over the seq axes (row-major)."""
        if not self.seq_axes:
            return 0
        idx = 0
        for a in self.seq_axes:
            idx = idx * self.backend.axis_size((a,)) + self.backend.axis_index(a)
        return idx

    def data_index(self):
        """Linearised index over the data axes (row-major)."""
        if not self.data_axes:
            return 0
        idx = 0
        for a in self.data_axes:
            idx = idx * self.backend.axis_size((a,)) + self.backend.axis_index(a)
        return idx


SINGLE = MeshCtx()  # single-device context: all collectives are identities


# ---------------------------------------------------------------------------
# gradlint attribution contract (repro.analysis)
# ---------------------------------------------------------------------------
# Every data-axis collective a traced step emits must reach the wire through
# one of these MeshCtx entry points — the static analyzer attributes each
# collective primitive in a jaxpr to the innermost frame of its traceback
# that names one of them, and flags any data-axis collective whose call
# chain passes through none (a hand-rolled collective escapes both the
# budget and the byte accounting).  Kept here, next to the entry points
# themselves, so adding a transport path and forgetting the ledger is a
# one-file diff review.

#: jaxpr primitive names that move bytes across a named axis
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "pmax", "pmin", "all_gather", "ppermute", "all_to_all",
    "reduce_scatter", "pbroadcast",
})

#: dist.py function name -> logical collective kind, matching the ``kind``
#: each site records into :class:`CollectiveStats`.  ``issue`` is
#: ``pmean_flat``'s per-chunk closure; ``_canonical_reduce`` is the
#: deterministic gather+tree-sum lowering of a reduce under
#: ``sync_mode="broadcast"`` (one all_gather primitive, kind "reduce").
COLLECTIVE_SITES = {
    "psum_data": "reduce",
    "pmean_data": "reduce",
    "pmean_flat": "reduce",
    "issue": "reduce",
    "_canonical_reduce": "reduce",
    "allgather_flat": "gather",
    "gather_data_weight": "gather",
    "broadcast_flat": "broadcast",
    "broadcast0": "broadcast",
}


def quant_sidecar_line() -> int:
    """Source line of the scale-sidecar ``all_gather`` in
    :meth:`MeshCtx.allgather_flat` (the ``scales = self.backend.all_gather``
    call).  A quantized gather ships its integer payload and its float32
    per-slot scales as two backend all_gathers but ONE logical collective —
    the analyzer folds the primitive at this line into its payload gather.
    Recomputed from the live source so edits to this module cannot stale it.
    """
    import ast as _ast
    import functools
    import inspect

    @functools.lru_cache(maxsize=1)
    def _find() -> int:
        src, base = inspect.getsourcelines(MeshCtx.allgather_flat)
        tree = _ast.parse("".join(
            line[4:] if line.startswith("    ") else line for line in src))
        for node in _ast.walk(tree):
            if (isinstance(node, _ast.Assign)
                    and isinstance(node.targets[0], _ast.Name)
                    and node.targets[0].id == "scales"
                    and isinstance(node.value, _ast.Call)):
                return base + node.lineno - 1
        raise AssertionError(
            "gradlint: scale-sidecar all_gather not found in allgather_flat")

    return _find()
