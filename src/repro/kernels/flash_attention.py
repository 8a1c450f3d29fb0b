"""Causal flash attention as Pallas TPU kernels, forward and backward.

The attention core of training and prefill on the TPU: softmax(q kᵀ / √d)
v under a causal mask, with q, k and v float32 in HBM.  Each (block_q ×
block_k) tile of scores lives in VMEM only; the softmax runs online over
the key blocks (running max and sum, float32), and the forward saves the
row logsumexp, from which the backward recomputes the probabilities.  Key
blocks wholly above the diagonal are neither computed nor loaded: each
kernel's last grid axis runs over the visible blocks only, their indices
in tables prefetched to scalar memory, so such a block takes no grid step
and no copy.  Only blocks the diagonal crosses build the element mask.
Blocks are square, 1024 rows at head_dim 128 (fewer at wider heads or
where 1024 does not divide the sequence): on a TPU v5e the three kernels
ran 14-16% faster than at 512 with a step for every block.

Precision is that of an XLA float32 dot at the default precision on the
TPU: each matmul takes bfloat16 operands, cast in the kernel just before
the dot, and accumulates in float32; everything else, and every array in
HBM (q, k, v, the output and their cotangents), is float32.

Grouped-query attention without expansion: q has ``Hq`` heads, k and v
``Hkv`` with ``Hq % Hkv == 0``, and q head ``h`` reads kv head ``h //
(Hq // Hkv)``.  Heads stay where the projections put them, ``(B, S, H,
D)``; a block is one head's ``D`` lanes of ``block`` rows.

Three kernels, after the structure of jax's
``pallas/ops/tpu/flash_attention.py``: the forward (grid batch × q head ×
visible (q block, kv block), q block by q block), dk/dv (batch × kv head
× visible (kv block, group member, q block), in the transposed
orientation sᵀ = k qᵀ so that the logsumexp and the ``rowsum(o·do)`` term
are rows), and dq (as the forward).

Checked in interpret mode against ``models/attention.py``'s chunked XLA
path (``tests/test_flash_attention.py``); compiled by Mosaic for a TPU
v5e at Qwen3-4B and OLMoE widths (``tests/test_chip_compile.py``).
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
BLOCKS = (1024, 512, 256, 128)  # square blocks, the largest that fits
MASK = -0.7 * float(jnp.finfo(jnp.float32).max)   # exp(MASK - m) == 0, no NaN
NT = (((1,), (1,)), ((), ()))   # a @ bᵀ
NN = (((1,), (0,)), ((), ()))   # a @ b


def applies(seq: int, head_dim: int, window: int) -> bool:
    """Whether the kernels take this attention: full causal, whole lanes
    per head, and a block that divides the sequence."""
    return (window == 0 and head_dim % LANE == 0
            and _blocks(seq, head_dim) is not None)


def _blocks(seq: int, head_dim: int):
    """(block_q, block_k): the largest of ``BLOCKS`` that divides ``seq``
    and keeps a block of q, k or v at 1024 x 128 floats or fewer; None if
    there is none."""
    fits = [b for b in BLOCKS
            if seq % b == 0 and b * head_dim <= BLOCKS[0] * LANE]
    return (fits[0], fits[0]) if fits else None


def _dot(a, b, dims):
    return lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                           dims, preferred_element_type=jnp.float32)


def _visible(i, j, bq, bk):
    """Some key of kv block ``j`` is at or before some query of q block
    ``i``."""
    return (i + 1) * bq - 1 >= j * bk


def _crossed(i, j, bq, bk):
    """The diagonal crosses block (i, j): some key comes after some
    query."""
    return (j + 1) * bk - 1 > i * bq


def _last(i, bq, bk):
    """The last kv block that q block ``i`` sees."""
    return ((i + 1) * bq - 1) // bk


def _causal(i, j, shape, q_axis):
    """Mask of block (i, j), keys at or before their query; ``q_axis`` is
    the axis of ``shape`` that runs over queries."""
    bq, bk = shape[q_axis], shape[1 - q_axis]
    qpos = i * bq + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = j * bk + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return kpos <= qpos


def _run(i, j, bq, bk, body):
    """``body(masked)``, masked where the diagonal crosses block (i, j)."""
    crossed = _crossed(i, j, bq, bk)
    pl.when(crossed)(lambda: body(True))
    pl.when(jnp.logical_not(crossed))(lambda: body(False))


def _steps(*ranges, visible):
    """The grid's last axis: one step per visible block, as int32 tables
    of each of ``ranges``' index, the first range outermost."""
    steps = [ix for ix in itertools.product(*map(range, ranges))
             if visible(*ix)]
    return [jnp.array(col, jnp.int32) for col in zip(*steps)]


def _lanes(x, n):
    """A lane-replicated (rows, LANE) array widened to (rows, n)."""
    return x if n == LANE else jnp.tile(x, (1, n // LANE))


def _call(kernel, steps, lead, **kw):
    """``pallas_call`` over grid ``lead + (len(steps[0]),)``, the step
    tables prefetched to scalar memory and handed to every index map."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(steps), grid=(*lead, len(steps[0])),
            in_specs=kw.pop("in_specs"), out_specs=kw.pop("out_specs"),
            scratch_shapes=kw.pop("scratch_shapes")),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        **kw)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc,
                l_sc, acc_sc, *, scale, bq, bk):
    t = pl.program_id(2)
    i, j = qi_ref[t], kj_ref[t]
    d = acc_sc.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, MASK)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def body(masked):
        s = _dot(q_ref[...], k_ref[...], NT) * scale            # (bq, bk)
        if masked:
            s = jnp.where(_causal(i, j, s.shape, 0), s, MASK)
        m_prev = m_sc[...]                                       # (bq, LANE)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - _lanes(m_next, bk))
        m_sc[...] = m_next
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = (acc_sc[...] * _lanes(alpha, d)
                       + _dot(p, v_ref[...], NN))

    _run(i, j, bq, bk, body)

    @pl.when(j == _last(i, bq, bk))
    def _finish():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / _lanes(l, d)).astype(o_ref.dtype)
        lse_ref[...] = m_sc[...] + jnp.log(l)


def _forward(q, k, v, interpret):
    """(o, lse): o like q; lse (B, Hq, S, LANE), lane-replicated."""
    b, s, hq, d = q.shape
    group = hq // k.shape[2]
    bq, bk = _blocks(s, d)
    steps = _steps(s // bq, s // bk,
                   visible=lambda i, j: _visible(i, j, bq, bk))
    row = pl.BlockSpec((None, bq, d), lambda b_, h, t, qi, kj: (b_, qi[t], h))
    kv = pl.BlockSpec((None, bk, d),
                      lambda b_, h, t, qi, kj: (b_, kj[t], h // group))
    o, lse = _call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d), bq=bq,
                          bk=bk),
        steps, (b, hq),
        in_specs=[row, kv, kv],
        out_specs=[row, pl.BlockSpec((None, None, bq, LANE),
                                     lambda b_, h, t, qi, kj:
                                     (b_, h, qi[t], 0))],
        out_shape=[jax.ShapeDtypeStruct((b, s, hq * d), q.dtype),
                   jax.ShapeDtypeStruct((b, hq, s, LANE), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, LANE), jnp.float32),
                        pltpu.VMEM((bq, LANE), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_fwd",
    )(*steps, *(x.reshape(b, s, -1) for x in (q, k, v)))
    return o.reshape(q.shape), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dkv_kernel(kj_ref, g_ref, qi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                di_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, scale, bq, bk,
                group, nq):
    t = pl.program_id(2)
    j, g, i = kj_ref[t], g_ref[t], qi_ref[t]

    @pl.when(jnp.logical_and(g == 0, i == (j * bk) // bq))
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def body(masked):
        q, do = q_ref[...], do_ref[...]
        st = _dot(k_ref[...], q, NT) * scale                     # (bk, bq)
        if masked:
            st = jnp.where(_causal(i, j, st.shape, 1), st, MASK)
        pt = jnp.exp(st - lse_ref[...])                          # rows (1, bq)
        dv_sc[...] += _dot(pt, do, NN)
        dpt = _dot(v_ref[...], do, NT)
        dk_sc[...] += _dot(pt * (dpt - di_ref[...]), q, NN)

    _run(i, j, bq, bk, body)

    @pl.when(jnp.logical_and(g == group - 1, i == nq - 1))
    def _finish():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _dq_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
               dq_ref, dq_sc, *, scale, bq, bk):
    t = pl.program_id(2)
    i, j = qi_ref[t], kj_ref[t]

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def body(masked):
        k = k_ref[...]
        s = _dot(q_ref[...], k, NT) * scale                      # (bq, bk)
        if masked:
            s = jnp.where(_causal(i, j, s.shape, 0), s, MASK)
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
        dp = _dot(do_ref[...], v_ref[...], NT)
        dq_sc[...] += _dot(p * (dp - jnp.expand_dims(di_ref[0], -1)), k, NN)

    _run(i, j, bq, bk, body)

    @pl.when(j == _last(i, bq, bk))
    def _finish():
        dq_ref[...] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _backward(q, k, v, o, lse, do, interpret):
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    bq, bk = _blocks(s, d)
    nq, nk = s // bq, s // bk
    scale = 1.0 / math.sqrt(d)
    # rows (B, Hq, 1, S): the logsumexp, and rowsum(o·do) of dsoftmax
    lse = lse[..., 0][:, :, None, :]
    di = jnp.sum(o * do, axis=-1).transpose(0, 2, 1)[:, :, None, :]
    q2, k2, v2, do2 = (x.reshape(b, s, -1) for x in (q, k, v, do))

    # dk, dv: kv block j gathers, over its group's q heads, the q blocks
    # that see it
    steps = _steps(nk, group, nq,
                   visible=lambda j, g, i: _visible(i, j, bq, bk))
    q_row = pl.BlockSpec((None, bq, d), lambda b_, h, t, kj, g, qi:
                         (b_, qi[t], h * group + g[t]))
    q_stat = pl.BlockSpec((None, None, 1, bq), lambda b_, h, t, kj, g, qi:
                          (b_, h * group + g[t], 0, qi[t]))
    kv_row = pl.BlockSpec((None, bk, d),
                          lambda b_, h, t, kj, g, qi: (b_, kj[t], h))
    dk, dv = _call(
        functools.partial(_dkv_kernel, scale=scale, bq=bq, bk=bk,
                          group=group, nq=nq),
        steps, (b, hkv),
        in_specs=[q_row, kv_row, kv_row, q_row, q_stat, q_stat],
        out_specs=[kv_row, kv_row],
        out_shape=[jax.ShapeDtypeStruct(k2.shape, k.dtype),
                   jax.ShapeDtypeStruct(v2.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dkv",
    )(*steps, q2, k2, v2, do2, lse, di)

    # dq: q block i gathers the kv blocks it sees
    steps = _steps(nq, nk, visible=lambda i, j: _visible(i, j, bq, bk))
    row = pl.BlockSpec((None, bq, d), lambda b_, h, t, qi, kj: (b_, qi[t], h))
    stat = pl.BlockSpec((None, None, 1, bq),
                        lambda b_, h, t, qi, kj: (b_, h, 0, qi[t]))
    kv = pl.BlockSpec((None, bk, d),
                      lambda b_, h, t, qi, kj: (b_, kj[t], h // group))
    dq = _call(
        functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk),
        steps, (b, hq),
        in_specs=[row, kv, kv, row, stat, stat],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct(q2.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(*steps, q2, k2, v2, do2, lse, di)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attention(q, k, v, interpret):
    return _forward(q, k, v, interpret)[0]


def _attention_fwd(q, k, v, interpret):
    o, lse = _forward(q, k, v, interpret)
    return o, (q, k, v, o, lse)


def _attention_bwd(interpret, res, do):
    return _backward(*res, do, interpret)


_attention.defvjp(_attention_fwd, _attention_bwd)


def causal_attention(q, k, v, *, interpret: bool = False):
    """Causal softmax(q kᵀ / √d) v.  q: (B, S, Hq, D); k, v: (B, S, Hkv, D)
    with ``Hq % Hkv == 0``; ``applies(S, D, 0)`` must hold."""
    b, s, hq, d = q.shape
    if not applies(s, d, 0) or hq % k.shape[2] or k.shape != v.shape:
        raise ValueError(f"no flash attention for q {q.shape}, k {k.shape}, "
                         f"v {v.shape}")
    return _attention(q, k, v, interpret)
