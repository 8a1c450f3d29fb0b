"""Pallas TPU kernels for the PowerSGD hot loop: the two tall-skinny
matmuls P = M Q and Q = Mᵀ P̂ over every gradient matrix, every step.

TPU adaptation: the gradient matrix M streams HBM→VMEM in (block_n ×
block_k) tiles; the skinny factor (rank r ≤ 32) is padded to the 128-lane
MXU width and kept resident in VMEM across the reduction dimension of the
grid.  fp32 accumulation in the output block.

Batched operation (the bucketed compression engine's hot path): 3-D inputs
``(B, n, k)`` run through kernels with a *leading batch grid dimension* —
grid ``(B, n/bn, k/bk)`` with block size 1 on the batch axis — so one
``pallas_call`` covers a whole shape bucket instead of dispatching one
kernel per matrix (vmap would trace B copies; the batch grid dim is a
single program).  Higher-rank inputs are flattened into the batch dim.

Checked against :mod:`repro.kernels.ref` in interpret mode on the CPU
(``tests/test_kernels.py``), and compiled by Mosaic for a TPU v5e at
qwen3-4b widths (``tests/test_chip_compile.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128          # MXU/VPU lane width: pad the rank dim up to this
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_K = 512


def _project_kernel(m_ref, q_ref, o_ref):
    """Grid (n/bn, k/bk): o[i] += m[i,j] @ q[j]."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(m_ref[...], q_ref[...],
                          preferred_element_type=jnp.float32)


def _project_2d(m, q, block_n, block_k, interpret):
    n, k = m.shape
    _, r = q.shape
    bn = min(block_n, n)
    bk = min(block_k, k)
    # pad every dim to its block/lane multiple (zero rows/cols are exact)
    np_, kp, rp = (-n) % bn + n, (-k) % bk + k, (-r) % LANE + r
    mp = jnp.pad(m, ((0, np_ - n), (0, kp - k)))
    qp = jnp.pad(q, ((0, kp - k), (0, rp - r)))
    out = pl.pallas_call(
        _project_kernel,
        grid=(np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bk, rp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, rp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, rp), jnp.float32),
        interpret=interpret,
    )(mp, qp)
    return out[:n, :r].astype(m.dtype)


def _backproject_kernel(m_ref, p_ref, o_ref):
    """Grid (k/bk, n/bn): o[i] += m[j,i]ᵀ @ p[j]."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(m_ref[...].T, p_ref[...],
                          preferred_element_type=jnp.float32)


def _backproject_2d(m, p_hat, block_n, block_k, interpret):
    n, k = m.shape
    _, r = p_hat.shape
    bk = min(block_k, k)
    bn = min(block_n, n)
    np_, kp, rp = (-n) % bn + n, (-k) % bk + k, (-r) % LANE + r
    mp = jnp.pad(m, ((0, np_ - n), (0, kp - k)))
    pp = jnp.pad(p_hat, ((0, np_ - n), (0, rp - r)))
    out = pl.pallas_call(
        _backproject_kernel,
        grid=(kp // bk, np_ // bn),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j: (j, i)),
            pl.BlockSpec((bn, rp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bk, rp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, rp), jnp.float32),
        interpret=interpret,
    )(mp, pp)
    return out[:k, :r].astype(m.dtype)


def _project_kernel_batched(m_ref, q_ref, o_ref):
    """Grid (B, n/bn, k/bk): o[b, i] += m[b, i, j] @ q[b, j]."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(m_ref[0], q_ref[0],
                          preferred_element_type=jnp.float32)[None]


def _project_3d(m, q, block_n, block_k, interpret):
    b, n, k = m.shape
    _, _, r = q.shape
    bn = min(block_n, n)
    bk = min(block_k, k)
    np_, kp, rp = (-n) % bn + n, (-k) % bk + k, (-r) % LANE + r
    mp = jnp.pad(m, ((0, 0), (0, np_ - n), (0, kp - k)))
    qp = jnp.pad(q, ((0, 0), (0, kp - k), (0, rp - r)))
    out = pl.pallas_call(
        _project_kernel_batched,
        grid=(b, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((1, bn, bk), lambda b_, i, j: (b_, i, j)),
            pl.BlockSpec((1, bk, rp), lambda b_, i, j: (b_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn, rp), lambda b_, i, j: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, np_, rp), jnp.float32),
        interpret=interpret,
    )(mp, qp)
    return out[:, :n, :r].astype(m.dtype)


def _backproject_kernel_batched(m_ref, p_ref, o_ref):
    """Grid (B, k/bk, n/bn): o[b, i] += m[b, j, i]ᵀ @ p[b, j]."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(m_ref[0].T, p_ref[0],
                          preferred_element_type=jnp.float32)[None]


def _backproject_3d(m, p_hat, block_n, block_k, interpret):
    b, n, k = m.shape
    _, _, r = p_hat.shape
    bk = min(block_k, k)
    bn = min(block_n, n)
    np_, kp, rp = (-n) % bn + n, (-k) % bk + k, (-r) % LANE + r
    mp = jnp.pad(m, ((0, 0), (0, np_ - n), (0, kp - k)))
    pp = jnp.pad(p_hat, ((0, 0), (0, np_ - n), (0, rp - r)))
    out = pl.pallas_call(
        _backproject_kernel_batched,
        grid=(b, kp // bk, np_ // bn),
        in_specs=[
            pl.BlockSpec((1, bn, bk), lambda b_, i, j: (b_, j, i)),
            pl.BlockSpec((1, bn, rp), lambda b_, i, j: (b_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bk, rp), lambda b_, i, j: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kp, rp), jnp.float32),
        interpret=interpret,
    )(mp, pp)
    return out[:, :k, :r].astype(m.dtype)


def _batched(fn2d, fn3d):
    """Route by rank: 2-D → single-matrix kernel; ≥3-D → flatten the leading
    dims into the kernels' batch grid dimension (one pallas_call per call,
    however many matrices the bucket holds)."""

    @functools.wraps(fn2d)
    def wrapped(m, other, *, block_n=DEFAULT_BLOCK_N, block_k=DEFAULT_BLOCK_K,
                interpret=None):
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        if m.ndim == 2:
            return fn2d(m, other, block_n=block_n, block_k=block_k,
                        interpret=interpret)
        batch = m.shape[:-2]
        mf = m.reshape((-1,) + m.shape[-2:])
        of = other.reshape((-1,) + other.shape[-2:])
        out = fn3d(mf, of, block_n=block_n, block_k=block_k,
                   interpret=interpret)
        return out.reshape(batch + out.shape[-2:])

    return wrapped


lowrank_project = _batched(_project_2d, _project_3d)
lowrank_backproject = _batched(_backproject_2d, _backproject_3d)
