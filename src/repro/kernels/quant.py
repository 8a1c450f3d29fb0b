"""Pallas kernels for the int4 wire format: nibble pack/unpack (ISSUE 9).

The fused transport ships int4 payloads as two's-complement nibbles, two
per uint8 byte (``repro.core.matrixize`` quantizes each flat-plan slot with
a symmetric per-slot scale first).  These kernels do the byte-level
combine/split on the VPU: the host strides the flat code vector into its
even/odd halves (a layout change XLA fuses away), pads to the 128-lane
width, and one elementwise grid kernel packs or unpacks a block at a time.

Checked bit-exactly against :mod:`repro.kernels.ref` in interpret mode
(``tests/test_wire_quant.py``) and compiled for a TPU v5e
(``tests/test_chip_compile.py``); the CPU/test substrates use the reference
path via the :mod:`repro.kernels.ops` dispatcher.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128          # VPU lane width
BLOCK_ROWS = 256    # rows per grid step (multiple of the int8 32-sublane tile)


def _pack_kernel(lo_ref, hi_ref, o_ref):
    """o = (lo & 0xF) | ((hi & 0xF) << 4), elementwise over one block.

    The bit arithmetic runs on int32: Mosaic has no 8-bit vector shift."""
    lo = lo_ref[...].astype(jnp.int32) & 0xF
    hi = hi_ref[...].astype(jnp.int32) & 0xF
    o_ref[...] = (lo | (hi << 4)).astype(jnp.uint8)


def _unpack_kernel(p_ref, lo_ref, hi_ref):
    """Split each byte into sign-extended low/high int4 codes."""
    p = p_ref[...].astype(jnp.int32)
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    lo_ref[...] = jnp.where(lo >= 8, lo - 16, lo).astype(jnp.int8)
    hi_ref[...] = jnp.where(hi >= 8, hi - 16, hi).astype(jnp.int8)


def _to_blocks(flat, rows_pad):
    k = flat.shape[0]
    total = rows_pad * LANE
    return jnp.pad(flat, (0, total - k)).reshape(rows_pad, LANE)


def _grid_rows(k):
    rows = max(1, -(-k // LANE))
    return (-rows) % BLOCK_ROWS + rows if rows > BLOCK_ROWS else rows


def nibble_pack(q, *, interpret=None):
    """Pack flat int4 codes (int8 in [-8, 7], shape ``(n,)``) two-per-byte.

    Same contract as :func:`repro.kernels.ref.nibble_pack`: even indices →
    low nibble, odd → high, odd-length tail zero-padded; returns uint8 of
    length ceil(n/2)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n = q.shape[0]
    half = (n + 1) // 2
    qp = jnp.pad(q, (0, 2 * half - n))
    lo, hi = qp[0::2], qp[1::2]
    rows = _grid_rows(half)
    br = min(BLOCK_ROWS, rows)
    out = pl.pallas_call(
        _pack_kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, LANE), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((br, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.uint8),
        interpret=interpret,
    )(_to_blocks(lo, rows), _to_blocks(hi, rows))
    return out.reshape(-1)[:half]


def nibble_unpack(packed, n, *, interpret=None):
    """Inverse of :func:`nibble_pack`: ``(ceil(n/2),)`` uint8 → ``(n,)``
    int8 codes in [-8, 7]."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    half = packed.shape[0]
    rows = _grid_rows(half)
    br = min(BLOCK_ROWS, rows)
    lo, hi = pl.pallas_call(
        _unpack_kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, LANE), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, LANE), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((rows, LANE), jnp.int8)] * 2,
        interpret=interpret,
    )(_to_blocks(packed, rows))
    inter = jnp.stack([lo.reshape(-1)[:half], hi.reshape(-1)[:half]],
                      axis=-1).reshape(2 * half)
    return inter[:n]
