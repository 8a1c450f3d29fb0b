"""Fused error-feedback apply kernel (Alg. 2 lines 11-13).

Unfused, the decompress → momentum → parameter update chain makes three
full-size round-trips over HBM per gradient matrix (materialise Δ' = P̂ Qᵀ,
update momentum, update params).  This kernel streams each (bn × bm) tile
once: the low-rank factors live in VMEM, Δ' is reconstructed on the fly in
registers, and momentum/params are read-modify-written in a single pass —
one HBM round-trip instead of three.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lowrank import LANE

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_M = 512


def _ef_apply_kernel(x_ref, mom_ref, p_ref, q_ref, lr_ref, lam_ref,
                     x_out, mom_out):
    delta = jnp.dot(p_ref[...], q_ref[...].T,
                    preferred_element_type=jnp.float32)
    lam = lam_ref[0]
    lr = lr_ref[0]
    new_mom = lam * mom_ref[...] + delta
    x_out[...] = x_ref[...] - lr * (delta + new_mom)
    mom_out[...] = new_mom


def _ef_apply_2d(x, mom, p_hat, q, lr, lam, block_n, block_m, interpret):
    n, m = x.shape
    r = q.shape[-1]
    bn, bm = min(block_n, n), min(block_m, m)
    np_, mp_, rp = (-n) % bn + n, (-m) % bm + m, (-r) % LANE + r
    xp = jnp.pad(x, ((0, np_ - n), (0, mp_ - m)))
    momp = jnp.pad(mom, ((0, np_ - n), (0, mp_ - m)))
    pp = jnp.pad(p_hat, ((0, np_ - n), (0, rp - r)))
    qp = jnp.pad(q, ((0, mp_ - m), (0, rp - r)))
    lr_arr = jnp.asarray(lr, jnp.float32).reshape(1)
    lam_arr = jnp.asarray(lam, jnp.float32).reshape(1)
    x2, mom2 = pl.pallas_call(
        _ef_apply_kernel,
        grid=(np_ // bn, mp_ // bm),
        in_specs=[
            pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
            pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
            pl.BlockSpec((bn, rp), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, rp), lambda i, j: (j, 0)),
            # lr and λ: Mosaic loads scalars from SMEM, never from ANY
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
            pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, mp_), jnp.float32),
            jax.ShapeDtypeStruct((np_, mp_), jnp.float32),
        ],
        interpret=interpret,
    )(xp, momp, pp, qp, lr_arr, lam_arr)
    return x2[:n, :m].astype(x.dtype), mom2[:n, :m].astype(mom.dtype)


def ef_apply(x, mom, p_hat, q, lr, lam, *, block_n=DEFAULT_BLOCK_N,
             block_m=DEFAULT_BLOCK_M, interpret=None):
    """Batched fused apply; leading dims of x/mom/p_hat/q are batch dims."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    f = functools.partial(_ef_apply_2d, lr=lr, lam=lam, block_n=block_n,
                          block_m=block_m, interpret=interpret)
    if x.ndim == 2:
        return f(x, mom, p_hat, q)
    batch = x.shape[:-2]
    out = jax.vmap(lambda a, b, c, d: f(a, b, c, d))(
        x.reshape((-1,) + x.shape[-2:]),
        mom.reshape((-1,) + mom.shape[-2:]),
        p_hat.reshape((-1,) + p_hat.shape[-2:]),
        q.reshape((-1,) + q.shape[-2:]),
    )
    return out[0].reshape(batch + x.shape[-2:]), out[1].reshape(batch + x.shape[-2:])
