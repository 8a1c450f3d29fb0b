"""Property suite for the quantized wire formats (ISSUE 9).

Round-trip laws for the int4 nibble pack/unpack pair (identity on
representable codes, odd-length tail padding, Pallas interpret-mode kernel
bit-exact against the pure-jnp reference), per-slot symmetric scale
correctness, and the elementwise quantization error bound
|x − dequant(quant(x))| ≤ scale/2 that error feedback relies on.

Runs real hypothesis when installed, else the bundled fallback sampler.
"""

import numpy as np

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - exercised on minimal images
    from _hypothesis_fallback import given, settings, strategies as st

import jax.numpy as jnp
import pytest

from repro.core import matrixize
from repro.kernels import ops, quant, ref


def _codes(n, seed, qmax=7):
    rng = np.random.default_rng(seed)
    return rng.integers(-qmax, qmax + 1, size=n).astype(np.int8)


# ---------------------------------------------------------------------------
# nibble pack/unpack round-trip laws
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=30)
@given(n=st.integers(min_value=1, max_value=700),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_nibble_roundtrip_identity(n, seed):
    """unpack ∘ pack == identity on representable int4 codes, any length."""
    codes = _codes(n, seed)
    packed = ref.nibble_pack(jnp.asarray(codes))
    assert packed.dtype == jnp.uint8
    assert packed.shape == ((n + 1) // 2,)
    back = ref.nibble_unpack(packed, n)
    np.testing.assert_array_equal(np.asarray(back), codes)


@settings(deadline=None, max_examples=20)
@given(n=st.integers(min_value=1, max_value=301),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_nibble_odd_tail_padding(n, seed):
    """An odd-length vector's last byte carries a zero high nibble, and the
    padding code never leaks back out of unpack."""
    n = 2 * (n // 2) + 1  # force odd
    codes = _codes(n, seed)
    packed = np.asarray(ref.nibble_pack(jnp.asarray(codes)))
    assert packed[-1] >> 4 == 0
    assert np.asarray(ref.nibble_unpack(jnp.asarray(packed), n)).shape == (n,)


@settings(deadline=None, max_examples=20)
@given(n=st.integers(min_value=1, max_value=1000),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_pallas_matches_reference_bitexact(n, seed):
    """Pallas interpret-mode kernels ≡ the pure-jnp reference, both ways."""
    codes = jnp.asarray(_codes(n, seed))
    ref_packed = ref.nibble_pack(codes)
    pl_packed = quant.nibble_pack(codes, interpret=True)
    np.testing.assert_array_equal(np.asarray(pl_packed),
                                  np.asarray(ref_packed))
    ref_back = ref.nibble_unpack(ref_packed, n)
    pl_back = quant.nibble_unpack(ref_packed, n, interpret=True)
    np.testing.assert_array_equal(np.asarray(pl_back), np.asarray(ref_back))


def test_pallas_multiblock_grid():
    """A payload larger than one (BLOCK_ROWS, LANE) block still round-trips
    bit-exactly through the gridded Pallas kernels."""
    n = 2 * quant.BLOCK_ROWS * quant.LANE + 77
    codes = jnp.asarray(_codes(n, seed=3))
    packed = quant.nibble_pack(codes, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(packed), np.asarray(ref.nibble_pack(codes)))
    np.testing.assert_array_equal(
        np.asarray(quant.nibble_unpack(packed, n, interpret=True)),
        np.asarray(codes))


def test_ops_dispatch_cpu_routes_to_reference():
    """On the CPU test substrate the ops dispatcher uses the reference path
    (vmap-safe) and agrees with an explicit Pallas interpret call."""
    codes = jnp.asarray(_codes(513, seed=11))
    packed = ops.nibble_pack(codes)  # default routing
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(ref.nibble_pack(codes)))
    np.testing.assert_array_equal(
        np.asarray(ops.nibble_unpack(packed, 513, use_pallas=True,
                                     interpret=True)),
        np.asarray(codes))


# ---------------------------------------------------------------------------
# symmetric scales + quantization error bound
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=25)
@given(n=st.integers(min_value=1, max_value=400),
       seed=st.integers(min_value=0, max_value=2**31 - 1),
       log_mag=st.integers(min_value=-8, max_value=8))
def test_scale_and_error_bound(n, seed, log_mag):
    """scale = max|x|/qmax, codes stay in [-qmax, qmax], and the round-trip
    error is ≤ scale/2 elementwise across 16 orders of magnitude — for both
    the int8 (qmax 127) and int4 (qmax 7) grids."""
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal(n) * 10.0 ** log_mag).astype(np.float32)
    xs = jnp.asarray(x)
    for qmax in (127, 7):
        sc = ref.quant_scale(xs, qmax)
        np.testing.assert_allclose(float(sc), np.abs(x).max() / qmax
                                   if np.abs(x).max() > 0 else 1.0, rtol=1e-6)
        q = ref.quantize(xs, sc, qmax)
        qn = np.asarray(q)
        assert qn.min() >= -qmax and qn.max() <= qmax
        err = np.abs(np.asarray(ref.dequantize(q, sc)) - x)
        assert err.max() <= float(sc) / 2 * (1 + 1e-6), (err.max(), float(sc))


def test_zero_array_scale_guard():
    """All-zero inputs quantize to all-zero codes with the guarded scale 1.0
    (no NaN/inf anywhere in the round trip)."""
    x = jnp.zeros(33, jnp.float32)
    sc = ref.quant_scale(x, 7)
    assert float(sc) == 1.0
    out = ref.dequantize(ref.quantize(x, sc, 7), sc)
    np.testing.assert_array_equal(np.asarray(out), np.zeros(33, np.float32))


# ---------------------------------------------------------------------------
# flat-plan integration: per-slot scales, packed offsets, honest bytes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("wd", ["int8", "int4"])
def test_flat_plan_per_slot_scale_correctness(wd):
    """Each slot in a quantized chunk is scaled by ITS OWN absmax — a huge
    neighbor slot must not crush a small slot's resolution — and the
    gather-path pack/unpack agrees with the reduce-path dequantized buffer
    exactly."""
    rng = np.random.default_rng(0)
    parts = [jnp.asarray(rng.standard_normal((5, 7)).astype(np.float32)),
             jnp.asarray(1e4 * rng.standard_normal(9).astype(np.float32)),
             jnp.asarray(1e-4 * rng.standard_normal(11).astype(np.float32))]
    plan = matrixize.plan_flat(parts, wire_dtype=wd)
    (chunk,) = plan.chunks
    assert chunk.quant == wd
    qmax = matrixize.QUANT_QMAX[wd]
    payload, scales = matrixize.quant_pack_flat(chunk, parts)
    for k, (s, p) in enumerate(zip(chunk.slots, parts)):
        x = np.asarray(p, np.float32).ravel()
        np.testing.assert_allclose(float(scales[k]), np.abs(x).max() / qmax,
                                   rtol=1e-6)
    out = matrixize.quant_unpack_flat(chunk, payload, scales)
    buf = np.asarray(matrixize.quant_dequant_flat(chunk, parts))
    ref_out = matrixize.unpack_flat(chunk, jnp.asarray(buf))
    for s in chunk.slots:
        x = np.asarray(parts[s.index], np.float32)
        got = np.asarray(out[s.index])
        np.testing.assert_array_equal(got, np.asarray(ref_out[s.index]))
        sc = float(scales[[i for i, t in enumerate(chunk.slots)
                           if t.index == s.index][0]])
        assert np.abs(got - x).max() <= sc / 2 * (1 + 1e-6)


def test_flat_plan_int4_packed_offsets_odd_slots():
    """Odd-size slots are each padded to their own even code count, so slot
    boundaries in the packed buffer stay byte-aligned and decodable."""
    rng = np.random.default_rng(7)
    parts = [jnp.asarray(rng.standard_normal(n).astype(np.float32))
             for n in (3, 5, 8, 1)]
    plan = matrixize.plan_flat(parts, wire_dtype="int4")
    (chunk,) = plan.chunks
    payload, scales = matrixize.quant_pack_flat(chunk, parts)
    assert payload.shape == (sum((n + 1) // 2 for n in (3, 5, 8, 1)),)
    assert matrixize.quant_slot_sizes(chunk) == [2, 3, 4, 1]
    out = matrixize.quant_unpack_flat(chunk, payload, scales)
    for i, p in enumerate(parts):
        assert out[i].shape == p.shape
        sc = float(scales[i])
        assert np.abs(np.asarray(out[i]) - np.asarray(p)).max() <= sc / 2 * (
            1 + 1e-6)


def test_flat_plan_ints_never_quantized_and_honest_bytes():
    """Integer parts keep their own exact chunks under a quantized wire, and
    the plan's byte accounting is 0.5 B/elem + 4 B/slot for int4."""
    parts = [jnp.ones((4, 4), jnp.float32), jnp.arange(6, dtype=jnp.int32),
             jnp.ones(5, jnp.float32)]
    plan = matrixize.plan_flat(parts, wire_dtype="int4")
    quant_chunks = [c for c in plan.chunks if c.quant]
    int_chunks = [c for c in plan.chunks if not c.quant]
    assert len(quant_chunks) == 1 and len(int_chunks) == 1
    qc, ic = quant_chunks[0], int_chunks[0]
    assert ic.wire_dtype == jnp.int32 and ic.overhead_bytes == 0
    assert qc.wire_itemsize == 0.5
    assert qc.wire_bytes == 21 * 0.5 + 2 * matrixize.SCALE_BYTES
    assert matrixize.plan_flat(parts, "int8").chunks[0].wire_bytes == 21 + 8
