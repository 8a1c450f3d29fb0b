"""The causal flash-attention kernels (``repro.kernels.flash_attention``)
against the chunked XLA path of ``models/attention.py``, in interpret mode.

Two comparisons.  With the kernels' dots in float32 at ``highest``
precision, the kernels and the XLA path (float32 dots on the CPU) compute
the same sums in another order: they agree to float32 rounding.  The
kernels as they run on the TPU take bfloat16 operands into each dot, so
the shipped kernels agree with the float32 path to bfloat16 rounding: each
output or gradient element passes through at most four rounded operands
in a row (q and k into the scores, p or ds and v or k/q into the product,
dp's do and v), each off by at most 2**-9 relative, and the errors of a
sum's terms partly cancel; 2**-6 of the largest element is that bound with
room, and a kernel that drops or doubles a block misses it many times
over.

The kernel path is taken here by handing ``attention``'s platform choice
its TPU branch and the kernels ``interpret=True``; nothing in the program
selects it off the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.configs.base import ModelConfig
from repro.core.dist import SINGLE
from repro.kernels import flash_attention
from repro.models import attention

KEY = jax.random.key(7)
EXACT = 1e-5          # float32 rounding, relative to the largest element
BF16 = 2.0 ** -6      # bfloat16 operands, see the module docstring


def _cfg(heads, kv_heads, head_dim=128):
    return ModelConfig(name="t", arch_type="dense", num_layers=1,
                       d_model=256, num_heads=heads, num_kv_heads=kv_heads,
                       head_dim=head_dim, rope_theta=10000.0)


@pytest.fixture
def kernel_path(monkeypatch):
    """``attention`` takes its TPU branch, the kernels interpreted."""
    monkeypatch.setattr(lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    monkeypatch.setattr(flash_attention, "causal_attention", functools.partial(
        flash_attention.causal_attention, interpret=True))


@pytest.fixture
def f32_dots(monkeypatch):
    """The kernels' dots in float32 at ``highest`` precision."""
    monkeypatch.setattr(flash_attention, "_dot", lambda a, b, dims:
                        lax.dot_general(a, b, dims, precision="highest",
                                        preferred_element_type=jnp.float32))


def _close(got, want, rel):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(w)))
        err = float(jnp.max(jnp.abs(g - w)))
        assert err <= rel * scale, (err, scale, rel)


def _forward_and_grads(cfg, batch, seq):
    """attention.forward's output and its gradients with respect to the
    parameters and the input, under a random cotangent."""
    params = attention.init(KEY, cfg, 1)
    x = jax.random.normal(jax.random.fold_in(KEY, 1),
                          (batch, seq, cfg.d_model))
    ct = jax.random.normal(jax.random.fold_in(KEY, 2), x.shape)
    out, vjp = jax.vjp(lambda p, x: attention.forward(p, x, cfg, SINGLE),
                       params, x)
    return out, vjp(ct)


def _core_and_grads(q, k, v, kv_idx, grouped):
    """_attend's output and q/k/v gradients under a random cotangent."""
    f = lambda q, k, v: attention._attend(q, k, v, kv_idx, grouped,
                                          q_chunk=64, window=0,
                                          scale=q.shape[-1] ** -0.5)
    out, vjp = jax.vjp(f, q, k, v)
    return out, vjp(jax.random.normal(jax.random.fold_in(KEY, 3), out.shape))


def _qkv(batch, seq, heads, kv_heads, head_dim=128):
    ks = jax.random.split(KEY, 3)
    return (jax.random.normal(ks[0], (batch, seq, heads, head_dim)),
            jax.random.normal(ks[1], (batch, seq, kv_heads, head_dim)),
            jax.random.normal(ks[2], (batch, seq, kv_heads, head_dim)))


# (heads, kv heads): grouped-query 4:1 and plain multi-head 1:1
HEADS = [(4, 1), (2, 2)]


@pytest.mark.parametrize("heads", HEADS, ids=["gqa4", "mha"])
def test_kernel_path_matches_xla_path(heads, kernel_path, f32_dots):
    cfg = _cfg(*heads)
    got = _forward_and_grads(cfg, 2, 256)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(flash_attention, "applies", lambda *a: False)
        want = _forward_and_grads(cfg, 2, 256)
    _close(got, want, EXACT)


@pytest.mark.parametrize("heads", HEADS, ids=["gqa4", "mha"])
def test_bf16_operands_within_rounding(heads, kernel_path):
    cfg = _cfg(*heads)
    got = _forward_and_grads(cfg, 2, 256)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(flash_attention, "applies", lambda *a: False)
        want = _forward_and_grads(cfg, 2, 256)
    _close(got, want, BF16)


def test_expanded_kv_on_a_non_contiguous_map(kernel_path, f32_dots):
    """A shard whose q heads read kv heads 0, 1, 1, 1 (the last a padded
    head's clamp) is given K/V expanded to its q heads."""
    q, k, v = _qkv(2, 256, 4, 2)
    kv_idx = jnp.array([0, 1, 1, 1])
    got = _core_and_grads(q, k, v, kv_idx, grouped=False)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(flash_attention, "applies", lambda *a: False)
        want = _core_and_grads(q, k, v, kv_idx, grouped=False)
    _close(got, want, EXACT)


@pytest.mark.parametrize("block", [128, 256])
def test_blocks_skip_and_mask_along_the_diagonal(block, monkeypatch,
                                                 f32_dots):
    """Several blocks a side, so that blocks above the diagonal are skipped,
    blocks on it masked and blocks below it taken whole; 4:1 groups."""
    monkeypatch.setattr(flash_attention, "BLOCKS", (block,))
    q, k, v = _qkv(1, 512, 4, 1)
    f = functools.partial(flash_attention.causal_attention, interpret=True)
    want = _core_and_grads(q, k, v, jnp.array([0, 0, 0, 0]), grouped=True)
    out, vjp = jax.vjp(f, q, k, v)
    ct = jax.random.normal(jax.random.fold_in(KEY, 3), out.shape)
    _close((out, vjp(ct)), want, EXACT)


def _jaxpr_text(cfg, seq, window):
    params = attention.init(KEY, cfg, 1)
    x = jnp.zeros((1, seq, cfg.d_model))
    return str(jax.make_jaxpr(lambda p, x: attention.forward(
        p, x, cfg, SINGLE, window=window))(params, x))


@pytest.mark.parametrize("seq,head_dim,window,kernel", [
    (256, 128, 0, True),
    (256, 128, 64, False),      # sliding window
    (200, 128, 0, False),       # no block divides the sequence
    (256, 64, 0, False),        # half-lane heads (MusicGen's 64)
], ids=["aligned", "window", "unaligned", "hd64"])
def test_predicate_keeps_the_xla_path(seq, head_dim, window, kernel):
    assert flash_attention.applies(seq, head_dim, window) == kernel
    text = _jaxpr_text(_cfg(2, 2, head_dim), seq, window)
    # off the kernel path nothing platform-dependent is even traced
    assert ("platform_index" in text) == kernel
    assert ("pallas_call" in text) == kernel


def test_dots_take_bf16_operands_and_hbm_stays_f32():
    q, k, v = _qkv(1, 256, 4, 2)
    f = lambda q, k, v: flash_attention.causal_attention(q, k, v,
                                                         interpret=True)
    jaxpr = jax.make_jaxpr(lambda q, k, v: jax.vjp(f, q, k, v)[1](
        jnp.ones_like(q)))(q, k, v)
    calls = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    assert len(calls) == 3          # forward, dk/dv, dq
    for call in calls:
        # the arrays in HBM: every float is float32 (the rest are the
        # grid's int32 step tables)
        floats = [a.aval.dtype for a in (*call.invars, *call.outvars)
                  if jnp.issubdtype(a.aval.dtype, jnp.floating)]
        assert floats and set(floats) == {jnp.dtype(jnp.float32)}
        dots = [e for e in _eqns(call.params["jaxpr"])
                if e.primitive.name == "dot_general"]
        assert dots
        for dot in dots:
            assert [a.aval.dtype for a in dot.invars] == [jnp.bfloat16] * 2
            assert dot.outvars[0].aval.dtype == jnp.float32


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)
