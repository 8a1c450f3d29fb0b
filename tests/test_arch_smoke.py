"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned architecture runs one forward/train step and one decode step on CPU,
asserting output shapes and the absence of NaNs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.core.dist import SINGLE
from repro.models import model as model_lib
from repro.launch.mesh import make_mesh

KEY = jax.random.key(0)


def _batch(cfg, b=2, s=64):
    batch = {
        "tokens": jax.random.randint(KEY, (b, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(KEY, (b, s), 0, cfg.vocab_size),
    }
    if cfg.frontend == "vision":
        batch["patches"] = jax.random.normal(KEY, (b, 16, cfg.frontend_dim))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_loss(arch):
    cfg = get_config(arch, reduced=True)
    assert cfg.num_layers <= 2 and cfg.d_model <= 512
    assert cfg.moe_num_experts <= 4
    params = model_lib.init(KEY, cfg, model_shards=1)
    loss, metrics = model_lib.loss_fn(params, _batch(cfg), cfg, SINGLE, q_chunk=32)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss))
    assert float(metrics["lm_loss"]) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step(arch):
    """One EF-PowerSGD train step on the (1,1) mesh: params move, stay finite."""
    from repro.launch.train import TrainHyper, make_train_step

    cfg = get_config(arch, reduced=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    hyper = TrainHyper(q_chunk=32, warmup_steps=2, remat=False, lr=0.05)
    step_fn, _, init_state = make_train_step(cfg, mesh, hyper)
    with jax.set_mesh(mesh):
        params, ef = init_state(KEY)
        batch = _batch(cfg, b=2, s=32)
        if cfg.frontend == "vision":
            batch["patches"] = jax.random.normal(KEY, (2, 8, cfg.frontend_dim))
        new_params, new_ef, metrics = step_fn(params, ef, batch, KEY)
    assert bool(jnp.isfinite(metrics["lm_loss"]))
    moved = any(
        float(jnp.abs(a - b).max()) > 0
        for a, b in zip(jax.tree_util.tree_leaves(new_params),
                        jax.tree_util.tree_leaves(
                            model_lib.init(KEY, cfg, model_shards=1))))
    assert moved
    for leaf in jax.tree_util.tree_leaves(new_params):
        assert bool(jnp.all(jnp.isfinite(leaf)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step(arch):
    cfg = get_config(arch, reduced=True)
    params = model_lib.init(KEY, cfg, model_shards=1)
    b = 2
    cache = model_lib.init_cache(cfg, 1, b, 32)
    tok = jnp.zeros((b, 1), jnp.int32)
    for pos in range(4):
        tok, logits, cache = model_lib.decode_step(
            params, cache, tok, jnp.int32(pos), cfg, SINGLE)
    assert tok.shape == (b, 1)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.all((tok >= 0) & (tok < cfg.vocab_size)))


@pytest.mark.parametrize("arch", ["llama3_8b", "mamba2_1p3b", "jamba_v01_52b"])
def test_prefill_matches_decode(arch):
    """prefill(prompt) then decode must equal token-by-token decode."""
    cfg = get_config(arch, reduced=True)
    cfg = dataclasses.replace(cfg, decode_window=0)
    params = model_lib.init(KEY, cfg, model_shards=1)
    b, s = 2, 16
    toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)
    logits_pf, cache_pf = model_lib.prefill_step(
        params, {"tokens": toks}, cfg, SINGLE, q_chunk=8)
    cache = model_lib.init_cache(cfg, 1, b, s)
    for pos in range(s):
        _, logits, cache = model_lib.decode_step(
            params, cache, toks[:, pos:pos + 1], jnp.int32(pos), cfg, SINGLE)
    np.testing.assert_allclose(np.asarray(logits_pf[:, 0]),
                               np.asarray(logits[:, 0]), atol=2e-4)


def test_full_configs_match_assignment():
    """The full (non-reduced) configs carry the exact assigned hyperparams."""
    import math

    expect = {
        "llama3_8b": dict(num_layers=32, d_model=4096, num_heads=32,
                          num_kv_heads=8, d_ff=14336, vocab_size=128256),
        "mamba2_1p3b": dict(num_layers=48, d_model=2048, d_ff=0,
                            vocab_size=50280, ssm_state=128),
        "jamba_v01_52b": dict(num_layers=32, d_model=4096, num_heads=32,
                              num_kv_heads=8, d_ff=14336, vocab_size=65536,
                              moe_num_experts=16, moe_top_k=2),
        "musicgen_medium": dict(num_layers=48, d_model=1536, num_heads=24,
                                num_kv_heads=24, d_ff=6144, vocab_size=2048),
        "llava_next_34b": dict(num_layers=60, d_model=7168, num_heads=56,
                               num_kv_heads=8, d_ff=20480, vocab_size=64000),
        "qwen3_moe_30b_a3b": dict(num_layers=48, d_model=2048, num_heads=32,
                                  num_kv_heads=4, d_ff=768, vocab_size=151936,
                                  moe_num_experts=128, moe_top_k=8,
                                  qk_norm=True),
        "codeqwen15_7b": dict(num_layers=32, d_model=4096, num_heads=32,
                              num_kv_heads=32, d_ff=13440, vocab_size=92416),
        "olmoe_1b_7b": dict(num_layers=16, d_model=2048, num_heads=16,
                            num_kv_heads=16, d_ff=1024, vocab_size=50304,
                            moe_num_experts=64, moe_top_k=8),
        "qwen3_4b": dict(num_layers=36, d_model=2560, num_heads=32,
                         num_kv_heads=8, d_ff=9728, vocab_size=151936,
                         qk_norm=True),
        "yi_6b": dict(num_layers=32, d_model=4096, num_heads=32,
                      num_kv_heads=4, d_ff=11008, vocab_size=64000),
    }
    for arch, fields in expect.items():
        cfg = get_config(arch)
        for f, v in fields.items():
            assert getattr(cfg, f) == v, (arch, f, getattr(cfg, f), v)
    # jamba interleave: 1 attention per 8 layers, MoE every other layer
    cfg = get_config("jamba_v01_52b")
    mixers = [s.mixer for s in cfg.slots]
    assert mixers.count("attn") == 1 and len(mixers) == 8
    assert [s.ffn for s in cfg.slots].count("moe") == 4
