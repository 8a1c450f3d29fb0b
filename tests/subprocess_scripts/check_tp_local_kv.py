"""Subprocess check: the tp_local_kv perf variant (skip the K/V all-gather
when kv heads shard evenly over the model axis) is numerically identical to
the baseline gather path, for both the train loss/grads and the prefill
cache+logits."""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from repro.configs.base import LayerSlot, ModelConfig, InputShape
from repro.core.dist import MeshCtx
from repro.models import model as model_lib
from repro.launch.mesh import make_mesh


def cfg_with(local_kv: bool) -> ModelConfig:
    # heads and kv heads both divisible by model shards (4)
    return ModelConfig(
        name="tpkv-test", arch_type="dense", num_layers=2, d_model=128,
        num_heads=8, num_kv_heads=8, d_ff=256, vocab_size=512,
        qk_norm=True, slots=(LayerSlot("attn", "dense"),),
        tp_local_kv=local_kv)


def run(local_kv: bool):
    cfg = cfg_with(local_kv)
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = MeshCtx(data_axes=("data",), model_axis="model",
                  seq_axes=("model",))
    key = jax.random.key(0)
    params = model_lib.init(key, cfg, model_shards=4)
    toks = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    def local(params, batch):
        loss, _ = model_lib.loss_fn(params, batch, cfg, ctx, q_chunk=16,
                                    remat=False)
        grads = jax.grad(
            lambda p: model_lib.loss_fn(p, batch, cfg, ctx, q_chunk=16,
                                        remat=False)[0])(params)
        logits, cache = model_lib.prefill_step(params, batch, cfg, ctx,
                                               q_chunk=16)
        # decode 2 tokens from the prefilled cache — validates the cache
        # contents end-to-end without exposing its sharded layout
        s = batch["tokens"].shape[1]
        tok = jnp.argmax(logits[..., : cfg.vocab_size], axis=-1).astype(jnp.int32)
        dec = []
        for i in range(2):
            tok, dlogits, cache = model_lib.decode_step(
                params, cache, tok, jnp.int32(s + i), cfg, ctx)
            dec.append(dlogits)
        return loss, grads, logits, jnp.concatenate(dec, axis=1)

    pps = model_lib.pspecs(cfg)
    fn = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(pps, {"tokens": P("data", None), "labels": P("data", None)}),
        out_specs=(P(), pps, P("data", None, None), P("data", None, None)),
        check_vma=False))
    with jax.set_mesh(mesh):
        loss, grads, logits, dec = fn(params, batch)
    return (np.asarray(loss),
            [np.asarray(g) for g in jax.tree_util.tree_leaves(grads)],
            np.asarray(logits), np.asarray(dec))


def main():
    loss_a, grads_a, logits_a, dec_a = run(False)
    loss_b, grads_b, logits_b, dec_b = run(True)
    np.testing.assert_allclose(loss_a, loss_b, rtol=2e-6)
    np.testing.assert_allclose(logits_a, logits_b, atol=2e-4)
    np.testing.assert_allclose(dec_a, dec_b, atol=2e-4)
    worst = max(float(np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-12))
                for a, b in zip(grads_a, grads_b))
    assert worst < 5e-5, f"grad mismatch: {worst}"
    print(f"loss {loss_a} == {loss_b}; worst grad rel diff {worst:.2e}")
    print("TP_LOCAL_KV_OK")


if __name__ == "__main__":
    main()
