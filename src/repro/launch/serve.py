"""Distributed serving steps: prefill and decode under shard_map.

Decode layouts (see specs.decode_layout):
  * ``decode_32k``  — batch over (pod, data); cache sequence over (model,)
                      with flash-decode logsumexp merging.
  * ``long_500k``   — batch=1 is unshardable: the cache sequence shards over
                      (pod, data, model) jointly.  Dense archs use their
                      sliding-window variant (ring cache of decode_window);
                      SSM/hybrid decode their O(1) state natively.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.dist import MeshCtx
from repro.configs.base import InputShape, ModelConfig
from repro.models import model
from repro.launch import mesh as mesh_lib
from repro.launch import specs as specs_lib


def make_decode_step(cfg: ModelConfig, mesh, shape: InputShape, *,
                     q_chunk: int = 512, unroll: int = 1):
    """Returns (jitted_decode, abstract_inputs_fn)."""
    dp_axes = mesh_lib.data_axes(mesh)
    maxis = mesh_lib.model_axis(mesh)
    model_shards = mesh.shape[maxis]
    layout = specs_lib.decode_layout(cfg, shape, dp_axes)
    ctx = MeshCtx(data_axes=dp_axes, model_axis=maxis,
                  seq_axes=layout.seq_axes)

    param_ps = model.pspecs(cfg)
    cache_sds, cache_ps = specs_lib.abstract_cache(
        cfg, layout, shape, mesh, model_shards)
    ba = layout.batch_axes if layout.batch_axes else None
    tok_ps = {"tokens": P(ba, None)}

    def local_step(params, cache, batch, pos):
        nxt, logits, new_cache = model.decode_step(
            params, cache, batch["tokens"], pos, cfg, ctx,
            window=layout.window, unroll=unroll)
        return nxt, new_cache

    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(param_ps, cache_ps, tok_ps, P()),
        out_specs=(P(ba, None), cache_ps),
        check_vma=False,
    )
    step_fn = jax.jit(sharded, donate_argnums=(1,))

    def abstract_inputs():
        params_sds = jax.eval_shape(
            lambda: model.init(jax.random.key(0), cfg, model_shards))
        params_sds = specs_lib.with_sharding(params_sds, param_ps, mesh)
        cache = specs_lib.with_sharding(cache_sds, cache_ps, mesh)
        toks = specs_lib.with_sharding(
            specs_lib.batch_specs(cfg, shape), tok_ps, mesh)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        return params_sds, cache, toks, pos

    return step_fn, abstract_inputs


def make_prefill_step(cfg: ModelConfig, mesh, shape: InputShape, *,
                      q_chunk: int = 512, unroll: int = 1):
    """Prefill: forward over the full prompt, emitting cache slices laid out
    exactly as decode expects (sequence over the model axis)."""
    dp_axes = mesh_lib.data_axes(mesh)
    maxis = mesh_lib.model_axis(mesh)
    model_shards = mesh.shape[maxis]
    # prefill caches are seq-sharded over the model axis (decode_32k layout)
    layout = specs_lib.DecodeLayout(
        batch_axes=tuple(dp_axes), seq_axes=(maxis,),
        cache_len=shape.seq_len, window=0)
    ctx = MeshCtx(data_axes=dp_axes, model_axis=maxis,
                  seq_axes=layout.seq_axes)

    param_ps = model.pspecs(cfg)
    cache_sds, cache_ps = specs_lib.abstract_cache(
        cfg, layout, shape, mesh, model_shards)
    batch_ps = specs_lib.batch_pspecs(cfg, shape, dp_axes)

    # use a sliding window in prefill too when the arch defines one and the
    # prompt exceeds it (keeps dense archs sub-quadratic at long context)
    window = cfg.decode_window if (cfg.decode_window and
                                   shape.seq_len > 4 * cfg.decode_window) else 0

    def local_step(params, batch):
        logits, cache = model.prefill_step(params, batch, cfg, ctx,
                                           window=window, q_chunk=q_chunk,
                                           unroll=unroll)
        return logits, cache

    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(param_ps, batch_ps),
        out_specs=(P(tuple(dp_axes), None, None), cache_ps),
        check_vma=False,
    )
    step_fn = jax.jit(sharded)

    def abstract_inputs():
        params_sds = jax.eval_shape(
            lambda: model.init(jax.random.key(0), cfg, model_shards))
        params_sds = specs_lib.with_sharding(params_sds, param_ps, mesh)
        batch = specs_lib.with_sharding(
            specs_lib.batch_specs(cfg, shape), batch_ps, mesh)
        return params_sds, batch

    return step_fn, abstract_inputs


# ---------------------------------------------------------------------------
# CLI driver: serve a reduced model end-to-end on the host devices
# ---------------------------------------------------------------------------

def main():
    import argparse
    import time

    import numpy as np

    from repro.configs.base import get_config

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--gen-tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=True)
    n_dev = len(jax.devices())
    mesh = mesh_lib.make_mesh((max(1, n_dev // 2), min(2, n_dev)),
                              ("data", "model"))
    model_shards = mesh.shape["model"]
    print(f"serving {cfg.name} on mesh "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")

    cache_len = args.prompt_len + args.gen_tokens
    pre_shape = InputShape("cli_prefill", args.prompt_len, args.batch,
                           "prefill")
    dec_shape = InputShape("cli_decode", cache_len, args.batch, "decode")

    prefill_fn, _ = make_prefill_step(cfg, mesh, pre_shape, q_chunk=32)
    decode_fn, abstract = make_decode_step(cfg, mesh, dec_shape)

    key = jax.random.key(0)
    with jax.set_mesh(mesh):
        params = model.init(key, cfg, model_shards)
        toks = jax.random.randint(jax.random.key(1),
                                  (args.batch, args.prompt_len), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks}
        if cfg.frontend == "vision":
            batch["patches"] = jax.random.normal(
                key, (args.batch, 8, cfg.frontend_dim))

        t0 = time.time()
        logits, _ = prefill_fn(params, batch)
        jax.block_until_ready(logits)
        t_pre = time.time() - t0
        # decode against a fresh full-length cache (prompt replayed)
        _, cache_sds, _, _ = abstract()
        cache = jax.tree_util.tree_map(
            lambda s: jax.device_put(jnp.zeros(s.shape, s.dtype), s.sharding),
            cache_sds)
        tok = jnp.zeros((args.batch, 1), jnp.int32)
        t0 = time.time()
        for pos in range(args.prompt_len):
            tok, cache = decode_fn(params, cache,
                                   {"tokens": toks[:, pos:pos + 1]},
                                   jnp.int32(pos))
        out = []
        for k in range(args.gen_tokens):
            tok, cache = decode_fn(params, cache, {"tokens": tok},
                                   jnp.int32(args.prompt_len + k))
            out.append(np.asarray(tok))  # gradlint: disable=host-transfer
        jax.block_until_ready(tok)
        t_dec = time.time() - t0

    total = args.prompt_len + args.gen_tokens
    print(f"prefill {args.batch}x{args.prompt_len}: {t_pre*1e3:.0f} ms; "
          f"decode {total} steps: {t_dec*1e3:.0f} ms "
          f"({args.batch*total/t_dec:.0f} tok/s)")
    print("generated token ids:",
          np.concatenate(out, axis=1)[:, :8].tolist())


if __name__ == "__main__":
    main()
