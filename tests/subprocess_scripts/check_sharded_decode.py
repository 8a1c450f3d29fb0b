"""Subprocess check: sharded decode (batch-sharded and seq-sharded cache
layouts) reproduces single-device decode token-for-token."""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from repro.launch.serve import make_decode_step
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_mesh
from repro.configs.base import get_config, InputShape
from repro.models import model as model_lib
from repro.core.dist import SINGLE


def main():
    key = jax.random.key(0)
    for arch in ["llama3-8b", "mamba2-1.3b", "jamba-v0.1-52b"]:
        for shp in [InputShape("batchsharded", 64, 8, "decode"),
                    InputShape("seqsharded", 64, 1, "decode")]:
            cfg = dataclasses.replace(get_config(arch, reduced=True),
                                      decode_window=0)
            m = make_mesh((2, 2, 2), ("pod", "data", "model"))
            step_fn, _ = make_decode_step(cfg, m, shp)
            params = model_lib.init(key, cfg, 2)
            b = shp.global_batch
            toks = jax.random.randint(jax.random.key(1), (b, 6), 0, cfg.vocab_size)
            c_ref = model_lib.init_cache(cfg, 1, b, shp.seq_len)
            for pos in range(6):
                nxt_ref, lg, c_ref = model_lib.decode_step(
                    params, c_ref, toks[:, pos:pos + 1], jnp.int32(pos), cfg, SINGLE)
            with jax.set_mesh(m):
                layout = specs_lib.decode_layout(cfg, shp, ("pod", "data"))
                cache = model_lib.init_cache(cfg, 1, b, shp.seq_len)
                _, cache_ps = specs_lib.abstract_cache(cfg, layout, shp, m, 2)
                put = lambda a, s: jax.device_put(a, NamedSharding(m, s))
                cache = jax.tree_util.tree_map(
                    put, cache, cache_ps, is_leaf=lambda x: isinstance(x, P))
                pps = model_lib.pspecs(cfg)
                params_sh = jax.tree_util.tree_map(
                    put, params, pps, is_leaf=lambda x: isinstance(x, P))
                for pos in range(6):
                    nxt, cache = step_fn(params_sh, cache,
                                         {"tokens": toks[:, pos:pos + 1]},
                                         jnp.int32(pos))
            ok = bool(jnp.all(nxt_ref == np.asarray(nxt)))
            print(f"{arch} {shp.name}: match={ok}")
            assert ok
    print("SHARDED_DECODE_OK")


if __name__ == "__main__":
    main()
