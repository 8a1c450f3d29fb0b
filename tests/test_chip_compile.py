"""The Pallas kernels compile for a TPU v5e, at qwen3-4b widths.

Interpret-mode tests (``test_kernels.py``, ``test_wire_quant.py``) check
what the kernels compute; only Mosaic, the TPU kernel compiler, checks
what the chip accepts: fast-memory limits, tiling alignment, the vector
ops it can legalize.  The TPU compiler is installed without a chip, so
each kernel is compiled here against a *described* ``v5e:2x2`` topology.
Nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test collection must
not depend on which worker got it.  Every kernel is called with
``interpret=False`` because the default backend here is the CPU.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core.dist import SINGLE
from repro.kernels import ef_apply, lowrank, quant
from repro.launch import compile_cache
from repro.models import attention

# qwen3-4b's two MLP matrix shapes (d_model 2560, d_ff 9728), both ways up
SHAPES = [(2560, 9728), (9728, 2560)]
RANKS = [2, 4]
LAYERS = 4      # a shape bucket's batch: the same matrix of 4 layers


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without it: keep the cache out of the way
    with compile_cache.disabled():
        yield SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batched", [False, True], ids=["2d", "3d"])
@pytest.mark.parametrize("rank", RANKS)
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_lowrank_project_compiles(one_chip, shape, rank, batched):
    n, k = shape
    lead = (LAYERS,) if batched else ()
    _assert_kernel_compiles(
        lambda m, q: lowrank.lowrank_project(m, q, interpret=False),
        _sds(one_chip, lead + (n, k)), _sds(one_chip, lead + (k, rank)))


@pytest.mark.parametrize("batched", [False, True], ids=["2d", "3d"])
@pytest.mark.parametrize("rank", RANKS)
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_lowrank_backproject_compiles(one_chip, shape, rank, batched):
    n, k = shape
    lead = (LAYERS,) if batched else ()
    _assert_kernel_compiles(
        lambda m, p: lowrank.lowrank_backproject(m, p, interpret=False),
        _sds(one_chip, lead + (n, k)), _sds(one_chip, lead + (n, rank)))


@pytest.mark.parametrize("batched", [False, True], ids=["2d", "3d"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_ef_apply_compiles(one_chip, shape, batched):
    n, m = shape
    lead = (LAYERS,) if batched else ()
    _assert_kernel_compiles(
        lambda x, mom, p, q: ef_apply.ef_apply(x, mom, p, q, 0.1, 0.9,
                                               interpret=False),
        _sds(one_chip, lead + (n, m)), _sds(one_chip, lead + (n, m)),
        _sds(one_chip, lead + (n, 2)), _sds(one_chip, lead + (m, 2)))


# the int4 wire of one rank-2 factor pair of the MLP, and an odd length
# that leaves a half-filled last byte and a grid of one partial block
@pytest.mark.parametrize("n", [2 * (2560 + 9728) * LAYERS, 12345])
def test_nibble_pack_compiles(one_chip, n):
    _assert_kernel_compiles(
        lambda q: quant.nibble_pack(q, interpret=False),
        _sds(one_chip, (n,), jnp.int8))


@pytest.mark.parametrize("n", [2 * (2560 + 9728) * LAYERS, 12345])
def test_nibble_unpack_compiles(one_chip, n):
    _assert_kernel_compiles(
        lambda p: quant.nibble_unpack(p, n, interpret=False),
        _sds(one_chip, ((n + 1) // 2,), jnp.uint8))


# the attention of both benchmark cells: qwen3-4b (32 q over 8 kv heads,
# qk-norm) at batch 1 and olmoe-1b-7b (16 heads) at batch 2, seq 4096
@pytest.mark.parametrize("name,batch", [("qwen3-4b", 1), ("olmoe-1b-7b", 2)])
def test_attention_grad_takes_flash_kernels(one_chip, name, batch):
    cfg = get_config(name)
    params = jax.tree_util.tree_map(
        lambda a: _sds(one_chip, a.shape),
        jax.eval_shape(lambda: attention.init(jax.random.key(0), cfg, 1)))
    x = _sds(one_chip, (batch, 4096, cfg.d_model))
    loss = lambda p, x: jnp.sum(attention.forward(p, x, cfg, SINGLE) ** 2)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    for kernel in ("flash_attention_fwd", "flash_attention_dkv",
                   "flash_attention_dq"):
        assert kernel in text
    # no chunk of scores, (..., 512 queries, 4096 keys), reaches HBM
    assert not re.search(r"f32\[[\d,]*512,4096\]", text)
