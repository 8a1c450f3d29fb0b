"""Named scopes of the training step (``repro.core.scopes``): which compiled
op each scope owns, that the scopes change nothing XLA compiles, and that
``exchange`` covers exactly the collectives ``CollectiveStats`` records."""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import tracing
from repro.configs.base import get_config
from repro.core import matrixize, scopes
from repro.core.compressors import PowerSGDCompressor
from repro.core.dist import COLLECTIVE_PRIMITIVES
from repro.core.simmesh import SimMesh
from repro.launch.mesh import make_mesh
from repro.launch.train import TrainHyper, make_sim_train_step, make_train_step

HLO = """\
HloModule jit_local_step, is_scheduled=true

FileNames
1 "train.py"

%fused_computation.1 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%p.1, %p.1), metadata={op_name="jit(local_step)/ef_apply/compress/mul" source_file="a.py" source_line=3}
}

%region_0.2 (x.2: f32[], y.2: f32[]) -> f32[] {
  %x.2 = f32[] parameter(0)
  %y.2 = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%x.2, %y.2), metadata={op_name="psum"}
}

%body.3 (t.3: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t.3 = (s32[], f32[8]{0}) parameter(0)
  %gte.3 = f32[8]{0} get-tuple-element(%t.3), index=1
  %add.3 = f32[8]{0} add(%gte.3, %gte.3), metadata={op_name="jit(local_step)/loss_grad/transpose(jvp())/while/body/add"}
  ROOT %tuple.3 = (s32[], f32[8]{0}) tuple(%gte.3, %add.3)
}

ENTRY %main.9 (a.9: f32[8]) -> f32[8] {
  %a.9 = f32[8]{0} parameter(0)
  %while.4 = (s32[], f32[8]{0}) while(%t.0), condition=%cond.3, body=%body.3, metadata={op_name="jit(local_step)/loss_grad/jvp()/while"}
  %fusion.5 = f32[8]{0} fusion(%a.9), kind=kLoop, calls=%fused_computation.1
  %all-reduce.6 = f32[8]{0} all-reduce(%fusion.5), replica_groups={}, to_apply=%region_0.2, metadata={op_name="jit(local_step)/ef_apply/compress/exchange/psum"}
  %add.7 = f32[8]{0} add(%a.9, %a.9), metadata={op_name="jit(local_step)/transpose(jvp(ef_apply))/add"}
  %add.8 = f32[8]{0} add(%a.9, %a.9), metadata={op_name="jit(local_step)/ef_applying/add"}
  %add.9 = f32[8]{0} add(%a.9, %a.9)
  ROOT %multiply.10 = f32[8]{0} multiply(%a.9, %a.9), metadata={op_name="jit(local_step)/add"}
}
"""


def test_op_scopes_on_hand_written_hlo():
    m = scopes.op_scopes(HLO)
    assert scopes.module_name(HLO) == "jit_local_step"
    # own path; jvp/transpose wrappers are peeled; innermost scope wins
    assert m["while.4"] == m["add.3"] == scopes.LOSS_GRAD
    assert m["add.7"] == scopes.EF_APPLY
    assert m["multiply.1"] == scopes.COMPRESS
    assert m["all-reduce.6"] == scopes.EXCHANGE
    # a fusion without metadata goes with its root; a loop body's and a
    # reducer's instructions without a scope go with their caller
    assert m["fusion.5"] == scopes.COMPRESS
    assert m["gte.3"] == m["tuple.3"] == scopes.LOSS_GRAD
    assert m["add.2"] == scopes.EXCHANGE
    # only whole path components match; unscoped instructions are left out
    for name in ("add.8", "add.9", "multiply.10", "a.9"):
        assert name not in m
    assert scopes.scope_of("a/vmap(loss_grad)/jvp()/dot") == scopes.LOSS_GRAD
    assert scopes.scope_of("a/ef_apply/compress/exchange/x") == \
        scopes.EXCHANGE
    assert scopes.scope_of("a/exchange_rate/x") is None


def _strip(hlo: str) -> str:
    """Compiled HLO text without op metadata and the stack-frame tables
    (file, function and line names) that precede the computations."""
    lines = hlo.splitlines()
    k = next(i for i, line in enumerate(lines)
             if line.startswith(("%", "ENTRY")))
    body = "\n".join(lines[k:])
    return lines[0] + "\n" + re.sub(r", metadata=\{[^}]*\}", "", body)


def _train_step_hlo():
    cfg = get_config("qwen3-4b", reduced=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    step_fn, abstract_state, _ = make_train_step(
        cfg, mesh, TrainHyper(q_chunk=16, warmup_steps=5))
    params, ef = abstract_state()
    sh = NamedSharding(mesh, P(("data",), None))
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32, sharding=sh)
             for k in ("tokens", "labels")}
    key = jax.eval_shape(lambda: jax.random.key(0))
    with jax.set_mesh(mesh):
        return step_fn.lower(params, ef, batch, key).compile().as_text()


@pytest.fixture(scope="module")
def train_hlo():
    return _train_step_hlo()


def test_train_step_scopes_own_ops(train_hlo):
    m = scopes.op_scopes(train_hlo)
    owned = set(m.values())
    assert {scopes.LOSS_GRAD, scopes.COMPRESS, scopes.EF_APPLY} <= owned
    whiles = re.findall(r'^\s+(?:ROOT\s+)?%?(while[\w.\-]*) = .*?'
                        r'op_name="([^"]*)"', train_hlo, re.M)
    assert whiles
    for name, path in whiles:
        # the model's layer and attention loops under the gradient, the
        # orthogonalization loops under compress
        want = scopes.LOSS_GRAD if "jvp(" in path else scopes.COMPRESS
        assert m[name] == want, (name, path)


def test_scopes_change_only_metadata(train_hlo, monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _train_step_hlo()
    assert not set(scopes.op_scopes(plain).values())
    assert _strip(plain) == _strip(train_hlo)


def test_sim_step_exchange_owns_ops():
    cfg = get_config("qwen3-4b", reduced=True)
    sim = SimMesh(2)
    step_fn, init_state = make_sim_train_step(
        cfg, sim, TrainHyper(q_chunk=16, warmup_steps=5, remat=False))
    params, ef = jax.eval_shape(init_state, jax.random.key(0))
    batch = {k: jax.ShapeDtypeStruct((2, 1, 32), jnp.int32)
             for k in ("tokens", "labels")}
    key = jax.eval_shape(lambda: jax.random.key(0))
    hlo = jax.jit(step_fn).lower(params, ef, batch, key).compile().as_text()
    owned = set(scopes.op_scopes(hlo).values())
    assert owned == set(scopes.SCOPES)


def test_exchange_covers_the_recorded_collectives():
    key = jax.random.key(7)
    grads, specs = {}, {}
    for i, shape in enumerate([(64, 32), (32, 16), (16,)]):
        grads[f"l{i}"] = jax.random.normal(jax.random.fold_in(key, i), shape)
        specs[f"l{i}"] = matrixize.default_spec(grads[f"l{i}"])
    art = tracing.trace_compress_step(PowerSGDCompressor(rank=2), grads,
                                      specs)
    under = 0
    for eqn in tracing.iter_eqns(art.closed_jaxpr.jaxpr):
        if (eqn.primitive.name not in COLLECTIVE_PRIMITIVES
                or tracing.DATA_AXIS not in tracing._eqn_axes(eqn)):
            continue
        path = str(eqn.source_info.name_stack).split("/")
        assert scopes.EXCHANGE in path, path
        assert path.index(scopes.COMPRESS) < path.index(scopes.EXCHANGE)
        under += 1
    assert under == len(art.logical()) == art.stats.data_collectives == 2
