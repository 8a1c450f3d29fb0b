"""The scope reduction (``bench/scopetrace.py``): the step's scopes and
their nesting read from its HLO, per-scope device time of the step module
read from a trace, idle gaps placed by step and host span, and the readers
of the per-scope metrics."""

import re
import textwrap

import pytest

import benchtiny  # noqa: F401

import cell as cell_lib
import scopetrace
import tracereduce
from repro.core import scopes

MS = 1_000_000  # ns

# window 0-100 ms.  Device 0: a loss_grad loop 0-40 with two body ops, an
# ef_apply op 40-50, a compress loop 50-70 around an exchange all-reduce
# 55-60, an unscoped op 70-75, idle 75-80, an op named like one of the
# step's but of another module 80-90, idle 90-100.  Device 1: the step's
# loss_grad op 0-20 only.
STEP_MODULE = "jit_local_step"
OPS = [("%while.1 = (f32[8]) while(...)", 0, 40, STEP_MODULE),
       ("%fusion.2 = f32[8] fusion(...)", 5, 15, STEP_MODULE),
       ("%fusion.3 = f32[8] fusion(...)", 20, 30, STEP_MODULE),
       ("%fusion.4 = f32[8] fusion(...)", 40, 50, STEP_MODULE),
       ("%while.5 = (f32[8]) while(...)", 50, 70, STEP_MODULE),
       ("%all-reduce.6 = f32[8] all-reduce(...)", 55, 60, STEP_MODULE),
       ("%fusion.7 = f32[8] fusion(...)", 70, 75, STEP_MODULE),
       ("%fusion.2 = f32[8] fusion(...)", 80, 90, "jit_fold_in")]
OP_SCOPE = {"while.1": "loss_grad", "fusion.2": "loss_grad",
            "fusion.3": "loss_grad", "fusion.4": "ef_apply",
            "while.5": "compress", "all-reduce.6": "exchange"}
WITHIN = {"compress": frozenset({"ef_apply"}),
          "exchange": frozenset({"ef_apply", "compress"})}
STEP = scopetrace.StepScopes(OP_SCOPE, WITHIN, STEP_MODULE)
READERS = ("model.fwd_bwd_ms", "compress.ms", "ef_apply.ms", "exchange.ms")

# the step's HLO as the compiled step gives it: the ops above with their
# op_name paths (the end of fusion.3's is PATH, filled in by each test),
# fusion.7 with no metadata
HLO = """\
HloModule jit_local_step, is_scheduled=true

ENTRY %main.9 (a.9: f32[8]) -> f32[8] {
  %a.9 = f32[8]{0} parameter(0)
  %while.1 = f32[8]{0} while(%a.9), condition=%cond.1, body=%body.1, metadata={op_name="jit(local_step)/loss_grad/jvp()/while"}
  %fusion.2 = f32[8]{0} fusion(%a.9), kind=kLoop, calls=%f.2, metadata={op_name="jit(local_step)/jvp(loss_grad)/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%a.9), kind=kLoop, calls=%f.3, metadata={op_name="jit(local_step)/transpose(jvp(loss_grad))/PATH"}
  %fusion.4 = f32[8]{0} fusion(%a.9), kind=kLoop, calls=%f.4, metadata={op_name="jit(local_step)/ef_apply/add"}
  %while.5 = f32[8]{0} while(%a.9), condition=%cond.5, body=%body.5, metadata={op_name="jit(local_step)/ef_apply/compress/while"}
  %all-reduce.6 = f32[8]{0} all-reduce(%a.9), replica_groups={}, to_apply=%r.6, metadata={op_name="jit(local_step)/ef_apply/compress/exchange/psum"}
  ROOT %fusion.7 = f32[8]{0} fusion(%a.9), kind=kLoop, calls=%f.7
}
"""


def timeline():
    return scopetrace.ScopedTimeline(
        window=(0.0, 100 * MS),
        devices=[[(n, s * MS, e * MS) for n, s, e, _ in OPS],
                 [("%fusion.2 = f32[8] fusion(...)", 0, 20 * MS)]],
        modules=[[m for *_, m in OPS], [STEP_MODULE]],
        runs=[[(STEP_MODULE, 1 * MS, 75 * MS), ("jit_fold_in", 80 * MS,
                                                 90 * MS)], []],
        host=[("bench_window", 0, 100 * MS), ("train", 0, 1 * MS),
              ("step.wait", 2 * MS, 85 * MS),
              ("window.drain", 88 * MS, 100 * MS)],
        steps=[(0.0, 3)])


def test_step_scopes_read_the_nesting_from_the_hlo():
    step = scopetrace.StepScopes.of(HLO.replace("PATH", "dot_general"))
    assert step == STEP
    assert scopetrace.StepScopes.of(re.sub(
        r", metadata=\{[^}]*\}", "", HLO)) == \
        scopetrace.StepScopes({}, {}, STEP_MODULE)


def test_scope_times_count_nested_ops_once_in_every_enclosing_scope():
    st = scopetrace.scope_times(timeline(), STEP)
    d0 = st["scope_s"][0]
    # the loop and its body count once: 40 ms, not 60
    assert d0["loss_grad"] == pytest.approx(40e-3)
    # the exchange inside the compress loop, and both inside ef_apply
    assert d0["exchange"] == pytest.approx(5e-3)
    assert d0["compress"] == pytest.approx(20e-3)
    assert d0["ef_apply"] == pytest.approx(30e-3)
    # the other module's op of the same name is left out
    assert st["module_busy_s"][0] == pytest.approx(75e-3)
    assert st["unscoped_s"][0] == pytest.approx(5e-3)
    for per, un, busy in zip(st["scope_s"], st["unscoped_s"],
                             st["module_busy_s"]):
        assert per["loss_grad"] + per["ef_apply"] + un == pytest.approx(busy)
    assert st["scope_s"][1] == pytest.approx(
        {"loss_grad": 20e-3, "ef_apply": 0.0, "compress": 0.0,
         "exchange": 0.0})


def test_gap_labels_carry_span_step_and_offset():
    gaps = scopetrace.idle_gaps(timeline())
    assert gaps == [["host: window.drain (step 3, +0.090 s)",
                     pytest.approx(10e-3)],
                    ["host: step.wait (step 3, +0.075 s)",
                     pytest.approx(5e-3)]]
    tl = timeline()
    tl.steps = []
    assert scopetrace.idle_gaps(tl)[0][0] == \
        "host: window.drain (step -, +0.090 s)"


def test_reduce_adds_to_tracereduce_only():
    tl = timeline()
    base = tracereduce.reduce(tl)
    plain = scopetrace.reduce(tl)
    assert "scope_s" not in plain
    scoped = scopetrace.reduce(tl, STEP)
    for key in ("window_s", "busy_s", "collective_s", "exposed_s",
                "device_ops"):
        assert plain[key] == scoped[key] == base[key]
    assert len(scoped["idle_gaps"]) == len(base["idle_gaps"])
    assert scoped["unscoped_s"] == pytest.approx([5e-3, 0.0])


def test_step_leads_pair_spans_with_module_runs():
    assert scopetrace.step_leads(timeline(), STEP_MODULE) == \
        [pytest.approx(1e-3)]


def test_scope_readers():
    r = scopetrace.reduce(timeline(), STEP)
    run = {"trace": r, "steps": 2}
    read = lambda m: cell_lib.reader(m)(run)
    assert read("model.fwd_bwd_ms") == pytest.approx((40 + 20) / 2 / 2)
    assert read("compress.ms") == pytest.approx(15 / 2 / 2)
    assert read("ef_apply.ms") == pytest.approx(10 / 2 / 2)
    assert read("exchange.ms") == pytest.approx(5 / 2 / 2)
    for per in r["scope_s"]:
        per["exchange"] = 0.0
    assert read("exchange.ms") is None
    # a step whose HLO maps no scope yields no scope number
    for trace in (None, scopetrace.reduce(timeline()),
                  scopetrace.reduce(timeline(), scopetrace.StepScopes(
                      {}, {}, STEP_MODULE))):
        run["trace"] = trace
        for m in READERS:
            assert read(m) is None


NESTED_READER = """
def read(run):
    tr = run.get("trace")
    if not tr or not tr.get("scope_s"):
        return None
    per = [d["attn"] for d in tr["scope_s"]]
    return 1e3 * sum(per) / len(per) / run["steps"]
"""


def test_a_scope_nested_in_loss_grad_is_read_by_its_own_file(tmp_path,
                                                              monkeypatch):
    def readings(path):
        step = scopetrace.StepScopes.of(HLO.replace("PATH", path))
        run = {"trace": scopetrace.reduce(timeline(), step), "steps": 2}
        return {m: cell_lib.reader(m)(run) for m in READERS + ("attn.ms",)}

    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "attn.ms.py").write_text(
        textwrap.dedent(NESTED_READER))
    monkeypatch.setattr(cell_lib, "SEARCH", (tmp_path,) + cell_lib.SEARCH)
    monkeypatch.setattr(scopes, "SCOPES", scopes.SCOPES + ("attn",))
    before = readings("dot_general")
    after = readings("transpose(jvp(attn))/dot_general")
    assert before.pop("attn.ms") == 0.0
    # fusion.3, 20-30 ms on device 0, none on device 1, over 2 steps
    assert after.pop("attn.ms") == pytest.approx(10 / 2 / 2)
    assert after == before
    assert None not in before.values()


def test_a_cached_step_without_scopes_is_compiled_again(capsys):
    import jax

    import program
    from benchtiny import small

    class Stripped:
        def __init__(self, compiled):
            self.compiled = compiled

        def as_text(self):
            return re.sub(r", metadata=\{[^}]*\}", "",
                          self.compiled.as_text())

    cell = small("qwen3-4b.s4096b1.powersgd.1chip")
    prog = program.Program(cell, jax.devices()[:1])
    key = program.base_key(3)
    batch = prog.put_ring(program.make_ring(cell, 3))[0]
    prog.compile(batch, key)
    assert scopetrace.step_scopes(prog, batch, key).op_scope
    assert "compiling it again" not in capsys.readouterr().err
    prog.compiled = Stripped(prog.compiled)
    step = scopetrace.step_scopes(prog, batch, key)
    assert "compiling it again" in capsys.readouterr().err
    assert not isinstance(prog.compiled, Stripped)
    assert set(step.op_scope.values()) == set(scopes.SCOPES)
    assert step.module == scopes.module_name(prog.compiled.as_text())


def test_load_keeps_modules_and_steps(tmp_path):
    import jax

    # a device op inside a jit_local_step execution and one outside any,
    # the window and one train span of step 3
    xspace = """
    planes { id: 1 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 50000000 } }
      lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 2 offset_ps: 1000000 duration_ps: 1000000 }
        events { metadata_id: 2 offset_ps: 60000000 duration_ps: 1000000 } }
      event_metadata { key: 1 value { id: 1 name: "jit_local_step(12)" } }
      event_metadata { key: 2
                       value { id: 2 name: "%fusion.1 = f32[8] fusion()" } } }
    planes { id: 2 name: "/host:CPU"
      lines { id: 1 name: "python" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
        events { metadata_id: 2 offset_ps: 500000 duration_ps: 100000
                 stats { metadata_id: 1 int64_value: 3 } } }
      event_metadata { key: 1 value { id: 1 name: "bench_window" } }
      event_metadata { key: 2 value { id: 2 name: "train" } }
      stat_metadata { key: 1 value { id: 1 name: "step_num" } } }
    """
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(xspace))
    tl = scopetrace.load(str(tmp_path))
    assert tl.window == (0.0, 100_000.0)
    assert tl.modules == [["jit_local_step", None]]
    assert tl.runs == [[("jit_local_step", 1000.0, 51000.0)]]
    assert tl.steps == [(500.0, 3)]
    assert scopetrace.step_leads(tl, "jit_local_step") == \
        [pytest.approx(500e-9)]


def test_collections_in_a_window_are_counted():
    import gc

    with scopetrace.Collections() as c:
        gc.collect()
    assert c.count >= 1 and c.longest > 0
    assert c.summary() == {"count": c.count, "seconds": c.seconds,
                           "longest_s": c.longest}


def test_compiles_in_a_window_are_counted():
    import jax
    import numpy as np

    f = jax.jit(lambda x: x * 2)
    three, four = np.ones(3, np.float32), np.ones(4, np.float32)
    f(three).block_until_ready()
    with scopetrace.Compiles() as warm:
        f(three).block_until_ready()
    with scopetrace.Compiles() as cold:
        f(four).block_until_ready()
    assert (warm.count, cold.count) == (0, 1)
