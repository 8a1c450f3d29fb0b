"""The step's named scopes in a profiler trace, and where the host was in
each idle gap.

Additions to ``tracereduce`` for a traced window whose host marks each of
its waits (:func:`window`) and each garbage collection of its Python
(:class:`Collections`):

* :func:`load` is ``tracereduce.load`` that also keeps each device op's HLO
  module and each ``train`` span's step number;
* :class:`StepScopes` reads the compiled step's HLO text: each
  instruction's innermost scope of ``repro.core.scopes`` (``op_scopes``),
  the scopes each scope lies within (from the ``op_name`` paths) and the
  step's module; :func:`step_scopes` takes it from a compiled step, compiled
  again where a cached executable maps no scope;
* :func:`scope_times` gives, per device, the step module's time in each of
  the program's scopes, nested scopes included, and in none;
* :func:`idle_gaps` labels each of device 0's longest idle gaps
  ``host: <innermost span> (step <n>, +<t> s)``: the step whose ``train``
  span last began, and the gap's offset from the window's start;
* :func:`reduce` is ``tracereduce.reduce`` with these;
* :func:`window` is the timed loop, each host wait under a span, and
  :func:`traced` runs it under the profiler and loads the trace.

Run as a script, it measures one cell on the chip::

    python3 bench/scopetrace.py --workload <cell> --seed <n> --seconds <s> \
        [--out <dir>]

set-up as ``bench/run.py`` takes it, a window with the profiler off, then
a traced one continuing the same state.  The last line of standard output
is one JSON object: both windows' tokens per second, compiles, garbage
collections and slowest dispatch interval; the traced window's per-layer
metrics (each ``bench/metrics/<name>.py`` whose reading is not ``None``),
milliseconds per step in each scope (nested scopes included), the ops that
took most time with their scopes, the labelled gaps, and how far each
step's ``train`` span began before the step's first device op.  With ``--out``, the timeline it
reduced is written there for a second look.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
for p in (str(CHECKOUT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import tracereduce  # noqa: E402
from tracereduce import length, merge, op_name, subtract  # noqa: E402

from repro.core import scopes  # noqa: E402

# the traced window's host spans: the dispatch of one step, the wait on the
# previous step's loss, the final wait on the state, the losses' conversion
STEP, WAIT, DRAIN, LOSSES = "train", "step.wait", "window.drain", \
    "window.losses"
GC = "python.gc"      # a garbage collection of the host's Python
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_DIR = CHECKOUT / ".bench_trace"


@dataclasses.dataclass
class ScopedTimeline(tracereduce.Timeline):
    modules: List[List[Optional[str]]] = dataclasses.field(
        default_factory=list)          # each device op's HLO module, or None
    runs: List[List[tracereduce.Span]] = dataclasses.field(
        default_factory=list)          # each device's module executions
    steps: List[Tuple[float, int]] = dataclasses.field(
        default_factory=list)          # (start_ns, step_num) of each STEP span


def _module(name: str) -> str:
    """``jit_local_step`` from the ``XLA Modules`` event
    ``jit_local_step(7)``."""
    return re.sub(r"\(\d*\)$", "", name)


def load(trace_dir: str) -> ScopedTimeline:
    """``tracereduce.load``, plus each device's module executions (its
    ``XLA Modules`` line: the v5e trace gives its ops no module of their
    own), each device op's HLO module (the execution it starts in) and each
    ``train`` span's ``step_num``."""
    import jax

    tl = tracereduce.load(trace_dir)
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    prof = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    modules, runs, steps = {}, {}, []
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mine = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           _module(e.name))
                          for e in lines.get("XLA Modules", []))
            starts = [s for s, _, _ in mine]
            mods = []
            for e in lines["XLA Ops"]:
                k = bisect.bisect_right(starts, e.start_ns) - 1
                mods.append(mine[k][2] if k >= 0 and e.start_ns < mine[k][1]
                            else None)
            modules[plane.name] = mods
            runs[plane.name] = [(n, s, e) for s, e, n in mine]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == STEP:
                        stats = dict(e.stats)
                        if "step_num" in stats:
                            steps.append((e.start_ns, int(stats["step_num"])))
    return ScopedTimeline(window=tl.window, devices=tl.devices, host=tl.host,
                          in_flight=tl.in_flight,
                          modules=[modules[k] for k in sorted(modules)],
                          runs=[runs[k] for k in sorted(runs)],
                          steps=sorted(steps))


def _module_ops(tl: ScopedTimeline, d: int, module: str):
    """Device ``d``'s ops of ``module``, clipped to the window."""
    lo, hi = tl.window
    mods = tl.modules[d] if d < len(tl.modules) else []
    return [(op_name(n), max(s, lo), min(e, hi))
            for (n, s, e), m in zip(tl.devices[d], mods)
            if m == module and e > lo and s < hi]


_OP_NAME = re.compile(r'op_name="([^"]*)"')


@dataclasses.dataclass(frozen=True)
class StepScopes:
    """Where the compiled step's work lies: each instruction's innermost
    scope (``scopes.op_scopes``), the scopes each scope lies within
    anywhere in the step, and the step's HLO module.  Empty where the
    step's HLO maps no scope."""

    op_scope: Dict[str, str]
    within: Dict[str, frozenset]
    module: Optional[str]

    @classmethod
    def of(cls, hlo_text: str) -> "StepScopes":
        """From ``compiled.as_text()``: a scope lies within each scope that
        comes before it in some instruction's ``op_name`` path."""
        within = {}
        for path in set(_OP_NAME.findall(hlo_text)):
            chain = [c for c in map(scopes.scope_of, path.split("/")) if c]
            for i, c in enumerate(chain):
                outer = set(chain[:i]) - {c}
                if outer:
                    within.setdefault(c, set()).update(outer)
        return cls(scopes.op_scopes(hlo_text),
                   {k: frozenset(v) for k, v in within.items()},
                   scopes.module_name(hlo_text))


def step_scopes(prog, batch, key) -> StepScopes:
    """:class:`StepScopes` of ``prog``'s compiled step.  JAX leaves op
    metadata out of its persistent-cache key, so an executable loaded from
    the cache can carry the metadata of another build that maps no scope;
    then the step is compiled once more with the cache off, and standard
    error says so."""
    import jax

    from repro.launch import compile_cache

    step = StepScopes.of(prog.compiled.as_text())
    if step.op_scope:
        return step
    print("scopetrace: the cached step maps no scope; compiling it again "
          "with the persistent cache off", file=sys.stderr, flush=True)
    jax.clear_caches()
    with compile_cache.disabled():
        prog.compile(batch, key)
    return StepScopes.of(prog.compiled.as_text())


def scope_times(tl: ScopedTimeline, step: StepScopes) -> dict:
    """Per device: seconds of the window in which an op of the step module
    runs under each of the program's scopes (``scopes.SCOPES``), as the
    union of the intervals of the ops whose innermost scope is that scope
    or one that lies within it (a loop and its body count once; so does a
    nested scope's time, in the scope and in every scope around it).
    ``unscoped_s`` is the module's busy time under no scope, and
    ``module_busy_s`` its busy time."""
    scope_s, unscoped, busy = [], [], []
    for d in range(len(tl.devices)):
        ops = [(s, e, step.op_scope.get(n))
               for n, s, e in _module_ops(tl, d, step.module)]
        every = merge((s, e) for s, e, _ in ops)
        scope_s.append({
            scope: length(merge(
                (s, e) for s, e, c in ops
                if c == scope or scope in step.within.get(c, ()))) * 1e-9
            for scope in scopes.SCOPES})
        scoped = merge((s, e) for s, e, c in ops if c is not None)
        busy.append(length(every) * 1e-9)
        unscoped.append(length(subtract(every, scoped)) * 1e-9)
    return {"scope_s": scope_s, "unscoped_s": unscoped,
            "module_busy_s": busy}


def _gap_label(tl: ScopedTimeline, s: float, e: float) -> str:
    began = [n for t, n in tl.steps if t <= (s + e) / 2]
    step = began[-1] if began else "-"
    span = tracereduce._host_label(tl.host, (s + e) / 2)
    return f"{span} (step {step}, +{(s - tl.window[0]) * 1e-9:.3f} s)"


def idle_gaps(tl: ScopedTimeline, top: int = 10) -> list:
    """Device 0's longest idle gaps in the window, as ``[label, seconds]``,
    each labelled with the host's innermost span and the step whose
    ``train`` span last began, both at its middle, and its offset."""
    lo, hi = tl.window
    every = merge((max(s, lo), min(e, hi)) for _, s, e in tl.devices[0]
                  if e > lo and s < hi)
    gaps = sorted(subtract([(lo, hi)], every),
                  key=lambda g: g[0] - g[1])[:top]
    return [[_gap_label(tl, s, e), (e - s) * 1e-9] for s, e in gaps
            if e - s >= tracereduce.MIN_GAP_NS]


def reduce(tl: ScopedTimeline, step: Optional[StepScopes] = None,
           top: int = 10) -> dict:
    """``tracereduce.reduce`` with labelled gaps and, given the step's
    scopes, :func:`scope_times` (none for a step whose HLO maps no
    scope)."""
    r = tracereduce.reduce(tl, top)
    r["idle_gaps"] = idle_gaps(tl, top)
    if step is not None and step.op_scope:
        r.update(scope_times(tl, step))
    return r


def step_leads(tl: ScopedTimeline, module: str) -> List[float]:
    """Seconds from each ``train`` span's start in the window to the start
    of the execution of ``module`` it dispatched on device 0 (the k-th
    execution in the window against the k-th span): positive where the
    host's and the device's clocks agree."""
    lo, hi = tl.window
    began = [s for n, s, _ in (tl.runs[0] if tl.runs else [])
             if n == module and lo <= s < hi]
    spans = [t for t, _ in tl.steps if lo <= t < hi]
    return [(s - t) * 1e-9 for t, s in zip(spans, began)]


class Compiles:
    """Counts backend compilations (a persistent-cache load included) while
    open, from ``jax.monitoring``'s compile-duration event."""

    def __enter__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)
        return self

    def _seen(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.count += 1

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._seen)


class Collections:
    """Python's garbage collections while open: counted and timed, each
    under a host span ``python.gc`` (on the profiler's clock while a trace
    is on), so that an idle gap a collection causes is labelled by it."""

    def __enter__(self):
        self.count, self.seconds, self.longest = 0, 0.0, 0.0
        self._open = None
        gc.callbacks.append(self._seen)
        return self

    def _seen(self, phase, info):
        import jax

        if phase == "start":
            self._open = (time.perf_counter(),
                          jax.profiler.TraceAnnotation(GC))
            self._open[1].__enter__()
        elif self._open is not None:
            t, span = self._open
            span.__exit__(None, None, None)
            took = time.perf_counter() - t
            self.count += 1
            self.seconds += took
            self.longest = max(self.longest, took)
            self._open = None

    def __exit__(self, *exc):
        gc.callbacks.remove(self._seen)

    def summary(self) -> dict:
        return {"count": self.count, "seconds": self.seconds,
                "longest_s": self.longest}


def window(prog, state, ring, key, first: int, seconds: float):
    """The timed window: whole steps of ``prog`` from the first dispatch to
    ``block_until_ready`` on the last, until ``seconds`` have passed, at
    most two steps in flight.  Each host wait is under a span of its own:
    ``train`` around each dispatch, ``step.wait`` around the wait on the
    previous step's loss, ``window.drain`` around the final wait and
    ``window.losses`` around reading the losses (the spans cost nothing
    while no trace is on).  ``slowest`` is the longest time between two
    dispatches on the host's clock: the step dispatched after it, and its
    start's offset in the window."""
    import jax
    import numpy as np

    losses, began = [], []
    i = first
    t0 = time.perf_counter()
    while True:
        began.append(time.perf_counter() - t0)
        with jax.profiler.StepTraceAnnotation(STEP, step_num=i):
            state, metrics = prog.step(state, ring[i % len(ring)], key, i)
        losses.append(metrics["lm_loss"])
        i += 1
        if len(losses) >= 2:
            with jax.profiler.TraceAnnotation(WAIT):
                losses[-2].block_until_ready()
        if time.perf_counter() - t0 >= seconds:
            break
    with jax.profiler.TraceAnnotation(DRAIN):
        jax.block_until_ready(state)
    elapsed = time.perf_counter() - t0
    with jax.profiler.TraceAnnotation(LOSSES):
        losses = np.asarray([float(x) for x in losses])
    k = max(range(1, len(began)), key=lambda j: began[j] - began[j - 1],
            default=0)
    slowest = {"step": first + k, "at_s": began[k - 1] if k else 0.0,
               "seconds": began[k] - began[k - 1] if k else 0.0}
    return state, {"steps": len(losses), "seconds": elapsed,
                   "failed": int(np.sum(~np.isfinite(losses))),
                   "slowest": slowest}


def traced(run):
    """``run()`` under the profiler, inside the window's host span; returns
    its result and the trace's :class:`ScopedTimeline`.  The trace is
    deleted once read."""
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracereduce.WINDOW):
            result = run()
    finally:
        jax.profiler.stop_trace()
    try:
        return result, load(str(TRACE_DIR))
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)


def _dump(tl: ScopedTimeline, op_scope, out: pathlib.Path,
          name: str) -> str:
    """The timeline and the scope map, gzipped JSON, for a second look."""
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"scopetrace_{name}.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"timeline": dataclasses.asdict(tl), "op_scope": op_scope},
                  f)
    return str(path)


def measure(cell, seed: int, seconds: float, devices, out=None) -> dict:
    import jax

    import peaks
    import program
    from cell import reader

    devices = list(devices)[:cell.chips]
    steps = cell.traffic["check_steps"]
    prog = program.Program(cell, devices)
    ring = prog.put_ring(program.make_ring(cell, seed))
    key = program.base_key(seed)
    prog.compile(ring[0], jax.random.fold_in(key, 0))
    step = step_scopes(prog, ring[0], jax.random.fold_in(key, 0))
    state = prog.init(key)
    state, _ = prog.first_steps(state, ring, key, steps)
    setup_s = time.perf_counter() - T0

    with Compiles() as plain_c, Collections() as plain_gc:
        state, plain = window(prog, state, ring, key, steps, seconds)
    first = steps + plain["steps"]
    traced_c, traced_gc = Compiles(), Collections()

    def counted():
        with traced_c, traced_gc:
            return window(prog, state, ring, key, first, seconds)

    (state, win), tl = traced(counted)
    summary = reduce(tl, step)
    leads = step_leads(tl, step.module)

    tps = lambda w: w["steps"] * cell.tokens_per_step / w["seconds"]
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    run = {"tokens_per_s": tps(win), "steps": win["steps"],
           "chips": len(devices), "trace": summary,
           "flops_per_token": cell.flops_per_token,
           "peak_flops": peaks.peaks(info["kind"])["bf16_flops"],
           "compiled_peak_bytes": prog.memory}
    metrics = {}
    for path in sorted((BENCH / "metrics").glob("*.py")):
        value = reader(path.stem)(run)
        if value is not None:
            metrics[path.stem] = value
    per_step = lambda s: 1e3 * s / win["steps"]
    n = len(summary["busy_s"])
    result = {
        "cell": cell.name, "seed": seed, "device": info,
        "setup_s": setup_s,
        "tokens_per_s": {"untraced": tps(plain), "traced": tps(win)},
        "steps": {"untraced": plain["steps"], "traced": win["steps"]},
        "compiles_in_window": {"untraced": plain_c.count,
                               "traced": traced_c.count},
        "slowest_dispatch": {"untraced": plain["slowest"],
                             "traced": win["slowest"]},
        "gc_in_window": {"untraced": plain_gc.summary(),
                         "traced": traced_gc.summary()},
        "failed": plain["failed"] + win["failed"],
        "metrics": metrics,
        "scope_ms": {k: per_step(sum(p[k] for p in summary["scope_s"]) / n)
                     for k in scopes.SCOPES},
        "unscoped_ms": per_step(sum(summary["unscoped_s"]) / n),
        "module_busy_ms": per_step(sum(summary["module_busy_s"]) / n),
        "busy_ms": per_step(sum(summary["busy_s"]) / n),
        "window_s": summary["window_s"],
        "module": step.module,
        "ops_without_module": sum(m is None for ms in tl.modules for m in ms),
        "step_lead_s": {"min": min(leads, default=None),
                        "median": (statistics.median(leads) if leads
                                   else None),
                        "max": max(leads, default=None),
                        "n": len(leads)},
        "idle_gaps": summary["idle_gaps"],
        "device_ops": [[n, t, step.op_scope.get(n)]
                       for n, t in summary["device_ops"]],
    }
    if out is not None:
        result["timeline"] = _dump(tl, step.op_scope, pathlib.Path(out),
                                   f"{cell.name}_{seed}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="directory for the reduced timeline")
    args = ap.parse_args(argv)

    import cell as cell_lib

    cell = cell_lib.load(args.workload)
    from repro.launch import compile_cache

    compile_cache.enable()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"scopetrace: {cell.name} needs {cell.chips} TPU chips, JAX "
              f"found {len(devices)} {devices[0].platform}", file=sys.stderr)
        return 2
    print(json.dumps(measure(cell, args.seed, args.seconds, devices,
                             args.out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
