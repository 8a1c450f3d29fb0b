"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` the JAX profiler writes into a
:class:`Timeline`: the traced window (the host span named ``WINDOW``), each
device's operations (its ``XLA Ops`` line), its asynchronous operations in
flight (``Async XLA Ops``, start to done) and the host's spans.  A device
is busy while an operation executes; a collective counts from the ops of
either line.
``reduce`` turns a timeline into busy time, collective time and the part of
it no other operation overlaps, per device, plus the operations that took
most time and device 0's longest idle gaps, each named by what the host was
doing in it.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import List, Tuple

WINDOW = "bench_window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
MIN_GAP_NS = 1_000        # shorter idle slivers are launch jitter, not gaps

Span = Tuple[str, float, float]      # (name, start_ns, end_ns)


@dataclasses.dataclass
class Timeline:
    window: Tuple[float, float]
    devices: List[List[Span]]          # each device's ops, as they execute
    host: List[Span]
    in_flight: List[List[Span]] = dataclasses.field(default_factory=list)
    #                                    each device's async ops, start to done


def op_name(event_name: str) -> str:
    """``fusion.12`` from ``%fusion.12 = f32[...] fusion(...), ...``: the
    trace names a TPU op by its whole HLO instruction."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def is_collective(name: str) -> bool:
    n = op_name(name).lower()
    return any(n.startswith(c) for c in COLLECTIVES)


def merge(iv):
    """Union of (start, end) intervals, as sorted disjoint intervals."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(iv) -> float:
    return sum(e - s for s, e in iv)


def subtract(a, b):
    """Disjoint sorted ``a`` minus disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _host_label(host: List[Span], t: float) -> str:
    """The innermost host span at ``t`` (other than the window itself)."""
    best = None
    for name, s, e in host:
        if s <= t < e and name != WINDOW and (best is None
                                              or e - s < best[1] - best[0]):
            best = (s, e, name)
    return f"host: {best[2]}" if best else "host: no span"


def reduce(tl: Timeline, top: int = 10) -> dict:
    lo, hi = tl.window
    busy, coll, exposed = [], [], []
    op_time = collections.Counter()
    gaps = []
    clip = lambda spans: [(n, max(s, lo), min(e, hi)) for n, s, e in spans
                          if e > lo and s < hi]
    for d, ops in enumerate(tl.devices):
        ops = clip(ops)
        flying = clip(tl.in_flight[d]) if d < len(tl.in_flight) else []
        every = merge((s, e) for _, s, e in ops)
        c_iv = merge((s, e) for n, s, e in ops + flying if is_collective(n))
        rest = merge((s, e) for n, s, e in ops if not is_collective(n))
        busy.append(length(every) * 1e-9)
        coll.append(length(c_iv) * 1e-9)
        exposed.append(length(subtract(c_iv, rest)) * 1e-9)
        for n, s, e in ops:
            op_time[op_name(n)] += (e - s) * 1e-9 / len(tl.devices)
        if d == 0:
            gaps = sorted(subtract([(lo, hi)], every),
                          key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy,
        "collective_s": coll,
        "exposed_s": exposed,
        "device_ops": [[n, t] for n, t in op_time.most_common(top)],
        "idle_gaps": [[_host_label(tl.host, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps if e - s >= MIN_GAP_NS],
    }


def load(trace_dir: str) -> Timeline:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    prof = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, flying, host, window = {}, {}, [], None
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                into = {"XLA Ops": devices,
                        "Async XLA Ops": flying}.get(line.name)
                if into is not None:
                    into.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    host.append(span)
                    if e.name == WINDOW:
                        window = span[1:]
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    if not devices:
        raise ValueError("no device operations in the trace")
    return Timeline(window=window,
                    devices=[devices[k] for k in sorted(devices)],
                    host=host,
                    in_flight=[flying.get(k, []) for k in sorted(devices)])
