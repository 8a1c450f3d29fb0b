"""Small copies of the benchmark's cells for the CPU tests: the same files,
with widths, depth, vocabulary and sequence cut so that a test run holds
them.  Each keeps its cell's limits, traffic and metrics.  A configuration's
CPU size is ``bench/tests/small/<config>.json``: the keys it changes in the
configuration file, ``changed`` (the program's fields) among them."""

from __future__ import annotations

import dataclasses
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import cell as cell_lib  # noqa: E402

SMALL = BENCH / "tests" / "small"
SEQ = 64


def small(name: str, data_ranks: int = 1) -> cell_lib.Cell:
    """Cell ``name`` of BENCHMARK.json at a size a CPU test holds; with
    ``data_ranks`` > 1, on a (data_ranks, 1) data mesh."""
    c = cell_lib.load(name)
    with open(SMALL / f"{c.config['name']}.json") as f:
        config = dict(c.config, **json.load(f))
    traffic = dict(c.traffic, seq=SEQ, mesh=[data_ranks, 1])
    traffic["hyper"] = dict(traffic["hyper"], q_chunk=16)
    return dataclasses.replace(c, config=config, traffic=traffic,
                               chips=data_ranks)
