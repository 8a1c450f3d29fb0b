"""Benchmark driver: one function per paper table (DESIGN.md §6).

Prints ``table,key=value,...`` CSV-ish lines and writes JSON to
experiments/benchmarks/.  ``--quick`` shrinks step counts for CI.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))
sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "src")))

import jax


def main() -> None:
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced step counts (CI)")
    ap.add_argument("--only", default=None,
                    help="comma-separated table names (e.g. table1,fig3)")
    ap.add_argument("--out", default="experiments/benchmarks")
    args = ap.parse_args()

    from benchmarks import tables
    from benchmarks.common import LMSpec
    from repro.models import model as model_lib
    from repro.configs.base import get_config

    steps = 40 if args.quick else 150
    spec = LMSpec(steps=steps, workers=4, batch_per_worker=4)

    # small params tree for timing-model tables
    cfg_small = get_config("llama3-8b", reduced=True)
    params_small = model_lib.init(jax.random.key(0), cfg_small, 1)
    specs_small = model_lib.mspecs(cfg_small)

    runs = {
        "table1_error_feedback": lambda: tables.table1_error_feedback(spec),
        "table2_warm_start": lambda: tables.table2_warm_start(spec),
        "table3_rank_sweep": lambda: tables.table3_rank_sweep(spec),
        "table4_compressor_zoo": lambda: tables.table4_compressor_zoo(spec),
        "table5_time_breakdown": lambda: tables.table5_time_breakdown(
            params_small, specs_small),
        "table6_other_methods": lambda: tables.table6_other_methods(spec),
        "table7_lstm": lambda: tables.table7_lstm(40 if args.quick else 120),
        "fig3_scaling": lambda: tables.fig3_scaling(params_small, specs_small),
        "adaptive_rank_profile": lambda: tables.adaptive_rank_profile(spec),
        "resume_overhead": lambda: tables.resume_overhead(
            spec, ckpt_every=10 if args.quick else 20),
        "comm_profile": lambda: tables.comm_profile(params_small, specs_small),
        "sync_mode_profile": lambda: tables.sync_mode_profile(
            params_small, specs_small),
        "zoo_transport_profile": lambda: tables.zoo_transport_profile(
            params_small, specs_small),
        "overlap_profile": lambda: tables.overlap_profile(
            params_small, specs_small),
        "appendixD_transformer": lambda: tables.appendixD_transformer(spec),
    }
    if args.only:
        keep = {k.strip() for k in args.only.split(",")}
        runs = {k: v for k, v in runs.items() if any(s in k for s in keep)}

    os.makedirs(args.out, exist_ok=True)
    for name, fn in runs.items():
        t0 = time.time()
        rows = fn()
        dt = time.time() - t0
        print(f"\n=== {name} ({dt:.1f}s) ===")
        for row in rows:
            print(name + "," + ",".join(f"{k}={v}" for k, v in row.items()))
        with open(os.path.join(args.out, name + ".json"), "w") as f:
            json.dump(rows, f, indent=2)


if __name__ == "__main__":
    main()
