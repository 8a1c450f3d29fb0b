"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --first-seed <n>

In one process on the cell's chips, for each seed: the program through its
normal path (compiled once) against the reference (the lower readings); on
the first ``--control`` seeds the control, the reference in bfloat16 at the
default precision, against the reference in float32 at ``highest``; on the
first ``--faults`` seeds each fault of ``reference.FAULTS`` that the cell can
have, planted in the reference put in the program's place.  A step that
returns its state unchanged reads 1 by the change number and needs no run.
Each reading is one JSON line on standard output (and in ``--out``); the
last line sums them up per number.  Not part of a benchmark run.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=5_000_000_000)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import cell as cell_lib
    from repro.launch import compile_cache

    cell = cell_lib.load(args.workload)
    compile_cache.enable()
    import jax
    import jax.numpy as jnp

    import check
    import program
    import reference

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"calibrate: {cell.name} needs {cell.chips} TPU chips",
              file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    out = open(args.out, "a") if args.out else None
    steps = cell.traffic["check_steps"]
    model = reference.Model.of(cell.arch, cell.config)
    optim = reference.Optim.from_traffic(cell.traffic)
    faults = [f for f in reference.FAULTS
              if f != "no_exchange" or cell.data_ranks > 1]
    prog = program.Program(cell, devices)
    rows = []

    def emit(kind, seed, nums, seconds):
        row = {"cell": cell.name, "kind": kind, "seed": seed,
               "seconds": seconds, **nums}
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for n in range(args.seeds):
        seed = args.first_seed + 7919 * n
        key = program.base_key(seed)
        ring_np = program.make_ring(cell, seed)
        ring = prog.put_ring(ring_np)
        if prog.compiled is None:
            prog.compile(ring[0], jax.random.fold_in(key, 0))
        t = time.perf_counter()
        state = prog.init(key)
        state, got = prog.first_steps(state, ring, key, steps)
        del state, ring
        t_prog = time.perf_counter() - t
        batches = program.batches_for_check(cell, ring_np, steps)
        t = time.perf_counter()
        ref = reference.train(model, optim, key, batches, devices)
        t_ref = time.perf_counter() - t
        emit("program", seed, check.numbers(got, ref),
             {"program": t_prog, "reference": t_ref})
        if n < args.control:
            t = time.perf_counter()
            ctl = reference.train(model, optim, key, batches, devices,
                                  dtype=jnp.bfloat16, precision="default")
            emit("control", seed, check.numbers(ctl, ref),
                 time.perf_counter() - t)
        if n < args.faults:
            for fault in faults:
                t = time.perf_counter()
                bad = reference.train(model, optim, key, batches, devices,
                                      fault=fault)
                emit(f"fault:{fault}", seed, check.numbers(bad, ref),
                     time.perf_counter() - t)

    summary = {"cell": cell.name, "kind": "summary",
               "process_s": time.perf_counter() - T0}
    for name in check.NUMBERS:
        by_kind = {}
        for r in rows:
            by_kind.setdefault(r["kind"], []).append(r[name])
        summary[name] = {k: [min(v), max(v)] for k, v in by_kind.items()}
    print(json.dumps(summary), flush=True)
    if out:
        out.write(json.dumps(summary) + "\n")
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
