"""Chip benchmark of EF-PowerSGD training: one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``, from process start): the cell's ring of
distinct batches drawn from the seed and put on the device, the step
compiled for the cell's one shape (JAX's persistent cache in
``<checkout>/.jax_cache``), weights and state from ``init_state`` on the
device, and the first steps the check compares.  The window then times
whole steps of the same object for ``--seconds``.  With ``--trace 1`` the
window is profiled and the cell's per-layer metrics are printed instead of
its end-to-end ones; set-up then also maps the compiled step's instructions
to the program's named scopes (``bench/scopetrace.py``).

After the window the program's state is freed and the plain reference
(``bench/reference.py`` with the configuration's ``bench/archs/<arch>.py``)
follows the same first steps; ``correct`` says
whether the program's readings are within the limits of
``bench/limits/<cell>.json``.  The last line of standard output is one JSON
object; the numbers compared are also the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
for p in (str(CHECKOUT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peak_in_use(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t0: float = None) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import jax

    import check
    import peaks
    import program
    import reference
    import scopetrace
    from cell import reader

    t0 = time.perf_counter() if t0 is None else t0
    devices = list(devices)[:cell.chips]
    steps = cell.traffic["check_steps"]
    ring_np = program.make_ring(cell, seed)
    prog = program.Program(cell, devices)
    ring = prog.put_ring(ring_np)
    key = program.base_key(seed)
    prog.compile(ring[0], jax.random.fold_in(key, 0))
    if trace:
        step = scopetrace.step_scopes(prog, ring[0],
                                      jax.random.fold_in(key, 0))
    state = prog.init(key)
    state, prog_read = prog.first_steps(state, ring, key, steps)
    setup_s = time.perf_counter() - t0
    log(f"{cell.name}: set-up {setup_s:.2f} s; first losses "
        f"{prog_read.losses}")

    if trace:
        (state, win), tl = scopetrace.traced(lambda: scopetrace.window(
            prog, state, ring, key, steps, seconds))
        summary = scopetrace.reduce(tl, step)
    else:
        state, win = scopetrace.window(prog, state, ring, key, steps,
                                       seconds)
    tokens_per_s = win["steps"] * cell.tokens_per_step / win["seconds"]
    log(f"{cell.name}: {win['steps']} steps in {win['seconds']:.3f} s, "
        f"{tokens_per_s:.1f} tokens/s")
    compiled_peak = prog.memory
    peak = max(peak_in_use(devices), compiled_peak or 0)
    del state, ring, prog
    gc.collect()

    ref = reference.train(
        reference.Model.of(cell.arch, cell.config),
        reference.Optim.from_traffic(cell.traffic), key,
        program.batches_for_check(cell, ring_np, steps), devices)
    nums = check.numbers(prog_read, ref)
    correct, checks = check.verdict(nums, cell.limits)
    log(f"{cell.name}: reference losses {ref.losses}; worst leaves: "
        f"grad {nums['grad_leaf']}, change {nums['change_leaf']}")

    info = device_info(devices)
    info["memory_peak_bytes"] = peak
    if trace:
        run = {"tokens_per_s": tokens_per_s, "steps": win["steps"],
               "chips": len(devices), "trace": summary,
               "flops_per_token": cell.flops_per_token,
               "peak_flops": peaks.peaks(info["kind"])["bf16_flops"],
               "compiled_peak_bytes": compiled_peak}
        metrics = {}
        for m in cell.per_layer:
            value = reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        info["busy_s"] = sum(summary["busy_s"]) / len(summary["busy_s"])
        info["window_s"] = summary["window_s"]
    else:
        values = {"tokens_per_s": tokens_per_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": correct, "attempted": win["steps"],
              "failed": win["failed"], "metrics": metrics, "device": info}
    if trace:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import cell as cell_lib

    cell = cell_lib.load(args.workload)
    from repro.launch import compile_cache

    compile_cache.enable()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: needs a TPU, JAX found {devices[0].platform}")
        return 2
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
            f"{len(devices)}")
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, t0=T0)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
