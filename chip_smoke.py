"""Smoke run of EF-PowerSGD training on a TPU, at Qwen3-4B widths.

Drives the trainer's main path once: ``make_train_step`` → ``init_state``
→ the jitted ``shard_map`` step, with PowerSGD over the data axis.  The
model is Qwen3-4B at its published widths (d_model 2560, 32 q / 8 kv heads
of 128, d_ff 9728, qk-norm) with two cuts: 4 of its 36 layers, and the
eighth of its 151936-row vocabulary that one chip holds when eight chips
share each layer's vocabulary.  Weights are random from a seed; the data is
the seeded MarkovLM token stream.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # a four-chip host: data meshes only

With ``--chips 4`` it runs only the cross-chip phase: the same model on a
(4,1) data mesh and on the trainer CLI's (2,2) data×model mesh, with
``powersgd`` and ``identity`` on each.

It fails, and prints no result, where JAX finds no TPU.  The last line of
its output is one JSON object: ``{"ok": ..., "device": {"platform",
"kind", "count"}}``.  Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

LAYERS = 4            # of Qwen3-4B's 36
VOCAB_SHARERS = 8     # chips that share each layer's vocabulary
SEQ = 4096
BATCH = 1             # sequences per data rank; sized by memory (PERF.md)
STEPS = 6
STEPS_4CHIP = 3
# step-0 band: at init the final rmsnorm gives unit-RMS features and the
# head has std 1/sqrt(d_model), so every logit is ~N(0, 1) and the expected
# loss is ln V + 1/2.  [ln V, ln V + 1] holds that with room for a batch's
# noise (per-token std ~1 nat over >=8k tokens) and rejects a loss over the
# wrong vocabulary size (a doubled V adds 0.69 nats) or unscaled logits.
BAND = (0.0, 1.0)
# step-0 losses of one batch on different meshes and compressors: the same
# math, summed in another order (the (2,2) mesh splits every contraction
# over two chips); 1e-4 relative is ~800 f32 ulps at 10 nats
LOSS_RTOL = 1e-4


def smoke_config():
    """Qwen3-4B at published widths, cut in depth and to one chip's vocab."""
    from repro.configs.base import get_config

    full = get_config("qwen3-4b")
    return dataclasses.replace(full, num_layers=LAYERS,
                               vocab_size=full.vocab_size // VOCAB_SHARERS)


def run(cfg, mesh, hyper, *, steps, batch, seq, compressor=None, log=print):
    """Train ``steps`` steps on one batch of ``batch`` sequences per data
    rank, drawn once from the seeded MarkovLM stream.

    One batch, repeated: the overfit-one-batch check.  Its loss falls at
    once if the compressed, error-fed update descends.  A fresh batch each
    step would not show that in a few steps: over 18992 tokens the order-2
    chain has 18992² contexts, and its loss stays within a batch's noise of
    the initial loss for the first tens of steps.

    Returns a dict: the per-step ``lm_loss`` list, the compile seconds, the
    per-step milliseconds (compile excluded, each timed to
    ``block_until_ready``), the compiled program's memory analysis and the
    last step's metrics."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data.synthetic import MarkovLM
    from repro.launch import mesh as mesh_lib
    from repro.launch.train import make_train_step

    step_fn, _, init_state = make_train_step(cfg, mesh, hyper,
                                             compressor=compressor)
    key = jax.random.key(0)
    with jax.set_mesh(mesh):
        params, ef = init_state(key)
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))

    dp = mesh_lib.data_axes(mesh)
    gbatch = batch * math.prod(mesh.shape[a] for a in dp)
    tok_sharding = NamedSharding(mesh, P(dp, None))
    toks = MarkovLM(vocab=cfg.vocab_size, seed=0).sample(gbatch, seq, step=0)
    b = {"tokens": jax.device_put(toks[:, :-1], tok_sharding),
         "labels": jax.device_put(toks[:, 1:].copy(), tok_sharding)}

    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        compiled = step_fn.lower(params, ef, b,
                                 jax.random.fold_in(key, 0)).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()

    losses, step_ms, metrics = [], [], {}
    for i in range(steps):
        t0 = time.perf_counter()
        params, ef, metrics = compiled(params, ef, b,
                                       jax.random.fold_in(key, i))
        jax.block_until_ready((params, ef, metrics))
        step_ms.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(metrics["lm_loss"]))
        log(f"  step {i} lm_loss={losses[-1]!r} ({step_ms[-1]:.1f} ms)")
    return {"losses": losses, "compile_s": compile_s, "step_ms": step_ms,
            "n_params": n_params, "global_batch": gbatch, "memory": mem,
            "metrics": {k: float(v) for k, v in metrics.items()}}


def _mem_line(mem) -> str:
    if mem is None:
        return "compiled memory: not reported"
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    return (f"compiled memory: arguments {mem.argument_size_in_bytes} B, "
            f"temporaries {mem.temp_size_in_bytes} B, peak estimate "
            f"{peak} B ({peak / 2**30:.2f} GiB)")


def _device_peaks(devices) -> str:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return "peak_bytes_in_use " + " ".join(str(p) for p in peaks)


def _band_ok(cfg, loss0) -> bool:
    lo = math.log(cfg.vocab_size) + BAND[0]
    hi = math.log(cfg.vocab_size) + BAND[1]
    return lo <= loss0 <= hi


def one_chip(cfg, devices, log=print) -> bool:
    from repro.launch.mesh import make_mesh
    from repro.launch.train import TrainHyper

    mesh = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    hyper = TrainHyper()
    log(f"one chip: {hyper}")
    r = run(cfg, mesh, hyper, steps=STEPS, batch=BATCH, seq=SEQ, log=log)
    losses = r["losses"]
    log(f"parameters {r['n_params']}  batch {r['global_batch']}x{SEQ}")
    log(f"compile {r['compile_s']:.2f} s")
    log(f"step ms: first {r['step_ms'][0]:.1f}, then "
        + " ".join(f"{t:.1f}" for t in r["step_ms"][1:]))
    log(_mem_line(r["memory"]))
    log(_device_peaks(devices[:1]))
    checks = {
        "finite": all(math.isfinite(x) for x in losses),
        f"step-0 loss in ln V + {list(BAND)}": _band_ok(cfg, losses[0]),
        "last loss below first": losses[-1] < losses[0],
    }
    for name, good in checks.items():
        log(f"check {name}: {'pass' if good else 'FAIL'}")
    return all(checks.values())


def four_chips(cfg, devices, log=print) -> bool:
    from repro.core.compressors import IdentityCompressor
    from repro.launch.mesh import make_mesh
    from repro.launch.train import TrainHyper

    hyper = TrainHyper(track_drift=True)
    log(f"four chips: {hyper}")
    rows = []
    for shape in ((4, 1), (2, 2)):
        mesh = make_mesh(shape, ("data", "model"), devices=devices[:4])
        # the same global batch on both meshes: BATCH per chip on (4,1)
        per_rank = BATCH * 4 // shape[0]
        for name, comp in (("powersgd", None),
                           ("identity", IdentityCompressor())):
            log(f"mesh {shape} {name}:")
            r = run(cfg, mesh, hyper, steps=STEPS_4CHIP, batch=per_rank,
                    seq=SEQ, compressor=comp, log=log)
            drift = {k: v for k, v in r["metrics"].items()
                     if k.startswith("drift_")}
            log(f"  compile {r['compile_s']:.2f} s; step ms "
                + " ".join(f"{t:.1f}" for t in r["step_ms"])
                + f"; {_device_peaks(devices[:4])}")
            log(f"  drift after step {STEPS_4CHIP - 1}: {drift}")
            rows.append((shape, name, r["losses"]))
    ref = rows[0][2][0]
    log("step-0 losses: " + ", ".join(
        f"{s} {n} {l[0]!r}" for s, n, l in rows))
    spread = max(abs(l[0] - ref) for _, _, l in rows)
    log(f"step-0 max |difference| {spread!r} (rtol {LOSS_RTOL})")
    checks = {
        "finite": all(math.isfinite(x) for _, _, l in rows for x in l),
        f"step-0 loss in ln V + {list(BAND)}": _band_ok(cfg, ref),
        "step-0 losses agree": spread <= LOSS_RTOL * abs(ref),
    }
    for name, good in checks.items():
        log(f"check {name}: {'pass' if good else 'FAIL'}")
    return all(checks.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip data-mesh phase")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache

    compile_cache.enable()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    cfg = smoke_config()
    print(f"device {dev.device_kind} x{len(devices)}")
    print(f"config {cfg.name}: d_model {cfg.d_model}, heads "
          f"{cfg.num_heads}/{cfg.num_kv_heads}x{cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, qk_norm {cfg.qk_norm}; cut to {cfg.num_layers} of 36 "
          f"layers and vocab {cfg.vocab_size} (1/{VOCAB_SHARERS} of 151936); "
          f"seq {SEQ}, {BATCH} sequences per chip")
    phase = four_chips if args.chips == 4 else one_chip
    ok = phase(cfg, devices)
    print(json.dumps({"ok": ok, "device": {"platform": dev.platform,
                                           "kind": dev.device_kind,
                                           "count": len(devices)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
