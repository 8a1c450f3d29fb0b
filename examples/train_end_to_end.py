"""End-to-end distributed training driver (deliverable b).

Trains a ~100M-parameter GQA transformer LM on the synthetic Markov stream
with EF-PowerSGD (Algorithm 1+2), data×model-parallel over the host devices,
and compares against full-precision SGD (IdentityCompressor) on loss and
bytes all-reduced per step.  Checkpoints via repro.checkpoint.

    # full run (~100M params, a few hundred steps — takes a while on CPU):
    PYTHONPATH=src python examples/train_end_to_end.py --steps 300

    # quick smoke (~7M params, 2 minutes):
    PYTHONPATH=src python examples/train_end_to_end.py --preset small --steps 40
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import save_checkpoint
from repro.configs.base import LayerSlot, ModelConfig
from repro.core.compressors import IdentityCompressor, PowerSGDCompressor
from repro.data.synthetic import MarkovLM
from repro.launch.train import TrainHyper, make_train_step
from repro.launch.mesh import make_mesh


PRESETS = {
    # ~101M params: 2*V*d + L*(4*d*hd*H... ) — dominated by embed+head
    "100m": ModelConfig(
        name="demo-100m", arch_type="dense", num_layers=8, d_model=768,
        num_heads=12, num_kv_heads=4, d_ff=2048, vocab_size=32768,
        slots=(LayerSlot("attn", "dense"),)),
    "small": ModelConfig(
        name="demo-7m", arch_type="dense", num_layers=4, d_model=256,
        num_heads=8, num_kv_heads=4, d_ff=512, vocab_size=8192,
        slots=(LayerSlot("attn", "dense"),)),
}


def run(name, compressor, cfg, mesh, args, log):
    hyper = TrainHyper(lr=args.lr, rank=args.rank, q_chunk=64,
                       warmup_steps=min(20, args.steps // 4), remat=False)
    step_fn, _, init_state = make_train_step(cfg, mesh, hyper,
                                             compressor=compressor)
    key = jax.random.key(args.seed)
    with jax.set_mesh(mesh):
        params, ef = init_state(key)
    data = MarkovLM(vocab=cfg.vocab_size, seed=0)
    it = data.batches(args.batch, args.seq)

    losses, t0 = [], time.time()
    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        with jax.set_mesh(mesh):
            params, ef, metrics = step_fn(params, ef, batch, key)
        loss = float(metrics["lm_loss"])
        losses.append(loss)
        if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
            print(f"  [{name}] step {i:4d} loss={loss:.4f} "
                  f"({time.time()-t0:.0f}s)", flush=True)
    if args.ckpt_dir:
        path = save_checkpoint(os.path.join(args.ckpt_dir, name),
                               args.steps, {"params": params})
        print(f"  [{name}] checkpoint: {path}")
    log[name] = {"final_loss": losses[-1],
                 "loss_curve": losses[:: max(1, args.steps // 50)],
                 "wall_s": round(time.time() - t0, 1)}
    return losses[-1]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="100m", choices=PRESETS)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--rank", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skip-sgd-baseline", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--out", default="experiments/train_end_to_end.json")
    args = ap.parse_args()

    cfg = PRESETS[args.preset]
    n_dev = len(jax.devices())
    mesh = make_mesh((max(1, n_dev // 2), min(2, n_dev)), ("data", "model"))
    print(f"model: {cfg.name}  params≈{cfg.param_count()/1e6:.1f}M  "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    # bytes all-reduced per step: PowerSGD vs raw gradient
    from repro.core import powersgd as ps_lib
    from repro.models import model as model_lib
    shapes = jax.eval_shape(lambda: model_lib.init(jax.random.key(0), cfg, 1))
    specs = model_lib.mspecs(cfg)
    total = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    sent = ps_lib.compressed_floats_total(shapes, specs, args.rank)
    print(f"gradient floats {total:,} -> all-reduced {sent:,} "
          f"({total/sent:.0f}x compression at rank {args.rank})\n")

    log = {"config": {k: v for k, v in vars(args).items()},
           "params_m": cfg.param_count() / 1e6,
           "compression_ratio": total / sent}
    run("powersgd", PowerSGDCompressor(rank=args.rank), cfg, mesh, args, log)
    if not args.skip_sgd_baseline:
        run("sgd", IdentityCompressor(), cfg, mesh, args, log)
        d = log["powersgd"]["final_loss"] - log["sgd"]["final_loss"]
        print(f"\nfinal loss: powersgd={log['powersgd']['final_loss']:.4f} "
              f"sgd={log['sgd']['final_loss']:.4f} (gap {d:+.4f}) — "
              f"with {total/sent:.0f}x less gradient traffic")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(log, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
