"""Subprocess check: PowerSGD linearity (paper Appendix A.3 / Lemma 3).

Running the distributed EF-PowerSGD train step on W data-parallel workers
must equal running it on 1 worker with the full batch — exactly (up to f32
reassociation).  Exits non-zero on failure.
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from repro.launch.train import TrainHyper, make_train_step
from repro.launch.mesh import make_mesh
from repro.configs.base import get_config
from repro.data.synthetic import MarkovLM


def run(mesh_shape, steps=3):
    cfg = get_config("llama3-8b", reduced=True)
    hyper = TrainHyper(q_chunk=32, warmup_steps=5, remat=False)
    key = jax.random.key(0)
    mesh = make_mesh(mesh_shape, ("data", "model"))
    step_fn, _, init_state = make_train_step(cfg, mesh, hyper)
    data = MarkovLM(vocab=cfg.vocab_size, seed=0)
    it = data.batches(8, 64)
    with jax.set_mesh(mesh):
        params, ef = init_state(key)
        for _ in range(steps):
            batch = {k: jnp.asarray(v) for k, v in next(it).items()}
            params, ef, _ = step_fn(params, ef, batch, key)
    return jax.tree_util.tree_map(lambda x: np.asarray(x), params)


def main():
    # same model-parallel degree (2), data parallelism 4 vs 1:
    # the compression blocking is identical, so Lemma 3 applies exactly
    p_multi = run((4, 2))
    p_single = run((1, 2))
    worst = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(p_multi),
                    jax.tree_util.tree_leaves(p_single)):
        rel = float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-12))
        worst = max(worst, rel)
    print(f"worst relative diff over params: {worst:.3e}")
    assert worst < 5e-5, f"linearity violated: {worst}"
    print("LINEARITY_OK")


if __name__ == "__main__":
    main()
