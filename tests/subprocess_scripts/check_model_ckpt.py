"""Subprocess model-parallel checkpoint suite: per-rank state round-trips
on a real 2×2 (data × model) fake-device mesh.

The bug this suite pins (and its fix certifies): Q factors of *row-parallel*
weights (embed ``P("model", None)``, attention out-proj, MLP down-proj) are
declared replicated over the model axis — their shape carries no model dim —
but each model rank's warm-start iteration ``Q = Mᵀ P̂`` is a function of its
LOCAL n-rows, so the "replicated" leaf holds distinct per-rank content
(model-LOCAL in ``repro.core.engine.StatePartition`` terms).  ``np.asarray``
at save time silently serializes device 0's (model rank 0's) replica, and a
plain restore broadcasts that copy to every rank: ranks ≥ 1 resume with the
wrong factors and the warm-start ablation (§3) silently degrades.

One phase per invocation (``argv[1]``):

``regression``
    Pins the pre-fix corruption against the PLAIN save/restore path (no
    mesh canonicalization — exactly what a pre-PR-7 driver did): after
    training long enough for the per-model-rank factors to diverge, a plain
    round-trip hands every rank model-rank-0's copy — bit-equal to rank 0's
    pre-save content, bit-different from rank 1's own.

``resume``
    The fixed path: ``canonicalize_mesh`` → ``save_train_state`` → (kill) →
    ``stack_model_template`` → ``restore_train_state(model_axis_size=...)``
    → ``replicate_mesh`` resumes bit-exactly — EVERY model rank's Q factors
    and EF buffers restore to their own pre-kill bytes, and the per-step
    losses of the continued run reproduce the uninterrupted run's
    bit-for-bit.  Also checks the degree guard: restoring the same envelope
    while claiming a different model degree raises CheckpointError naming
    both sizes.

Exits non-zero on failure; prints a phase sentinel on success.
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from repro.checkpoint import (CheckpointError, TrainState, canonicalize_mesh,
                              replicate_mesh, restore_train_state,
                              save_train_state, stack_model_template)
from repro.configs.base import get_config
from repro.core.engine import MODEL_LOCAL
from repro.core.error_feedback import EFState
from repro.data.synthetic import MarkovLM
from repro.launch.train import (TrainHyper, make_train_step,
                                train_state_partition)
from repro.launch.mesh import make_mesh

BATCH, SEQ = 8, 128
SAVE_AT, STEPS = 3, 6
MESH_SHAPE = (2, 2)  # (data, model)


def build(cfg, mesh, hyper):
    step_fn, _, init_state = make_train_step(cfg, mesh, hyper)
    data = MarkovLM(vocab=cfg.vocab_size, seed=0)

    def batch_at(i):
        toks = data.sample(BATCH, SEQ, step=i)
        return {"tokens": jnp.asarray(toks[:, :-1]),
                "labels": jnp.asarray(toks[:, 1:].copy())}

    return step_fn, init_state, batch_at


def setup():
    cfg = get_config("llama3-8b", reduced=True)
    # sync_mode="broadcast": replica-deterministic data-axis aggregation, so
    # "bit-exact resume" is a meaningful target on any substrate
    hyper = TrainHyper(lr=0.05, rank=2, q_chunk=64, warmup_steps=20,
                       remat=False, sync_mode="broadcast")
    mesh = make_mesh(MESH_SHAPE, ("data", "model"))
    parts = train_state_partition(cfg, mesh)
    return cfg, hyper, mesh, parts


def per_rank_comp(mesh, params, ef, parts):
    """Host-side stacked per-model-rank content of every model-LOCAL comp
    leaf (reuses the save path's gather), as a flat {path: (S, ...) array}."""
    _, ef_c = canonicalize_mesh(mesh, params, ef, parts)
    out = {}
    flat_p = jax.tree_util.tree_flatten_with_path(
        parts.comp, is_leaf=lambda x: x is None)[0]
    flat_q = jax.tree_util.tree_flatten_with_path(
        ef_c.comp, is_leaf=lambda x: x is None)[0]
    for (pp, part), (qp, q) in zip(flat_p, flat_q):
        assert jax.tree_util.keystr(pp) == jax.tree_util.keystr(qp)
        if part is not None and part.model == MODEL_LOCAL:
            out[jax.tree_util.keystr(pp)] = np.asarray(q)
    return out


def run_to(step_fn, mesh, params, ef, key, batch_at, lo, hi):
    losses = []
    with jax.set_mesh(mesh):
        for i in range(lo, hi):
            params, ef, met = step_fn(params, ef, batch_at(i),
                                      jax.random.fold_in(key, i))
            losses.append(float(met["lm_loss"]))
    return params, ef, losses


def phase_regression():
    """Plain (pre-fix) save/restore hands every model rank model-rank-0's
    warm-start factors — pinned at the bytes level."""
    cfg, hyper, mesh, parts = setup()
    step_fn, init_state, batch_at = build(cfg, mesh, hyper)
    key = jax.random.key(0)
    with jax.set_mesh(mesh):
        params, ef = init_state(key)
    params, ef, _ = run_to(step_fn, mesh, params, ef, key, batch_at,
                           0, SAVE_AT)

    pre = per_rank_comp(mesh, params, ef, parts)
    assert pre, "no model-LOCAL comp leaves on a (2,2) mesh — mspecs changed?"
    diverged = [p for p, q in pre.items()
                if any(not np.array_equal(q[m], q[0])
                       for m in range(1, q.shape[0]))]
    assert diverged, (
        f"model ranks' Q factors are bit-identical after {SAVE_AT} steps — "
        f"the regression scenario is vacuous (warm start off? rank-invariant "
        f"init?): {sorted(pre)}")

    with tempfile.TemporaryDirectory() as d:
        # the pre-fix path: no canonicalize_mesh, no model_axis_size —
        # np.asarray inside the envelope writer picks device 0's replica
        save_train_state(d, TrainState(
            params=params, ef=ef, key=key,
            data_step=jnp.asarray(int(ef.step), jnp.int32)))
        with jax.set_mesh(mesh):
            p2, ef2 = init_state(key)
        state, _ = restore_train_state(d, TrainState(
            params=p2, ef=ef2, key=key,
            data_step=jnp.zeros((), jnp.int32)))

    flat = dict(
        (jax.tree_util.keystr(p), leaf) for p, leaf in
        jax.tree_util.tree_flatten_with_path(
            state.ef.comp, is_leaf=lambda x: x is None)[0])
    for path in diverged:
        got = np.asarray(flat[path])
        q = pre[path]
        assert np.array_equal(got, q[0]), (
            f"{path}: plain restore no longer equals model-rank-0's copy — "
            f"did the envelope writer stop using np.asarray on replicated "
            f"leaves?  Update this phase and docs/checkpoint.md together")
        assert not np.array_equal(got, q[1]), f"{path}: expected corruption"
    print(f"pinned rank-0-copy corruption on {len(diverged)} model-LOCAL "
          f"leaves (of {len(pre)}): plain restore == rank 0's bytes, != "
          f"rank 1's own")
    print("REGRESSION_PINNED_OK")


def phase_resume():
    """Mesh-aware save → kill → restore: bit-exact on every model rank."""
    cfg, hyper, mesh, parts = setup()
    step_fn, init_state, batch_at = build(cfg, mesh, hyper)
    model_size = int(mesh.shape["model"])
    key = jax.random.key(0)

    # uninterrupted reference run, snapshotting at SAVE_AT
    with jax.set_mesh(mesh):
        params, ef = init_state(key)
    params, ef, _ = run_to(step_fn, mesh, params, ef, key, batch_at,
                           0, SAVE_AT)
    pre = per_rank_comp(mesh, params, ef, parts)
    pre_error = np.asarray(jax.tree_util.tree_leaves(ef.error)[0])
    with tempfile.TemporaryDirectory() as d:
        p_c, ef_c = canonicalize_mesh(mesh, params, ef, parts)
        save_train_state(
            d, TrainState(params=p_c, ef=ef_c, key=key,
                          data_step=jnp.asarray(int(ef.step), jnp.int32)),
            model_axis_size=model_size,
            mesh_shape={a: int(mesh.shape[a]) for a in mesh.axis_names})
        params, ef, ref_losses = run_to(step_fn, mesh, params, ef, key,
                                        batch_at, SAVE_AT, STEPS)
        ref_final = per_rank_comp(mesh, params, ef, parts)

        # "kill": fresh state, restore through the mesh-aware path
        with jax.set_mesh(mesh):
            p2, ef2 = init_state(jax.random.key(7))  # different init — all
            #   restored content must come from the envelope, not survive here

        # degree guard first: same envelope, wrong claimed degree
        try:
            restore_train_state(
                d, TrainState(params=p2,
                              ef=stack_model_template(ef2, parts, 4),
                              key=key, data_step=jnp.zeros((), jnp.int32)),
                model_axis_size=4)
        except CheckpointError as e:
            assert "2" in str(e) and "4" in str(e), str(e)
        else:
            raise AssertionError("degree-mismatched restore did not raise")

        state, meta = restore_train_state(
            d, TrainState(params=p2,
                          ef=stack_model_template(ef2, parts, model_size),
                          key=key, data_step=jnp.zeros((), jnp.int32)),
            model_axis_size=model_size)
    assert meta["model_axis_size"] == model_size, meta
    assert meta["ef_rescale"]["path"] == "identity", meta["ef_rescale"]
    with jax.set_mesh(mesh):
        p3, ef3 = replicate_mesh(mesh, state.params, state.ef, parts)

    # every model rank's Q factors are its OWN pre-kill bytes again
    post = per_rank_comp(mesh, p3, ef3, parts)
    for path, q in pre.items():
        assert np.array_equal(post[path], q), (
            f"{path}: restored per-model-rank factors differ from their "
            f"own pre-kill content")
    assert np.array_equal(
        np.asarray(jax.tree_util.tree_leaves(ef3.error)[0]), pre_error), \
        "EF buffers did not round-trip bit-exactly"
    print(f"per-rank round-trip bit-exact on {len(pre)} model-LOCAL leaves")

    # continue: per-step losses must reproduce the reference run's bits
    p3, ef3, res_losses = run_to(step_fn, mesh, p3, ef3, key, batch_at,
                                 SAVE_AT, STEPS)
    assert [l.hex() for l in res_losses] == [l.hex() for l in ref_losses], (
        f"post-resume losses diverged from the uninterrupted run:\n"
        f"  ref    {[l.hex() for l in ref_losses]}\n"
        f"  resume {[l.hex() for l in res_losses]}")
    res_final = per_rank_comp(mesh, p3, ef3, parts)
    for path, q in ref_final.items():
        assert np.array_equal(res_final[path], q), (
            f"{path}: factors diverged from the uninterrupted run "
            f"after resume")
    print(f"losses {SAVE_AT}..{STEPS - 1} bit-equal after resume: "
          f"{[f'{l:.6f}' for l in res_losses]}")
    print("MODEL_RESUME_OK")


PHASES = {"regression": phase_regression, "resume": phase_resume}

if __name__ == "__main__":
    PHASES[sys.argv[1]]()
