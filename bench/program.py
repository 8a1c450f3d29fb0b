"""The system under test, driven through its normal path.

``make_train_step`` and its ``init_state`` from ``repro.launch.train``: the
jitted ``shard_map`` step through ``models/``, ``core/error_feedback.py``,
``core/powersgd.py`` via ``core/engine.py``, and ``core/dist.py``.  One
object holds the compiled step, its state and the cell's ring of batches on
the device; set-up drives it through the first steps the check compares,
and the window (``scopetrace.window``) then continues the same object.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import cell as cell_lib
import data as data_lib
import reference


def base_key(seed: int):
    """The run's PRNG key; seeds may exceed 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def batches_for_check(cell: cell_lib.Cell, ring, steps: int):
    """``ring`` split per data rank, as the reference takes it."""
    b = cell.traffic["batch_per_rank"]
    return [[(tok[w * b:(w + 1) * b], lab[w * b:(w + 1) * b])
             for w in range(cell.data_ranks)]
            for tok, lab in ring[:steps]]


class Program:
    def __init__(self, cell: cell_lib.Cell, devices):
        from repro.core.compressors import IdentityCompressor
        from repro.launch.mesh import data_axes, make_mesh
        from repro.launch.train import TrainHyper, make_train_step

        t = cell.traffic
        self.cell = cell
        self.cfg = cell_lib.program_config(cell.config)
        self.hyper = TrainHyper(**t["hyper"])
        if (t["rank"], t["wire_dtype"], t["staleness"]) != (
                self.hyper.rank, self.hyper.wire_dtype, self.hyper.staleness):
            raise SystemExit(f"{cell.name}: traffic rank/wire/staleness "
                             f"disagree with its hyper block")
        mesh_shape = tuple(t["mesh"])
        self.mesh = make_mesh(mesh_shape, ("data", "model"),
                              devices=devices[:math.prod(mesh_shape)])
        compressor = {"powersgd": None,
                      "identity": IdentityCompressor()}[t["compressor"]]
        self.step_fn, self.abstract_state, self.init_state = make_train_step(
            self.cfg, self.mesh, self.hyper, compressor=compressor)
        self.tok_sharding = NamedSharding(self.mesh,
                                          P(data_axes(self.mesh), None))
        self.compiled = None
        self.memory = None

    # -- set-up ------------------------------------------------------------
    def put_ring(self, ring):
        return [{"tokens": jax.device_put(tok, self.tok_sharding),
                 "labels": jax.device_put(lab, self.tok_sharding)}
                for tok, lab in ring]

    def compile(self, batch, key):
        params, ef = self.abstract_state()
        with jax.set_mesh(self.mesh):
            self.compiled = self.step_fn.lower(params, ef, batch,
                                               key).compile()
        mem = self.compiled.memory_analysis()
        self.memory = None if mem is None else (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)

    def init(self, key):
        with jax.set_mesh(self.mesh):
            return self.init_state(key)

    def step(self, state, batch, key, i):
        params, ef = state
        params, ef, metrics = self.compiled(params, ef, batch,
                                            jax.random.fold_in(key, i))
        return (params, ef), metrics

    def first_steps(self, state, ring, key, steps: int):
        """Drive the step through the first ``steps`` batches of the ring
        and take the check's readings: each step's loss, the leaf norms of
        the first gradient as the optimizer got it (worked out from the
        state after one step: mean error over the data ranks + momentum, as
        both started at zero) and the leaf norms of the parameters' change
        over the steps."""
        params0 = jax.tree_util.tree_map(jnp.copy, state[0])
        losses, grad_norms = [], None
        for i in range(steps):
            state, metrics = self.step(state, ring[i], key, i)
            losses.append(float(metrics["lm_loss"]))
            if i == 0:
                grad_norms = _first_grad_norms(state[1])
        change_norms = _change_norms(state[0], params0)
        del params0
        return state, reference.Readings(losses, grad_norms, change_norms)


@jax.jit
def _first_grad(ef):
    return jax.tree_util.tree_map(lambda e, m: jnp.mean(e, axis=0) + m,
                                  ef.error, ef.momentum)


def _first_grad_norms(ef):
    return reference.leaf_norms(_first_grad(ef))


def _change_norms(params, params0):
    return reference.leaf_norms(jax.jit(lambda x, y: jax.tree_util.tree_map(
        jnp.subtract, x, y))(params, params0))


def make_ring(cell: cell_lib.Cell, seed: int):
    t = cell.traffic
    return data_lib.ring(seed, t["ring"], cell.global_rows, t["seq"],
                         cell.config["vocab_size"], **t["data"])
