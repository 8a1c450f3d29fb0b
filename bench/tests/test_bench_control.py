"""The control: the reference put in the program's place and computed one
precision lower (bfloat16, default matmul precision) has to fail at least
one of the cell's numbers; the program has to pass them.  Kept at a small
size on the CPU; the chip readings at the cells' own sizes are in PERF.md
(from ``bench/calibrate.py``)."""

import pytest

import benchtiny  # noqa: F401
from benchtiny import small

ONE_CHIP = ("qwen3-4b.s4096b1.powersgd.1chip",
            "olmoe-1b-7b.s4096b2.powersgd.1chip")


@pytest.mark.parametrize("seed", (7, 2**32 + 99, 3_000_000_001))
@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_fails_and_program_passes(name, seed):
    import jax
    import jax.numpy as jnp

    import check
    import program
    import reference

    cell = small(name)
    steps = cell.traffic["check_steps"]
    key = program.base_key(seed)
    ring_np = program.make_ring(cell, seed)
    batches = program.batches_for_check(cell, ring_np, steps)
    model = reference.Model.of(cell.arch, cell.config)
    optim = reference.Optim.from_traffic(cell.traffic)
    devices = jax.devices()[:1]
    ref = reference.train(model, optim, key, batches, devices)
    ctl = reference.train(model, optim, key, batches, devices,
                          dtype=jnp.bfloat16, precision="default")
    ok, checks = check.verdict(check.numbers(ctl, ref), cell.limits)
    assert not ok, checks

    prog = program.Program(cell, devices)
    ring = prog.put_ring(ring_np)
    prog.compile(ring[0], jax.random.fold_in(key, 0))
    _, got = prog.first_steps(prog.init(key), ring, key, steps)
    ok, checks = check.verdict(check.numbers(got, ref), cell.limits)
    assert ok, checks
