"""The harness, driven past its look for a chip at a small size on the CPU:
a sound run comes out correct, and each fault a cell can have, planted in
the program's timed path, comes out not correct under the cell's limits.
The four-device case runs the Qwen3 cell on a (4, 1) data mesh, the
layout of the four-chip cell left for a later PR, where the exchange
between chips can be left out."""

import contextlib
import json
import os
import subprocess
import sys

import pytest

import benchtiny  # noqa: F401  (puts bench/ and src/ on the path)
import faults
from benchtiny import BENCH, small

ONE_CHIP = ("qwen3-4b.s4096b1.powersgd.1chip",
            "olmoe-1b-7b.s4096b2.powersgd.1chip")
SEED = 2**33 + 12345


def run(name, fault=None):
    import jax

    from run import run_cell

    with faults.planted(fault) if fault else contextlib.nullcontext():
        return run_cell(small(name), SEED, 0.3, False, jax.devices())


@pytest.mark.parametrize("name", ONE_CHIP)
def test_sound_run_is_correct(name):
    r = run(name)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", ("unchanged", "half_batch", "double_head"))
@pytest.mark.parametrize("name", ONE_CHIP)
def test_fault_is_caught(name, fault):
    r = run(name, fault)
    assert not r["correct"], r["checks"]


FOUR_DEVICES = """
import contextlib, json, sys
sys.path.insert(0, {tests!r})
import benchtiny, faults, jax
from run import run_cell
out = {{}}
for fault in (None, "unchanged", "half_batch", "no_exchange", "double_head"):
    with faults.planted(fault) if fault else contextlib.nullcontext():
        r = run_cell(benchtiny.small("qwen3-4b.s4096b1.powersgd.1chip", 4),
                     {seed}, 0.3, False, jax.devices())
    out[str(fault)] = r["correct"]
import check, program, reference, jax.numpy as jnp
cell = benchtiny.small("qwen3-4b.s4096b1.powersgd.1chip", 4)
key = program.base_key({seed})
batches = program.batches_for_check(cell, program.make_ring(cell, {seed}), 3)
args = (reference.Model.of(cell.arch, cell.config),
        reference.Optim.from_traffic(cell.traffic), key, batches, jax.devices())
ref = reference.train(*args)
ctl = reference.train(*args, dtype=jnp.bfloat16, precision="default")
out["control"] = check.verdict(check.numbers(ctl, ref), cell.limits)[0]
print(json.dumps(out))
"""


def test_four_chip_faults_and_control_are_caught():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_DEVICES.format(tests=str(BENCH / "tests"), seed=SEED)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got == {"None": True, "unchanged": False, "half_batch": False,
                   "no_exchange": False, "double_head": False, "control": False}
