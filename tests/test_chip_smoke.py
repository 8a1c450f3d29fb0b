"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
training function drives the real train step to finite losses."""

from __future__ import annotations

import math
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run_script(path, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, path], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_smoke_fails_without_tpu(tmp_path, where):
    """On the CPU, and in a directory holding only the script, it exits
    non-zero and prints no result."""
    path = SMOKE
    if where == "alone":
        path = str(tmp_path / "chip_smoke.py")
        shutil.copy(SMOKE, path)
    proc = _run_script(path, cwd=str(tmp_path))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_smoke_run_trains_reduced_config_on_cpu():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    from repro.configs.base import get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.train import TrainHyper

    cfg = get_config("qwen3-4b", reduced=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    hyper = TrainHyper(q_chunk=32, remat=True)
    r = chip_smoke.run(cfg, mesh, hyper, steps=2, batch=2, seq=64,
                       log=lambda *_: None)
    assert len(r["losses"]) == 2
    assert all(math.isfinite(x) for x in r["losses"])
    assert chip_smoke._band_ok(cfg, r["losses"][0])
    assert r["n_params"] > 0 and r["global_batch"] == 2
