"""The yardstick's arithmetic: FLOPs per token (the decoder's), the table of
peaks, the trace reduction, the check's worst-leaf rule, the traffic
generator, and the harness's refusal to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import benchtiny  # noqa: F401
from benchtiny import BENCH

import cell as cell_lib
import check
import data
import peaks
import reference
import tracereduce


def config(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def test_flops_per_token_qwen3():
    c = config("qwen3-4b-l4")
    arch = cell_lib.arch_module(c)
    # per layer: q,o 2560x4096 each, k,v 2560x1024 each, SwiGLU 3x2560x9728
    assert arch.matmul_weights_per_token(c) == 4 * 100_925_440 + 48_619_520
    # causal attention, fwd+bwd: 3 x 4 x (32x128) x 4097/2 per layer
    assert arch.attention_flops_per_token(c, 4096) == 4 * 100_687_872
    assert arch.flops_per_token(c, 4096) == 3_116_679_168


def test_flops_per_token_olmoe():
    c = config("olmoe-1b-7b-l1")
    arch = cell_lib.arch_module(c)
    # attention 4x2048x2048, router 2048x64, top-8 of 3x2048x1024, head
    assert arch.matmul_weights_per_token(c) == 67_239_936 + 12_877_824
    assert arch.attention_flops_per_token(c, 4096) == 50_343_936
    assert arch.flops_per_token(c, 4096) == 531_050_496


def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_trace_reduction_pins_each_number():
    # window 0..100 us; device 0: a fusion reading an all-reduce's result
    # (not a collective) 0-40, all-reduce 30-60 (exposed 40-60), idle
    # 60-80, the fusion again 80-100; device 1: compute 10-30, an async
    # collective-permute in flight 50-70 (not busy), idle otherwise
    tl = tracereduce.Timeline(
        window=(0.0, 100_000.0),
        devices=[[("%fusion.1 = f32[] fusion(f32[8] %all-reduce.9)", 0, 40_000),
                  ("%all-reduce.2 = f32[8] all-reduce(f32[8] %p)",
                   30_000, 60_000),
                  ("%fusion.1 = f32[] fusion(f32[8] %all-reduce.9)",
                   80_000, 100_000)],
                 [("fusion.3", 10_000, 30_000)]],
        in_flight=[[], [("%collective-permute-start.4 = (f32[8]) "
                         "collective-permute-start(f32[8] %x)",
                         50_000, 70_000)]],
        host=[("bench_window", 0, 100_000), ("train", 55_000, 90_000),
              ("PjitFunction(step)", 62_000, 78_000)])
    r = tracereduce.reduce(tl)
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx([80e-6, 20e-6])
    assert r["collective_s"] == pytest.approx([30e-6, 20e-6])
    assert r["exposed_s"] == pytest.approx([20e-6, 20e-6])
    assert r["device_ops"][0][0] == "fusion.1"
    assert r["device_ops"][0][1] == pytest.approx(30e-6)
    assert r["idle_gaps"] == [["host: PjitFunction(step)",
                               pytest.approx(20e-6)]]

    run = {"trace": r, "steps": 2, "tokens_per_s": 1000.0, "chips": 2,
           "flops_per_token": 1e9, "peak_flops": 197e12,
           "compiled_peak_bytes": 12_133_019_136}
    read = lambda m: cell_lib.reader(m)(run)
    assert read("device.idle_share") == pytest.approx(50.0)
    assert read("exchange.collective_ms") == pytest.approx(25e-6 * 1e3 / 2)
    assert read("exchange.exposed_ms") == pytest.approx(20e-6 * 1e3 / 2)
    assert read("step.mfu") == pytest.approx(100 * 1e12 / (2 * 197e12))
    assert read("memory.peak_gb") == pytest.approx(12.133019136)
    run["trace"] = dict(r, collective_s=[0.0, 0.0], exposed_s=[0.0, 0.0])
    assert read("exchange.collective_ms") is None
    assert read("exchange.exposed_ms") is None


def test_interval_arithmetic():
    assert tracereduce.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3),
                                                                  (5, 9)]
    assert tracereduce.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) == [
        (0, 2), (4, 8), (22, 30)]


def test_worst_leaf_uses_the_median_floor():
    ref = {"a": 10.0, "b": 1.0, "c": 1e-6}
    prog = {"a": 10.1, "b": 1.0, "c": 2e-6}
    gap, leaf = check.worst_leaf(prog, ref)
    assert leaf == "a" and gap == pytest.approx(0.01)


def test_still_leaves_leave_the_change_comparison():
    ref = reference.Readings([1.0], {"a": 1.0, "b": 1.0, "c": 1e-5},
                             {"a": 1.0, "b": 1.0, "c": 1e-9})
    prog = reference.Readings([1.0], {"a": 1.0, "b": 1.0, "c": 1e-5},
                              {"a": 1.0, "b": 1.0, "c": 1.0})
    assert check.numbers(prog, ref)["change_gap"] == 0.0


def test_traffic_is_seeded_and_rows_differ():
    a = data.sample(2**35 + 1, 0, 3, 128, 1000)
    b = data.sample(2**35 + 1, 0, 3, 128, 1000)
    c = data.sample(2**35 + 2, 0, 3, 128, 1000)
    assert a.shape == (3, 129) and a.dtype.name == "int32"
    assert (a == b).all() and not (a == c).all()
    assert len({r.tobytes() for r in a}) == 3
    assert a.min() >= 0 and a.max() < 1000


def test_every_cell_loads_with_its_files():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        c = cell_lib.load(w["name"])
        assert set(c.limits) == set(check.NUMBERS)
        assert c.chips == c.traffic["mesh"][0] * c.traffic["mesh"][1]
        for m in c.per_layer:
            assert callable(cell_lib.reader(m["name"]))


def test_run_without_a_tpu_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "qwen3-4b.s4096b1.powersgd.1chip", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout


def test_run_without_the_program_prints_no_result(tmp_path):
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-4b.s4096b1.powersgd.1chip", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout
