"""Small copies of the benchmark's cells for the CPU tests: the same files,
with widths, depth, vocabulary and sequence cut so that a test run holds
them.  Each keeps its cell's limits, traffic and metrics."""

from __future__ import annotations

import dataclasses
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import cell as cell_lib  # noqa: E402

SMALL = {
    "qwen3-4b-l4": {
        "changed": {"num_layers": 2, "vocab_size": 512, "d_model": 128,
                    "num_heads": 4, "num_kv_heads": 2, "head_dim": 32,
                    "d_ff": 256},
        "hidden_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
        "num_hidden_layers": 2, "vocab_size": 512},
    "olmoe-1b-7b-l1": {
        "changed": {"num_layers": 1, "vocab_size": 512, "d_model": 128,
                    "num_heads": 4, "num_kv_heads": 4, "head_dim": 32,
                    "d_ff": 64, "moe_num_experts": 16, "moe_top_k": 4},
        "hidden_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 32, "intermediate_size": 64,
        "num_hidden_layers": 1, "vocab_size": 512, "num_experts": 16,
        "num_experts_per_tok": 4},
}
SEQ = 64


def small(name: str, data_ranks: int = 1) -> cell_lib.Cell:
    """Cell ``name`` of BENCHMARK.json at a size a CPU test holds; with
    ``data_ranks`` > 1, on a (data_ranks, 1) data mesh."""
    c = cell_lib.load(name)
    config = dict(c.config, **SMALL[c.config["name"]])
    traffic = dict(c.traffic, seq=SEQ, mesh=[data_ranks, 1])
    traffic["hyper"] = dict(traffic["hyper"], q_chunk=16)
    return dataclasses.replace(c, config=config, traffic=traffic,
                               chips=data_ranks)
