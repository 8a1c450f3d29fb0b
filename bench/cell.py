"""A cell of ``BENCHMARK.json``, and the files it is made of.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own, found by name:

* ``bench/configs/<config>.json``: the model as run (its published key
  names), the registry name and the keys changed from it;
* ``bench/traffic/<traffic>.json``: sequence, batch per data rank, mesh,
  compressor and every ``TrainHyper`` field, pinned;
* ``bench/limits/<cell>.json``: the limit of each number the check compares;
* ``bench/metrics/<metric>.py``: a ``read(run)`` for each per-layer metric.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple = ()
    per_layer: tuple = ()

    @property
    def data_ranks(self) -> int:
        return self.traffic["mesh"][0]

    @property
    def global_rows(self) -> int:
        return self.data_ranks * self.traffic["batch_per_rank"]

    @property
    def tokens_per_step(self) -> int:
        return self.global_rows * self.traffic["seq"]


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load(name: str, benchmark: pathlib.Path = CHECKOUT / "BENCHMARK.json"
         ) -> Cell:
    spec = _json(benchmark)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in {benchmark.name}; "
                         f"have {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    mine = lambda m: name in m.get("workloads", [name])
    return Cell(
        name=name, chips=w["chips"],
        config=_json(CHECKOUT / conf["file"]),
        traffic=_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=_json(BENCH / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in spec["end_to_end"] if mine(m)),
        per_layer=tuple(m for m in spec["per_layer"] if mine(m)))


def reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# the program's ModelConfig fields each published key of a config file
# names; the harness checks that the program builds the model the file states
PROGRAM_FIELDS = {
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "qk_norm": "qk_norm",
    "num_experts": "moe_num_experts",
    "num_experts_per_tok": "moe_top_k",
    "capacity_factor": "moe_capacity_factor",
    "router_aux_loss_coef": "moe_aux_weight",
}


def program_config(config: dict):
    """The program's ModelConfig for a config file: its registry entry with
    the file's ``changed`` keys, checked against every published key."""
    from repro.configs.base import get_config

    cfg = dataclasses.replace(get_config(config["registry"]),
                              **config["changed"])
    for key, field in PROGRAM_FIELDS.items():
        if key not in config:
            continue
        have, want = getattr(cfg, field), config[key]
        if (have != want if isinstance(want, (bool, int, str))
                else not math.isclose(have, want)):
            raise SystemExit(f"{config['name']}: the program's {field} is "
                             f"{have!r}, the config file states {key} = "
                             f"{want!r}")
    if cfg.dtype != config["dtype"]:
        raise SystemExit(f"{config['name']}: program dtype {cfg.dtype}, "
                         f"file {config['dtype']}")
    return cfg
