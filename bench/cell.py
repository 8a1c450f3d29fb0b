"""A cell of ``BENCHMARK.json``, and the files it is made of.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own, found by name:

* ``bench/configs/<config>.json``: the model as run (its published key
  names), the registry name, the keys changed from it and, optionally, the
  name of its architecture (``"arch"``, default ``decoder``);
* ``bench/archs/<arch>.py``: the plain model of that architecture for the
  reference (``reference.Model``), its FLOPs per token and the program
  fields its published keys name;
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
import sys

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
# where a file found by name (``<dir>/archs/<arch>.py``,
# ``<dir>/metrics/<metric>.py``) is looked for, first hit wins; tests put a
# directory of their own in front
SEARCH = (BENCH,)
DEFAULT_ARCH = "decoder"


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

    @property
    def arch(self):
        """The configuration's architecture module."""
        return arch_module(self.config)

    @property
    def flops_per_token(self) -> float:
        return self.arch.flops_per_token(self.config, self.traffic["seq"])


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


def _module(kind: str, name: str):
    """The module of ``<dir>/<kind>/<name>.py`` for the first ``dir`` of
    :data:`SEARCH` that has it, loaded once per file."""
    path = next((p for p in (d / kind / f"{name}.py" for d in SEARCH)
                 if p.is_file()), None)
    if path is None:
        raise SystemExit(f"no {kind}/{name}.py under "
                         f"{[str(d) for d in SEARCH]}")
    key = f"bench_{kind}_" + name.replace(".", "_").replace("-", "_")
    mod = sys.modules.get(key)
    if mod is None or mod.__file__ != str(path):
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    return _module("metrics", metric).read


def arch_module(config: dict):
    """The architecture module ``bench/archs/<arch>.py`` of a config file,
    ``arch`` its ``"arch"`` key (default ``decoder``)."""
    return _module("archs", config.get("arch", DEFAULT_ARCH))


# the program's ModelConfig fields that published keys every config file has
# name; an architecture module's ``PROGRAM_FIELDS`` adds its own.  The harness
# checks that the program builds the model the file states
PROGRAM_FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size",
}


def program_config(config: dict):
    """The program's ModelConfig for a config file: its registry entry with
    the file's ``changed`` keys, checked against every published key."""
    from repro.configs.base import get_config

    cfg = dataclasses.replace(get_config(config["registry"]),
                              **config["changed"])
    fields = {**PROGRAM_FIELDS,
              **getattr(arch_module(config), "PROGRAM_FIELDS", {})}
    for key, field in fields.items():
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
