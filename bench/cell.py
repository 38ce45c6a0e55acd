"""A cell of ``BENCHMARK.json`` and the files the harness finds by its names.

* ``bench/configs/<config>.json``: the model as it is run (``model``, every
  field of the program's ``ModelConfig``), the repo configuration it is
  derived from with the overrides, the learning rate chosen for it, its
  public ``source``, ``reduced`` and ``assumed`` sizes, and the deployment
  the cut stands for.
* ``bench/traffic/<traffic>.json``: the nodes and how they gossip (topology,
  compressor, engine flags, state dtype) and the data each node is fed
  (sequence length, rows per node, heterogeneity).
* ``bench/limits/<cell>.json``: the limit of each number the correctness
  comparison reads.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict           # bench/configs/<config>.json
    traffic_name: str
    traffic: Dict          # bench/traffic/<traffic>.json
    limits: Dict           # bench/limits/<cell>.json
    end_to_end: list       # the BENCHMARK.json metrics this cell reports
    per_layer: list

    @property
    def model(self) -> Dict:
        return self.config["model"]


def _load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; one of "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load(os.path.join(root, "bench", "traffic",
                                 w["traffic"] + ".json"))
    limits = _load(os.path.join(root, "bench", "limits", name + ".json"))
    if traffic["nodes"] != w["chips"]:
        raise ValueError(f"{name}: traffic {w['traffic']} runs "
                         f"{traffic['nodes']} nodes, one per chip, but the "
                         f"cell asks for {w['chips']} chips")
    return Cell(name=name, chips=w["chips"], config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                limits=limits,
                end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
                per_layer=[m for m in spec["per_layer"] if _reports(m, name)])
