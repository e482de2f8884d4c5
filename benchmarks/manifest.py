"""BENCHMARK.json and the files it names.  A cell resolves to its
configuration file (named in the manifest), its traffic file
(traffic/<traffic>.json) and its metrics; whatever is missing fails with
the list of what exists."""

from __future__ import annotations

import copy
import json
import os
from dataclasses import dataclass, field
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
TRAFFIC_DIR = os.path.join(HERE, "traffic")


class ManifestError(Exception):
    pass


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)


def load(path: str = MANIFEST) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"no manifest at {path}") from None


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for key, val in over.items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = _merge(out[key], val)
        else:
            out[key] = copy.deepcopy(val)
    return out


def _read_json(path: str, what: str, have: list) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(
            f"{what}: no file {path}; there is {sorted(have)}") from None


def traffic_names() -> list:
    return [os.path.splitext(f)[0] for f in os.listdir(TRAFFIC_DIR)
            if f.endswith(".json")]


def metrics_of(manifest: dict, cell_name: str) -> tuple:
    """(end_to_end, per_layer) entries that this cell reports.  A metric
    with a `workloads` key belongs to the cells it lists; an end-to-end
    metric without one to every cell; a per-layer metric without one to
    every cell that reports the end-to-end metric it moves."""
    e2e = [m for m in manifest["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    mine = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if (cell_name in m["workloads"] if "workloads" in m
                 else m["moves"] in mine)]
    return e2e, layer


def resolve(manifest: dict, workload: str, rehearse: bool = False,
            root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise ManifestError(f"no workload {workload!r}; the manifest has "
                            f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    if w["config"] not in configs:
        raise ManifestError(f"workload {workload!r} names config "
                            f"{w['config']!r}; the manifest has "
                            f"{sorted(configs)}")
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]),
                        f"config {w['config']!r}",
                        [c["file"] for c in configs.values()])
    traffic = _read_json(os.path.join(TRAFFIC_DIR, w["traffic"] + ".json"),
                         f"traffic {w['traffic']!r}", traffic_names())
    if rehearse:
        config = _merge(config, config.get("rehearse", {}))
        traffic = _merge(traffic, traffic.get("rehearse", {}))
    e2e, layer = metrics_of(manifest, workload)
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=layer)
