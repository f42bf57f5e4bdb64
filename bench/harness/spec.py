"""Find every part of a cell by name, so that a configuration, a traffic
mix, a cell or a per-layer metric is added by adding files:

  BENCHMARK.json               workloads: name, config, traffic, chips
  bench/configs/<config>.json  the configuration as run, with its source
  bench/traffic/<mix>.json     length distributions, arrivals, sharing
  bench/cells/<cell>.json      serve settings, offered rate, limits
  bench/families/<family>.py   weight builder, counts, plain reference
  bench/metrics/<metric>.py    one reader per per-layer metric
  bench/peaks.json             device peaks keyed by device_kind
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    settings: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: Path

    @property
    def serve(self) -> Dict[str, Any]:
        return self.settings["serve"]

    def family(self):
        fam = self.config["family"]
        return load_module(self.bench_dir / "families" / f"{fam}.py",
                           f"bench_family_{fam}")

    def metric_reader(self, name: str):
        mod = load_module(self.bench_dir / "metrics" / f"{name}.py",
                          f"bench_metric_{name.replace('.', '_')}")
        return mod.read


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Path = BENCH) -> Cell:
    bm = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"],
        config=_json(bench_dir / "configs" / f"{w['config']}.json"),
        traffic_name=w["traffic"],
        traffic=_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        settings=_json(bench_dir / "cells" / f"{name}.json"),
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)


def peaks(device_kind: str, bench_dir: Path = BENCH) -> Dict[str, float]:
    table = _json(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
