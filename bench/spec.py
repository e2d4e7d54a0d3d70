"""Find every part of a cell by the names ``BENCHMARK.json`` gives.

A cell names a configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); each per-layer metric is a reader in
``metrics/<name>.py``.  Adding a configuration, a mix or a metric adds a
file and an entry, and edits nothing here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    conf: dict
    traffic: dict
    chips: int = 1
    end_to_end: List[dict] = dataclasses.field(default_factory=list)
    per_layer: List[dict] = dataclasses.field(default_factory=list)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_config(name: str) -> dict:
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


def load_traffic(name: str) -> dict:
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


def compose(name: str, config_name: str, traffic_name: str,
            bench: Optional[dict] = None, chips: int = 1) -> Cell:
    """A cell from a configuration and a traffic mix, with the metrics
    ``bench`` lists for it (a metric without ``workloads`` is in every
    cell that reports what it moves)."""
    cell = Cell(name, load_config(config_name), load_traffic(traffic_name),
                chips)
    for m in (bench or {}).get("end_to_end", []):
        if name in m.get("workloads", [name]):
            cell.end_to_end.append(m)
    moved = {m["name"] for m in cell.end_to_end}
    for m in (bench or {}).get("per_layer", []):
        if name in m.get("workloads", [name]) and m["moves"] in moved:
            cell.per_layer.append(m)
    return cell


def resolve(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return compose(name, w["config"], w["traffic"], bench,
                           w["chips"])
    raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                     f"{[w['name'] for w in bench['workloads']]}")


def metric_reader(name: str) -> Callable[[dict], Optional[float]]:
    """``metrics/<name>.py``'s ``read(record)``: the metric, or None when
    the run has nothing for it to read."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json; known: {sorted(table['devices'])}")
    return table["devices"][device_kind]
