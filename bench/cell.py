"""What one cell is: found by name in BENCHMARK.json, its configuration and
traffic mix read from their own files, its metrics from their own readers.

Nothing here names a cell, a configuration or a metric: a new one is a new
file under bench/configs, bench/traffic or bench/metrics plus its entry in
BENCHMARK.json.
"""

from __future__ import annotations

import importlib
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list = field(default_factory=list)   # BENCHMARK.json entries
    per_layer: list = field(default_factory=list)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return read_json(BENCH / "configs" / f"{name}.json")


def load_traffic(name: str) -> dict:
    return read_json(BENCH / "traffic" / f"{name}.json")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = read_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = read_json(root / configs[w["config"]]["file"])
    return Cell(
        name=name, config=config, traffic=load_traffic(w["traffic"]),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def metric_reader(name: str):
    """The `read(record)` function of bench/metrics/<name>.py."""
    return importlib.import_module(f"bench.metrics.{name}").read


def cost_module(name: str):
    """bench/costs/<name>.py: operation and byte counts."""
    return importlib.import_module(f"bench.costs.{name}")


def peaks(device_kind: str) -> dict:
    """The peak rates of a device kind. An unknown kind is an error."""
    table = read_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]
