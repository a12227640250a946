"""Discovery: every cell, configuration, traffic mix and per-layer metric
is found by the name ``BENCHMARK.json`` gives it.

    BENCHMARK.json                      cells, metrics, bounds
    bench/configs/<config>.json         model sizes, init rules, reference
    bench/traffic/<traffic>.json        protocol and data parameters
    bench/workloads/<cell>.json         the cell's correctness limits
    bench/metrics/<metric>.py           reader of one per-layer metric
    bench/reference/<name>.py           plain reference of an architecture

Adding a cell, a configuration or a metric adds files; no file that is
already there changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    bench = benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(entries)}")
    w = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    workload = load_json(root / "bench" / "workloads" / f"{name}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        workload=workload,
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold '-' or '.')."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """``read(ctx) -> float | None`` of the per-layer metric ``name``."""
    return load_module(root / "bench" / "metrics" / f"{name}.py").read


def reference_module(config: dict):
    """The configuration's plain reference, ``bench/reference/<name>.py``
    (imported as a module of the ``reference`` directory: the harness puts
    ``bench`` on the path)."""
    return importlib.import_module(f"reference.{config['reference']}")
