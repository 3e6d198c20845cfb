"""Finds a cell's pieces by name, so that a new cell is new files only.

- ``BENCHMARK.json`` at the checkout's root lists the cells (``workloads``)
  as (``config``, ``traffic``) pairs and the metrics.
- ``bench/configs/<config>.json``: one model configuration.
- ``bench/traffic/<traffic>.json``: one traffic mix.
- ``bench/metrics/<metric>.py``: one per-layer metric's reader.
- ``bench/peaks.json``: the peaks of each device kind.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its configuration,
    traffic mix and the metrics it reports."""
    bench = _json(root / "BENCHMARK.json")
    got = [w for w in bench["workloads"] if w["name"] == name]
    if not got:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    w = got[0]
    here = root / "bench"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(here / "configs" / f"{w['config']}.json"),
        traffic=_json(here / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def load_metric(name: str, root: Path = ROOT):
    """The reader module ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(kind: str, root: Path = ROOT) -> dict:
    """Peaks of one device kind; an unknown kind is an error."""
    table = _json(root / "bench" / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]
