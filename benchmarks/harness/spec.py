"""What a cell is made of, found by name from ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric sits in a file of its own under ``benchmarks/``:

* ``configs/<config>.json``        the published ``config.json`` keys plus a
                                    ``deployment`` block (``MLConfig`` overrides,
                                    context, chips)
* ``traffic/<traffic>.json``       a generator ``kind`` and its parameters
* ``cells/<cell>.json``            numbers that belong to one pairing only (the
                                    fixed arrival rate); optional
* ``layer_metrics/<metric>.json``  a reader ``kind`` and its selectors

A generator kind is ``generators/<kind>.py`` and a reader kind is
``readers/<kind>.py``, imported by name. Adding any of them never edits a
file that exists, except to add an entry to ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
REPO_DIR = BENCH_DIR.parent


def _load(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_benchmark(path: Path | None = None) -> dict:
    return _load(path or REPO_DIR / "BENCHMARK.json")


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    params: dict = field(default_factory=dict)  # cells/<cell>.json
    end_to_end: list[dict] = field(default_factory=list)
    per_layer: list[dict] = field(default_factory=list)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with every file it names."""
    bench = bench or load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json "
            f"(has: {[w['name'] for w in bench['workloads']]})"
        )
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return make_cell(
        name=name,
        config=_load(REPO_DIR / cfg_entry["file"]),
        traffic=load_traffic(entry["traffic"]),
        chips=int(entry["chips"]),
        config_name=entry["config"],
        traffic_name=entry["traffic"],
        params=load_cell_params(name),
        bench=bench,
    )


def make_cell(*, name, config, traffic, chips, config_name="", traffic_name="",
              params=None, bench=None) -> Cell:
    bench = bench or {"end_to_end": [], "per_layer": []}
    return Cell(
        name=name, config_name=config_name, traffic_name=traffic_name,
        chips=chips, config=config, traffic=traffic, params=dict(params or {}),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def load_traffic(name: str) -> dict:
    return _load(BENCH_DIR / "traffic" / f"{name}.json")


def load_cell_params(name: str) -> dict:
    path = BENCH_DIR / "cells" / f"{name}.json"
    return _load(path) if path.exists() else {}


def load_layer_metric(name: str) -> dict:
    return _load(BENCH_DIR / "layer_metrics" / f"{name}.json")


def generator(kind: str):
    """``generators/<kind>.py``: ``plan(traffic, params, seed, seconds)``."""
    return importlib.import_module(f"benchmarks.generators.{kind}")


def reader(kind: str):
    """``readers/<kind>.py``: ``read(obs, spec) -> float | None``."""
    return importlib.import_module(f"benchmarks.readers.{kind}")
