"""Resolve one cell of ``BENCHMARK.json`` to the files that define it.

A cell names a configuration and a traffic mix. Its configuration file is
the ``file`` of its ``configs`` entry; its mix is
``bench/mixes/<traffic>.json``; its limits are
``bench/limits/<workload>.json``; the mix names its driver, the module
``bench.drivers.<driver>``; each per-layer metric that the cell reports
has its reader in ``bench.metrics.<metric>``. Adding a cell is adding
such files and entries: nothing here names a cell, a mix or a metric.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib
from types import ModuleType


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: str = ""
    layer: str = ""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    root: pathlib.Path
    config_name: str
    config: dict
    traffic: str
    mix: dict
    limits: dict
    driver: ModuleType
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]
    readers: dict  # per-layer metric name -> reader module


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def _read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(root: pathlib.Path, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``."""
    root = pathlib.Path(root)
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = _read_json(root / cfg_entry["file"])
    mix = _read_json(root / "bench" / "mixes" / f"{w['traffic']}.json")
    limits = _read_json(root / "bench" / "limits" / f"{workload}.json")
    e2e = tuple(
        Metric(m["name"], m["unit"], m["better"], m["source"])
        for m in bench["end_to_end"]
        if _reports(m, workload)
    )
    e2e_names = {m.name for m in e2e}
    per_layer = tuple(
        Metric(m["name"], m["unit"], m["better"], m["source"], m["moves"], m["layer"])
        for m in bench["per_layer"]
        if _reports(m, workload) and m["moves"] in e2e_names
    )
    readers = {
        m.name: importlib.import_module(f"bench.metrics.{m.name}") for m in per_layer
    }
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        root=root,
        config_name=w["config"],
        config=config,
        traffic=w["traffic"],
        mix=mix,
        limits=limits,
        driver=importlib.import_module(f"bench.drivers.{mix['driver']}"),
        end_to_end=e2e,
        per_layer=per_layer,
        readers=readers,
    )


def reference_module(cell: Cell) -> ModuleType:
    """The plain reference that the configuration file names, beside it."""
    return importlib.import_module(f"bench.configs.{cell.config['reference']}")
