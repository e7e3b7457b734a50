"""Find a cell and everything it names, by name, from files.

``load_cell(name)`` reads ``<repo>/BENCHMARK.json`` and returns a
``Cell`` holding the cell's entry, its configuration, its traffic mix, the
per-layer metrics that report in it and its correctness limits.  A later
change adds a cell, a configuration, a mix or a metric by adding files only:
nothing here lists them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


class UnknownNameError(KeyError):
    """A cell, configuration, mix, metric or reference that has no file."""


@dataclasses.dataclass(frozen=True)
class Metric:
    """A per-layer metric: ``read(ctx)`` returns its value, or None where
    the traced run holds nothing for it to read."""
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple      # the metric entries of BENCHMARK.json
    per_layer: tuple       # Metric, those that report in this cell
    limits: dict           # number name -> limit


def _read_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise UnknownNameError(f"no {what} file at {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str, what: str):
    """Import one Python file by path (metric readers, references)."""
    if not os.path.isfile(path):
        raise UnknownNameError(f"no {what} file at {path}")
    mod_name = "chipbench_" + what + "_" + "".join(
        c if c.isalnum() else "_" for c in os.path.basename(path)[:-3])
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(bench_dir: str, entry: dict) -> Metric:
    mod = load_module(os.path.join(bench_dir, "metrics",
                                   entry["name"] + ".py"), "metric")
    return Metric(entry["name"], entry["unit"], mod.read)


def reports_in(entry: dict, cell_name: str, cell_e2e: set) -> bool:
    """Whether a per-layer metric reports in a cell: its own list, or else
    every cell that reports the end-to-end metric it moves."""
    wl = entry.get("workloads")
    if wl is not None:
        return cell_name in wl
    return entry["moves"] in cell_e2e


def load_benchmark(repo_root: str = REPO_ROOT) -> dict:
    return _read_json(os.path.join(repo_root, "BENCHMARK.json"), "benchmark")


def load_cell(name: str, repo_root: str = REPO_ROOT) -> Cell:
    bench = load_benchmark(repo_root)
    bench_dir = os.path.join(repo_root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise UnknownNameError(
            f"unknown workload {name!r}; BENCHMARK.json has "
            f"{sorted(cells)}")
    w = cells[name]
    config = _read_json(
        os.path.join(bench_dir, "configs", w["config"] + ".json"),
        "configuration")
    traffic = _read_json(
        os.path.join(bench_dir, "traffic", w["traffic"] + ".json"),
        "traffic")
    limits = _read_json(os.path.join(bench_dir, "limits", name + ".json"),
                        "limits")
    e2e = tuple(m for m in bench["end_to_end"]
                if m.get("workloads") is None or name in m["workloads"])
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(load_metric(bench_dir, m) for m in bench["per_layer"]
                      if reports_in(m, name, e2e_names))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                limits=limits["limits"])
