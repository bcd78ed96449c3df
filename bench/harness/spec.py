"""Find a cell's parts by name: BENCHMARK.json, its configuration file,
its traffic mix, the per-layer metric readers, and the family's work
counts and plain reference.

Everything a cell needs is a file named after it, so a later change adds a
configuration, a mix, a metric or a family by adding a file here and an
entry in ``BENCHMARK.json``; nothing in this module lists them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

__all__ = ["Cell", "load_cell", "load_benchmark", "metric_reader",
           "counts_module", "reference_module", "load_peaks", "BENCH_DIR",
           "ROOT"]


@dataclasses.dataclass
class Cell:
    name: str
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    chips: int
    end_to_end: list        # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _load_json(*parts) -> dict:
    path = os.path.join(BENCH_DIR, *parts)
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench: dict | None = None) -> Cell:
    """The workload ``name`` of BENCHMARK.json with its files loaded."""
    bench = bench if bench is not None else load_benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(by_name)}")
    w = by_name[name]
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[w["config"]]
    with open(os.path.join(os.path.dirname(BENCH_DIR), cfg_file)) as f:
        config = json.load(f)
    return Cell(
        name=name, config=config,
        traffic=_load_json("traffic", f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def _load_module(kind: str, name: str):
    """The module ``<BENCH_DIR>/<kind>/<name>.py``, imported once per
    path (and kept in ``sys.modules``, as a dataclass in it needs)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} module {path}")
    mod_name = f"bench_{kind}_{name.replace('.', '_')}"
    mod = sys.modules.get(mod_name)
    if mod is not None and mod.__file__ == path:
        return mod
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[mod_name]
        raise
    return mod


def metric_reader(name: str):
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    return _load_module("metrics", name).read


def counts_module(family: str):
    """Work counts of one model family, ``bench/counts/<family>.py``."""
    return _load_module("counts", family)


def reference_module(family: str):
    """Plain reference of one model family, ``bench/references/<family>.py``:
    its ``REGISTRY_KEYS``, ``Shape`` and ``layers`` (see ``dense.py``). A
    family with no such file is an error, never another family's layers."""
    return _load_module("references", family)


def load_peaks(kind: str) -> dict:
    """Published peaks of the device JAX reports as ``kind``; a device that
    is not in ``bench/peaks.json`` is an error, never a default."""
    table = _load_json("peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no entry in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]
