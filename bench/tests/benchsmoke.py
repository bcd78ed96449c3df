"""Cells of the benchmark cut to the registry's smoke sizes, for CPU tests.

``smoke_cell(name)`` loads a cell of ``BENCHMARK.json`` and replaces the
model's sizes with its registry ``SMOKE`` config (each published key of
the file that its family's ``REGISTRY_KEYS`` maps, set to the field it
maps to), the deployment with 4 slots of 128 tokens and chunk 8, and the
traffic lengths with short ones;
``smoke_model(cell)`` is the matching registry config.
``fixed_steps(monkeypatch, n)`` makes every window of ``run.run`` hold
``n`` steps whatever the CPU's speed: its clock advances by a fixed
amount per reading instead of with time.
"""
from __future__ import annotations

import dataclasses
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec  # noqa: E402

__all__ = ["smoke_cell", "smoke_model", "fixed_steps", "BENCH"]

# clock readings the window loop takes per step (loop test, t0, t1)
READS_PER_STEP = 3


def smoke_model(cell, **overrides):
    from repro.configs.registry import smoke_config
    dep = cell.config["deployment"]
    cfg = smoke_config(cell.config["model"], quant="serve",
                       quant_format=dep["codec"], kv_quant=dep["kv_quant"])
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def smoke_cell(name: str = "qwen2.5-14b.decode", **overrides):
    cell = spec.load_cell(name)
    cfg = smoke_model(cell, **overrides)
    keys = spec.reference_module(cell.config["family"]).REGISTRY_KEYS
    cell.config.update({key: getattr(cfg, field)
                        for key, field in keys.items()
                        if key in cell.config})
    cell.config["deployment"].update(n_slots=4, max_len=128,
                                     prefill_chunk=8)
    cell.config["reference"] = {"max_seqs": 4, "max_tokens": 256}
    cell.traffic.update(prompt_tokens=[8, 24], output_tokens=[16, 40],
                        first_wave={"context_tokens": [8, 24],
                                    "output_tokens": [1, 40]}, deck=8)
    return cell


def fixed_steps(monkeypatch, n: int, seconds: float = 3.0) -> None:
    """Windows of ``seconds`` hold ``n`` steps: ``run.run``'s window reads
    a clock that advances ``seconds / (3 n)`` per reading, so the served
    tokens, and the sample compared, do not depend on the host's load."""
    import run as bench_run
    from harness import loop

    def run_window(*args, **kw):
        t = [0.0]

        def clock():
            t[0] += seconds / (READS_PER_STEP * n)
            return t[0]
        return loop.run_window(*args, clock=clock, **kw)
    monkeypatch.setattr(bench_run, "run_window", run_window)
