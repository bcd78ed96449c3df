#!/usr/bin/env python3
"""Readings that set the correctness limits, on the chip.

    python3 bench/control.py --workload CELL --seconds S --seeds N [N ...]
                             [--fault NAME]

For each seed, in one process: a run of the cell as ``bench/run.py`` makes
it (weights from the seed, set-up, a window of ``--seconds``, the sample
of served tokens), then, on the same prompts and tokens, the reference's
numbers for the served tokens (the program's readings), the control's
(``bench.harness.control``: the reference one precision step lower) and
those of the served tokens with every 4th one altered. Each is judged
against the cell's limits (``bench/limits/<cell>.json``) as a run judges
the program; the control and the altered tokens have to come out not
correct. ``--dump DIR`` writes each seed's per-token readings (the
sequence of each served token and its gap in standard deviations, for the
program, the control and the altered tokens) to ``DIR/<seed>.json``.
``--fault`` plants a fault of ``bench.harness.faults`` under the
program for the whole process, so the program's own readings are the
fault's. Prints one JSON line per seed. The benchmark's own runs never run
the control.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
from harness import faults, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=faults.FAULTS)
    ap.add_argument("--dump")
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"control: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(args.workload)
    peaks = spec.load_peaks(dev.device_kind)
    planted = faults.plant(args.fault) if args.fault \
        else contextlib.nullcontext()
    with planted:
        for seed in args.seeds:
            device = {"platform": dev.platform, "kind": dev.device_kind,
                      "count": cell.chips}
            res = bench_run.run(cell, seed, args.seconds, False, device,
                                peaks, control=True)
            if args.dump:
                os.makedirs(args.dump, exist_ok=True)
                with open(os.path.join(args.dump, f"{seed}.json"), "w") as f:
                    json.dump(res["detail"], f)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "fault": args.fault,
                              "program": {"correct": res["correct"],
                                          **res["program_numbers"]},
                              **res["readings"],
                              "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
