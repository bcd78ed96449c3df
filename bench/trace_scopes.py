#!/usr/bin/env python3
"""Windows of one cell in one process: what each part of a step costs.

    python3 bench/trace_scopes.py --workload CELL --seed N --seconds S
        --windows alternate,profile [--keep DIR]

Set-up is ``bench/run.py``'s (weights from the seed, the closed loop
primed); then one window of ``--seconds`` per entry of ``--windows``, each
continuing the loop the last one left:

* ``alternate``: no profiler, and the program's spans (``REPRO_OBS=trace``:
  ``obs.span`` times each one and opens its profiler annotation) switched
  on and off step by step, so the two halves of one window share the
  chip, the traffic and the host's state: what the spans cost, as each
  half's ``tok_s`` (its tokens over its steps' seconds);
* ``profile``: the spans on and the profiler over a stretch of at least
  six steps from a tenth of the window on (``harness.profile.TraceWindow``,
  as the benchmark's traced run), reduced by
  ``harness.scopes.reduce_scopes``: device time by named scope, the idle
  gaps labelled by the program's spans on the trace's clock, and the
  metrics the scopes feed.

Each window prints one JSON line: ``tok_s`` and ``itl_p95_ms`` as the
benchmark computes them, the engine's always-on split of the step
(``ServeStats``: dispatch, wait for the logits, guard, rest) per step and
for the window's longest step, and the benchmark's ``host in window``
line. ``--keep DIR`` writes each profile's trace (gzipped) and its traced
steps there. No reference check: ``bench/run.py`` judges correctness.
Refuses any platform but a TPU.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run as bench  # noqa: E402  (bench/run.py: set-up, host watch)
from harness import spec  # noqa: E402
from harness.loop import itl_quantile_ms, prime, run_window  # noqa: E402
from harness.loop import tok_s  # noqa: E402
from harness.traffic import ClosedLoopTraffic  # noqa: E402

MODES = {"alternate": "", "profile": "trace"}      # REPRO_OBS at the open


class Alternate:
    """``after_step`` that turns the spans on after every even step and
    off after every odd one, and remembers which steps ran with them."""

    def __init__(self):
        self.on = []                    # indices of steps run with spans

    def __call__(self, w) -> None:
        if os.environ.get("REPRO_OBS"):
            self.on.append(len(w.steps) - 1)
        os.environ["REPRO_OBS"] = "trace" if len(w.steps) % 2 else ""

    def halves(self, w) -> dict:
        """``tok_s`` and median step milliseconds of each half, over its
        decode launches (a prefill launch, ten times longer, would land in
        one half only)."""
        out = {}
        for half, on in (("off", False), ("spans", True)):
            steps = [s for i, s in enumerate(w.steps)
                     if (i in self.on) == on and s.kind == "decode"]
            ms = sorted(1e3 * (s.t1 - s.t0) for s in steps)
            out[half] = {
                "steps": len(steps),
                "tok_s": sum(s.prompt_tokens + s.generated for s in steps)
                / sum(s.t1 - s.t0 for s in steps),
                "median_step_ms": ms[len(ms) // 2]}
        return out


def split_ms(stats, before: dict, steps: int) -> dict:
    """Mean dispatch, wait and guard milliseconds per step since
    ``before`` (a ``ServeStats.to_dict()``)."""
    return {k: 1e3 * (getattr(stats, k) - before[k]) / max(steps, 1)
            for k in ("dispatch_s", "wait_s", "guard_s")}


def profile_metrics(red: dict, n_steps: int) -> dict:
    """What the scope reduction gives the three metrics it feeds."""
    op_s = sum(red["scopes"].values())
    disp = [e - s for n, s, e in red["spans"]
            if n == "serve.launch.dispatch"]
    return {
        "act_quant_share_pct": 100 * red["scopes"]["act_quant"] / op_s,
        "attention_share_pct": 100 * red["scopes"]["attention"] / op_s,
        "serve_gemm_share_pct": 100 * red["scopes"]["serve_gemm"] / op_s,
        "gemm_share_pct": 100 * red["kernel_ns"] / red["launch_ns"],
        "scoped_share_pct": 100 * (1 - red["scopes"]["other"] / op_s),
        "op_share_of_launch_pct": 100 * op_s * 1e9 / red["launch_ns"],
        "dispatch_ms": 1e-6 * sum(disp) / max(n_steps, 1),
    }


def profile_line(xplane: str, n_steps: int) -> dict:
    """The scope reduction of one profile, as fields of a window's line."""
    from harness.scopes import reduce_scopes
    red = reduce_scopes(xplane)
    by_label = {}
    for label, s in red["gaps"]:
        by_label[label] = by_label.get(label, 0.0) + s
    return {
        "scopes_s": red["scopes"], "other_top": red["other_top"],
        "metrics": profile_metrics(red, n_steps),
        "idle_gaps": sorted(by_label.items(), key=lambda kv: -kv[1]),
        "gaps_over_1ms": sorted(([lb, s] for lb, s in red["gaps"]
                                 if s > 1e-3), key=lambda g: -g[1]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--windows", default="alternate,profile")
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    modes = args.windows.split(",")
    if not set(modes) <= set(MODES):
        ap.error(f"--windows takes {sorted(MODES)}")

    cell = spec.load_cell(args.workload)
    import jax
    dev = jax.devices()[0]
    bench.log(f"device: platform={dev.platform} kind={dev.device_kind}")
    if dev.platform != "tpu":
        bench.log(f"trace_scopes: needs a TPU, JAX found {dev.platform!r}")
        return 2
    from harness.profile import TraceWindow
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    os.environ["REPRO_OBS"] = ""
    conf, dep = cell.config, cell.config["deployment"]
    cfg = bench.model_config(conf)
    compiles = bench.CompileCounter()
    eng = bench.build_engine(cfg, dep, args.seed)
    traffic = ClosedLoopTraffic(cell.traffic, dep["n_slots"],
                                cfg.vocab_size, args.seed, dep["max_len"])
    served = prime(eng, traffic)
    bench.log(f"set-up: {time.perf_counter() - bench.T_START:.1f}s, "
              f"{compiles.n} lowerings, cache {compiles.cache}")
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)

    for i, mode in enumerate(modes):
        os.environ["REPRO_OBS"] = MODES[mode]
        from repro import obs
        obs.tracer().reset()
        tracer, kw = None, {}
        if mode == "profile":                # the loop is steady already
            tracer = TraceWindow(args.seconds, start_frac=0.1)
            kw["annotate"] = jax.profiler.TraceAnnotation
        elif mode == "alternate":
            tracer = Alternate()
        before = eng.stats.to_dict()
        eng.stats.longest_step = None
        watch = bench.HostWatch()
        w = run_window(eng, traffic, args.seconds, primed=served,
                       after_step=tracer, compile_count=compiles, **kw)
        host = watch.report(w)
        served = w.served
        n = len(w.steps)
        longest = {k: 1e3 * v for k, v in eng.stats.longest_step.items()}
        line = {"window": i, "mode": mode, "seed": args.seed,
                "tok_s": tok_s(w), "itl_p95_ms": itl_quantile_ms(w, 0.95),
                "steps": n, "compiles": w.compiles,
                "split_ms_per_step": split_ms(eng.stats, before, n),
                "longest_step_ms": longest, "host": host}
        if mode == "alternate":
            line["halves"] = tracer.halves(w)
        elif tracer is not None:
            tracer.stop(w)
            traced = [s for s in w.steps if s.traced]
            if args.keep:
                stem = os.path.join(args.keep, f"w{i}_{args.seed}")
                with open(tracer.xplane(), "rb") as src, \
                        gzip.open(stem + ".xplane.pb.gz", "wb") as dst:
                    shutil.copyfileobj(src, dst)
                with open(stem + ".json", "w") as f:
                    json.dump({"steps": [{"t0": s.t0, "t1": s.t1,
                                          "kind": s.kind} for s in traced]},
                              f)
            line.update(profile_line(tracer.xplane(), len(traced)))
            tracer.cleanup()
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
