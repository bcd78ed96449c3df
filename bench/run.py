#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix, limits, per-layer metrics and work counts are
files under ``bench/`` found by name. A run:

1. refuses any platform but a TPU, or fewer chips than the cell asks for;
2. builds the cell's packed weights on the device from ``--seed``
   (``init_packed_params``) and one ``ServeEngine`` with the deployment
   settings of the configuration file;
3. primes the closed loop (``bench.harness.loop.prime``): every client's
   first request, the context of a conversation under way, is prefilled
   in chunks and decoded once, which compiles (or loads from the cache)
   every launch the window uses (``prefill_fn`` at (B, chunk), the slot
   reset, ``decode_fn`` at (B, 1)); it checks that every serve GEMM took
   the Pallas kernel, and reports the lowerings and compile-cache hits
   and misses of set-up;
4. drives the closed-loop traffic for ``--seconds`` and counts the
   compilations inside the window (there should be none); with
   ``--trace 1`` it also traces a steady stretch of the window;
5. frees the program's state and compares a sample of the served tokens
   with the plain reference (``bench.harness.reference`` and the family's
   layers, ``bench/references/<family>.py``);
6. prints one JSON line: ``correct``, ``attempted``, ``failed``,
   ``metrics`` (end-to-end ones, or per-layer ones with ``--trace 1``),
   ``device``, ``breakdown`` (traced runs) and, last, ``checks``: each
   number compared with its limit, which also end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

from harness import check, spec  # noqa: E402
from harness.loop import (itl_quantile_ms, prime, run_window,  # noqa: E402
                          tok_s, ttft_quantile_ms)
from harness.traffic import ClosedLoopTraffic  # noqa: E402

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "misses"}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def model_config(conf: dict):
    """The registry config the file names, checked against the file by
    the ``REGISTRY_KEYS`` of its family's reference, so the file is the
    configuration that runs."""
    from repro.configs.registry import get_config
    dep = conf["deployment"]
    cfg = get_config(conf["model"], quant="serve",
                     quant_format=dep["codec"], kv_quant=dep["kv_quant"])
    keys = spec.reference_module(conf["family"]).REGISTRY_KEYS
    for key, field in keys.items():
        if key in conf and getattr(cfg, field) != conf[key]:
            raise SystemExit(f"{conf['model']}: file says {key}="
                             f"{conf[key]!r}, registry runs {field}="
                             f"{getattr(cfg, field)!r}")
    return cfg


def build_engine(cfg, dep: dict, seed: int):
    import jax
    from repro.serve import ServeEngine
    from repro.serve.prequant import init_packed_params
    packed = jax.block_until_ready(init_packed_params(weights_key(seed), cfg))
    return ServeEngine(packed, cfg, n_slots=dep["n_slots"],
                       max_len=dep["max_len"],
                       prefill_chunk=dep["prefill_chunk"])


def weights_key(seed: int):
    import jax
    return jax.random.PRNGKey(seed % 2 ** 32)


def gemm_backends() -> dict:
    from repro import obs
    out = {}
    for labels, n in obs.counter("repro_serve_gemm_traces_total") \
            .samples().items():
        b = dict(labels)["backend"]
        out[b] = out.get(b, 0) + n
    return out


class CompileCounter:
    """Lowerings (``n``) and persistent compile-cache hits and misses
    since it was made."""

    def __init__(self):
        import jax
        self.n = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if event == LOWERING:
            self.n += 1

    def _on_event(self, event, **kw):
        if event in CACHE_EVENTS:
            self.cache[CACHE_EVENTS[event]] += 1

    def __call__(self) -> int:
        return self.n


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader (``bench/metrics/<name>.py``) sees."""
    trace: dict                 # harness.profile.reduce_xplane
    steps: list                 # traced StepRecords
    spans: list                 # program spans (name, t0, t1) in the stretch
    model: dict                 # the configuration file
    deployment: dict
    peaks: dict
    counts: object              # bench/counts/<family>.py
    bits: float                 # codec bits per weight element


class HostWatch:
    """What the host did during the window, for an informational line:
    the garbage collector's pauses, the process's CPU time and its
    involuntary context switches (the host taking the CPU away), the
    longest steps and the longest host gaps between steps. A slow run's
    tail then shows whether a few steps stalled, and on what."""

    def __init__(self):
        self.pauses = []                    # (t0, t1, generation)
        self._t0 = None
        self.ru0 = resource.getrusage(resource.RUSAGE_SELF)
        self.load0 = os.getloadavg()[0]
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((self._t0, time.perf_counter(),
                                info["generation"]))
            self._t0 = None

    def report(self, w) -> str:
        gc.callbacks.remove(self._on_gc)
        ru = resource.getrusage(resource.RUSAGE_SELF)
        ms = [1e3 * (s.t1 - s.t0) for s in w.steps]
        gaps = [1e3 * (b.t0 - a.t1) for a, b in zip(w.steps, w.steps[1:])]
        top = sorted(range(len(ms)), key=lambda i: -ms[i])[:5]
        pauses = [(1e3 * (b - a), g) for a, b, g in self.pauses
                  if w.t_open <= a <= w.t_close]
        cpu = (ru.ru_utime - self.ru0.ru_utime
               + ru.ru_stime - self.ru0.ru_stime)
        return (f"host in window: {len(ms)} steps, median "
                f"{sorted(ms)[len(ms) // 2]:.2f} ms, longest "
                f"{[[i, round(ms[i], 2)] for i in top]} (step, ms); "
                f"longest gap between steps {max(gaps, default=0):.2f} ms; "
                f"gc {len(pauses)} pauses, "
                f"{sum(p for p, _ in pauses):.2f} ms, longest "
                f"{max(pauses, default=(0, -1))}; cpu {cpu:.2f} s of "
                f"{w.seconds:.2f} s; involuntary switches "
                f"{ru.ru_nivcsw - self.ru0.ru_nivcsw}; load "
                f"{self.load0:.2f} -> {os.getloadavg()[0]:.2f}; threads "
                f"{len(os.listdir('/proc/self/task'))} [informational]")


def end_to_end(cell, w, setup_s: float) -> dict:
    """The cell's end-to-end metrics; one with no sample in the window
    (no gap, no first token) is left out, and the run says so."""
    values = {"tok_s": lambda: tok_s(w), "setup_s": lambda: setup_s,
              "itl_p95_ms": lambda: itl_quantile_ms(w, 0.95),
              "ttft_p90_ms": lambda: ttft_quantile_ms(w, 0.90)}
    out = {}
    for m in cell.end_to_end:
        try:
            out[m["name"]] = {"value": values[m["name"]](), "unit": m["unit"]}
        except ValueError as e:
            log(f"bench: {m['name']} has no sample in the window: {e}")
    return out


def per_layer(cell, view) -> dict:
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(view)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def breakdown(red: dict) -> dict:
    by_label = {}
    for label, s in red["gaps"]:
        by_label[label] = by_label.get(label, 0.0) + s
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": red["top_ops"][:10],
            "idle_gaps": [[k, v] for k, v in gaps]}


def program_spans(t0: float, t1: float) -> list:
    from repro import obs
    out = []
    for ev in obs.tracer().events():
        if ev.get("ph") == "X":
            s, e = ev["ts"] / 1e6, (ev["ts"] + ev["dur"]) / 1e6
            if s >= t0 and e <= t1:
                out.append((ev["name"], s, e))
    return out


def correctness(cell, model, window, seed: int, control: bool = False):
    """Numbers the comparison reads (and, with ``control``, the readings
    of ``harness.control`` on the same sample), or a reason there are
    none."""
    from harness.reference import reference_gaps
    ref = cell.config["reference"]
    sample = check.choose_sample(window.served, seed, ref["max_seqs"],
                                 ref["max_tokens"])
    if not sample:
        return {}, {}, "no request served a token"
    rows_pad = -(-ref["max_tokens"] // 256) * 256
    tokens, rows, chk, n = check.reference_batch(
        sample, ref["max_seqs"], cell.config["deployment"]["max_len"],
        rows_pad)
    t0 = time.perf_counter()
    if control:
        from harness.control import control_readings
        readings = control_readings(weights_key(seed), model, tokens, rows,
                                    chk, n)
        return readings.pop("program"), readings, ""
    gap, _, sd = reference_gaps(weights_key(seed), model, tokens, rows, chk)
    log(f"reference: {len(sample)} requests, {n} served tokens, "
        f"{time.perf_counter() - t0:.1f}s; per request, tokens over "
        f"{check.SD_OVER:g} sd: "
        f"{check.per_sequence_over(gap, sd, rows, tokens.shape[1], n)} "
        f"[informational]")
    return check.gap_numbers(gap, sd, n), {}, ""


def run(cell, seed: int, seconds: float, trace: bool, device: dict,
        peaks: dict, control: bool = False) -> dict:
    """Everything after the device check: set-up, window, reference.
    Returns the result line as a dict; with ``control`` (never in a
    benchmark run) it also holds, under ``"readings"``, the control's and
    the altered tokens' numbers, each judged against the cell's limits."""
    import jax
    os.environ["REPRO_OBS"] = "metrics"       # counts GEMM dispatch at trace
    conf, dep = cell.config, cell.config["deployment"]
    cfg = model_config(conf)
    compiles = CompileCounter()
    eng = build_engine(cfg, dep, seed)
    log(f"weights built: {time.perf_counter() - T_START:.1f}s since start "
        f"[informational]")
    traffic = ClosedLoopTraffic(cell.traffic, dep["n_slots"], cfg.vocab_size,
                                seed, dep["max_len"])
    primed = prime(eng, traffic)
    backends = gemm_backends()
    log(f"set-up: {compiles.n} lowerings, persistent compile cache "
        f"{compiles.cache['hits']} hits, {compiles.cache['misses']} misses; "
        f"primed {sum(len(s.req.prompt) for s in primed)} context tokens "
        f"in {eng.stats.steps} launches [informational]")
    os.environ["REPRO_OBS"] = "trace" if trace else ""
    tracer, kw = None, {}
    if trace:
        from harness.profile import TraceWindow
        tracer = TraceWindow(seconds)
        kw["annotate"] = jax.profiler.TraceAnnotation
    setup_s = time.perf_counter() - T_START
    watch = HostWatch()
    window = run_window(eng, traffic, seconds, primed=primed,
                        after_step=tracer, compile_count=compiles, **kw)
    host_line = watch.report(window)
    if tracer is not None:
        tracer.stop(window)
    print(f"compiles in window: {window.compiles}", flush=True)
    log(host_line)
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    del eng
    gc.collect()

    result = {"correct": False, "attempted": len(window.served),
              "failed": sum(s.failed for s in window.served)}
    extra = {}
    if trace:
        from harness.profile import reduce_xplane
        traced = [s for s in window.steps if s.traced]
        spans = program_spans(traced[0].t0, traced[-1].t1)
        red = reduce_xplane(tracer.xplane(), spans, [s.t0 for s in traced])
        tracer.cleanup()
        view = RunView(red, traced, spans, conf, dep, peaks,
                       spec.counts_module(conf["family"]),
                       dep["bits_per_weight"])
        result["metrics"] = per_layer(cell, view)
        device["busy_s"] = red["busy_ns"] / 1e9
        device["window_s"] = red["window_ns"] / 1e9
        extra["breakdown"] = breakdown(red)
    else:
        result["metrics"] = end_to_end(cell, window, setup_s)

    numbers, readings, why = correctness(cell, conf, window, seed, control)
    numbers["xla_gemm_sites"] = backends.get("xla", 0)
    cell_limits = check.load_limits(cell.name, BENCH)
    limits = dict(cell_limits, xla_gemm_sites={"limit": 0})
    ok, checks = check.judge(numbers, limits)
    if why:
        log(f"bench: not correct: {why}")
    log(f"compared (informational): {json.dumps(numbers)}")
    result["correct"] = bool(ok and not why and backends.get("pallas"))
    result["device"] = device
    result.update(extra)
    if control:
        result["program_numbers"] = numbers
        result["detail"] = readings.pop("detail")
        result["readings"] = {
            name: {"correct": check.judge(nums, cell_limits)[0], **nums}
            for name, nums in readings.items()}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        log(f"bench: needs a TPU, JAX found platform {dev.platform!r}")
        return 2
    if len(devs) < cell.chips:
        log(f"bench: cell needs {cell.chips} chips, JAX found {len(devs)}")
        return 2
    peaks = spec.load_peaks(dev.device_kind)
    from repro.launch.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips}
    result = run(cell, args.seed, args.seconds, bool(args.trace), device,
                 peaks)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
