#!/usr/bin/env python3
"""Record the small device trace the scope reduction's tests read.

    python3 bench/record_scoped_testdata.py OUT_DIR
    python3 bench/record_scoped_testdata.py --trim TRACE.xplane.pb STEPS.json

On a TPU, serves a ``qwen2.5-smoke`` registry config widened so that all
seven GEMMs of a layer tile for the serve kernel (d_model 256, d_ff 512,
two heads and one KV head of 128) through ``ServeEngine`` (4 slots of 128
tokens, chunk 8) in a closed loop, with the program's spans on
(``REPRO_OBS=trace``, so each ``serve.*`` span is also a profiler
annotation), traces a few steps with the benchmark's annotations, and
writes ``OUT_DIR/smoke_scoped.xplane.pb`` and ``OUT_DIR/smoke_scoped.json``
(the traced steps as the loop recorded them), both cut to the first
``KEEP`` traced steps (``trim``). Refuses any platform but a TPU.
``--trim`` cuts a trace and its steps file that were recorded before, in
place, on any machine.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

from harness.scopes import OP_NAME_STAT, xplane_pb2  # noqa: E402

KEEP = 4        # traced steps kept: prefill launches and a decode one
HOST_KEPT = ("bench.", "serve.")       # host annotations the reduction reads


def trim(trace: str, steps_json: str, keep: int = KEEP) -> None:
    """Cut a trace, in place, to what the scope reduction reads over the
    first ``keep`` ``bench.step`` annotations: the TPU plane's ``XLA Ops``
    and ``XLA Modules`` events with their names and the op-name statistic
    (``OP_NAME_STAT``, on their metadata), and the host's
    ``bench.*`` and ``serve.*`` annotations that overlap them. The steps
    file keeps its first ``keep`` steps."""
    pb2 = xplane_pb2()
    space = pb2.XSpace()
    with open(trace, "rb") as f:
        space.ParseFromString(f.read())

    def span(line, ev):
        t0 = line.timestamp_ns * 1000 + ev.offset_ps
        return t0, t0 + ev.duration_ps

    steps = sorted(span(line, ev) for plane in space.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if plane.event_metadata[ev.metadata_id].name
                   == "bench.step")
    lo, hi = steps[0][0], steps[keep - 1][1]
    out = pb2.XSpace()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not (device or plane.name.startswith("/host:")):
            continue
        stat_ids = {k for k, m in plane.stat_metadata.items()
                    if device and m.name == OP_NAME_STAT}
        new = pb2.XPlane(id=plane.id, name=plane.name)
        used = set()
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            events = [ev for ev in line.events
                      if span(line, ev)[1] > lo and span(line, ev)[0] < hi
                      and (device or plane.event_metadata[ev.metadata_id]
                           .name.startswith(HOST_KEPT))]
            if not events:
                continue
            new_line = new.lines.add()
            new_line.CopyFrom(line)
            del new_line.events[:]
            new_line.events.extend(events)
            for ev in new_line.events:
                kept = [s for s in ev.stats if s.metadata_id in stat_ids]
                del ev.stats[:]
                ev.stats.extend(kept)
            used.update(ev.metadata_id for ev in events)
        if not new.lines:
            continue
        for k in used:
            meta = plane.event_metadata[k]
            new.event_metadata[k].id = k
            new.event_metadata[k].name = meta.name
            new.event_metadata[k].stats.extend(
                s for s in meta.stats if s.metadata_id in stat_ids)
        for k in stat_ids:
            new.stat_metadata[k].CopyFrom(plane.stat_metadata[k])
        out.planes.add().CopyFrom(new)
    with open(trace, "wb") as f:
        f.write(out.SerializeToString())
    with open(steps_json) as f:
        recorded = json.load(f)
    with open(steps_json, "w") as f:
        json.dump({"steps": recorded["steps"][:keep]}, f, indent=1)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv[0] == "--trim":
        trim(argv[1], argv[2])
        return 0
    out = argv[0]
    os.makedirs(out, exist_ok=True)
    os.environ["REPRO_OBS"] = "trace"
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_testdata: needs a TPU", file=sys.stderr)
        return 2
    from harness.loop import run_window
    from harness.traffic import ClosedLoopTraffic
    from repro.configs.registry import smoke_config
    from repro.serve import ServeEngine
    from repro.serve.prequant import init_packed_params

    cfg = dataclasses.replace(
        smoke_config("qwen2.5-14b", quant="serve"), d_model=256, d_ff=512,
        n_heads=2, n_kv_heads=1, head_dim=128)
    eng = ServeEngine(init_packed_params(jax.random.PRNGKey(0), cfg), cfg,
                      n_slots=4, max_len=128, prefill_chunk=8)
    for _ in range(4):
        eng.submit([1] * 9, 2)
    while eng.scheduler.has_work:
        eng.step()
    mix = {"loop": "closed", "prompt_tokens": [8, 24],
           "output_tokens": [4, 12],
           "first_wave": {"context_tokens": [8, 24], "output_tokens": [1, 12]},
           "deck": 8}
    traffic = ClosedLoopTraffic(mix, 4, cfg.vocab_size, 0, 128)
    tmp = tempfile.mkdtemp(prefix="bench_testdata_")
    state = {"on": False}

    def after_step(w):
        if not state["on"] and len(w.steps) == 3:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
            state.update(on=True, first=len(w.steps))
        elif state["on"] and len(w.steps) == state["first"] + 8:
            jax.profiler.stop_trace()
            state["on"] = False
            for s in w.steps[state["first"]:]:
                s.traced = True

    w = run_window(eng, traffic, 3.0, after_step=after_step,
                   annotate=jax.profiler.TraceAnnotation)
    if state["on"]:
        jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    trace = os.path.join(out, "smoke_scoped.xplane.pb")
    steps_json = os.path.join(out, "smoke_scoped.json")
    shutil.copy(src, trace)
    with open(steps_json, "w") as f:
        json.dump({"steps": [{"t0": s.t0, "t1": s.t1, "kind": s.kind,
                              "fed": s.fed, "pos": s.pos}
                             for s in w.steps if s.traced]}, f)
    shutil.rmtree(tmp, ignore_errors=True)
    trim(trace, steps_json)
    print(f"recorded {KEEP} traced steps into {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
