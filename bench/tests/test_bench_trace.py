"""The reduction from a device trace to metrics, checked on a real trace:
``bench/testdata/smoke.xplane.pb`` holds 4 closed-loop steps (two prefill
launches, two decode launches) of the registry's ``qwen2.5-smoke`` config
served on one TPU v5e, cut to the lines the reduction reads
(``bench/record_testdata.py``); ``smoke.json`` holds the steps as the loop
recorded them."""
import json
import os

import numpy as np
import pytest

import benchsmoke
from harness import profile

DATA = os.path.join(benchsmoke.BENCH, "testdata")
TRACE = os.path.join(DATA, "smoke.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return profile.reduce_xplane(TRACE)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "smoke.json")) as f:
        return json.load(f)


def _device_ops():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(TRACE)
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = list(profile._events(plane, "XLA Ops"))
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events
            if e.name == "bench.step"]
    return ops, sorted(host, key=lambda a: a[1])


def test_union_merges_overlaps():
    assert profile.union_ns([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)]) == \
        [(0, 3), (5, 8), (10, 11)]


def test_self_time_subtracts_nested_ops():
    ev = [("loop", 0, 10), ("a", 1, 3), ("b", 4, 8), ("c", 5, 6),
          ("d", 12, 13)]
    assert dict(profile.self_times(ev)) == {
        "loop": 4, "a": 2, "b": 3, "c": 1, "d": 1}


def test_busy_is_the_union_of_device_ops(reduced):
    """Against a plain count of the microseconds in which some op ran."""
    ops, steps = _device_ops()
    lo, hi = steps[0][1], steps[-1][2]
    us = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, e in ops:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            us[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = True
    assert reduced["window_ns"] == hi - lo
    assert reduced["busy_ns"] == pytest.approx(us.sum() * 1000, rel=0.05)
    idle = 1 - reduced["busy_ns"] / reduced["window_ns"]
    assert 0.0 < idle < 1.0


def test_one_module_event_per_traced_launch(reduced, recorded):
    kinds = [s["kind"] for s in recorded["steps"]]
    assert reduced["n_steps"] == len(kinds) == 4
    assert set(kinds) == {"prefill", "decode"}
    assert [n.split("(")[0] for n, _ in reduced["launches"]] == \
        [f"jit_{k}_fn" for k in kinds]
    assert reduced["launch_ns"] == sum(d for _, d in reduced["launches"])


def test_kernel_events_are_the_serve_gemm_calls(reduced):
    """At the smoke widths only gate and up (N = 128) tile for the kernel;
    the other five GEMMs take the XLA mirror: 2 calls x 2 layers per
    launch."""
    assert reduced["kernel_calls"] == 4 * len(reduced["launches"])
    assert 0 < reduced["kernel_ns"] < reduced["launch_ns"]


def test_gaps_are_labelled_by_the_open_annotation(reduced):
    labels = {label for label, _ in reduced["gaps"]}
    assert labels and labels <= {"bench.step", "bench.route",
                                 "bench.submit"}
    idle_s = sum(s for _, s in reduced["gaps"])
    assert idle_s == pytest.approx(
        (reduced["window_ns"] - reduced["busy_ns"]) / 1e9, rel=1e-6)


def test_gap_takes_the_innermost_span():
    spans = [("bench.step", 0, 100), ("serve.step", 5, 95),
             ("serve.sample", 60, 70)]
    assert profile.label_gaps([(62, 66), (20, 30), (96, 99), (200, 210)],
                              spans) == [
        ("serve.sample", 4e-9), ("serve.step", 1e-8), ("bench.step", 3e-9),
        ("none", 1e-8)]


def test_traced_stretch_holds_every_launch_kind(monkeypatch):
    """A window of one long prefill launch and five decode launches a
    cycle: the stretch starts after the first prefill and runs on until
    it has held a prefill launch too."""
    import jax
    from harness.loop import StepRecord, WindowResult
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    tw = profile.TraceWindow(51.0)
    w = WindowResult(0.0, 0.0, [], [], 0)
    t = 0.0
    for kind, dt in ([("prefill", 22.6)] + [("decode", 0.5)] * 5) * 3:
        w.steps.append(StepRecord(t, t + dt, kind, [], [], 0, 0))
        t += dt
        w.t_close = t
        tw(w)
    tw.cleanup()
    assert calls == ["start", "stop"]
    traced = [s.kind for s in w.steps if s.traced]
    assert traced == ["decode"] * 5 + ["prefill"]
