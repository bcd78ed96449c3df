"""Device time by named scope and gaps labelled on the trace's clock
(``harness.scopes``), and the ``dispatch_ms`` reader, on synthetic events
and on ``bench/testdata/smoke_scoped.xplane.pb``: 4 closed-loop steps
(three prefill launches, one decode launch) of a ``qwen2.5-smoke`` config
widened so every GEMM takes the serve kernel, served with the program's
spans on on one TPU v5e, cut to what the reduction reads
(``bench/record_scoped_testdata.py``)."""
import json
import os

import pytest

import benchsmoke
from harness import profile, scopes, spec

DATA = os.path.join(benchsmoke.BENCH, "testdata")
TRACE = os.path.join(DATA, "smoke_scoped.xplane.pb")


def test_innermost_scope_of_an_op_name():
    f = scopes.innermost_scope
    assert f("jit(decode_fn)/while/body/closed_call/attention/"
             "kv_cache/vmap(vmap())/scatter") == "kv_cache"
    assert f("jit(decode_fn)/while/body/closed_call/act_quant/"
             "jit(quantize_act_m2xfp)/jit(searchsorted)/while") \
        == "act_quant"
    assert f("jit(decode_fn)/lm_head/dot_general") == "lm_head"
    assert f("jit(decode_fn)/while/body/add") is None
    assert f("jit(attention_decode)/act_quantizer/mul") is None
    assert f("") is None


def test_scope_times_charge_own_time_and_inherit_from_the_loop():
    """A loop op carrying ``act_quant`` lends it to its body's ops, which
    name no scope; an unscoped op under an unscoped loop is ``other``; a
    scope inside another scope wins; times are each op's own."""
    pre = "jit(decode_fn)/while/body/closed_call/"
    ev = [
        ("%while.1", "jit(decode_fn)/while", 0, 100),            # layers
        ("%fusion.1", pre + "act_quant/mul", 0, 10),
        ("%while.2", pre + "act_quant/jit(searchsorted)/while", 10, 30),
        ("%fusion.2", "jit(searchsorted)/while/body/gather", 12, 20),
        ("%fusion.3", "", 20, 28),
        ("%kernel.1", pre + "serve_gemm/jit(k)/pallas_call", 30, 50),
        ("%fusion.4", pre + "attention/kv_cache/scatter", 50, 55),
        ("%fusion.5", pre + "attention/dot_general", 55, 60),
        ("%fusion.6", "jit(decode_fn)/while/body/add", 60, 70),
        ("%fusion.7", "jit(decode_fn)/lm_head/dot_general", 100, 110),
    ]
    by_scope, other = scopes.scope_times(ev)
    assert by_scope == {"act_quant": 30, "serve_gemm": 20, "attention": 5,
                        "kv_cache": 5, "lm_head": 10, "other": 40}
    # the layer loop's own 30 (100 less its nested 70) and the add
    assert other == {"%while.1": 30, "%fusion.6": 10}
    assert sum(by_scope.values()) == 110


def test_dispatch_ms_is_the_mean_dispatch_span_per_traced_step():
    read = spec.metric_reader("dispatch_ms")

    class Run:
        steps = [object()] * 4
        spans = [("serve.step", 0.0, 0.8), ("serve.launch.dispatch", 0.1,
                                             0.1004),
                 ("serve.launch.wait", 0.1004, 0.7),
                 ("serve.launch.dispatch", 1.0, 1.0002),
                 ("serve.launch.dispatch", 2.0, 2.0002)]
    assert read(Run) == pytest.approx(1e3 * 0.0008 / 4)
    Run.spans = [("serve.step", 0.1, 0.2)]         # a program without
    assert read(Run) is None                       # the span


@pytest.fixture(scope="module")
def reduced():
    return scopes.reduce_scopes(TRACE)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "smoke_scoped.json")) as f:
        return json.load(f)


def _device_events():
    pb = scopes.xplane_pb2().XSpace()
    with open(TRACE, "rb") as f:
        pb.ParseFromString(f.read())
    plane = next(p for p in pb.planes if p.name == "/device:TPU:0")
    return (scopes._plane_events(plane, {"XLA Ops"}, op_names=True),
            scopes._plane_events(plane, {"XLA Modules"}))


def test_scopes_add_up_to_the_launches_device_time(reduced, recorded):
    """Against a plain union of the op intervals inside the launches,
    which the launches' module events hold."""
    assert reduced["n_launches"] == len(recorded["steps"]) == 4
    ops, modules = _device_events()
    launches = [(s, e) for n, _, s, e in modules
                if n.startswith(profile.LAUNCHES)]
    assert len(launches) == 4
    busy = sum(e - s for s, e in profile.union_ns(
        [(s, e) for _, _, s, e in ops
         if any(ms <= s and e <= me for ms, me in launches)]))
    assert sum(reduced["scopes"].values()) == pytest.approx(busy / 1e9,
                                                            rel=1e-6)
    assert busy <= reduced["launch_ns"]
    for name in scopes.SCOPES:
        assert reduced["scopes"][name] > 0, name


def test_serve_gemm_scope_holds_the_kernel(reduced):
    """Every GEMM of the recorded config takes the kernel: each kernel call
    is attributed to ``serve_gemm``, which holds the kernel's device time
    as ``profile.reduce_xplane`` counts it, and the output converts."""
    kernel = [ev for ev in _device_events()[0]
              if ev[0].startswith(profile.KERNEL_OP + ".")]
    by_scope, _ = scopes.scope_times(kernel)
    assert by_scope["serve_gemm"] == sum(e - s for _, _, s, e in kernel)
    assert sum(by_scope.values()) == by_scope["serve_gemm"]
    assert reduced["scopes"]["serve_gemm"] >= reduced["kernel_ns"] / 1e9 > 0
    red = profile.reduce_xplane(TRACE)            # whole nanoseconds
    assert red["kernel_calls"] == len(kernel) == 7 * 2 * 4
    assert abs(reduced["kernel_ns"] - red["kernel_ns"]) <= len(kernel)


def test_every_gap_is_labelled_by_a_span_on_the_trace_clock(reduced):
    names = {n for n, _, _ in reduced["spans"]}
    assert {"serve.step", "serve.launch.dispatch", "serve.launch.wait",
            "serve.guard.drain", "bench.step"} <= names
    labels = [label for label, _ in reduced["gaps"]]
    assert labels and "none" not in labels
    assert set(labels) <= names
    assert any(label.startswith("serve.") for label in labels)
