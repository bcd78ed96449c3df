"""Observability layer: registry math, span tracing, quantization health,
engine integration, and the REPRO_OBS=off bit-identity guarantee.

The bit-identity test is the contract the whole layer rests on: with
REPRO_OBS unset the serve path must produce exactly the tokens an
uninstrumented build produces (no probe may perturb the traced graphs).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.models.config import ModelConfig
from repro.models.model import init_params
from repro.serve import ServeEngine, prequantize_params, tree_nbytes
from repro.serve.engine import ServeStats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    """Every test starts with observability off and empty buffers."""
    monkeypatch.delenv("REPRO_OBS", raising=False)
    monkeypatch.delenv("REPRO_OBS_DIR", raising=False)
    obs.reset()
    yield
    obs.reset()


def tiny_cfg(**kw):
    kw.setdefault("quant", "serve")
    kw.setdefault("kv_quant", "m2xfp")
    return ModelConfig(name="obs-test", family="dense", n_layers=2,
                       d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
                       vocab_size=256, remat=False, **kw)


def tiny_packed(cfg):
    return prequantize_params(init_params(jax.random.PRNGKey(0), cfg), cfg)


PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8]]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_labels_and_monotonicity():
    c = obs.counter("t_total", "help text")
    c.inc()
    c.inc(2.5, site="a")
    c.inc(site="a")
    assert c.value() == 1.0
    assert c.value(site="a") == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_add():
    g = obs.gauge("t_gauge")
    g.set(2.0, k="x")
    g.add(0.5, k="x")
    assert g.value(k="x") == 2.5
    assert g.value() == 0.0                    # unseen label set


def test_histogram_cumulative_buckets():
    h = obs.histogram("t_hist", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0, 0.1):
        h.observe(v)
    snap = h.snapshot()
    assert snap["buckets"] == {"1.0": 2, "10.0": 3, "+Inf": 4}
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(55.6)


def test_registry_kind_mismatch():
    obs.counter("t_same")
    with pytest.raises(TypeError):
        obs.gauge("t_same")


def test_prometheus_exposition_format():
    obs.counter("t_req_total", "requests").inc(3, route="/v1")
    h = obs.histogram("t_lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05, phase="p")
    h.observe(0.5, phase="p")
    text = obs.registry().render_prometheus()
    assert "# HELP t_req_total requests" in text
    assert "# TYPE t_req_total counter" in text
    assert 't_req_total{route="/v1"} 3.0' in text
    assert 't_lat_seconds_bucket{phase="p",le="0.1"} 1' in text
    assert 't_lat_seconds_bucket{phase="p",le="+Inf"} 2' in text
    assert 't_lat_seconds_count{phase="p"} 2' in text


def test_jsonl_dump_appends(tmp_path):
    obs.counter("t_a").inc()
    path = str(tmp_path / "m.jsonl")
    n1 = obs.registry().dump_jsonl(path)
    obs.counter("t_a").inc()
    n2 = obs.registry().dump_jsonl(path)
    assert n1 == n2 == 1
    recs = [json.loads(l) for l in open(path)]
    assert len(recs) == 2
    assert recs[-1]["value"] == 2.0            # last record wins semantics


def test_enabled_modes(monkeypatch):
    assert not obs.enabled()
    monkeypatch.setenv("REPRO_OBS", "0")
    assert not obs.enabled("trace")
    monkeypatch.setenv("REPRO_OBS", "1")
    assert all(obs.enabled(p) for p in obs.PILLARS)
    monkeypatch.setenv("REPRO_OBS", "metrics,trace")
    assert obs.enabled("metrics") and obs.enabled("trace")
    assert not obs.enabled("health")
    monkeypatch.setenv("REPRO_OBS", "metrcs")
    with pytest.raises(ValueError, match="unknown pillar"):
        obs.enabled()


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_spans_disabled_record_nothing():
    with obs.span("t.outer"):
        pass
    obs.instant("t.mark")
    assert obs.tracer().events() == []


def test_span_nesting_and_export(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_OBS", "trace")
    with obs.span("t.outer", cat="t", job=1):
        with obs.span("t.inner", cat="t"):
            pass
    evs = obs.tracer().events()
    assert [e["name"] for e in evs] == ["t.inner", "t.outer"]
    inner, outer = evs
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert inner["tid"] == outer["tid"] and outer["ph"] == "X"
    assert outer["args"] == {"job": 1}

    path = str(tmp_path / "trace.json")
    n = obs.export_chrome_trace(path)
    assert n == 2
    doc = json.load(open(path))
    names = [e["name"] for e in doc["traceEvents"]]
    assert "process_name" in names and "t.outer" in names


# ---------------------------------------------------------------------------
# quantization health
# ---------------------------------------------------------------------------

def test_weight_tree_health_report(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "health")
    from repro.models.quant import pack_serving_weight
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.standard_normal((64, 16)).astype(np.float32) * 0.1)
    report = obs.quant_health.weight_tree_health(
        {"layer0": pack_serving_weight(w)})
    st = report["layer0"]
    assert st["elems"] == w.size
    assert 0.0 <= st["clip_rate"] <= 1.0
    # each meta byte packs four 2-bit subgroup codes
    assert sum(st["meta_hist"]) == 4 * st["groups"]
    assert st["reencode_drift"] < 1e-3           # Sg-EM ~idempotent
    g = obs.gauge("repro_quant_clip_rate")
    assert g.value(layer="layer0", codec="m2xfp",
                   kind="weight") == st["clip_rate"]


def test_act_reencode_drift_small():
    rng = np.random.default_rng(4)
    x = rng.standard_normal((8, 64)).astype(np.float32)
    assert obs.quant_health.act_reencode_drift(x) < 1e-3


def test_e8m0_bounds_constants():
    # repro.core.scaling clamps exponents to [-126, 127] -> bytes [1, 254]
    assert obs.quant_health.E8M0_BYTE_LOW == 1
    assert obs.quant_health.E8M0_BYTE_HIGH == 254


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_engine_emits_metrics_and_trace(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_OBS", "1")
    cfg = tiny_cfg()
    eng = ServeEngine(tiny_packed(cfg), cfg, n_slots=2, max_len=32,
                      prefill_chunk=4)
    outs = eng.generate(PROMPTS, max_new_tokens=4)
    jax.effects_barrier()              # flush debug.callback health drains
    assert [len(o) for o in outs] == [4, 4]

    text = obs.registry().render_prometheus()
    # acceptance: TTFT + step-latency histograms in the exposition
    assert "repro_serve_step_latency_seconds_bucket" in text
    assert "repro_serve_ttft_steps_bucket" in text
    assert "repro_serve_steps_total" in text
    assert "repro_serve_occupancy" in text
    # acceptance: per-layer clip rate + online site health
    assert ('repro_quant_clip_rate{codec="m2xfp",kind="online",'
            'site="serve_gemm"}' in text)
    assert ('repro_quant_clip_rate{codec="m2xfp",kind="online",'
            'site="kv_encode"}' in text)
    assert 'kind="weight"' in text
    assert "repro_quant_reencode_drift" in text
    assert "repro_quant_meta_total" in text

    # acceptance: nested spans step -> phase -> launch dispatch and wait,
    # step -> guard drain
    evs = obs.tracer().events()
    byname = {}
    for e in evs:
        byname.setdefault(e["name"], []).append(e)
    for required in ("serve.run", "serve.step", "serve.plan",
                     "serve.launch.dispatch", "serve.launch.wait",
                     "serve.guard.drain", "serve.weight_health",
                     "serve.sample"):
        assert required in byname, f"missing span {required}"
    assert ("serve.phase.decode" in byname or
            "serve.phase.prefill" in byname)

    def contains(outer, inner):
        return (outer["ts"] <= inner["ts"] + 1e-6 and
                inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
                + 1e-6 and outer["tid"] == inner["tid"])

    phases = (byname.get("serve.phase.decode", []) +
              byname.get("serve.phase.prefill", []))
    for disp, wait in zip(byname["serve.launch.dispatch"],
                          byname["serve.launch.wait"]):
        phase = next(p for p in phases if contains(p, disp))
        assert contains(phase, wait)
        assert disp["ts"] + disp["dur"] <= wait["ts"] + 1e-6
        step = next(s for s in byname["serve.step"] if contains(s, phase))
        assert contains(step, phase)
    assert len(byname["serve.launch.dispatch"]) == len(phases)
    for drain in byname["serve.guard.drain"]:
        assert any(contains(s, drain) for s in byname["serve.step"])
        assert not any(contains(p, drain) for p in phases)

    # the trace file is a loadable Chrome trace
    path = str(tmp_path / "trace.json")
    obs.export_chrome_trace(path)
    doc = json.load(open(path))
    assert any(e.get("ph") == "X" for e in doc["traceEvents"])


@pytest.mark.smoke
def test_obs_off_bit_identical_tokens(monkeypatch):
    """Tier-1 acceptance: REPRO_OBS unset leaves serve output bit-identical
    to a REPRO_OBS=1 run (instrumentation never perturbs the math)."""
    cfg = tiny_cfg()
    packed = tiny_packed(cfg)

    monkeypatch.delenv("REPRO_OBS", raising=False)
    eng_off = ServeEngine(packed, cfg, n_slots=2, max_len=32)
    out_off = eng_off.generate(PROMPTS, max_new_tokens=6)

    monkeypatch.setenv("REPRO_OBS", "1")
    obs.reset()
    eng_on = ServeEngine(packed, cfg, n_slots=2, max_len=32)
    out_on = eng_on.generate(PROMPTS, max_new_tokens=6)
    jax.effects_barrier()

    assert out_off == out_on
    assert "repro_serve_steps_total" in obs.registry().render_prometheus()


def test_span_mirrors_a_trace_annotation_when_on(monkeypatch):
    """With the trace pillar on, each span opens a profiler annotation of
    its own name around the timed body; with it off nothing is called."""
    import jax.profiler
    opened = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with obs.span("t.off"):
        pass
    assert opened == [] and obs.tracer().events() == []

    monkeypatch.setenv("REPRO_OBS", "trace")
    with obs.span("t.outer"):
        with obs.span("t.inner"):
            opened.append(("body", "t.inner"))
    assert opened == [("enter", "t.outer"), ("enter", "t.inner"),
                      ("body", "t.inner"), ("exit", "t.inner"),
                      ("exit", "t.outer")]
    assert [e["name"] for e in obs.tracer().events()] == ["t.inner",
                                                          "t.outer"]


def test_servestats_split_fits_inside_each_step():
    """The always-on split of a step's host time: dispatch, wait and guard
    add up to no more than the step's wall time, the longest step is kept
    with its rest, and the totals are the sums over steps."""
    cfg = tiny_cfg()
    eng = ServeEngine(tiny_packed(cfg), cfg, n_slots=2, max_len=32,
                      prefill_chunk=4)
    for p in PROMPTS:
        eng.submit(p, 4)
    walls, splits = [], []
    note = eng.stats.note_step

    def record(wall_s, **split):
        walls.append(wall_s)
        splits.append(split)
        note(wall_s, **split)

    eng.stats.note_step = record
    while eng.scheduler.has_work:
        eng.step()
    assert splits and all(sum(sp.values()) <= w
                          for w, sp in zip(walls, splits))
    assert all(sp["dispatch_s"] > 0 and sp["wait_s"] > 0
               and sp["guard_s"] > 0 for sp in splits)
    s = eng.stats
    for part in ("dispatch_s", "wait_s", "guard_s"):
        assert getattr(s, part) == pytest.approx(
            sum(sp[part] for sp in splits))
    longest = s.longest_step
    assert longest["wall_s"] == max(walls)
    assert longest["rest_s"] >= 0
    assert longest["rest_s"] == pytest.approx(
        longest["wall_s"] - longest["dispatch_s"] - longest["wait_s"]
        - longest["guard_s"])
    json.dumps(s.to_dict())
    s.longest_step = None
    s.note_step(0.5, dispatch_s=0.1, wait_s=0.2, guard_s=0.05)
    assert s.longest_step["rest_s"] == pytest.approx(0.15)


SCOPES = {"act_quant", "serve_gemm", "attention", "kv_cache", "lm_head"}


@pytest.mark.parametrize("kv_quant", ["none", "m2xfp"])
@pytest.mark.parametrize("launch", ["decode_step", "prefill_chunk"])
def test_model_step_carries_the_named_scopes(launch, kv_quant):
    """Each of the five named scopes a device trace attributes time to
    appears in the compiled launch's ``op_name`` metadata."""
    import re
    from repro.models import model
    cfg = tiny_cfg(kv_quant=kv_quant)
    caches = model.init_caches(cfg, 2, 32, per_slot=True)
    index = jnp.zeros((2,), jnp.int32)
    if launch == "decode_step":
        fn = jax.jit(lambda p, b, c, i: model.decode_step(p, cfg, b, c, i))
        args = ({"tokens": jnp.zeros((2, 1), jnp.int32)}, caches, index)
    else:
        fn = jax.jit(lambda p, b, c, i, l: model.prefill_chunk(
            p, cfg, b, c, i, l))
        args = ({"tokens": jnp.zeros((2, 4), jnp.int32)}, caches, index,
                jnp.array([4, 1], jnp.int32))
    text = fn.lower(tiny_packed(cfg), *args).compile().as_text()
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        found.update(SCOPES.intersection(op_name.split("/")))
    assert found == SCOPES


def test_obs_off_records_nothing():
    cfg = tiny_cfg()
    eng = ServeEngine(tiny_packed(cfg), cfg, n_slots=2, max_len=32)
    eng.generate(PROMPTS, max_new_tokens=2)
    assert obs.registry().render_prometheus() == ""
    assert obs.tracer().events() == []


def test_autodump_writes_obs_dir(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_OBS", "1")
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path / "dump"))
    cfg = tiny_cfg(kv_quant="none")
    eng = ServeEngine(tiny_packed(cfg), cfg, n_slots=2, max_len=32)
    eng.generate(PROMPTS, max_new_tokens=2)
    assert (tmp_path / "dump" / "metrics.jsonl").exists()
    assert (tmp_path / "dump" / "trace.json").exists()


# ---------------------------------------------------------------------------
# satellites: ServeStats.to_dict, tree_nbytes, _env_int, obs_report
# ---------------------------------------------------------------------------

def test_servestats_to_dict():
    s = ServeStats(n_slots=4, steps=10, decode_steps=8, prefill_steps=2,
                   slot_steps=30, prefill_tokens=40, generated_tokens=20,
                   wall_s=2.0, prefill_wall_s=0.5, decode_wall_s=1.5)
    d = s.to_dict()
    assert d["steps"] == 10 and d["n_slots"] == 4
    assert d["tokens_per_sec"] == pytest.approx(30.0)
    assert d["prefill_tokens_per_sec"] == pytest.approx(80.0)
    assert d["decode_tokens_per_sec"] == pytest.approx(20.0 / 1.5)
    assert d["occupancy"] == pytest.approx(0.75)
    json.dumps(d)                                   # plain scalars only
    assert ServeStats().to_dict()["tokens_per_sec"] == 0.0


def test_tree_nbytes_packed_checkpoint():
    """Packed trees count their u8 streams exactly (satellite: packed-u8
    checkpoints)."""
    cfg = tiny_cfg()
    dense = init_params(jax.random.PRNGKey(0), cfg)
    packed = tiny_packed(cfg)
    expect = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(packed)
                 if hasattr(x, "dtype"))
    assert tree_nbytes(packed) == expect
    assert 0 < tree_nbytes(packed) < tree_nbytes(dense)

    from repro.models.quant import pack_serving_weight
    w = jnp.zeros((64, 16), jnp.float32)
    pw = pack_serving_weight(w)
    # codes (K/2, N) + scales (K/32, N) + meta (K/32, N), all u8
    assert tree_nbytes(pw) == 32 * 16 + 2 * 16 + 2 * 16
    assert {np.dtype(x.dtype) for x in jax.tree.leaves(pw)} == {
        np.dtype(np.uint8)}


def test_tree_nbytes_mixed_dtype_cache_tree():
    tree = {
        "f32": jnp.zeros((4, 4), jnp.float32),        # 64
        "bf16": jnp.zeros((8,), jnp.bfloat16),        # 16
        "i32": np.zeros((3,), np.int32),              # 12
        "u8": np.zeros((5,), np.uint8),               # 5
        "plain": 7,                                   # no dtype: skipped
    }
    assert tree_nbytes(tree) == 64 + 16 + 12 + 5

    from repro.models.model import init_caches
    caches = init_caches(tiny_cfg(), 2, 32, per_slot=True)
    expect = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches)
                 if hasattr(x, "dtype"))
    assert tree_nbytes(caches) == expect > 0
    dtypes = {np.dtype(x.dtype) for x in jax.tree.leaves(caches)}
    assert len(dtypes) > 1                            # genuinely mixed


def test_env_int_validation(monkeypatch):
    from repro.models.attention import _env_int
    monkeypatch.delenv("T_OBS_X", raising=False)
    assert _env_int("T_OBS_X", 7) == 7
    monkeypatch.setenv("T_OBS_X", "3")
    assert _env_int("T_OBS_X", 7) == 3
    monkeypatch.setenv("T_OBS_X", "0")
    with pytest.raises(ValueError, match="must be >= 1"):
        _env_int("T_OBS_X", 7)
    monkeypatch.setenv("T_OBS_X", "-2")
    with pytest.raises(ValueError, match="must be >= 1"):
        _env_int("T_OBS_X", 7)
    monkeypatch.setenv("T_OBS_X", "banana")
    with pytest.raises(ValueError, match="not an integer"):
        _env_int("T_OBS_X", 7)
    monkeypatch.setenv("T_OBS_X", "4")
    assert _env_int("T_OBS_X", 7, minimum=4) == 4


def test_obs_report_renders_dump(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_OBS", "1")
    obs.counter("repro_demo_total", "demo").inc(5, site="x")
    obs.histogram("repro_demo_seconds", "demo",
                  buckets=(0.1, 1.0)).observe(0.5)
    obs.gauge("repro_quant_clip_rate", "").set(
        0.25, layer="l0", kind="weight")
    with obs.span("demo.work", cat="demo"):
        pass
    d = str(tmp_path / "dump")
    obs.dump(d)

    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"), d],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert proc.returncode == 0, proc.stderr
    out = proc.stdout
    assert "repro_demo_total{site=x} = 5" in out
    assert "count=1" in out and "p50=" in out
    assert "top clip-rate layers" in out and "l0" in out
    assert "demo.work" in out
