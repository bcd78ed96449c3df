"""The closed loop and the end-to-end arithmetic, on the CPU at the
registry's smoke size (``run.py`` itself refuses any platform but a TPU;
these tests call the loop directly)."""
import json
import os
import shutil
import types

import jax
import pytest

import benchsmoke
from harness import loop, spec
from harness.traffic import ClosedLoopTraffic, stratified

import run as bench_run


def _engine(cell, **kw):
    from repro.serve import ServeEngine
    from repro.serve.prequant import init_packed_params
    cfg = benchsmoke.smoke_model(cell)
    dep = cell.config["deployment"]
    return ServeEngine(init_packed_params(jax.random.PRNGKey(0), cfg), cfg,
                       n_slots=dep["n_slots"], max_len=dep["max_len"],
                       prefill_chunk=dep["prefill_chunk"], **kw)


def _traffic(cell, seed=5):
    dep = cell.config["deployment"]
    return ClosedLoopTraffic(cell.traffic, dep["n_slots"],
                             cell.config["vocab_size"], seed, dep["max_len"])


@pytest.fixture(scope="module")
def window():
    cell = benchsmoke.smoke_cell()
    eng = _engine(cell)
    traffic = _traffic(cell)
    primed = loop.prime(eng, traffic)
    compiles = bench_run.CompileCounter()
    w = loop.run_window(eng, traffic, 4.0, primed=primed,
                        compile_count=compiles)
    return cell, w


def test_warm_up_leaves_nothing_to_compile_in_the_window(window):
    _, w = window
    assert len(w.steps) > 20
    assert {s.kind for s in w.steps} == {"decode", "prefill"}
    assert w.compiles == 0


def test_closed_loop_refills_each_slot_on_finish(window):
    cell, w = window
    n = cell.config["deployment"]["n_slots"]
    by_client = {}
    for s in w.served:
        by_client.setdefault(s.client, []).append(s)
    assert sorted(by_client) == list(range(n))
    for reqs in by_client.values():
        # every request but a client's last finished; the next one was
        # submitted at the end of the step that finished it
        assert all(s.finished for s in reqs[:-1])
        assert not reqs[-1].finished or reqs[-1].token_t[-1] == w.t_close
        for a, b in zip(reqs, reqs[1:]):
            assert b.submit_t == max(a.token_t[-1], w.t_open)
    assert len(w.served) > 2 * n


def test_first_wave_is_staggered(window):
    cell, w = window
    n = cell.config["deployment"]["n_slots"]
    first = [s for s in w.served if s.submit_t < w.t_open]
    assert len(first) == n
    assert all(s.token_t[0] < w.t_open for s in first)   # primed in set-up
    wave = cell.traffic["first_wave"]
    assert sorted(s.req.max_new_tokens for s in first) == \
        stratified(*wave["output_tokens"], n).tolist()
    assert sorted(len(s.req.prompt) for s in first) == \
        stratified(*wave["context_tokens"], n).tolist()


def test_same_work_for_every_seed():
    cell = benchsmoke.smoke_cell()
    decks = []
    for seed in (1, 2 ** 33 + 5):
        t = _traffic(cell, seed)
        reqs = [t.next(c) for c in range(4) for _ in range(8)]
        wave = [t.first(c) for c in range(4)]
        decks.append([sorted((len(p), o) for p, o in r)
                      for r in (reqs, wave)])
    for a, b in zip(*decks):
        assert sorted(x[0] for x in a) == sorted(x[0] for x in b)
        assert sorted(x[1] for x in a) == sorted(x[1] for x in b)


def _served(client, submit, tokens, state="finished"):
    req = types.SimpleNamespace(state=state)
    return loop.Served(client=client, req=req, submit_t=submit,
                       token_t=list(tokens))


def _window(served, steps, t_open=10.0, t_close=20.0):
    return loop.WindowResult(t_open, t_close, served, steps, 0)


def test_tok_s_counts_only_work_inside_the_window():
    steps = [loop.StepRecord(10.0, 12.0, "prefill", [8], [0], 8, 0),
             loop.StepRecord(12.0, 20.0, "decode", [1, 1], [8, 3], 0, 2)]
    assert loop.tok_s(_window([], steps)) == pytest.approx(10 / 10.0)


def test_itl_pools_every_gap_inside_the_window():
    a = _served(0, 9.0, [9.5, 11.0, 12.0, 15.0])    # 9.5 is before the open
    b = _served(1, 10.0, [13.0, 13.5, 21.0])        # 21.0 is after the close
    gaps = loop.itl_gaps_s(_window([a, b], []))
    assert sorted(gaps) == [0.5, 1.0, 3.0]
    assert loop.itl_quantile_ms(_window([a, b], []), 0.5) == \
        pytest.approx(1000.0)


def test_ttft_runs_from_submit_for_first_tokens_in_the_window():
    a = _served(0, 8.0, [10.5, 11.0])       # submitted before the open
    b = _served(1, 12.0, [14.0])
    c = _served(2, 19.0, [20.5])            # first token after the close
    d = _served(3, 9.0, [9.5, 12.0])        # first token before the open
    assert sorted(loop.ttft_s(_window([a, b, c, d], []))) == [2.0, 2.5]


def test_failed_counts_quarantined_expired_and_shed():
    cell = benchsmoke.smoke_cell()
    eng = _engine(cell, default_ttl_steps=6, max_queue=1)
    eng.submit([1] * 9, 2)          # one request warms both launch shapes
    eng.run()
    w = loop.run_window(eng, _traffic(cell), 2.0)
    states = [s.req.state if s.req else "shed" for s in w.served]
    assert "shed" in states and "expired" in states
    failed = sum(s.failed for s in w.served)
    assert failed == states.count("shed") + states.count("expired") + \
        states.count("quarantined")
    q = _served(0, 10.0, [11.0], state="quarantined")
    assert q.failed and not q.finished


def test_cell_parts_are_found_by_name(tmp_path, monkeypatch):
    """A new configuration, mix, metric and family count are files; no
    registry in the harness needs an edit."""
    bench = tmp_path / "bench"
    shutil.copytree(benchsmoke.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    root = json.load(open(os.path.join(os.path.dirname(benchsmoke.BENCH),
                                       "BENCHMARK.json")))
    conf = json.load(open(bench / "configs" / "qwen2.5-14b.json"))
    conf["family"] = "newfam"
    json.dump(conf, open(bench / "configs" / "new.json", "w"))
    mix = json.load(open(bench / "traffic" / "decode.json"))
    mix["output_tokens"] = [8, 9]
    json.dump(mix, open(bench / "traffic" / "newmix.json", "w"))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    (bench / "counts" / "newfam.py").write_text("FAMILY = 'newfam'\n")
    root["configs"].append({"name": "new", "source": "x", "reduced": [],
                            "file": "bench/configs/new.json", "why": "x"})
    root["workloads"].append({"name": "new.newmix", "config": "new",
                              "traffic": "newmix", "chips": 1, "why": "x"})
    root["per_layer"].append({"name": "new_metric", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "tok_s",
                              "workloads": ["new.newmix"]})
    json.dump(root, open(tmp_path / "BENCHMARK.json", "w"))
    monkeypatch.setattr(spec, "BENCH_DIR", str(bench))
    cell = spec.load_cell("new.newmix", spec.load_benchmark(str(tmp_path)))
    assert cell.config["family"] == "newfam"
    assert cell.traffic["output_tokens"] == [8, 9]
    assert [m["name"] for m in cell.per_layer][-1] == "new_metric"
    assert spec.metric_reader("new_metric")(None) == 42.0
    assert spec.counts_module("newfam").FAMILY == "newfam"
    assert "ttft_p90_ms" not in [m["name"] for m in cell.end_to_end]


def test_model_config_is_the_file():
    cell = spec.load_cell("qwen2.5-14b.decode")
    cfg = bench_run.model_config(cell.config)
    assert (cfg.d_model, cfg.d_ff, cfg.n_layers) == (5120, 13824, 48)
    cell.config["intermediate_size"] = 1024
    with pytest.raises(SystemExit, match="intermediate_size"):
        bench_run.model_config(cell.config)


def test_run_refuses_a_cpu(capsys):
    assert bench_run.main(["--workload", "qwen2.5-14b.decode", "--seed",
                           "1", "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""
