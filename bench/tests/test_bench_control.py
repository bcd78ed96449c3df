"""The control (the reference one precision step lower, float8_e4m3fn
where the configuration states bfloat16) comes out not correct against
the cell's own limits, at a size a test run holds: a whole run at the
registry's smoke widths, 16 layers deep (at 2 the float8 control reads
as close as rounding), on the CPU, whose served tokens are all the
reference's best, while the control's tokens at the same positions, and
the served tokens with every 4th one altered, are judged not correct.
The window holds a fixed 60 steps (``benchsmoke.fixed_steps``), so the
sample does not follow the host's load; the control's share at this
size moves with the sample (0.115-0.172 over windows of 40-100 steps,
0.039 at 120)."""
import benchsmoke
from harness import check

import run as bench_run

CELL = "qwen2.5-14b.decode"
SIZE = {"n_layers": 16, "d_model": 128, "d_ff": 256}


def test_control_is_not_correct(monkeypatch):
    cell = benchsmoke.smoke_cell(CELL, **SIZE)
    cfg = benchsmoke.smoke_model(cell, **SIZE)
    monkeypatch.setattr(bench_run, "model_config", lambda conf: cfg)
    monkeypatch.setattr(bench_run, "gemm_backends", lambda: {"pallas": 7})
    benchsmoke.fixed_steps(monkeypatch, 60)
    res = bench_run.run(cell, 7, 3.0, False,
                        {"platform": "cpu", "kind": "cpu", "count": 1}, {},
                        control=True)
    program, readings = res["program_numbers"], res["readings"]
    limits = check.load_limits(CELL, benchsmoke.BENCH)
    assert res["correct"] and check.judge(program, limits)[0]
    for name in ("control", "altered_tokens"):
        assert readings[name]["tokens"] == program["tokens"] > 50
        assert readings[name]["correct"] is False, (name, readings[name])
