"""``correct`` comes out false when the timed path is broken underneath.

Each test drives a whole run (``run.run``: weights, set-up, closed-loop
window, sample, reference) on the CPU at the registry's smoke size, past
the harness's look for a chip, with one fault of ``harness.faults``
planted in the program, and sees ``correct`` false against the cell's own
limits file; the same run without a fault is correct (at the smoke size
on the CPU every served token is the reference's best). The window holds
a fixed 60 steps (``benchsmoke.fixed_steps``): on a loaded host a 3 s
window can hold so few decode steps that most served tokens come from
the unfaulted prefill."""
import pytest

import benchsmoke
from harness import check, faults

import run as bench_run

CELL = "qwen2.5-14b.decode"
NUMBER = f"share_over_{check.SD_OVER:g}sd"


@pytest.fixture
def smoke_run(monkeypatch):
    cell = benchsmoke.smoke_cell(CELL)
    cfg = benchsmoke.smoke_model(cell)
    monkeypatch.setattr(bench_run, "model_config", lambda conf: cfg)
    # the smoke widths are narrower than the kernel's 128-lane tile, so
    # some of their GEMMs take the XLA mirror here; the chip cells cannot
    monkeypatch.setattr(bench_run, "gemm_backends", lambda: {"pallas": 7})
    benchsmoke.fixed_steps(monkeypatch, 60)

    def go(seed=20251):
        return bench_run.run(cell, seed, 3.0, False,
                             {"platform": "cpu", "kind": "cpu", "count": 1},
                             {})
    return go


def _limit():
    return check.load_limits(CELL, benchsmoke.BENCH)[NUMBER]["limit"]


def test_sound_run_is_correct(smoke_run):
    res = smoke_run()
    assert res["correct"], res["checks"]
    assert res["checks"][NUMBER]["value"] == 0.0
    assert list(res)[-1] == "checks"


def test_altered_tokens_are_not_correct(smoke_run):
    with faults.plant("altered_tokens"):
        res = smoke_run()
    assert not res["correct"]
    assert res["checks"][NUMBER]["value"] > _limit()


def test_state_left_unchanged_is_not_correct(smoke_run):
    with faults.plant("state_unchanged"):
        res = smoke_run()
    assert not res["correct"]
    assert res["checks"][NUMBER]["value"] > _limit()


def test_half_the_batch_left_out_is_not_correct(smoke_run):
    """Only the first half of the slots is computed; the other half is
    handed the first half's logits."""
    with faults.plant("half_batch"):
        res = smoke_run()
    assert not res["correct"]
    assert res["checks"][NUMBER]["value"] > _limit()
