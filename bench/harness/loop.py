"""The measured window: a closed loop of clients driving ``ServeEngine``.

``prime`` is set-up: it submits each client's first request (the context
of a conversation under way, see ``harness.traffic``) and steps the engine
until every client has its first token and a decode launch has run, so
every launch shape the window uses is compiled and the window opens on a
server in steady decode. ``run_window`` then alternates ``engine.step()``
with the clients' reactions: every token a step returns is stamped by the
host clock, and a client whose request ended submits its next one before
the following step. ``step()`` ends in a
blocking device-to-host copy of the logits, so the host clock bounds the
device work of each step.

The end-to-end numbers are pure functions of what the loop recorded
(``tok_s``, ``itl_quantile_ms``, ``ttft_quantile_ms``), so they are the
same arithmetic on the chip and in the tests.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, List, Optional

import numpy as np

__all__ = ["Served", "StepRecord", "WindowResult", "prime", "run_window",
           "tok_s",
           "itl_quantile_ms", "ttft_quantile_ms", "quantile"]

TERMINAL_FAILED = ("quarantined", "expired")


@dataclasses.dataclass
class Served:
    """One request as its client saw it."""
    client: int
    req: object                        # repro.serve.scheduler.Request
    submit_t: float
    token_t: List[float] = dataclasses.field(default_factory=list)
    consumed: int = 0                  # prompt tokens fed, as last seen
    shed: bool = False

    @property
    def finished(self) -> bool:
        return self.req is not None and self.req.state == "finished"

    @property
    def failed(self) -> bool:
        return self.shed or (self.req is not None
                             and self.req.state in TERMINAL_FAILED)


@dataclasses.dataclass
class StepRecord:
    """One engine launch: when it ran, its kind, and per request the
    tokens it fed and the position of the first of them (what the work
    counts need)."""
    t0: float
    t1: float
    kind: str                           # "decode" | "prefill"
    fed: List[int]                      # tokens fed, per progressing slot
    pos: List[int]                      # position of the first fed token
    prompt_tokens: int                  # prompt tokens consumed
    generated: int                      # tokens returned (rows sampled)
    traced: bool = False


@dataclasses.dataclass
class WindowResult:
    t_open: float
    t_close: float
    served: List[Served]
    steps: List[StepRecord]
    compiles: int                       # lowerings inside the window

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def _noop(_name):
    return contextlib.nullcontext()


def _launch_kind(engine, decode_steps: int) -> str:
    return "decode" if engine.stats.decode_steps > decode_steps \
        else "prefill"


def prime(engine, traffic, *,
          clock: Callable[[], float] = time.perf_counter) -> List[Served]:
    """Set-up: every client's first request, stepped until each has its
    first token (or has ended) and a decode launch has run. Raises when
    the first wave runs no prefill launch although later prompts need one,
    since the window would then compile it."""
    served = []
    for c in range(traffic.n_clients):
        prompt, n_out = traffic.first(c)
        served.append(Served(client=c, req=engine.submit(prompt, n_out),
                             submit_t=clock()))
    kinds = set()
    while engine.scheduler.has_work:
        decode_steps = engine.stats.decode_steps
        engine.step()
        t = clock()
        kinds.add(_launch_kind(engine, decode_steps))
        for s in served:
            s.consumed = s.req.consumed
            s.token_t.extend([t] * (len(s.req.output) - len(s.token_t)))
        waiting = [s for s in served if not s.req.output
                   and s.req.state in ("queued", "running")]
        if "decode" in kinds and not waiting:
            break
    if traffic.mix["prompt_tokens"][1] > 1 and "prefill" not in kinds:
        raise ValueError("the first wave ran no prefill launch; its "
                         "contexts must be longer than one token")
    return served


def run_window(engine, traffic, seconds: float, *,
               primed: Optional[List[Served]] = None,
               clock: Callable[[], float] = time.perf_counter,
               annotate: Callable = _noop,
               after_step: Optional[Callable] = None,
               compile_count: Callable[[], int] = lambda: 0) -> WindowResult:
    """Drive ``engine`` with ``traffic`` (``ClosedLoopTraffic``) for
    ``seconds`` of host clock, from the clients ``prime`` left running
    (``primed``), or with every client's first request submitted at the
    open. ``after_step(result_so_far)`` runs after every step (the traced
    run starts and stops the profiler there); ``compile_count()`` is
    sampled at the open and the close."""
    from repro.serve.scheduler import AdmissionError

    served: List[Served] = list(primed or [])
    current: dict = {s.client: s for s in served}
    steps: List[StepRecord] = []
    compiles0 = compile_count()

    def submit(c: int, first: bool, t: float) -> None:
        prompt, n_out = traffic.first(c) if first else traffic.next(c)
        with annotate("bench.submit"):
            try:
                req = engine.submit(prompt, n_out)
                s = Served(client=c, req=req, submit_t=t)
            except AdmissionError:
                s = Served(client=c, req=None, submit_t=t, shed=True)
        served.append(s)
        current[c] = s

    def refill(t: float) -> None:
        for c, s in list(current.items()):
            if s.shed or s.req.state in ("finished",) + TERMINAL_FAILED:
                submit(c, False, t)

    t_open = clock()
    if primed is None:
        for c in range(traffic.n_clients):
            submit(c, True, t_open)
    refill(t_open)
    result = WindowResult(t_open, t_open, served, steps, 0)
    t_end = t_open + seconds
    while clock() < t_end:
        before = {c: (s.consumed, len(s.req.output))
                  for c, s in current.items() if s.req is not None}
        decode_steps = engine.stats.decode_steps
        t0 = clock()
        with annotate("bench.step"):
            engine.step()
        t1 = clock()
        with annotate("bench.route"):
            rec = StepRecord(t0, t1, _launch_kind(engine, decode_steps),
                             [], [], 0, 0)
            for c, (consumed0, out0) in before.items():
                s = current[c]
                req = s.req
                d_cons = req.consumed - consumed0
                d_out = len(req.output) - out0
                s.consumed = req.consumed
                if d_cons or d_out:
                    rec.fed.append(d_cons if d_cons else d_out)
                    rec.pos.append(consumed0 if d_cons else
                                   len(req.prompt) + out0 - 1)
                rec.prompt_tokens += d_cons
                rec.generated += d_out
                s.token_t.extend([t1] * d_out)
            steps.append(rec)
            refill(t1)
        result.t_close = t1
        if after_step is not None:
            after_step(result)
    result.compiles = compile_count() - compiles0
    return result


def tok_s(w: WindowResult) -> float:
    """Prompt tokens prefilled plus tokens generated in the window, over
    the window's seconds."""
    work = sum(s.prompt_tokens + s.generated for s in w.steps)
    return work / w.seconds


def quantile(values, q: float) -> float:
    """The ``q`` quantile (0..1) by linear interpolation between order
    statistics (numpy's default); ValueError when there is no value."""
    if len(values) == 0:
        raise ValueError("no samples")
    return float(np.quantile(np.asarray(values, np.float64), q))


def itl_gaps_s(w: WindowResult) -> list:
    """Every gap between consecutive output tokens of one request inside
    the window, pooled over all requests."""
    gaps = []
    for s in w.served:
        ts = [t for t in s.token_t if w.t_open <= t <= w.t_close]
        gaps.extend(np.diff(ts).tolist())
    return gaps


def itl_quantile_ms(w: WindowResult, q: float) -> float:
    return 1e3 * quantile(itl_gaps_s(w), q)


def ttft_s(w: WindowResult) -> list:
    """Due (submit) time to first token, for every request whose first
    token fell inside the window."""
    return [s.token_t[0] - s.submit_t for s in w.served
            if s.token_t and w.t_open <= s.token_t[0] <= w.t_close]


def ttft_quantile_ms(w: WindowResult, q: float) -> float:
    return 1e3 * quantile(ttft_s(w), q)
