"""Device trace of a steady stretch of the window, and its reduction.

The traced run starts ``jax.profiler`` once the window is under way and
stops it after a few steps. The reduction reads the ``.xplane.pb`` with
``jax.profiler.ProfileData``:

* device time: the ``XLA Ops`` line of the first TPU plane; busy time is
  the union of its op intervals, clipped to the traced stretch;
* launches: the ``XLA Modules`` line, one event per jitted launch;
* the serve-GEMM kernel: op events named after the Pallas kernel
  (``%m2xfp_matmul_kernel.<n>``), inside the traced launches;
* each op's own time: an op (a ``while`` over layers, say) is charged its
  duration less the ops nested inside it, so the top ops are leaves;
* the stretch runs from the start of the first traced ``bench.step``
  annotation to the end of the last one;
* idle gaps: stretches with no device op, labelled by the innermost host
  span open at their midpoint (the benchmark's annotations, and the
  program's own ``serve.*`` spans moved onto the trace's clock).
"""
from __future__ import annotations

import collections
import glob
import os
import shutil
import tempfile

__all__ = ["TraceWindow", "reduce_xplane", "union_ns", "label_gaps"]

KERNEL_OP = "%m2xfp_matmul_kernel"    # the serve-GEMM Pallas call's op
LAUNCHES = ("jit_decode_fn", "jit_prefill_fn")


class TraceWindow:
    """Starts the profiler at ``start_frac`` of the window and stops it
    after at least ``min_s`` seconds and ``min_steps`` steps, and not
    before it has seen every kind of launch (decode, prefill) that the
    window ran before it started."""

    def __init__(self, seconds: float, start_frac: float = 0.4,
                 min_s: float = 2.0, min_steps: int = 6):
        self.start_at = start_frac * seconds
        self.min_s, self.min_steps = min_s, min_steps
        self.dir = None
        self.t_start = None
        self.first_step = None
        self.kinds = set()
        self.done = False

    def __call__(self, w) -> None:
        import jax
        if self.done:
            return
        now = w.t_close
        if self.dir is None and now - w.t_open >= self.start_at:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host annotations, no Python
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_start, self.first_step = now, len(w.steps)
            self.kinds = {s.kind for s in w.steps}
        elif self.dir is not None:
            traced = w.steps[self.first_step:]
            if now - self.t_start >= self.min_s and \
                    len(traced) >= self.min_steps and \
                    self.kinds <= {s.kind for s in traced}:
                self.stop(w)

    def stop(self, w) -> None:
        import jax
        if self.dir is None or self.done:
            return
        jax.profiler.stop_trace()
        self.done = True
        for s in w.steps[self.first_step:]:
            s.traced = True

    def xplane(self) -> str:
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
        return found[0]

    def cleanup(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def union_ns(intervals) -> list:
    """Merge (start, end) intervals; returns the disjoint union, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def label_gaps(gaps, spans) -> list:
    """``(label, seconds)`` of each gap: the innermost (shortest) span
    ``(name, start, end)`` open at the gap's midpoint, else ``"none"``."""
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        open_ = [(end - start, name) for name, start, end in spans
                 if start <= mid <= end]
        out.append((min(open_)[1] if open_ else "none", (e - s) / 1e9))
    return out


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def short_name(op: str) -> str:
    """``%fusion.424 = f32[1769472]{...} fusion(...)`` -> ``%fusion.424
    f32[1769472]``: the op and its result type, without the layout."""
    name, _, rest = op.partition(" = ")
    result = rest.split("{")[0].split(" ")[0] if rest else ""
    return f"{name} {result}".strip()


def self_times(events) -> list:
    """``(name, self_ns)`` of each (name, start, end): its duration less
    the durations of the events directly nested in it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e - s for _, s, e in events]
    stack = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(events[i][0], own[i]) for i in range(len(events))]


def reduce_xplane(path: str, program_spans=(), step_t0=()) -> dict:
    """Reduce one trace. ``program_spans``: ``(name, t0_s, t1_s)`` on the
    host's ``perf_counter`` clock; ``step_t0``: that clock's start of each
    traced step, which pins it to the trace's ``bench.step`` events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device = None
    host_ann = []
    for plane in pd.planes:
        if device is None and plane.name.startswith("/device:TPU:") \
                and any(ln.name == "XLA Ops" for ln in plane.lines):
            device = plane
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_ann.append((ev.name, ev.start_ns,
                                         ev.start_ns + ev.duration_ns))
    if device is None:
        raise ValueError(f"{path}: no TPU plane with an 'XLA Ops' line")
    steps = sorted(a for a in host_ann if a[0] == "bench.step")
    if not steps:
        raise ValueError(f"{path}: no bench.step annotation")
    lo, hi = steps[0][1], steps[-1][2]
    ops = [e for e in _events(device, "XLA Ops") if e[2] > lo and e[1] < hi]
    busy = union_ns(_clip([(s, e) for _, s, e in ops], lo, hi))
    busy_ns = sum(e - s for s, e in busy)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))

    modules = [e for e in _events(device, "XLA Modules")
               if e[1] >= lo and e[2] <= hi]
    launches = [m for m in modules if m[0].startswith(LAUNCHES)]
    kernel = [(n, s, e) for n, s, e in ops
              if n.split(" = ")[0].startswith(KERNEL_OP + ".")
              and any(ms <= s and e <= me for _, ms, me in launches)]
    by_op = collections.Counter()
    for n, t in self_times(list(_clip_named(ops, lo, hi))):
        by_op[short_name(n)] += t

    spans = list(host_ann)
    if program_spans and step_t0:
        # the k-th traced step's annotation starts when the loop stamped it
        k = min(len(steps), len(step_t0))
        off = sorted(steps[i][1] - step_t0[i] * 1e9 for i in range(k))[k // 2]
        spans += [(n, t0 * 1e9 + off, t1 * 1e9 + off)
                  for n, t0, t1 in program_spans]
    labelled = label_gaps(gaps, spans)
    return {
        "window_ns": hi - lo,
        "busy_ns": busy_ns,
        "n_steps": len(steps),
        "launches": [(n, e - s) for n, s, e in launches],
        "launch_ns": sum(e - s for _, s, e in launches),
        "kernel_calls": len(kernel),
        "kernel_ns": sum(e - s for _, s, e in kernel),
        "top_ops": [[n, t / 1e9] for n, t in by_op.most_common(10)],
        "gaps": labelled,
    }


def _clip_named(events, lo, hi):
    for n, s, e in events:
        s2, e2 = max(s, lo), min(e, hi)
        if e2 > s2:
            yield n, s2, e2

