"""Device time by the model's named scopes, and idle gaps labelled by the
program's own spans on the trace's clock.

The model wraps its device work in ``jax.named_scope`` (``SCOPES``); the
names land in each HLO op's ``op_name`` metadata, which the TPU profiler
keeps in the ``tf_op`` statistic of each ``XLA Ops`` event's metadata
(``op_name:op_type``). ``jax.profiler.ProfileData`` does not expose
event metadata, so this module reads the ``.xplane.pb`` protobuf itself.
The reduction:

* attributes each op of a traced launch to the innermost scope named in
  its ``op_name`` path; an op whose path names none takes the scope of the
  innermost op event that encloses it on the device timeline, else
  ``other``;
* charges each op its own time (``profile.self_times``), so the scopes
  and ``other`` add up to the device time of the launches' ops;
* labels each idle gap of the traced stretch by the innermost host
  annotation open at its midpoint, from the benchmark's ``bench.*``
  annotations and the program's ``serve.*`` spans, which the program
  mirrors as profiler annotations when its spans are on: both on the
  trace's own clock.
"""
from __future__ import annotations

import collections
import importlib.util
import os
import sys

from . import profile

__all__ = ["SCOPES", "OP_NAME_STAT", "innermost_scope", "scope_times",
           "reduce_scopes", "xplane_pb2"]

SCOPES = ("act_quant", "serve_gemm", "attention", "kv_cache", "lm_head")
OTHER = "other"
OP_NAME_STAT = "tf_op"
HOST_PREFIXES = ("bench.", "serve.")


def xplane_pb2():
    """The ``XSpace`` protobuf module that ships inside TensorFlow, loaded
    from its file alone (it needs only ``google.protobuf``): importing the
    ``tensorflow`` package for it costs about 14 s and 0.8 GB."""
    name = "bench_xplane_pb2"
    if name not in sys.modules:
        pkg = importlib.util.find_spec("tensorflow")
        if pkg is None:
            raise ImportError("reading a trace's op metadata needs the "
                              "xplane protobuf shipped with tensorflow")
        path = os.path.join(pkg.submodule_search_locations[0], "tsl",
                            "profiler", "protobuf", "xplane_pb2.py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


def innermost_scope(op_name: str):
    """The last of ``SCOPES`` among the ``/``-separated components of an
    ``op_name``, or None."""
    found = None
    for part in (op_name or "").split("/"):
        if part in SCOPES:
            found = part
    return found


def scope_times(events) -> tuple:
    """``(by_scope, other_ops)`` of ``(name, op_name, start, end)`` op
    events: each op's own time (its duration less the ops nested in it)
    summed by scope, with an op that names no scope taking that of the
    innermost op enclosing it, and the own time of each op left in
    ``other`` by name. ``by_scope`` holds every scope and ``other``."""
    own = profile.self_times([(n, s, e) for n, _, s, e in events])
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][2], -events[i][3]))
    scope = [None] * len(events)
    stack = []
    for i in order:
        _, op_name, s, e = events[i]
        while stack and events[stack[-1]][3] <= s:
            stack.pop()
        outer = scope[stack[-1]] if stack and e <= events[stack[-1]][3] \
            else None
        scope[i] = innermost_scope(op_name) or outer
        stack.append(i)
    by_scope = dict.fromkeys(SCOPES + (OTHER,), 0)
    other_ops = collections.Counter()
    for i, (name, t) in enumerate(own):
        by_scope[scope[i] or OTHER] += t
        if scope[i] is None:
            other_ops[profile.short_name(name)] += t
    return by_scope, other_ops


def _plane_events(plane, line_names, op_names: bool = False):
    """``(name, op_name, start_ns, end_ns)`` of the events on the named
    lines of one plane; ``op_name`` from the ``OP_NAME_STAT`` of the
    event's metadata when ``op_names``, else ``""``."""
    meta = {}
    if op_names:
        stat = {k for k, m in plane.stat_metadata.items()
                if m.name == OP_NAME_STAT}
        for k, md in plane.event_metadata.items():
            for s in md.stats:
                if s.metadata_id in stat:
                    value = plane.stat_metadata[s.ref_value].name \
                        if s.HasField("ref_value") else s.str_value
                    meta[k] = value.rpartition(":")[0] or value
    out = []
    for line in plane.lines:
        if line.name not in line_names:
            continue
        for ev in line.events:
            t0 = line.timestamp_ns + ev.offset_ps / 1e3
            out.append((plane.event_metadata[ev.metadata_id].name,
                        meta.get(ev.metadata_id, ""), t0,
                        t0 + ev.duration_ps / 1e3))
    return out


def reduce_scopes(path: str) -> dict:
    """Reduce one trace: device seconds by scope over the traced launches
    (``scopes``, with ``other``), the top ops left in ``other``
    (``other_top``), the launches' device time (``launch_ns``, from their
    ``XLA Modules`` events) and count, the serve-GEMM kernel's device time
    (``kernel_ns``, as ``profile.reduce_xplane`` counts it), the host
    annotations of the traced stretch (first to last ``bench.step``) on
    the trace's clock (``spans``, ``(name, start_ns, end_ns)``), and the
    stretch's idle gaps labelled by them (``gaps``, ``(label,
    seconds)``)."""
    space = xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    device, spans = None, []
    for plane in space.planes:
        if device is None and plane.name.startswith("/device:TPU:") \
                and any(ln.name == "XLA Ops" for ln in plane.lines):
            device = plane
        if plane.name.startswith("/host:"):
            spans += [(n, s, e) for n, _, s, e in _plane_events(
                plane, {ln.name for ln in plane.lines})
                if n.startswith(HOST_PREFIXES)]
    if device is None:
        raise ValueError(f"{path}: no TPU plane with an 'XLA Ops' line")
    steps = sorted(s for s in spans if s[0] == "bench.step")
    if not steps:
        raise ValueError(f"{path}: no bench.step annotation")
    lo, hi = steps[0][1], steps[-1][2]
    launches = [(n, s, e) for n, _, s, e in
                _plane_events(device, {"XLA Modules"})
                if s >= lo and e <= hi and n.startswith(profile.LAUNCHES)]
    all_ops = _plane_events(device, {"XLA Ops"}, op_names=True)
    ops = [ev for ev in all_ops
           if any(ms <= ev[2] and ev[3] <= me for _, ms, me in launches)]
    by_scope, other_ops = scope_times(ops)
    kernel_ns = sum(e - s for n, _, s, e in ops
                    if n.split(" = ")[0].startswith(profile.KERNEL_OP + "."))

    busy = profile.union_ns([(max(s, lo), min(e, hi))
                             for _, _, s, e in all_ops if e > lo and s < hi])
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    spans = [sp for sp in spans if sp[2] > lo and sp[1] < hi]
    return {
        "scopes": {k: v / 1e9 for k, v in by_scope.items()},
        "other_top": [[n, t / 1e9] for n, t in other_ops.most_common(3)],
        "launch_ns": sum(e - s for _, s, e in launches),
        "n_launches": len(launches),
        "kernel_ns": kernel_ns,
        "spans": spans,
        "gaps": profile.label_gaps(gaps, spans),
    }
