"""Device busy and idle time from a JAX profiler trace.

The trace is read with ``jax.profiler.ProfileData`` into plain
``(plane, line, name, start_ns, dur_ns)`` events (:func:`events_of`),
and everything after that is plain arithmetic on those events, checked
on a small recorded trace under ``bench/tests/data``:

* busy time is the union of the intervals of the operations on each
  device plane (``/device:...``), clipped to the traced span;
* the traced span is the host annotation :data:`SPAN` that the harness
  opens right after the profiler starts and closes right before it
  stops;
* each idle gap between device events is labelled with the host layer
  that was innermost for most of it.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

#: Host annotation that brackets the traced part of a run.
SPAN = "bench.traced"
#: Device line of single operations; where a plane has it, its other
#: lines (modules, steps) only repeat the same intervals coarser.
OPS_LINE = "XLA Ops"
#: Label of idle time that no layer annotation covers.
UNCOVERED = "unlisted"
TOP = 10


def options():
    """Profiler options: host annotations on, Python call tracing off
    (it would record every simulator call and swamp the trace)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def events_of(trace_dir: str) -> list[tuple]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append((plane.name, line.name, e.name,
                            float(e.start_ns), float(e.duration_ns)))
    return out


def is_device(plane: str) -> bool:
    return plane.startswith("/device:")


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _innermost(spans: list[tuple]) -> list[tuple]:
    """Flatten properly nested host spans into ``(start, end, name)``
    pieces named by the innermost span open at each instant, so an idle
    gap is labelled by the layer doing the work, not the loop around
    it."""
    out, stack, t = [], [], None
    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        t = s if t is None else max(t, s)
        stack.append((e, n))
    while stack:
        end, name = stack.pop()
        if end > t:
            out.append((t, end, name))
            t = end
    return out


def reduce(events: list[tuple], layers: set[str]) -> dict:
    """``busy_s``, ``window_s``, ``idle_share``, ``n_device_planes`` and
    ``breakdown`` (top device ops, longest idle gaps by host layer) of
    the traced span. Busy time is averaged over the device planes."""
    span = [(s, s + d) for p, _, n, s, d in events
            if not is_device(p) and n == SPAN]
    if len(span) != 1:
        raise ValueError(f"expected one {SPAN!r} span, found {len(span)}")
    w0, w1 = span[0]
    dev = defaultdict(list)
    op_time = defaultdict(float)
    with_ops = {p for p, line, *_ in events
                if is_device(p) and line == OPS_LINE}
    for plane, line, name, s, d in events:
        if not is_device(plane) or (plane in with_ops and line != OPS_LINE):
            continue
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            dev[plane].append((a, b))
            op_time[name] += (b - a) * 1e-9
    busy = [_union(iv) for iv in dev.values()]
    n_dev = max(1, len(busy))
    busy_s = sum(b - a for u in busy for a, b in u) * 1e-9 / n_dev
    window_s = (w1 - w0) * 1e-9
    host = _innermost([(s, s + d, n) for p, _, n, s, d in events
                       if not is_device(p) and n in layers])
    gaps = []
    for u in busy or [[]]:
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        cover = defaultdict(float)
        for s, e, n in host:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                cover[n] += ov
        label = max(cover, key=cover.get) if cover else UNCOVERED
        labelled.append([label, (b - a) * 1e-9])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "n_device_planes": len(busy),
        "breakdown": {"device_ops": [[n, t] for n, t in ops],
                      "idle_gaps": labelled},
    }
