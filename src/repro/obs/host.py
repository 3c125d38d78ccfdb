"""Host-clock spans and counters: where a replay's host time goes.

Every other span in :mod:`repro.obs` is on the **simulated** clock (a
request's queued → decode tree, a channel's telemetry windows). This
module is their host-clock counterpart: it says which part of the
simulator spent the host's wall time, on ``time.perf_counter_ns``.

It is off by default. Off, :func:`span` hands back one shared null
context manager and :func:`count` returns at once, so the sites in the
program cost a function call each. No site sits inside a per-record,
per-transaction or per-event-loop-iteration loop: the finest runs once
per step, per channel batch or per stream.

On (:func:`enable`), the tracer keeps a stack of open spans and
accumulates, per span name, ``self`` time (the time a span was the
innermost open one), ``total`` time (outermost entry to exit, so a span
nested in itself counts once) and its number of entries; counters add
up. With ``annotate`` set — pass ``jax.profiler.TraceAnnotation`` inside
``jax.profiler.trace`` — every span also opens an annotation of its own
name, so the spans land in the profiler's host plane on the device
trace's clock.

Every name is declared in :data:`DECLARED` with its layer; emitting an
undeclared name while tracing is on raises :class:`UndeclaredName`.
Observation never changes a result (``tests/test_obs_host.py``).
"""
from __future__ import annotations

import functools
import time

SPAN = "span"
COUNTER = "counter"

#: name -> (kind, layer) of every host span and counter the program emits.
DECLARED: dict[str, tuple[str, str]] = {
    "recorder.submit": (SPAN, "recorder + batcher"),
    "recorder.step": (SPAN, "recorder + batcher"),
    "recorder.kv_streams": (SPAN, "recorder + batcher"),
    "recorder.interleave": (SPAN, "recorder + batcher"),
    "recorder.records": (COUNTER, "recorder + batcher"),
    "census": (SPAN, "extent census"),
    "pricing": (SPAN, "analytic pricing"),
    "steps.analytic": (COUNTER, "analytic pricing"),
    "steps.cycle": (COUNTER, "cycle engine"),
    "cycle.run": (SPAN, "cycle engine"),
    "cycle.setup": (SPAN, "cycle engine"),
    "cycle.advance": (SPAN, "cycle engine"),
    "cycle.txns": (COUNTER, "cycle engine"),
    "cycle.iters": (COUNTER, "cycle engine"),
    "cycle.ready_evals": (COUNTER, "cycle engine"),
    "fleet.run": (SPAN, "router / fleet"),
    "build": (SPAN, "build"),
}


class UndeclaredName(KeyError):
    """A span or counter name that :data:`DECLARED` does not hold."""


def _check(name: str, kind: str) -> None:
    if DECLARED.get(name, (None,))[0] != kind:
        raise UndeclaredName(f"host {kind} {name!r} is not declared in "
                             f"repro.obs.host.DECLARED")


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


#: The context manager :func:`span` returns while tracing is off.
NULL_SPAN = _NullSpan()


class _Span:
    """One declared span of one tracer; entering it is reentrant (the
    per-entry state lives on the tracer's stack)."""

    __slots__ = ("tracer", "name", "acc")

    def __init__(self, tracer: "HostTracer", name: str):
        self.tracer = tracer
        self.name = name
        self.acc = [0, 0, 0, 0]           # self_ns, total_ns, n, open

    def __enter__(self):
        t = self.tracer
        now = t._clock()
        stack = t._stack
        if stack:
            stack[-1][0].acc[0] += now - t._last
        t._last = now
        acc = self.acc
        acc[2] += 1
        acc[3] += 1
        ann = None
        if t._annotate is not None:
            ann = t._annotate(self.name)
            ann.__enter__()
        stack.append((self, now, ann))

    def __exit__(self, *exc):
        t = self.tracer
        _, entered, ann = t._stack.pop()
        if ann is not None:
            ann.__exit__(None, None, None)
        now = t._clock()
        acc = self.acc
        acc[0] += now - t._last
        t._last = now
        acc[3] -= 1
        if not acc[3]:
            acc[1] += now - entered
        return False


class HostTracer:
    """Self/total/entry accounting over a stack of host spans."""

    def __init__(self, annotate=None, clock=time.perf_counter_ns):
        self._annotate = annotate
        self._clock = clock
        self._stack: list = []          # (span, enter_ns, annotation)
        self._last = 0
        self._spans: dict[str, _Span] = {}
        self.counters: dict[str, int] = {}

    def span(self, name: str) -> _Span:
        sp = self._spans.get(name)
        if sp is None:
            _check(name, SPAN)
            sp = self._spans[name] = _Span(self, name)
        return sp

    def count(self, name: str, n: int) -> None:
        c = self.counters
        if name not in c:
            _check(name, COUNTER)
            c[name] = 0
        c[name] += n

    def snapshot(self) -> dict:
        """The accounts so far, with every open span charged up to now."""
        now = self._clock()
        extra_self = extra_total = None
        if self._stack:
            extra_self = {self._stack[-1][0].name: now - self._last}
            extra_total = {}
            for sp, entered, _ in self._stack:     # outermost first
                extra_total.setdefault(sp.name, now - entered)
        spans = {}
        for name, sp in self._spans.items():
            self_ns, total_ns, n, _ = sp.acc
            if extra_self:
                self_ns += extra_self.get(name, 0)
                total_ns += extra_total.get(name, 0)
            spans[name] = {"self_s": self_ns * 1e-9,
                           "total_s": total_ns * 1e-9, "n": n}
        return {"spans": spans, "counters": dict(self.counters)}


_active: HostTracer | None = None


def span(name: str):
    """A context manager that times ``name`` while tracing is on."""
    t = _active
    return NULL_SPAN if t is None else t.span(name)


def spanned(name: str):
    """Decorator: the whole call runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            t = _active
            if t is None:
                return fn(*args, **kwargs)
            with t.span(name):
                return fn(*args, **kwargs)
        return call
    return wrap


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` while tracing is on."""
    t = _active
    if t is not None:
        t.count(name, n)


def enable(annotate=None, clock=time.perf_counter_ns) -> None:
    """Start a fresh trace. ``annotate(name)`` (such as
    ``jax.profiler.TraceAnnotation``) is opened around every span;
    ``clock`` returns integer nanoseconds."""
    global _active
    _active = HostTracer(annotate, clock)


def disable() -> dict:
    """Stop tracing; returns the final :func:`snapshot`."""
    global _active
    t, _active = _active, None
    return _empty() if t is None else t.snapshot()


def snapshot() -> dict:
    """``{"spans": {name: {"self_s", "total_s", "n"}}, "counters":
    {name: int}}``; empty while tracing is off."""
    t = _active
    return _empty() if t is None else t.snapshot()


def _empty() -> dict:
    return {"spans": {}, "counters": {}}


def diff(before: dict, after: dict) -> dict:
    """What happened between two snapshots of one trace: spans with time
    or entries in between, counters that moved."""
    spans = {}
    for name, a in after["spans"].items():
        b = before["spans"].get(name, {"self_s": 0.0, "total_s": 0.0,
                                       "n": 0})
        d = {k: a[k] - b[k] for k in ("self_s", "total_s", "n")}
        if d["n"] or d["self_s"] or d["total_s"]:
            spans[name] = d
    counters = {name: v - before["counters"].get(name, 0)
                for name, v in after["counters"].items()}
    return {"spans": spans,
            "counters": {k: v for k, v in counters.items() if v}}


def span_s(snap: dict, name: str, field: str = "self_s") -> float | None:
    """Seconds of ``field`` (``self_s`` or ``total_s``) of span ``name``
    in a snapshot or :func:`diff`; None where the span did not run."""
    _check(name, SPAN)
    s = snap["spans"].get(name)
    return None if s is None else s[field]


def counter(snap: dict, name: str) -> int:
    """Counter ``name`` in a snapshot or :func:`diff` (0 if never
    counted)."""
    _check(name, COUNTER)
    return snap["counters"].get(name, 0)


__all__ = ["DECLARED", "SPAN", "COUNTER", "NULL_SPAN", "UndeclaredName",
           "span", "spanned", "count", "enable", "disable", "snapshot",
           "diff", "span_s", "counter"]
