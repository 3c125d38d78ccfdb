"""Per-layer host self time, for traced runs only.

Each per-layer metric file lists the qualified names of the program
functions whose calls make up its layer. :func:`install` wraps them; a
wrapped call charges the host time it spends to its layer, minus the
time spent inside another wrapped call (self time). Every transition
also opens a ``jax.profiler.TraceAnnotation`` named by the layer, so the
profiler trace can say what the host was doing in a device-idle gap.

A name that no longer resolves raises :class:`LayerError` at install:
a metric never silently reads 0.
"""
from __future__ import annotations

import functools
import importlib
import sys
import time
from collections import defaultdict

#: Host time inside the window outside every listed function.
UNLISTED = "unlisted"


class LayerError(LookupError):
    """A listed program function does not resolve."""


def resolve(qualname: str):
    """``(owner, attr, function)`` for a dotted name: the longest
    importable module prefix, then attribute lookups."""
    parts = qualname.split(".")
    for cut in range(len(parts) - 1, 0, -1):
        try:
            owner = importlib.import_module(".".join(parts[:cut]))
        except ImportError:
            continue
        try:
            for attr in parts[cut:-1]:
                owner = getattr(owner, attr)
            raw = (owner.__dict__[parts[-1]] if isinstance(owner, type)
                   else getattr(owner, parts[-1]))
        except (AttributeError, KeyError) as e:
            raise LayerError(f"{qualname!r} does not resolve: {e}") from e
        if not callable(raw):
            raise LayerError(f"{qualname!r} is not a function")
        return owner, parts[-1], raw
    raise LayerError(f"no module prefix of {qualname!r} imports")


class LayerClock:
    """Exclusive host-time accounting over a stack of wrapped calls."""

    def __init__(self, annotate=None):
        self._annotate = annotate
        self._stack: list[str] = []
        self._spans: list = []
        self._active = False
        self._last = 0.0
        self.self_s: dict[str, float] = defaultdict(float)
        self._patched: list = []

    # -- accounting -----------------------------------------------------------

    def _charge(self, now: float) -> None:
        if self._active:
            top = self._stack[-1] if self._stack else UNLISTED
            self.self_s[top] += now - self._last
        self._last = now

    def start(self, t: float) -> None:
        self._charge(t)
        self._active = True
        self._last = t
        self.self_s.clear()

    def snapshot(self) -> dict:
        self._charge(time.perf_counter())
        return dict(self.self_s)

    def _enter(self, layer: str) -> None:
        self._charge(time.perf_counter())
        span = None
        if self._annotate is not None and (not self._stack
                                           or self._stack[-1] != layer):
            span = self._annotate(layer)
            span.__enter__()
        self._stack.append(layer)
        self._spans.append(span)

    def _exit(self) -> None:
        self._charge(time.perf_counter())
        self._stack.pop()
        span = self._spans.pop()
        if span is not None:
            span.__exit__(None, None, None)

    # -- wrapping -------------------------------------------------------------

    def _wrap(self, layer: str, fn):
        @functools.wraps(fn)
        def timed(*args, **kwargs):
            self._enter(layer)
            try:
                return fn(*args, **kwargs)
            finally:
                self._exit()
        return timed

    def install(self, functions: dict[str, str]) -> None:
        """Wrap every ``qualified name -> layer`` entry. Module-level
        functions are replaced in every loaded ``repro`` module that
        imported them by name, methods on their class."""
        for qualname, layer in sorted(functions.items()):
            owner, attr, raw = resolve(qualname)
            if isinstance(owner, type):
                if isinstance(raw, staticmethod):
                    new = staticmethod(self._wrap(layer, raw.__func__))
                elif isinstance(raw, classmethod):
                    new = classmethod(self._wrap(layer, raw.__func__))
                else:
                    new = self._wrap(layer, raw)
                self._patched.append((owner, attr, raw))
                setattr(owner, attr, new)
                continue
            new = self._wrap(layer, raw)
            for name, mod in list(sys.modules.items()):
                if (name == "repro" or name.startswith("repro.")) and \
                        getattr(mod, attr, None) is raw:
                    self._patched.append((mod, attr, raw))
                    setattr(mod, attr, new)
            if getattr(owner, attr) is raw:
                self._patched.append((owner, attr, raw))
                setattr(owner, attr, new)

    def uninstall(self) -> None:
        while self._patched:
            owner, attr, raw = self._patched.pop()
            setattr(owner, attr, raw)


def layer_functions(readers: dict) -> dict[str, str]:
    """``qualified name -> layer`` from the cell's metric readers; a
    function claimed by two layers is an error."""
    out: dict[str, str] = {}
    for name, mod in sorted(readers.items()):
        for fn in getattr(mod, "FUNCTIONS", ()):
            if out.setdefault(fn, mod.LAYER) != mod.LAYER:
                raise LayerError(f"{fn} is listed by layers {out[fn]!r} "
                                 f"and {mod.LAYER!r}")
    return out
