"""repro.obs — time-resolved telemetry, request spans, trace export.

The observability subsystem turns end-of-run scalars into timelines
(docs/observability.md):

``metrics``
    The counter registry: every counts key a scheduler policy may emit,
    with counter-vs-high-water semantics. ``scripts/lint.py`` enforces
    that no policy grows an undeclared key.
``probe``
    :class:`MetricsProbe` — windowed channel telemetry folded from the
    engine's state samples (bus utilization, queue depth, row-hit rate,
    command mix, refresh backlog, write-drain residency). Zero-cost when
    detached; bit-identical results either way.
``spans``
    :class:`ObsCollector` — request/step span trees from serve replays
    and fleet runs (queued → admitted → prefill chunks → decode → done)
    with per-span memory-time attribution.
``export``
    Chrome/Perfetto ``trace_event`` JSON + flat metrics JSONL, plus the
    read-back helpers ``scripts/obs_report.py`` and the round-trip
    tests share.
``demo``
    The one-command equal-pin HBM4-vs-RoMe trace pair
    (examples/obs_trace.py).
``host``
    Host-clock spans and counters: where the simulator spends the
    host's wall time (off by default). Every other span here is on the
    simulated clock.

Attach points: ``SystemSim.attach_probe(probe)`` for raw extent runs,
``build_replay(..., collector=ObsCollector(probe=...))`` for serve
replays, ``ClusterSim(..., collector=...)`` for fleet runs.
"""
from . import host
from .export import (chrome_trace_events, counter_final, counter_series,
                     load_chrome_trace, slices, trace_row_hit_rate,
                     trace_total_bytes, write_chrome_trace,
                     write_metrics_jsonl)
from .metrics import (COUNTER_REGISTRY, WINDOW_FIELDS, MetricSpec,
                      counter_names, is_highwater)
from .probe import ChannelWindow, MetricsProbe, StepSample
from .spans import ObsCollector, Span, StepEvent

__all__ = [
    "MetricsProbe", "ChannelWindow", "StepSample",
    "ObsCollector", "Span", "StepEvent",
    "COUNTER_REGISTRY", "MetricSpec", "WINDOW_FIELDS", "counter_names",
    "is_highwater",
    "chrome_trace_events", "write_chrome_trace", "write_metrics_jsonl",
    "load_chrome_trace", "slices", "counter_series", "counter_final",
    "trace_row_hit_rate", "trace_total_bytes", "host",
]
