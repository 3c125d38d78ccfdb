"""The program's own host spans (``repro.obs.host``) agree with the
harness's wrapper layers in a traced CPU run.

The harness takes no snapshot of the program's tracer, so this test
enables it around ``runner.run(..., trace=True)`` and snapshots it
wherever the window snapshots the wrapper clock: at window open and at
each counted step. Each program layer's self time in that window is then
held against the self time that the wrappers of the same functions
charge to the layer.
"""
import time

import pytest

from _cell import SECONDS
from harness import device, layers, runner
from harness.manifest import Manifest
from repro.obs import host

DECODE = "dsv3-rome-x9.decode-poisson"
FLEET = "dsv3-rome-x9.fleet8-bursty"
#: wrapper layer -> the program spans whose self time makes it up
TWINS = {"recorder + batcher": ("recorder.submit", "recorder.step",
                                "recorder.kv_streams", "recorder.interleave"),
         "extent census": ("census",),
         "analytic pricing": ("pricing",),
         "cycle engine": ("cycle.run", "cycle.setup", "cycle.advance"),
         "router / fleet": ("fleet.run",)}
#: what the window of each cell runs
SPANS = {DECODE: {"recorder.step", "recorder.kv_streams",
                  "recorder.interleave", "census", "pricing", "cycle.run",
                  "cycle.setup", "cycle.advance"},
         FLEET: {"recorder.step", "recorder.kv_streams",
                 "recorder.interleave", "census", "pricing", "fleet.run"}}
COUNTERS = {DECODE: {"recorder.records", "steps.cycle", "cycle.txns",
                     "cycle.iters"},
            FLEET: {"recorder.records", "steps.analytic"}}


@pytest.fixture(scope="module", params=[DECODE, FLEET])
def traced(request):
    jax = device.init_jax()
    cell = Manifest.load().cell(request.param)
    marks = {}
    start, snapshot = layers.LayerClock.start, layers.LayerClock.snapshot

    def start_and_mark(self, t):
        marks["open"] = host.snapshot()
        start(self, t)

    def snapshot_and_mark(self):
        marks["wrapped"] = snapshot(self)
        marks["last"] = host.snapshot()
        return marks["wrapped"]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(layers.LayerClock, "start", start_and_mark)
        mp.setattr(layers.LayerClock, "snapshot", snapshot_and_mark)
        host.enable(annotate=jax.profiler.TraceAnnotation)
        try:
            out = runner.run(cell, 2 ** 31 + 5, SECONDS, True,
                             time.perf_counter(), None, jax=jax)
        finally:
            host.disable()
    assert out["correct"], out["checks"]
    return (request.param, marks["open"],
            host.diff(marks["open"], marks["last"]), marks["wrapped"])


def test_the_window_runs_every_span_and_counter_of_its_cell(traced):
    name, opened, window, _ = traced
    assert host.span_s(opened, "build", "total_s") > 0
    assert SPANS[name] <= set(window["spans"]), \
        SPANS[name] - set(window["spans"])
    assert COUNTERS[name] <= set(window["counters"]), \
        COUNTERS[name] - set(window["counters"])
    assert set(window["spans"]) | set(window["counters"]) <= set(
        host.DECLARED)


def test_program_spans_agree_with_the_wrapper_layers(traced):
    _, _, window, wrapped = traced
    pairs = [(layer, spans) for layer, spans in TWINS.items()
             if layer in wrapped and wrapped[layer] > 0]
    assert len(pairs) >= 3, sorted(wrapped)
    for layer, spans in pairs:
        ours = sum(host.span_s(window, s) or 0.0 for s in spans)
        theirs = wrapped[layer]
        if layer == "router / fleet":
            # Each wrapper books its own entry (the profiler annotation it
            # opens, its Python frames) to the layer it wraps; the
            # program's spans start inside the wrappers, so the fleet
            # loop that calls the recorder, census and pricing carries
            # that cost in its span: never less than the wrapper's
            # reading, about 15 % above it on a CPU.
            assert theirs <= ours <= 1.25 * theirs, (layer, ours, theirs)
        else:
            assert ours == pytest.approx(theirs, rel=0.10), \
                (layer, ours, theirs)
