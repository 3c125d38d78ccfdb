"""One run of one cell: build, warm up, measure, check.

The set-up (imports, device, build, warm-up) ends when the window
opens; the window drives the program's own ``run()``; once it closes the
device peak is read, the program's state is dropped, and the reference
checks the sampled steps.
"""
from __future__ import annotations

import gc
import json
import shutil
import time
from contextlib import nullcontext
from types import SimpleNamespace

from . import cells, layers, profile, reference
from .device import describe, peak_bytes
from .manifest import BENCH_DIR, Cell
from .window import Window, WindowClosed, tail

#: Where a traced run writes its profile, inside the checkout; removed
#: once reduced.
TRACE_DIR = BENCH_DIR / ".traces"


def load_limits() -> dict:
    with open(BENCH_DIR / "checks.json") as f:
        return json.load(f)


def _end_to_end(cell: Cell, win: Window, setup_s: float) -> dict:
    values = {"setup_s": setup_s,
              "sim_steps_per_s": win.sim_steps_per_s()}
    if any(m.name == "step_host_ms_p95" for m in cell.end_to_end):
        values["step_host_ms_p95"] = tail(win.step_host_ms())
    if any(m.name == "round_host_ms_p95" for m in cell.end_to_end):
        values["round_host_ms_p95"] = tail(win.round_host_ms())
    out = {}
    for m in cell.end_to_end:
        if m.name not in values:
            raise KeyError(f"the harness takes no end-to-end metric "
                           f"{m.name!r}")
        out[m.name] = {"value": values[m.name], "unit": m.unit}
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices: list | None, jax=None, control: str | None = None,
        report: bool = True) -> dict:
    """The result line's fields plus ``checks``; ``devices`` None (tests
    on the CPU) skips the device metrics. ``control="float32"`` adds,
    under ``control``, the readings and the verdict of the reference
    computed in float32 in the program's place (``bench/control.py``).
    ``report=False`` skips the metrics (readings only)."""
    import repro.core.queue_model  # noqa: F401  (bind names before wrapping)
    import repro.serve.cluster.sim  # noqa: F401
    import repro.serve.replay  # noqa: F401

    traffic, config = cell.traffic, cell.config
    limits = load_limits()
    clock = None
    if trace:
        clock = layers.LayerClock(annotate=jax.profiler.TraceAnnotation
                                  if jax is not None else None)
        clock.install(layers.layer_functions(cell.readers))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR),
                                 profiler_options=profile.options())
    span = (jax.profiler.TraceAnnotation(profile.SPAN) if trace
            else nullcontext())
    try:
        with span:
            sim, feed, system = cells.build(config, traffic, seed)
            fleet = traffic["engine"] == "fleet"
            win = Window(seconds, traffic["warmup_steps"],
                         traffic["sample_steps"], seed, feed, fleet,
                         clock=clock,
                         pricer=system.pricer if trace else None,
                         until_full=(int(traffic["n_slots"])
                                     if traffic.get("warmup_until_full")
                                     else 0))
            sim.collector = win
            try:
                sim.run()
            except WindowClosed:
                pass
            else:
                raise RuntimeError("the request stream drained before the "
                                   "window closed; lengthen the traffic")
        prof = None
        if trace:
            jax.profiler.stop_trace()
            names = {m.LAYER for m in cell.readers.values()}
            prof = profile.reduce(profile.events_of(str(TRACE_DIR)), names)
    finally:
        if clock is not None:
            clock.uninstall()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    setup_s = win.t0 - t_start
    device = (describe(devices) | {"memory_peak_bytes": peak_bytes(devices)}
              if devices else None)
    ctx = SimpleNamespace(
        steps=win.n_steps, rounds=win.n_rounds,
        analytic_steps=win.n_analytic, cycle_txns=win.cycle_txns,
        self_s=win.clock_at_last or {},
        pricer_open=win.pricer_at_open, pricer_last=win.pricer_at_last,
        profile=prof)
    metrics = ({} if not report else _per_layer(cell, ctx) if trace
               else _end_to_end(cell, win, setup_s))
    steps = win.checked_steps()
    log, reqs = win.log, feed.reqs
    del sim, system, feed, win
    gc.collect()
    t_ref = time.perf_counter()
    readings = reference.check(config, traffic, reqs, log, steps)
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in readings.items()}
    failed = count_failed(readings, limits)
    ctrl = None
    if control == "float32":
        low = reference.check(config, traffic, reqs, log, steps,
                              answers="control")
        n = count_failed(low, limits)
        ctrl = {"readings": low, "failed": n, "correct": n == 0}
    elif control is not None:
        raise ValueError(f"control {control!r} is not float32")
    out = {"correct": failed == 0,
           "attempted": ctx.steps,
           "failed": failed,
           "metrics": metrics,
           "device": device,
           "reference_s": time.perf_counter() - t_ref,
           "checked_steps": len(steps)}
    if trace and device is not None:
        device["busy_s"] = prof["busy_s"]
        device["window_s"] = prof["window_s"]
        out["breakdown"] = prof["breakdown"]
    if ctrl is not None:
        out["control"] = ctrl
    out["checks"] = checks
    return out


def count_failed(readings: dict, limits: dict) -> int:
    """The numbers over their limits: the run is correct when none is."""
    return sum(v > limits[k] for k, v in readings.items())


def _per_layer(cell: Cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        v = cell.readers[m.name].read(ctx)
        if v is not None:
            out[m.name] = {"value": v, "unit": m.unit}
    return out
