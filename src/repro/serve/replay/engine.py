"""Replay recorded serve traffic through SystemSim; fold makespans into
request timelines.

:class:`ReplayEngine` runs the closed loop: at each step it asks the
:class:`~.recorder.ServeTraceRecorder` for the step's multi-tenant
extent stream, simulates it on the configured
:class:`~repro.core.system_sim.SystemSim` — under per-step reset
semantics by default, or carrying channel state across steps with
``warm=True`` (a :meth:`SystemSim.warm_session`; see that docstring for
the contract) — and advances the replay clock by the measured makespan.
Warm replay is the right mode once chunked prefill is on: a prefill
burst can leave channels still draining at the step boundary, and only
a warm session charges that backlog to the next step. Because admission
windows depend on the clock, the recorded trace is *policy-dependent*:
a slower memory system admits later and queues longer, which is exactly
the SLO-level effect RoMe's bandwidth claim has to cash out as.

Step duration = memory makespan + ``overhead_ns``. Weight-read arrival
pacing inside the step already carries the compute/roofline serialization
(``from_layer_ops``), so a memory-bound regime needs no extra compute
term; ``overhead_ns`` models per-step launch/sync cost when wanted.

The result (:class:`ReplayResult`) reports per-request TTFT / TPOT (in
simulated ns, from the folded timelines), their p50/p95/p99, slot
occupancy, and goodput against the offered load.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ...core.system_sim import SystemSim
from ...obs import host
from .recorder import ServeTraceRecorder, StepTrace


@dataclass
class RequestReport:
    """One request's folded timeline (simulated ns)."""

    rid: int
    arrival_ns: float
    prompt_len: int
    max_new_tokens: int
    admitted_ns: float = -1.0
    prefill_done_ns: float = -1.0   # last prompt chunk landed (chunked only)
    first_token_ns: float = -1.0
    completed_ns: float = -1.0
    n_out: int = 0

    @property
    def ttft_ns(self) -> float:
        """Arrival -> first token (queue wait + first decode step)."""
        return self.first_token_ns - self.arrival_ns

    @property
    def tpot_ns(self) -> float | None:
        """Mean time per output token after the first; None for
        single-token outputs."""
        if self.n_out < 2:
            return None
        return (self.completed_ns - self.first_token_ns) / (self.n_out - 1)


@dataclass
class StepSummary:
    index: int
    start_ns: float
    dur_ns: float
    n_active: int
    bytes_moved: int      # MC-granularity bytes the sim moved (overfetch in)
    stream_bytes: int     # request-side bytes of the step's extent stream
    mode: str = "cycle"   # pricing path the SystemSim took for this step
    kind: str = "decode"  # "decode" | "prefill" | "mixed" (StepTrace.kind)


@dataclass
class ReplayResult:
    requests: list[RequestReport]
    steps: list[StepSummary]
    makespan_ns: float
    occupancy: float
    traces: list[StepTrace] = field(default_factory=list)

    @property
    def completed(self) -> int:
        return sum(r.completed_ns >= 0 for r in self.requests)

    @property
    def goodput_rps(self) -> float:
        """Completed requests per simulated second."""
        if self.makespan_ns <= 0:
            return 0.0
        return self.completed / (self.makespan_ns / 1e9)

    @property
    def hybrid_fraction(self) -> float:
        """Fraction of decode steps priced by the queue-window analytic
        model (0.0 for a pure-cycle replay)."""
        if not self.steps:
            return 0.0
        return sum(s.mode == "analytic" for s in self.steps) / len(self.steps)

    @property
    def ttfts_ns(self) -> list[float]:
        return [r.ttft_ns for r in self.requests if r.first_token_ns >= 0]

    @property
    def tpots_ns(self) -> list[float]:
        return [t for r in self.requests
                if (t := r.tpot_ns) is not None]

    def percentiles(self, values: list[float],
                    qs=(50, 95, 99)) -> dict[str, float]:
        if not values:
            return {f"p{q}": 0.0 for q in qs}
        return {f"p{q}": round(float(np.percentile(values, q)), 1)
                for q in qs}

    def summary(self) -> dict:
        """Flat metrics dict (benchmark/baseline currency)."""
        out = {
            "n_requests": len(self.requests),
            "completed": self.completed,
            "n_steps": len(self.steps),
            "makespan_ns": round(self.makespan_ns, 1),
            "occupancy": round(self.occupancy, 4),
            "goodput_rps": round(self.goodput_rps, 1),
            # bytes_moved is what the memory system transferred (MC
            # access granularity) — under RoMe it exceeds stream_bytes
            # by the whole-row rounding of sub-row KV appends (§VII
            # overfetch); stream_bytes is the software-side demand.
            "bytes_moved": int(sum(s.bytes_moved for s in self.steps)),
            "stream_bytes": int(sum(s.stream_bytes for s in self.steps)),
            "hybrid_fraction": round(self.hybrid_fraction, 4),
            "n_prefill_steps": sum(s.kind == "prefill" for s in self.steps),
            "n_mixed_steps": sum(s.kind == "mixed" for s in self.steps),
        }
        for name, vals in (("ttft", self.ttfts_ns), ("tpot", self.tpots_ns)):
            for k, v in self.percentiles(vals).items():
                out[f"{name}_{k}_ns"] = v
            out[f"{name}_mean_ns"] = (round(float(np.mean(vals)), 1)
                                      if vals else 0.0)
        return out


class ReplayEngine:
    """Drive a recorder's decode steps through a SystemSim.

    ``keep_traces=True`` retains every recorded :class:`StepTrace`
    (stream included) on the result — the hook for conservation checks
    and for re-simulating the same trace open-loop under another policy
    via :meth:`SystemSim.run_steps`.

    ``warm=True`` prices the whole replay as one warm cross-step session
    (:meth:`SystemSim.warm_session`): channel state — open rows, queued
    backlog, refresh debt — persists between steps, and any backlog a
    step leaves lands on the next step's duration. Reset (the default)
    remains the cheap decode-only contract.

    ``collector`` attaches a :class:`repro.obs.ObsCollector`: every
    executed step is recorded as a span event on the replay clock and
    the folded request timelines land in the collector at the end — the
    input to the Chrome-trace exporter (docs/observability.md).
    Observation never changes the replay (asserted in tests/test_obs.py).
    """

    def __init__(self, recorder: ServeTraceRecorder, system: SystemSim,
                 overhead_ns: float = 0.0, keep_traces: bool = False,
                 max_steps: int = 100_000, warm: bool = False,
                 collector=None):
        self.recorder = recorder
        self.system = system
        self.overhead_ns = overhead_ns
        self.keep_traces = keep_traces
        self.max_steps = max_steps
        self.warm = warm
        self.collector = collector
        if collector is not None and collector.probe is not None:
            system.attach_probe(collector.probe)

    def run(self) -> ReplayResult:
        rec = self.recorder
        reports: dict[int, RequestReport] = {}
        steps: list[StepSummary] = []
        traces: list[StepTrace] = []
        session = self.system.warm_session() if self.warm else None
        now = 0.0
        while not rec.drained():
            for req in rec.submit_due(now):
                spec = rec.specs[req.rid]
                reports[req.rid] = RequestReport(
                    req.rid, spec.arrival_ns, spec.prompt_len,
                    spec.max_new_tokens)
            st = rec.step(now)
            if st is None:
                nxt = rec.arrivals.next_arrival_ns()
                if nxt is None:
                    break              # nothing queued, nothing to come
                now = max(now, nxt)
                continue
            # start_ns rebases lazily: analytic steps are priced on the
            # recorded stream itself (features are shift-invariant), so
            # the hybrid fast path never copies GB-scale step streams.
            # A warm session never rebases at all — the recorded stream
            # is already on the session's absolute clock.
            if session is not None:
                res = session.step(st.stream, start_ns=now)
            else:
                res = self.system.run(st.stream, start_ns=now)
            dur = res.total_ns + self.overhead_ns
            end = now + dur
            for rid in st.admitted:
                reports[rid].admitted_ns = now
            for rid in st.prefill_done:
                reports[rid].prefill_done_ns = end
            for rid in st.active:
                rep = reports[rid]
                rep.n_out += 1
                if rep.first_token_ns < 0:
                    rep.first_token_ns = end
            for rid in st.finished:
                reports[rid].completed_ns = end
                rec.arrivals.on_complete(end)
            steps.append(StepSummary(st.index, now, dur, len(st.active),
                                     res.bytes_moved,
                                     st.stream.total_bytes,
                                     mode=res.mode, kind=st.kind))
            if self.collector is not None:
                self.collector.on_step(st, res, now, dur)
            if self.keep_traces:
                traces.append(st)
            now = end
            if len(steps) >= self.max_steps:
                raise RuntimeError(
                    f"replay exceeded max_steps={self.max_steps}; "
                    f"offered load too high for the pool/slots?")
        if session is not None:
            session.check()
        result = ReplayResult(
            requests=[reports[rid] for rid in sorted(reports)],
            steps=steps,
            makespan_ns=now,
            occupancy=rec.batcher.occupancy,
            traces=traces)
        if self.collector is not None:
            self.collector.fold_reports(result.requests)
        return result


@host.spanned("build")
def build_replay(workload: str = "deepseek-v3",
                 policy: str = "hbm4_frfcfs",
                 rate_rps: float = 1e5,
                 n_requests: int = 16,
                 kind: str = "poisson",
                 seed: int = 0,
                 length_scale: float = 1 / 32,
                 n_slots: int = 4,
                 n_ops: int = 4,
                 scale: float = 2 ** -15,
                 n_channels: int = 2,
                 keep_traces: bool = False,
                 overhead_ns: float = 0.0,
                 mix=None,
                 sim_mode: str = "cycle",
                 warm: bool = False,
                 prefill_chunk_tokens: int | None = None,
                 prefill_overlap: bool = True,
                 collector=None,
                 **arrival_kw):
    """Wire a complete replay for one (workload, policy, load) cell.

    ``policy`` names a :class:`repro.core.sched.registry.PolicySpec` —
    the registered scheduling point whose family (hbm4/rome) also picks
    the scaled accelerator the weight slice is paced on. Returns
    ``(engine, acc)``; ``engine.run()`` produces the
    :class:`ReplayResult`, ``acc`` is the
    :func:`~repro.perfmodel.accelerator.scaled_accelerator` needed for
    the analytic cross-check (``perfmodel.tpot.stream_mem_ns``).

    The default ``scale`` keeps steps tiny for fast structural tests;
    in that regime HBM4 steps are ACT-issue-bound and sit *outside* the
    analytic model's validity. The band-valid cycle regime
    (benchmarks/serve_trace.py) uses ``scale=2**-12`` — ≈240 KB/step,
    large enough that data transfer hides ACT-command serialization,
    which is what the established 15 % engine_xval band assumes.

    ``scale=1.0`` replays the *unscaled* weight slice — decode steps in
    the tens of GB that would decompose into ~1e9 transactions each.
    That path requires ``sim_mode="hybrid"`` (or ``"analytic"``): the
    queue-window model prices the bulk weight stream in O(n_records),
    and the KV pool base auto-raises past the unscaled slice's end (the
    recorder rejects aliasing layouts otherwise). ``sim_mode`` is passed
    straight to :meth:`PolicySpec.system_sim` as the SystemSim ``mode``.

    ``prefill_chunk_tokens`` turns on chunked prefill (real prefill
    extents through the memory system; see
    :class:`~.recorder.ServeTraceRecorder`), ``prefill_overlap``
    selects packing-prefetch vs prefill-priority stalls, and ``warm``
    prices the replay as one warm cross-step session — the recommended
    trio for prefill studies (benchmarks/serve_trace.py).

    ``collector`` threads a :class:`repro.obs.ObsCollector` into the
    engine; a collector carrying a :class:`~repro.obs.MetricsProbe` also
    attaches it to the SystemSim, turning on windowed channel telemetry
    for every cycle-priced step (examples/obs_trace.py).
    """
    from ...configs.paper_workloads import PAPER_WORKLOADS, SERVING_MIXES
    from ...core.sched.registry import policy_spec
    from ...perfmodel.accelerator import scaled_accelerator
    from ...trace.layergraph import ROW
    from .arrivals import ArrivalProcess
    from .recorder import (KV_BASE_ADDR, ServeTraceRecorder, make_kv_cache,
                           weight_step_stream)

    spec = policy_spec(policy)
    w = PAPER_WORKLOADS[workload]
    mix = SERVING_MIXES[workload] if mix is None else mix
    acc = scaled_accelerator(spec.family, n_channels=n_channels)
    ws, chain_ns = weight_step_stream(w, acc, n_ops=n_ops, scale=scale)
    # An unscaled slice overruns the default KV base; park the pool at
    # the first row past the weights so layouts never alias at any scale.
    w_end = max((r.end for r in ws), default=0)
    kv_base = max(KV_BASE_ADDR, -(-w_end // ROW) * ROW)
    max_tokens = (max(1, round(mix.prompt_max * length_scale))
                  + max(1, round(mix.out_max * length_scale)))
    cache = make_kv_cache(n_slots, max_tokens)
    arrivals = ArrivalProcess(kind, rate_rps, n_requests, mix=mix,
                              length_scale=length_scale, seed=seed,
                              **arrival_kw)
    recorder = ServeTraceRecorder(arrivals, cache, weight_stream=ws,
                                  kv_offset_ns=chain_ns,
                                  kv_base_addr=kv_base,
                                  prefill_chunk_tokens=prefill_chunk_tokens,
                                  prefill_overlap=prefill_overlap)
    system = spec.system_sim(n_channels=n_channels, mode=sim_mode)
    engine = ReplayEngine(recorder, system, overhead_ns=overhead_ns,
                          keep_traces=keep_traces, warm=warm,
                          collector=collector)
    return engine, acc


__all__ = ["ReplayEngine", "ReplayResult", "RequestReport", "StepSummary",
           "build_replay"]
