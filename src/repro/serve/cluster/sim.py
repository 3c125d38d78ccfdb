"""Fleet-scale serving simulation: N replicas behind a router.

:class:`ClusterSim` scales the single-cube replay loop
(:class:`~repro.serve.replay.engine.ReplayEngine`) to a fleet: each
replica owns a :class:`~repro.serve.replay.recorder.ServeTraceRecorder`
(continuous batcher + row-paged KV pool + the shared weight slice) and
its own clock; one fleet-level
:class:`~repro.serve.replay.arrivals.ArrivalProcess` generates requests;
a pluggable :class:`~.router.Router` places (or rejects) each request at
routing time. One shared hybrid :class:`~repro.core.system_sim.SystemSim`
prices every replica's decode steps — replicas are homogeneous cubes,
and the fleet loop **explicitly opts into per-step reset semantics**
(``warm=False`` on every :meth:`~repro.core.system_sim.SystemSim
.run_steps` call): a whole round of steps can then be priced in one
batched, order-free call. Warm cross-step state
(:class:`~repro.core.system_sim.WarmRunState`) would force one
sequential session per replica and serialize the round — for
prefill-heavy studies that need it, run per-cube
``ReplayEngine(warm=True)`` instead (docs/serve_replay.md).

**Clock semantics.** Replica clocks advance independently; the fleet
loop is a conservative round-based discrete-event simulation. Each
iteration either (a) delivers every arrival up to the next-arrival
frontier to the router — so routing decisions always see replica states
no older than one decode step — or (b) steps, in one batched pricing
call, every replica whose next step starts strictly before that
frontier. Causality is therefore respected to within one decode step:
the same granularity at which the single-cube engine batches admissions
(requests landing mid-step wait for the step boundary there too).
Closed-loop completions are replayed into the arrival process in global
(completion time, rid) order, so seeded runs are bit-reproducible — and
``workers`` only parallelizes cycle-path channel sims, which are
bit-identical to their serial runs, so the worker count can never change
a result.

**Why it scales.** Millions of requests are tractable because every
per-step cost the naive N× replication pays is hoisted or batched: the
queue-window features of a whole fleet round are extracted in one
vectorized census (:func:`~repro.core.queue_model.stream_features_many`),
repeated step shapes hit the :class:`~repro.core.queue_model.StepPricer`
signature cache instead of being re-priced, arrival delivery is a
bisect (not a scan) per round, cycle-path channels run in the shared
persistent process pool, and per-request bookkeeping lives in flat
numpy arrays (:class:`ClusterResult`) with recorder-side dicts pruned at
completion.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field

import numpy as np

from ...core.system_sim import SystemSim
from ...obs import host
from ..replay.arrivals import ArrivalProcess, RequestSpec
from ..replay.recorder import (KV_BASE_ADDR, ServeTraceRecorder,
                               make_kv_cache, weight_step_stream)
from .router import Router, make_router

#: replica_of sentinel values
UNROUTED = -1
REJECTED = -2


class RoutedQueue:
    """Per-replica arrival queue, duck-typed as the recorder's
    ``ArrivalProcess``. The fleet router pushes specs in global
    (arrival, rid) order — each push is therefore an append — and the
    recorder pops them with the same bisect-pointer ``due`` discipline
    as the real process. ``on_complete`` is a no-op here: closed-loop
    regeneration belongs to the *fleet* arrival process and is driven
    by :class:`ClusterSim` in deterministic completion order.
    """

    def __init__(self):
        self._q: list[RequestSpec] = []
        self._next = 0
        self.closed = False          # fleet arrivals exhausted

    def push(self, spec: RequestSpec) -> None:
        self._q.append(spec)

    def pending(self) -> int:
        return len(self._q) - self._next

    def due(self, now_ns: float) -> list[RequestSpec]:
        q, lo = self._q, self._next
        hi = bisect.bisect_right(q, now_ns, lo=lo,
                                 key=lambda s: s.arrival_ns)
        if hi == lo:
            return []
        out = q[lo:hi]
        self._next = hi
        if self._next > 4096 and self._next * 2 > len(q):
            del q[:self._next]
            self._next = 0
        return out

    def next_arrival_ns(self) -> float | None:
        if self._next >= len(self._q):
            return None
        return self._q[self._next].arrival_ns

    def on_complete(self, now_ns: float) -> None:
        pass

    def exhausted(self) -> bool:
        return self.closed and self._next >= len(self._q)


class Replica:
    """One serving replica: recorder + routed queue + private clock."""

    def __init__(self, index: int, cache, weight_stream, kv_offset_ns,
                 kv_base_addr, n_slots: int):
        self.index = index
        self.n_slots = n_slots
        self.queue = RoutedQueue()
        self.rec = ServeTraceRecorder(self.queue, cache,
                                      weight_stream=weight_stream,
                                      kv_offset_ns=kv_offset_ns,
                                      kv_base_addr=kv_base_addr)
        self.clock = 0.0
        self.ema_step_ns = 0.0
        #: worst-case KV pages of every routed-but-not-finished request —
        #: the admission currency the least_kv router balances.
        self.outstanding_pages = 0
        self._worst: dict[int, int] = {}
        self.n_steps = 0
        self.n_requests = 0

    def backlog(self) -> int:
        """Requests routed here but not yet admitted to a batch slot."""
        return self.queue.pending() + len(self.rec.batcher.queue)

    def push(self, spec: RequestSpec) -> None:
        worst = self.rec.cache.pages_for(spec.prompt_len
                                         + spec.max_new_tokens)
        self._worst[spec.rid] = worst
        self.outstanding_pages += worst
        self.n_requests += 1
        self.queue.push(spec)

    def next_event_ns(self) -> float | None:
        """Earliest time this replica can run a decode step: now if the
        batcher holds work, else its next routed arrival; None when it
        has nothing at all."""
        if not self.rec.idle():
            return self.clock
        nq = self.queue.next_arrival_ns()
        if nq is None:
            return None
        return max(self.clock, nq)

    def begin_step(self):
        """Advance to the next event and emit that step's trace."""
        t = self.next_event_ns()
        self.clock = t
        self.rec.submit_due(t)
        st = self.rec.step(t)
        assert st is not None, "begin_step called with no runnable work"
        return st

    def finish_step(self, st, dur_ns: float) -> float:
        """Fold the measured duration back: advance the clock, update
        the EMA the SLO router reads, release finished requests' page
        reservations, and prune recorder-side bookkeeping so memory
        stays O(live requests) across million-request sweeps."""
        end = self.clock + dur_ns
        self.clock = end
        self.ema_step_ns = (dur_ns if self.ema_step_ns == 0.0
                            else 0.8 * self.ema_step_ns + 0.2 * dur_ns)
        self.n_steps += 1
        for rid in st.finished:
            self.outstanding_pages -= self._worst.pop(rid)
            self.rec.requests.pop(rid, None)
            self.rec.specs.pop(rid, None)
        self.rec.batcher.completed.clear()
        return end


@dataclass
class ClusterResult:
    """Flat-array fleet outcome: per-request timelines indexed by rid
    (numpy, not per-request objects — a million-request sweep stays a
    few hundred MB of arrays, not millions of dataclasses)."""

    n_replicas: int
    arrival_ns: np.ndarray          # -1 = never issued (closed-loop budget)
    admitted_ns: np.ndarray         # -1 = never admitted
    first_token_ns: np.ndarray
    completed_ns: np.ndarray
    n_out: np.ndarray
    replica_of: np.ndarray          # UNROUTED / REJECTED sentinels
    makespan_ns: float
    steps_total: int
    steps_analytic: int
    bytes_moved: int
    occupancy: float
    requests_per_replica: np.ndarray
    steps_per_replica: np.ndarray
    pricer_stats: dict = field(default_factory=dict)

    @property
    def issued(self) -> int:
        return int((self.arrival_ns >= 0).sum())

    @property
    def completed(self) -> int:
        return int((self.completed_ns >= 0).sum())

    @property
    def rejected(self) -> int:
        return int((self.replica_of == REJECTED).sum())

    @property
    def goodput_rps(self) -> float:
        if self.makespan_ns <= 0:
            return 0.0
        return self.completed / (self.makespan_ns / 1e9)

    @property
    def hybrid_fraction(self) -> float:
        if not self.steps_total:
            return 0.0
        return self.steps_analytic / self.steps_total

    @property
    def ttfts_ns(self) -> np.ndarray:
        m = self.first_token_ns >= 0
        return self.first_token_ns[m] - self.arrival_ns[m]

    @property
    def tpots_ns(self) -> np.ndarray:
        m = (self.completed_ns >= 0) & (self.n_out >= 2)
        return ((self.completed_ns[m] - self.first_token_ns[m])
                / (self.n_out[m] - 1))

    def slo_goodput_rps(self, ttft_slo_ns: float,
                        tpot_slo_ns: float = float("inf")) -> float:
        """Completed-*within-deadline* requests per simulated second —
        the metric the SLO-aware router optimizes (a late token is a
        miss, not a partial credit)."""
        if self.makespan_ns <= 0:
            return 0.0
        done = self.completed_ns >= 0
        ttft = self.first_token_ns - self.arrival_ns
        ok = done & (ttft <= ttft_slo_ns)
        multi = done & (self.n_out >= 2)
        tpot = np.zeros_like(self.completed_ns)
        tpot[multi] = ((self.completed_ns[multi]
                        - self.first_token_ns[multi])
                       / (self.n_out[multi] - 1))
        ok &= ~multi | (tpot <= tpot_slo_ns)
        return float(ok.sum()) / (self.makespan_ns / 1e9)

    def percentiles(self, values: np.ndarray,
                    qs=(50, 95, 99)) -> dict:
        if values.size == 0:
            return {f"p{q}": 0.0 for q in qs}
        return {f"p{q}": round(float(np.percentile(values, q)), 1)
                for q in qs}

    def summary(self) -> dict:
        out = {
            "n_replicas": self.n_replicas,
            "n_requests": self.issued,
            "completed": self.completed,
            "rejected": self.rejected,
            "n_steps": self.steps_total,
            "makespan_ns": round(self.makespan_ns, 1),
            "occupancy": round(self.occupancy, 4),
            "goodput_rps": round(self.goodput_rps, 1),
            "bytes_moved": int(self.bytes_moved),
            "hybrid_fraction": round(self.hybrid_fraction, 4),
            "max_replica_share": round(
                float(self.requests_per_replica.max())
                / max(1, self.issued), 4),
        }
        for name, vals in (("ttft", self.ttfts_ns), ("tpot", self.tpots_ns)):
            for k, v in self.percentiles(vals).items():
                out[f"{name}_{k}_ns"] = v
            out[f"{name}_mean_ns"] = (round(float(vals.mean()), 1)
                                      if vals.size else 0.0)
        if self.pricer_stats:
            out["pricer_hit_rate"] = self.pricer_stats.get("hit_rate", 0.0)
        return out


class ClusterSim:
    """N homogeneous replicas + router + one shared pricing SystemSim.

    Construction mirrors :func:`~repro.serve.replay.engine.build_replay`
    per replica (same policy registry, same scaled weight slice, same KV
    sizing); ``router`` is a registered name or a :class:`~.router
    .Router` instance. ``attach_pricer=True`` (default) routes all step
    pricing through a shared :class:`~repro.core.queue_model.StepPricer`
    signature cache whose stats land in the result.
    """

    @host.spanned("build")
    def __init__(self, workload: str = "deepseek-v3",
                 policy: str = "hbm4_frfcfs",
                 n_replicas: int = 4,
                 router="round_robin",
                 rate_rps: float = 1e5,
                 n_requests: int = 64,
                 kind: str = "poisson",
                 seed: int = 0,
                 length_scale: float = 1 / 32,
                 n_slots: int = 4,
                 n_ops: int = 4,
                 scale: float = 1.0,
                 n_channels: int = 8,
                 sim_mode: str = "hybrid",
                 overhead_ns: float = 0.0,
                 workers: int = 1,
                 mix=None,
                 attach_pricer: bool = True,
                 recheck_every: int = 64,
                 max_steps: int = 20_000_000,
                 keep_sample_streams: int = 0,
                 warm: bool = False,
                 collector=None,
                 **arrival_kw):
        if warm:
            raise NotImplementedError(
                "ClusterSim prices whole fleet rounds in one batched "
                "run_steps call and therefore opts into per-step reset "
                "semantics; warm cross-step state would serialize every "
                "round into per-replica sessions. For warm (prefill-"
                "aware) studies run a per-cube ReplayEngine(warm=True) — "
                "see docs/serve_replay.md.")
        from ...configs.paper_workloads import PAPER_WORKLOADS, SERVING_MIXES
        from ...core.sched.registry import policy_spec
        from ...perfmodel.accelerator import scaled_accelerator
        from ...trace.layergraph import ROW

        spec = policy_spec(policy)
        w = PAPER_WORKLOADS[workload]
        mix = SERVING_MIXES[workload] if mix is None else mix
        acc = scaled_accelerator(spec.family, n_channels=n_channels)
        ws, chain_ns = weight_step_stream(w, acc, n_ops=n_ops, scale=scale)
        w_end = max((r.end for r in ws), default=0)
        kv_base = max(KV_BASE_ADDR, -(-w_end // ROW) * ROW)
        max_tokens = (max(1, round(mix.prompt_max * length_scale))
                      + max(1, round(mix.out_max * length_scale)))
        self.arrivals = ArrivalProcess(kind, rate_rps, n_requests, mix=mix,
                                       length_scale=length_scale, seed=seed,
                                       **arrival_kw)
        self.replicas = [
            Replica(i, make_kv_cache(n_slots, max_tokens), ws, chain_ns,
                    kv_base, n_slots)
            for i in range(n_replicas)]
        self.router: Router = make_router(router)
        self.system: SystemSim = spec.system_sim(n_channels=n_channels,
                                                 mode=sim_mode)
        if attach_pricer:
            self.system.attach_pricer(recheck_every=recheck_every)
        #: optional :class:`repro.obs.ObsCollector` — every replica step
        #: lands as a span event on its replica's track, and the folded
        #: request marks carry the owning replica; a collector-borne
        #: probe also samples the shared system's cycle-path channels.
        self.collector = collector
        if collector is not None and collector.probe is not None:
            self.system.attach_probe(collector.probe)
        self.overhead_ns = overhead_ns
        self.workers = workers
        self.max_steps = max_steps
        self.keep_sample_streams = keep_sample_streams
        self.sample_streams: list = []

    # -- fleet loop ----------------------------------------------------------

    @host.spanned("fleet.run")
    def run(self) -> ClusterResult:
        arr = self.arrivals
        reps = self.replicas
        n = arr.n_requests
        arrival = np.full(n, -1.0)
        admitted = np.full(n, -1.0)
        first_tok = np.full(n, -1.0)
        completed = np.full(n, -1.0)
        n_out = np.zeros(n, np.int64)
        replica_of = np.full(n, UNROUTED, np.int64)
        steps_total = steps_analytic = 0
        bytes_moved = 0

        def route(T: float) -> None:
            for spec in arr.due(T):
                arrival[spec.rid] = spec.arrival_ns
                ri = self.router.place(spec, reps, spec.arrival_ns)
                if ri is None:
                    replica_of[spec.rid] = REJECTED
                    # Closed loop: a rejected user got a fast error and
                    # moves on to their next request after a think time.
                    arr.on_complete(spec.arrival_ns)
                else:
                    replica_of[spec.rid] = ri
                    reps[ri].push(spec)

        while True:
            na = arr.next_arrival_ns()
            live = [(t, i) for i, r in enumerate(reps)
                    if (t := r.next_event_ns()) is not None]
            if not live:
                if na is None:
                    break
                route(na)
                continue
            if na is not None and na <= min(t for t, _ in live):
                # Deliver arrivals before anyone steps past them: the
                # router must never see a replica state from the future.
                route(na)
                continue
            stepping = [i for t, i in live if na is None or t < na]
            traces = [(i, reps[i].begin_step()) for i in stepping]
            # warm=False by contract: rounds mix steps of *different*
            # replicas, so carrying channel state across the batch would
            # couple cubes that share no hardware (module docstring).
            results = self.system.run_steps(
                [st.stream for _, st in traces],
                workers=self.workers,
                starts_ns=[st.start_ns for _, st in traces],
                warm=False)
            completions: list[tuple[float, int]] = []
            for (i, st), res in zip(traces, results):
                dur = res.total_ns + self.overhead_ns
                end = reps[i].finish_step(st, dur)
                if self.collector is not None:
                    self.collector.on_step(st, res, st.start_ns, dur,
                                           replica=i)
                steps_total += 1
                steps_analytic += res.mode == "analytic"
                bytes_moved += res.bytes_moved
                for rid in st.admitted:
                    admitted[rid] = st.start_ns
                for rid in st.active:
                    n_out[rid] += 1
                    if first_tok[rid] < 0:
                        first_tok[rid] = end
                for rid in st.finished:
                    completed[rid] = end
                    completions.append((end, rid))
                if len(self.sample_streams) < self.keep_sample_streams:
                    self.sample_streams.append(st.stream)
            # Deterministic closed-loop regeneration: completions feed
            # the seeded generator in global (time, rid) order no matter
            # which replicas stepped together this round.
            for end, rid in sorted(completions):
                arr.on_complete(end)
            if steps_total > self.max_steps:
                raise RuntimeError(
                    f"cluster exceeded max_steps={self.max_steps}; "
                    f"offered load far beyond fleet capacity?")
        for r in reps:
            r.queue.closed = True
        if self.collector is not None:
            # Per-replica folding: each request's lifecycle marks carry
            # the replica the router placed it on (rejected/unrouted
            # requests fold on replica 0, flagged incomplete).
            for rid in range(n):
                if arrival[rid] < 0:
                    continue
                self.collector.add_request(
                    rid, replica=max(int(replica_of[rid]), 0),
                    arrival_ns=float(arrival[rid]),
                    admitted_ns=float(admitted[rid]),
                    first_token_ns=float(first_tok[rid]),
                    completed_ns=float(completed[rid]),
                    n_out=int(n_out[rid]))

        slot_steps = sum(r.rec.batcher.slot_steps for r in reps)
        busy = sum(r.rec.batcher.busy_slot_steps for r in reps)
        pricer = self.system.pricer
        return ClusterResult(
            n_replicas=len(reps),
            arrival_ns=arrival,
            admitted_ns=admitted,
            first_token_ns=first_tok,
            completed_ns=completed,
            n_out=n_out,
            replica_of=replica_of,
            makespan_ns=float(max((r.clock for r in reps), default=0.0)),
            steps_total=steps_total,
            steps_analytic=steps_analytic,
            bytes_moved=int(bytes_moved),
            occupancy=busy / slot_steps if slot_steps else 0.0,
            requests_per_replica=np.array([r.n_requests for r in reps],
                                          np.int64),
            steps_per_replica=np.array([r.n_steps for r in reps],
                                       np.int64),
            pricer_stats=dict(pricer.stats) if pricer is not None else {},
        )


__all__ = ["ClusterSim", "ClusterResult", "Replica", "RoutedQueue",
           "UNROUTED", "REJECTED"]
