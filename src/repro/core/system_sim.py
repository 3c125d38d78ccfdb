"""Multi-channel system simulator: timed extent streams end to end.

:class:`SystemSim` closes the gap between the single-channel cycle-level
engine and the extent-level analytic model. Its primary entry point is
:meth:`SystemSim.run`, which takes an
:class:`repro.workloads.ExtentStream` — the unified workload currency —
and decomposes every record through
:class:`~repro.core.address_map.AddressMap` into per-channel transaction
streams, honouring each record's kind (read/write), arrival time, and
stream tag (channel selection by stripe rotation; the channel-local
layout is the bandwidth-maximizing map the calibration uses — bg_striped
columns for HBM4, VBA-striped rows for RoMe). Every loaded channel runs
through :class:`~repro.core.sched.ChannelSimCore`; the result reports
per-channel finish times, aggregate bandwidth, and the measured
load-balance ratio. That gives both ``analytic.transfer_time_ns`` and
the TPOT model (``perfmodel.tpot.stream_mem_ns``) a ground-truth
cross-validation path at the extent level (tests/test_core_memory.py,
benchmarks/engine_xval.py). :meth:`run_extents` survives as a thin
wrapper that lifts a homogeneous (addr, nbytes) list into a one-kind
stream.

Channels are independent after address decomposition (no shared resource
is modeled between channels), so they compose by taking the max finish —
exactly the "most-loaded channel gates completion" structure the
analytic model assumes, but measured. That independence also makes the
simulation embarrassingly parallel: ``run(stream, workers=N)`` farms
channels out to a process pool, which is what makes full-cube (32–36
channel) cycle-level runs practical. :meth:`SystemSim.run_steps` extends
that to serving traces: a list of per-step streams simulated either
under per-step **reset** semantics (the default — each step starts on an
idle system, parallel over (step, channel) pairs) or, with
``warm=True``, as one :class:`WarmRunState` session that carries channel
state (open rows, queues, refresh debt) across steps — the contract
chunked-prefill replays need once steps overlap (see the
:meth:`run_steps` docstring and docs/serve_replay.md).
"""
from __future__ import annotations

import inspect
from dataclasses import dataclass, field

import numpy as np

from ..obs import host
from ..workloads.stream import ExtentRecord, ExtentStream
from .address_map import AddressMap, make_address_map
from .pool import get_pool
from .sched import SimResult, Txn, make_channel_sim
from .sched.channels import CHANNEL_SIM_KINDS
from .sched.traces import hbm4_unit_location, rome_unit_location
from .sched.vectorized import advance_states, run_channels
from .timing import MemSystemConfig

MODES = ("cycle", "analytic", "hybrid")

#: Fraction of the above-threshold queue pressure a warm session carries
#: into the next analytically priced step (see :class:`WarmRunState`):
#: the backlog left at a step boundary is at most the over-threshold
#: excess, and it decays geometrically as later steps absorb it.
WARM_CARRY_FRAC = 0.5


@dataclass
class SystemResult:
    """Outcome of one multi-channel extent-level run."""

    total_ns: float                 # makespan = max finish over channels
    bytes_moved: int                # sum of per-channel bytes (MC granularity)
    channel_bytes: np.ndarray       # bytes per channel (MC granularity)
    channel_finish_ns: np.ndarray   # per-channel makespan (0 for idle)
    channel_results: dict           # channel -> SimResult (loaded channels)
    #: channel -> the exact txn list the channel sim ran, in the input
    #: order its SimResult.finish_ns indexes — so per-txn attribution
    #: (e.g. read latency) never depends on re-running decompose().
    #: Empty for analytically priced runs (no txns are materialized).
    channel_txns: dict = field(default_factory=dict)
    #: how this run was priced: "cycle" (event loop) or "analytic"
    #: (queue-window model) — a hybrid SystemSim stamps each run with
    #: the path it actually took.
    mode: str = "cycle"
    #: modeled queue pressure (queue-window correction / roofline floor);
    #: 0.0 when the classifier did not run (pure cycle mode).
    queue_pressure: float = 0.0

    @property
    def bandwidth_gbps(self) -> float:
        if self.total_ns <= 0:
            return 0.0
        return self.bytes_moved / self.total_ns   # B/ns == GB/s

    @property
    def load_balance_ratio(self) -> float:
        """Measured LBR = mean / max channel bytes (cf. Fig 13)."""
        mx = self.channel_bytes.max(initial=0)
        if mx == 0:
            return 1.0
        return float(self.channel_bytes.mean() / mx)

    @property
    def cmd_counts(self) -> dict:
        out: dict = {}
        for r in self.channel_results.values():
            for k, v in r.cmd_counts.items():
                out[k] = out.get(k, 0) + v
        return out

    @property
    def row_hit_rate(self) -> float:
        """System-wide row-buffer hit rate, ``(RD+WR hits) / column
        commands`` over the summed per-channel command counts
        (:func:`repro.core.sched.counts_row_hit_rate`). 0.0 for
        row-granular (always-precharge) controllers — RoMe has no row
        buffer to hit — and 0.0 on analytically priced runs, which issue
        no commands (``channel_results`` is empty there; check
        :attr:`mode` before reading locality off a hybrid run)."""
        from .sched import counts_row_hit_rate
        return counts_row_hit_rate(self.cmd_counts)


def _run_channel(kind: str, kwargs: dict, txns: list[Txn]) -> SimResult:
    """Simulate one channel — module-level so a process pool can pickle
    the call. Reconstructs the channel sim from its factory spec."""
    return make_channel_sim(kind, **kwargs).run(txns)


class SystemSim:
    """N independent channel sims behind one address map.

    Parameters mirror the single-channel sims; ``n_channels`` (or an
    explicit ``amap``) sets the system width — pass a small count to keep
    serial cycle-level runs tractable, or ``workers=N`` to
    :meth:`run` for full-width systems; the per-channel behaviour is
    identical either way. ``max_ref_postpone`` defaults to 32 (the
    *well-tuned* pooled-refresh MC that the analytic calibration models).

    ``mode`` selects the pricing engine:

    * ``"cycle"`` (default) — every run goes through the per-channel
      event loops (the lockstep vectorized advance in-process, a process
      pool with ``workers > 1``). Ground truth.
    * ``"analytic"`` — every run is priced by the calibrated
      queue-window model (:mod:`repro.core.queue_model`): roofline floor
      plus the fitted per-step/per-txn corrections, O(n_records), no
      transactions materialized. Trustworthy at low queue pressure.
    * ``"hybrid"`` — each run/step is classified by its modeled queue
      pressure: uncontended ones (pressure <= ``pressure_threshold``,
      defaulting to the policy's own *calibrated* cut from the
      queue-window table) are priced analytically, contended ones drop
      into the cycle engine. Runs whose decomposed transaction count would exceed
      ``max_cycle_txns`` are *always* priced analytically — that guard
      is what makes unscaled production traces (GB-scale steps that
      would decompose into millions of transactions) runnable at all.

    ``policy_name`` names the registered :class:`~.sched.PolicySpec`
    whose persisted queue-window calibration the analytic path uses
    (``PolicySpec.system_sim`` threads it automatically); without it the
    family's default point is assumed (``hbm4_frfcfs`` / ``hbm4_closed``
    by page policy, ``rome_qd2``).

    ``check_timing=True`` turns on sanitizer mode: every cycle-path
    channel run emits its command trace and is replayed through the
    independent :mod:`repro.analysis.timing_checker`; any JEDEC/Table III
    protocol violation raises :class:`~repro.analysis.TimingProtocolError`
    (docs/timing_sanitizer.md). Analytically priced runs issue no
    commands, so there is nothing to check on that path.
    """

    def __init__(self, cfg: MemSystemConfig,
                 amap: AddressMap | None = None,
                 n_channels: int | None = None,
                 queue_depth: int | None = None,
                 refresh: bool = True,
                 max_ref_postpone: int = 32,
                 page_policy: str = "open",
                 channel_kind: str | None = None,
                 channel_kwargs: dict | None = None,
                 sids: int = 1,
                 sid_capacity_bytes: int = 64 << 20,
                 mode: str = "cycle",
                 pressure_threshold: float | None = None,
                 max_cycle_txns: int = 500_000,
                 policy_name: str | None = None,
                 check_timing: bool = False):
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        self.mode = mode
        self.check_timing = check_timing
        self.max_cycle_txns = max_cycle_txns
        self.policy_name = policy_name
        # None -> the policy's own calibrated cut (resolved lazily with
        # the queue-window params; see QueueWindowParams.pressure_threshold).
        self.pressure_threshold = pressure_threshold
        self._eff = None               # lazy ChannelEfficiency cache
        self._qparams = None           # lazy QueueWindowParams cache
        #: optional :class:`repro.core.queue_model.StepPricer` — when
        #: attached, every feature extraction goes through its signature
        #: memo cache (see :meth:`attach_pricer`).
        self.pricer = None
        #: optional :class:`repro.obs.MetricsProbe` — when attached (see
        #: :meth:`attach_probe`), cycle-path channel sims sample windowed
        #: telemetry and every run/step result is folded into the probe.
        self.probe = None
        self.cfg = cfg
        self.is_rome = cfg.ag_mc_bytes >= cfg.row_bytes
        if channel_kind is not None:
            # The decomposition granularity is set by cfg; a channel kind
            # of the other family would silently mis-shape every txn.
            if (channel_kind == "rome") != self.is_rome:
                raise ValueError(
                    f"channel_kind {channel_kind!r} does not match the "
                    f"{'rome' if self.is_rome else 'hbm4'}-granularity cfg "
                    f"{cfg.name!r}")
        self.channel_kind = channel_kind
        self.channel_kwargs = dict(channel_kwargs or {})
        if sids < 1:
            raise ValueError(f"sids must be >= 1, got {sids}")
        self.sids = sids
        self.sid_capacity_bytes = sid_capacity_bytes
        if amap is None:
            amap = make_address_map(cfg, n_cubes=1)
            if n_channels is not None:
                amap = AddressMap(n_channels=n_channels,
                                  stripe_bytes=amap.stripe_bytes,
                                  banks_per_channel=amap.banks_per_channel,
                                  row_bytes=amap.row_bytes)
        elif n_channels is not None and n_channels != amap.n_channels:
            raise ValueError("pass either amap or n_channels, not both")
        self.amap = amap
        self.queue_depth = (cfg.request_queue_depth if queue_depth is None
                            else queue_depth)
        self.refresh = refresh
        self.max_ref_postpone = max_ref_postpone
        self.page_policy = page_policy

    # -- decomposition -----------------------------------------------------

    def _units_of(self, addr: int, nbytes: int) -> range:
        """Global stripe-unit indices touched by one extent (an extent
        touching any byte of a unit transfers the whole unit — the MC
        access granularity / row-rounding overfetch)."""
        g = self.amap.stripe_bytes
        return range(addr // g, (addr + nbytes - 1) // g + 1)

    @host.spanned("census")
    def decompose(self, stream: ExtentStream) -> dict[int, list[Txn]]:
        """Per-channel transaction streams for a timed extent stream.

        Each record's units inherit its arrival time, read/write kind,
        and stream tag. Channel selection follows the address map's
        stripe rotation; the channel-local (bank, row, col) placement of
        a unit is a pure function of its channel-local unit index, so
        overlapping extents hit the same locations and contiguous
        extents reproduce the calibration stream on every loaded
        channel. Records are walked in stream (issue) order, so a stream
        sorted by arrival yields arrival-ordered per-channel queues.
        """
        nch = self.amap.n_channels
        geo = self.cfg.geometry.channel
        n_vbas = self.cfg.vbas_per_channel
        per_channel: dict[int, list[Txn]] = {}
        for rec in stream:
            # SID (stack level) from the address region: tenants/buffers
            # in different stack levels exercise the cross-SID (tCCDR /
            # tX2XR) timing paths. sids=1 (the default) keeps every txn
            # on SID 0 — bit-identical to the pre-SID decomposition.
            sid = ((rec.addr // self.sid_capacity_bytes) % self.sids
                   if self.sids > 1 else 0)
            for unit in self._units_of(rec.addr, rec.nbytes):
                c = unit % nch
                u = unit // nch                # channel-local unit index
                if self.is_rome:
                    bank, row, col = rome_unit_location(u, n_vbas)
                else:
                    # bg_striped: the §VI-A bandwidth-maximizing map — the
                    # same one the calibration streams use.
                    bank, row, col = hbm4_unit_location(u, geo)
                per_channel.setdefault(c, []).append(
                    Txn(rec.arrival_ns, bank=bank, row=row, col=col,
                        is_write=rec.is_write, sid=sid,
                        stream=rec.stream_id))
        return per_channel

    def _sim_spec(self) -> tuple[str, dict]:
        """(kind, kwargs) for ``make_channel_sim`` — picklable, so worker
        processes can rebuild the exact channel sim.

        The sims must see the same ChannelGeometry the decomposition
        used, or bank ids and timing would silently desynchronize.
        ``channel_kwargs`` keys the selected channel-sim class does not
        accept raise immediately — a typo'd knob (``quue_depth=2``)
        must never be silently ignored."""
        geo = self.cfg.geometry.channel
        common = dict(geometry=geo, queue_depth=self.queue_depth,
                      refresh=self.refresh,
                      max_ref_postpone=self.max_ref_postpone)
        if self.check_timing:
            common["emit_trace"] = True
        if self.probe is not None:
            common["sample_window_ns"] = self.probe.window_ns
        if self.is_rome:
            common |= {"n_vbas": self.cfg.vbas_per_channel}
        kind = self.channel_kind
        if kind is None:
            if self.is_rome:
                kind = "rome"
            else:
                kind = "hbm4" if self.page_policy == "open" else "hbm4_closed"
        allowed = set(inspect.signature(
            CHANNEL_SIM_KINDS[kind].__init__).parameters) - {"self"}
        unknown = set(self.channel_kwargs) - allowed
        if unknown:
            raise ValueError(
                f"unknown channel_kwargs {sorted(unknown)} for channel kind "
                f"{kind!r}; accepted keys: {sorted(allowed)}")
        # Registered per-policy kwargs (queue_depth, watermarks, variant,
        # ...) win over the SystemSim-level defaults.
        return kind, common | self.channel_kwargs

    def _make_sim(self):
        kind, kwargs = self._sim_spec()
        return make_channel_sim(kind, **kwargs)

    def _sanitize(self, results: "dict[int, SimResult]",
                  step: int | None = None) -> None:
        """Sanitizer mode: replay every loaded channel's command trace
        through the independent :mod:`repro.analysis.timing_checker` and
        raise :class:`~repro.analysis.TimingProtocolError` on the first
        run with any protocol violation. Lazy import — repro.analysis
        depends on repro.core, not the other way around."""
        from ..analysis.timing_checker import (TimingProtocolError,
                                               check_sim_result)
        sim = self._make_sim()
        agg = None
        tag = "" if step is None else f"step {step} "
        for c, r in sorted(results.items()):
            rep = check_sim_result(sim, r, f"{tag}channel {c}")
            if not rep.ok:
                if agg is None:
                    agg = rep
                else:
                    agg.merge(rep)
        if agg is not None:
            raise TimingProtocolError(agg)

    # -- analytic pricing / hybrid classification --------------------------

    def _queue_params(self):
        """The queue-window calibration for this scheduling point
        (explicit ``policy_name`` when threaded from a ``PolicySpec``,
        else the family default)."""
        if self._qparams is None:
            from .queue_model import queue_window_params
            name = self.policy_name
            if name is None:
                kind, _ = self._sim_spec()
                name = {"hbm4": "hbm4_frfcfs", "hbm4_closed": "hbm4_closed",
                        "hbm4_writedrain": "hbm4_writedrain",
                        "hbm4_sidgroup": "hbm4_sidgroup",
                        "rome": "rome_qd2"}[kind]
            self._qparams = queue_window_params(name)
        return self._qparams

    def attach_pricer(self, maxsize: int = 65536, recheck_every: int = 64):
        """Create (or return) this sim's :class:`~repro.core.queue_model
        .StepPricer`: a bounded LRU over step-pricing features keyed on
        an exact stream-shape signature, with sampled hit re-pricing as
        a correctness guard. Decode steps from continuous batching are
        highly repetitive, so the fleet paths attach one pricer per
        cluster and skip re-pricing the repeats."""
        if self.pricer is None:
            from .analytic import calibrate
            from .queue_model import StepPricer
            if self._eff is None:
                self._eff = calibrate(self.cfg)
            self.pricer = StepPricer(self.cfg, self.amap,
                                     self._queue_params(), eff=self._eff,
                                     maxsize=maxsize,
                                     recheck_every=recheck_every)
        return self.pricer

    def attach_probe(self, probe):
        """Attach a :class:`repro.obs.MetricsProbe`: cycle-path channel
        sims start sampling windowed telemetry (``sample_window_ns``
        threads through :meth:`_sim_spec`), and every
        :class:`SystemResult` produced by :meth:`run` / :meth:`run_steps`
        / a warm session is folded into the probe. The probe inherits
        this config's per-channel bus bandwidth as its utilization
        denominator unless it already has one. Pass ``None`` to detach.
        Telemetry never alters simulated results — asserted bit-identical
        in tests/test_obs.py."""
        if probe is not None and getattr(probe, "channel_bw_gbps",
                                         None) is None:
            probe.channel_bw_gbps = self.cfg.channel_bw_gbps
        self.probe = probe
        return probe

    def _features(self, stream: ExtentStream) -> dict:
        return self._features_many([stream])[0]

    def _features_many(self, streams) -> "list[dict]":
        if self.pricer is not None:
            return self.pricer.features_many(streams)
        from .analytic import calibrate
        from .queue_model import stream_features_many
        if self._eff is None:
            self._eff = calibrate(self.cfg)
        return stream_features_many(streams, self.cfg, self.amap,
                                    eff=self._eff)

    def _pressure(self, feats: dict) -> float:
        floor = max(feats["base_ns"], feats["span_ns"])
        if floor <= 0.0:
            return 0.0
        extra = self._queue_params().predict_extra_ns(
            feats["txns_gating"], feats["fine_txns_gating"],
            feats["ext_gating"])
        return extra / floor

    def _threshold(self) -> float:
        """The classification cut: an explicit ``pressure_threshold``
        wins; otherwise the policy's own calibrated threshold."""
        if self.pressure_threshold is not None:
            return self.pressure_threshold
        return self._queue_params().pressure_threshold

    def _use_cycle(self, feats: dict, pressure: float) -> bool:
        """Hybrid classification: contended windows go to the cycle
        engine — unless their decomposed transaction count would blow the
        cycle budget, in which case analytic pricing is the only option
        that keeps unscaled traces runnable."""
        return (pressure > self._threshold()
                and feats["total_txns"] <= self.max_cycle_txns)

    def _analytic_result(self, feats: dict, pressure: float) -> SystemResult:
        """Price one stream with the queue-window model. Byte accounting
        matches the cycle engine exactly (both move whole stripe units);
        per-channel finish times spread the makespan proportionally to
        channel load, with the gating channel defining the makespan."""
        floor = max(feats["base_ns"], feats["span_ns"])
        total = floor + self._queue_params().predict_extra_ns(
            feats["txns_gating"], feats["fine_txns_gating"],
            feats["ext_gating"])
        ch_bytes = feats["mc_channel_bytes"].astype(np.int64)
        mx = ch_bytes.max(initial=0)
        if mx == 0:
            total, ch_finish = 0.0, np.zeros(self.amap.n_channels)
        else:
            ch_finish = total * (ch_bytes / mx)
        return SystemResult(
            total_ns=float(total),
            bytes_moved=int(ch_bytes.sum()),
            channel_bytes=ch_bytes,
            channel_finish_ns=ch_finish,
            channel_results={},
            channel_txns={},
            mode="analytic",
            queue_pressure=pressure,
        )

    # -- run ---------------------------------------------------------------

    @host.spanned("pricing")
    def run(self, stream: ExtentStream, workers: int = 1,
            start_ns: float | None = None) -> SystemResult:
        """Simulate or price a timed extent stream on all loaded
        channels; idle channels cost nothing. The pricing engine follows
        this sim's ``mode``: ``"cycle"`` always runs the event loops,
        ``"analytic"`` always uses the queue-window model, ``"hybrid"``
        classifies by modeled queue pressure (see the class docstring).
        ``workers > 1`` simulates cycle-path channels in the shared
        persistent process pool (:mod:`repro.core.pool`; channels share
        no modeled resource, so serial and parallel runs are identical —
        asserted in tests/test_core_memory); in-process, channels
        advance in lockstep via the vectorized driver, which is
        bit-identical to per-channel loops. Returns the system-level
        :class:`SystemResult`, stamped with the path taken.

        ``start_ns`` rebases the stream's arrivals to that clock value
        (equivalent to ``run(stream.shifted(-start_ns))``) — but
        *lazily*: every queue-model feature is shift-invariant, so an
        analytically priced run never materializes the shifted copy.
        That is the fleet fast path: a replay engine passes its clock
        instead of shifting GB-scale step streams it will never cycle-
        simulate."""
        if self.mode != "cycle":
            feats = self._features(stream)
            pressure = self._pressure(feats)
            if self.mode == "analytic" or not self._use_cycle(feats,
                                                              pressure):
                res = self._analytic_result(feats, pressure)
            else:
                res = self._run_cycle(self._rebase(stream, start_ns),
                                      workers, pressure=pressure)
        else:
            res = self._run_cycle(self._rebase(stream, start_ns), workers)
        host.count("steps.analytic" if res.mode == "analytic"
                   else "steps.cycle")
        if self.probe is not None:
            # Cycle-path telemetry clocks are relative to the rebased
            # stream; t0 places the windows back on the caller's clock.
            self.probe.observe_run(res, t0=float(start_ns or 0.0))
        return res

    @staticmethod
    def _rebase(stream: ExtentStream,
                start_ns: float | None) -> ExtentStream:
        if start_ns is None or not start_ns:
            return stream
        return stream.shifted(-start_ns)

    @host.spanned("cycle.run")
    def _run_cycle(self, stream: ExtentStream, workers: int = 1,
                   pressure: float = 0.0) -> SystemResult:
        per_channel = self.decompose(stream)
        items = sorted(per_channel.items())
        results: dict[int, SimResult] = {}
        kind, kwargs = self._sim_spec()
        if workers > 1 and len(items) > 1:
            # Spawn, not fork: the caller's process often has JAX's thread
            # pool alive (fork would risk deadlock). The pool is the
            # process-wide persistent one — interpreter start-up is paid
            # once per process, not once per call.
            pool = get_pool(workers)
            futures = [(c, pool.submit(_run_channel, kind, kwargs, txns))
                       for c, txns in items]
            for c, fut in futures:
                results[c] = fut.result()
        elif items:
            sims = run_channels(kind, kwargs, [txns for _, txns in items])
            results = {c: r for (c, _), r in zip(items, sims)}
        if self.check_timing:
            self._sanitize(results)

        nch = self.amap.n_channels
        ch_bytes = np.zeros(nch, dtype=np.int64)
        ch_finish = np.zeros(nch)
        for c, r in results.items():
            ch_bytes[c] = r.bytes_moved
            ch_finish[c] = r.total_ns
        return SystemResult(
            total_ns=float(ch_finish.max(initial=0.0)),
            bytes_moved=int(ch_bytes.sum()),
            channel_bytes=ch_bytes,
            channel_finish_ns=ch_finish,
            channel_results=results,
            channel_txns=dict(items),
            queue_pressure=pressure,
        )

    def warm_session(self) -> "WarmRunState":
        """Open a warm cross-step session: a :class:`WarmRunState` whose
        per-channel event-loop states persist across :meth:`WarmRunState
        .step` calls (open rows, queues, refresh debt, absolute clock).
        See :meth:`run_steps` for the warm-vs-reset contract."""
        return WarmRunState(self)

    @host.spanned("pricing")
    def run_steps(self, streams: "list[ExtentStream]",
                  workers: int = 1,
                  starts_ns: "list[float] | None" = None,
                  warm: bool = False) -> "list[SystemResult]":
        """Simulate a sequence of per-step streams (one serving step
        each) under one of two cross-step contracts:

        **Reset semantics** (``warm=False``, the default): every step
        starts on an idle memory system — no row-buffer, queue, or
        refresh-debt state carries over from the previous step. For
        decode-only replays that is a good model: decode steps are
        separated by kernel-launch/compute gaps long enough (µs at real
        scale) that open rows are precharged by refresh rotation and
        queues drain; what *is* simulated is all intra-step contention
        between tenants. Each stream's arrivals are rebased to its step
        start — the matching entry of ``starts_ns`` when given (pass
        each recorded step's ``StepTrace.start_ns`` to reproduce a
        replay engine's durations exactly, idle lead-in included), else
        the stream's earliest arrival. A step's makespan is then
        directly its duration. Because steps share no simulated state,
        ``workers > 1`` farms (step, channel) sims out to one process
        pool — the batched path for re-simulating a recorded serve
        trace under another policy, where no step-by-step clock
        feedback is needed.

        **Warm semantics** (``warm=True``): the whole sequence runs as
        one :class:`WarmRunState` session on this sim's absolute clock —
        per-channel event loops are suspended at each step boundary and
        resumed with the next step's transactions, so open rows, queued
        backlog and refresh debt carry over. This is the contract
        chunked-prefill replays need: once a prefill burst can leave a
        channel still draining at the step boundary, per-step reset
        would silently forgive the backlog. On uncontended sequences
        (queues drained, gaps long enough for state to quiesce) warm and
        reset agree bit for bit (tests/test_warm_steps.py); on contended
        ones warm can only finish later. Steps are causally ordered, so
        the warm path is sequential — ``workers`` is ignored (suspended
        event-loop states cannot cheaply round-trip a process pool).

        **Hybrid mode** classifies each step by modeled queue pressure:
        an uncontended step (pressure <= ``pressure_threshold``, or a
        decomposed transaction count past ``max_cycle_txns``) is priced
        by the queue-window model, a contended one runs through the
        cycle engine. Under reset semantics both price against an idle
        system and no state flows between steps in *any* mode, so mixing
        pricing engines step-by-step cannot leak contention across a
        step boundary. Under warm semantics the session threads a
        carried-pressure correction through analytically priced steps
        and real channel state through cycle-priced ones (see
        :class:`WarmRunState`). Each returned :class:`SystemResult` is
        stamped with the ``mode`` it took (:func:`hybrid_fraction`
        summarizes the split).
        """
        if starts_ns is not None and len(starts_ns) != len(streams):
            raise ValueError(
                f"starts_ns has {len(starts_ns)} entries for "
                f"{len(streams)} streams")
        if warm:
            sess = self.warm_session()
            out: "list[SystemResult]" = []
            for i, s in enumerate(streams):
                t0 = starts_ns[i] if starts_ns is not None else None
                out.append(sess.step(s, start_ns=t0))
            sess.check()
            return out

        out: list[SystemResult | None] = [None] * len(streams)
        cycle_steps: list[tuple[int, float]] = []    # (step, pressure)
        if self.mode != "cycle":
            # Classification is batched (one vectorized census over every
            # step's records) and runs on the *unshifted* streams — all
            # queue-model features are shift-invariant, so analytically
            # priced steps never materialize a rebased copy.
            feats_all = self._features_many(streams)
            for i, feats in enumerate(feats_all):
                pressure = self._pressure(feats)
                if self.mode == "analytic" or not self._use_cycle(feats,
                                                                  pressure):
                    out[i] = self._analytic_result(feats, pressure)
                else:
                    cycle_steps.append((i, pressure))
        else:
            cycle_steps = [(i, 0.0) for i in range(len(streams))]
        host.count("steps.analytic", len(streams) - len(cycle_steps))
        host.count("steps.cycle", len(cycle_steps))

        def _cycle_stream(i: int) -> ExtentStream:
            s = streams[i]
            t0 = (starts_ns[i] if starts_ns is not None
                  else min((r.arrival_ns for r in s), default=0.0))
            return s.shifted(-t0) if t0 else s

        prepared = {i: sorted(self.decompose(_cycle_stream(i)).items())
                    for i, _ in cycle_steps}
        all_results: dict[int, dict[int, SimResult]] = {
            i: {} for i in prepared}
        flat = [(i, c, txns) for i, items in prepared.items()
                for c, txns in items]
        kind, kwargs = self._sim_spec()
        if workers > 1 and len(flat) > 1:
            pool = get_pool(workers)
            futures = [(i, c, pool.submit(_run_channel, kind, kwargs,
                                          txns))
                       for i, c, txns in flat]
            for i, c, fut in futures:
                all_results[i][c] = fut.result()
        elif flat:
            sims = run_channels(kind, kwargs, [txns for _, _, txns in flat])
            for (i, c, _), r in zip(flat, sims):
                all_results[i][c] = r
        if self.check_timing:
            for i in sorted(all_results):
                self._sanitize(all_results[i], step=i)
        nch = self.amap.n_channels
        for i, pressure in cycle_steps:
            items = prepared[i]
            results = all_results[i]
            ch_bytes = np.zeros(nch, dtype=np.int64)
            ch_finish = np.zeros(nch)
            for c, r in results.items():
                ch_bytes[c] = r.bytes_moved
                ch_finish[c] = r.total_ns
            out[i] = SystemResult(
                total_ns=float(ch_finish.max(initial=0.0)),
                bytes_moved=int(ch_bytes.sum()),
                channel_bytes=ch_bytes,
                channel_finish_ns=ch_finish,
                channel_results=results,
                channel_txns=dict(items),
                queue_pressure=pressure,
            )
        if self.probe is not None:
            # Reset-mode steps were rebased to their own start; shift each
            # step's telemetry back onto the replay clock before folding.
            for i, res in enumerate(out):
                t0 = (starts_ns[i] if starts_ns is not None
                      else min((r.arrival_ns for r in streams[i]),
                               default=0.0))
                self.probe.observe_run(res, t0=float(t0))
        return out

    def run_extents(self, extents: list[tuple[int, int]],
                    is_write: bool = False,
                    arrival_ns: float = 0.0,
                    workers: int = 1) -> SystemResult:
        """Legacy entry point: one homogeneous batch of (addr, nbytes)
        extents, all one kind, all arriving at once. Thin wrapper that
        lifts the list into a one-kind :class:`ExtentStream` — verified
        bit-for-bit against the pre-stream decomposition
        (tests/test_core_memory.py)."""
        kind = "write" if is_write else "read"
        stream = ExtentStream(
            ExtentRecord(addr, nbytes, kind, arrival_ns)
            for addr, nbytes in extents if nbytes > 0)
        return self.run(stream, workers=workers)


class WarmRunState:
    """A warm cross-step session over one :class:`SystemSim`.

    Where :meth:`SystemSim.run_steps` (reset semantics) starts every step
    on an idle system, a warm session keeps one suspended
    :class:`~repro.core.sched.ChannelRunState` per loaded channel for its
    whole lifetime and runs every step on the same **absolute clock**:

    * **cycle-priced steps** feed the step's transactions (absolute
      arrival times — no rebase) into the persistent per-channel states
      via :meth:`~repro.core.sched.ChannelRunState.feed` and drain them
      with the lockstep vectorized driver. Open rows, per-PC timing
      clocks, queued backlog and refresh debt all carry over; a step's
      duration is its channels' latest absolute finish minus the step
      start, so backlog left by the previous step lands on this step's
      makespan instead of being forgiven.
    * **analytically priced steps** (hybrid/analytic modes) cannot carry
      event-loop state — there is none — so the session threads a scalar
      *carried-pressure* correction instead: each step is classified at
      ``pressure_eff = pressure + carry`` and priced at ``floor + extra +
      carry * floor``; afterwards ``carry = WARM_CARRY_FRAC * max(0,
      pressure_eff - threshold)``. Below the classification threshold the
      carry is exactly zero, so uncontended warm sequences price
      bit-identically to reset mode; above it the correction is a
      first-order, strictly-delaying model of the backlog a real warm
      channel would still be draining. A step that drops into the cycle
      engine resets the carry — the real channel state embodies it.

    Steps must be supplied in clock order (non-decreasing starts); a
    session is single-threaded by construction. With
    ``SystemSim(check_timing=True)``, call :meth:`check` once after the
    last step: it replays each channel's *cumulative* cross-step command
    trace through the independent timing checker — strictly stronger
    than per-step checks, since it also validates protocol spacing
    across step boundaries.
    """

    def __init__(self, system: SystemSim):
        self.system = system
        self._kind, self._kwargs = system._sim_spec()
        self._states: "dict[int, object]" = {}    # channel -> ChannelRunState
        self._carry = 0.0
        self._last_start = 0.0
        self.n_steps = 0

    @property
    def carry(self) -> float:
        """The carried-pressure correction pending for the next
        analytically priced step (0.0 in pure cycle mode)."""
        return self._carry

    @host.spanned("pricing")
    def step(self, stream: ExtentStream,
             start_ns: float | None = None) -> SystemResult:
        """Price/simulate one step on the session clock. ``start_ns``
        is the step's start (defaults to the stream's earliest arrival);
        the returned makespan is measured from it. Arrivals are
        interpreted on the absolute session clock — never rebased."""
        sys_ = self.system
        start = (float(start_ns) if start_ns is not None
                 else min((r.arrival_ns for r in stream), default=0.0))
        if start < self._last_start:
            raise ValueError(
                f"warm steps must be clock-ordered: step start {start} ns "
                f"precedes the previous step's start "
                f"{self._last_start} ns")
        self._last_start = start
        self.n_steps += 1
        if sys_.mode != "cycle":
            feats = sys_._features(stream)
            pressure_eff = sys_._pressure(feats) + self._carry
            if sys_.mode == "analytic" or not sys_._use_cycle(feats,
                                                              pressure_eff):
                res = self._analytic_step(feats, pressure_eff)
            else:
                self._carry = 0.0
                res = self._cycle_step(stream, start, pressure_eff)
        else:
            res = self._cycle_step(stream, start, 0.0)
        host.count("steps.analytic" if res.mode == "analytic"
                   else "steps.cycle")
        if sys_.probe is not None:
            # Warm sessions run on the absolute clock already (t0=0);
            # analytic steps still need their start for placement.
            sys_.probe.observe_run(res, t0=0.0, start_ns=start)
        return res

    def _analytic_step(self, feats: dict,
                       pressure_eff: float) -> SystemResult:
        sys_ = self.system
        floor = max(feats["base_ns"], feats["span_ns"])
        extra = sys_._queue_params().predict_extra_ns(
            feats["txns_gating"], feats["fine_txns_gating"],
            feats["ext_gating"])
        total = floor + extra + self._carry * floor
        ch_bytes = feats["mc_channel_bytes"].astype(np.int64)
        mx = ch_bytes.max(initial=0)
        if mx == 0:
            total, ch_finish = 0.0, np.zeros(sys_.amap.n_channels)
        else:
            ch_finish = total * (ch_bytes / mx)
        self._carry = WARM_CARRY_FRAC * max(
            0.0, pressure_eff - sys_._threshold())
        return SystemResult(
            total_ns=float(total),
            bytes_moved=int(ch_bytes.sum()),
            channel_bytes=ch_bytes,
            channel_finish_ns=ch_finish,
            channel_results={},
            channel_txns={},
            mode="analytic",
            queue_pressure=pressure_eff,
        )

    @host.spanned("cycle.run")
    def _cycle_step(self, stream: ExtentStream, start: float,
                    pressure: float) -> SystemResult:
        sys_ = self.system
        items = sorted(sys_.decompose(stream).items())
        host.count("cycle.txns", sum(len(txns) for _, txns in items))
        stepped = []
        with host.span("cycle.setup"):
            for c, txns in items:
                st = self._states.get(c)
                if st is None:
                    st = make_channel_sim(
                        self._kind, **self._kwargs).start_run(txns)
                    self._states[c] = st
                else:
                    st.feed(txns)
                stepped.append((c, st))
        advance_states([st for _, st in stepped])
        nch = sys_.amap.n_channels
        ch_bytes = np.zeros(nch, dtype=np.int64)
        ch_finish = np.zeros(nch)
        results: "dict[int, SimResult]" = {}
        for c, st in stepped:
            r = st.result()
            results[c] = r
            ch_bytes[c] = r.bytes_moved
            # Finish times are absolute; a step's duration is measured
            # from its own start, so carried backlog shows up here.
            ch_finish[c] = max(0.0, r.total_ns - start)
        return SystemResult(
            total_ns=float(ch_finish.max(initial=0.0)),
            bytes_moved=int(ch_bytes.sum()),
            channel_bytes=ch_bytes,
            channel_finish_ns=ch_finish,
            channel_results=results,
            channel_txns=dict(items),
            queue_pressure=pressure,
        )

    def check(self) -> None:
        """Sanitizer pass for warm sessions: with ``check_timing=True``
        on the underlying sim, replay every channel's cumulative
        cross-step command trace through the independent timing checker
        (no-op otherwise). Call once, after the last step."""
        if not self.system.check_timing or not self._states:
            return
        full = {
            c: SimResult(st.finish, float(st.now),
                         st.n_txns * st.policy.bytes_per_txn,
                         dict(st.counts), trace=st.trace)
            for c, st in self._states.items()
        }
        self.system._sanitize(full)


def hybrid_fraction(results: "list[SystemResult]") -> float:
    """Fraction of runs a hybrid SystemSim priced analytically (1.0 =
    every step took the fast path; 0.0 for an all-cycle run or an empty
    list)."""
    if not results:
        return 0.0
    return sum(r.mode == "analytic" for r in results) / len(results)


def bulk_stream_extents(nbytes: int, n_extents: int = 1,
                        base_addr: int = 0,
                        gap_bytes: int = 0) -> list[tuple[int, int]]:
    """Helper: `n_extents` contiguous extents totalling exactly `nbytes`
    (the last extent absorbs the division remainder), optionally separated
    by `gap_bytes` holes (to exercise load imbalance). The legacy
    extent-list view of :func:`repro.workloads.bulk_stream`."""
    # Lazy import: repro.core.__init__ pulls this module in while
    # workloads.builders is still importing through repro.core.analytic.
    from ..workloads.builders import bulk_stream
    return bulk_stream(nbytes, n_extents, base_addr=base_addr,
                       gap_bytes=gap_bytes).extents()


__all__ = ["SystemSim", "SystemResult", "WarmRunState",
           "bulk_stream_extents", "hybrid_fraction", "MODES",
           "WARM_CARRY_FRAC"]
