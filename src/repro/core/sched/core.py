"""Shared channel-simulation core: one event loop, N policies.

:class:`ChannelSimCore` owns everything both memory controllers have in
common — the event clock, the arrival-ordered :class:`_PendingQueue`, the
demand-aware bounded-postponement refresh governor, the idle-advance rule
(jump to min(next arrival, next refresh due)), and per-transaction finish
accounting. Everything controller-specific — which command to issue next,
what per-bank/per-VBA state exists, how a refresh stalls the array — lives
behind the :class:`~repro.core.sched.policies.SchedulerPolicy` interface.

The split makes the paper's Table IV complexity contrast *structural* in
the code: the conventional FR-FCFS policy carries 64 seven-state bank FSMs
and ~15 timing clocks; the RoMe policy carries 5 four-state FSMs and the
ten Table III row-to-row gaps. The loop they plug into is identical.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from itertools import islice
from typing import NamedTuple

import numpy as np

from ...obs import host


class CmdRecord(NamedTuple):
    """One emitted memory command, for the trace sanitizer.

    HBM4 policies emit DRAM-level ops (``ACT``/``RD``/``WR``/``PRE``/
    ``REF``); the RoMe policy emits row-level ops (``RD_row``/``WR_row``/
    ``REF``) — Table III *is* its protocol, so conformance is checked at
    the granularity the MC actually schedules. Fields that don't apply to
    an op (row for PRE/REF, data window for non-column commands, sid for
    refresh) are ``-1``. A NamedTuple keeps records cheap, picklable
    (they ride back through ``core.pool`` inside :class:`SimResult`) and
    comparable (the vectorized driver asserts full trace identity).
    """

    t_ns: float            # command issue time on the C/A bus
    op: str                # ACT | RD | WR | PRE | REF | RD_row | WR_row
    bank: int              # flat bank id (HBM4) / VBA id (RoMe)
    pc: int                # pseudo channel (RoMe lockstep: always 0)
    sid: int               # stack id, -1 when not request-driven
    row: int               # row (ACT/RD/WR) or -1
    data_start_ns: float   # first data beat on the DQ bus, -1.0 if none
    data_end_ns: float     # last data beat leaves the bus, -1.0 if none


@dataclass
class Txn:
    """One memory transaction at MC access granularity."""

    arrival_ns: float
    bank: int           # flat bank id within the channel (HBM4) / VBA id (RoMe)
    row: int
    col: int = 0        # column index within the row (HBM4 only)
    is_write: bool = False
    sid: int = 0        # stack id (rank)
    stream: int = 0     # software stream tag (for stats only)


def counts_row_hit_rate(cmd_counts: dict) -> float:
    """Row-buffer hit rate derived from a command-count dict.

    ``RD``/``WR`` are the column commands; every ``ACT`` opens a row for
    an access that missed the row buffer, so ``hits = (RD + WR) - ACT``
    and the rate is ``hits / (RD + WR)``. Row-granular controllers
    (counts carrying ``row_commands``) precharge after every row access
    — there is no row buffer to hit, so their rate is 0.0 *by
    construction*; the HBM4-vs-RoMe row-hit gap a telemetry report shows
    is therefore exactly the locality an RH+-style policy could exploit,
    not a bug. Returns 0.0 when no column command was issued."""
    if "row_commands" in cmd_counts:
        return 0.0
    col = cmd_counts.get("RD", 0) + cmd_counts.get("WR", 0)
    if col <= 0:
        return 0.0
    return max(0.0, (col - cmd_counts.get("ACT", 0)) / col)


@dataclass
class SimResult:
    finish_ns: np.ndarray          # completion time per txn (input order)
    total_ns: float                # makespan
    bytes_moved: int
    cmd_counts: dict = field(default_factory=dict)  # ACT/RD/WR/PRE/REF/row cmds
    trace: list | None = None      # CmdRecords when run with emit_trace=True
    #: Telemetry samples when run with ``sample_window_ns`` set: tuples
    #: ``(t_ns, queue_depth, ref_backlog, draining, counts_snapshot)``
    #: appended at window-boundary crossings (see
    #: :class:`repro.obs.MetricsProbe`); None when sampling is off.
    samples: list | None = None

    @property
    def bandwidth_gbps(self) -> float:
        if self.total_ns <= 0:
            return 0.0
        return self.bytes_moved / self.total_ns  # B/ns == GB/s

    @property
    def row_hit_rate(self) -> float:
        """Row-buffer hit rate of this run (:func:`counts_row_hit_rate`
        over :attr:`cmd_counts`): ``(RD+WR hits) / column commands``,
        0.0 for row-granular (always-precharge) controllers."""
        return counts_row_hit_rate(self.cmd_counts)


class _PendingQueue:
    """Arrival-ordered outstanding transactions with O(1) dequeue.

    ``list.remove`` made every dequeue O(n) worst-case in the number of
    outstanding transactions — and, because it matches by dataclass
    equality, it removed the *wrong object* when two field-identical
    transactions were in flight (one got serviced twice, the other
    never). Removal here is by identity: tombstone the slot via an
    id->slot map, with a head cursor that skips tombstones. The scheduler
    only removes transactions inside the first ``queue_depth`` live
    entries, so at most ``queue_depth`` interior tombstones exist at any
    time and every window scan is O(queue_depth); with no interior
    tombstones (the common head-of-queue dequeue) the window is a plain
    list slice."""

    __slots__ = ("_slots", "_pos", "_head", "_n", "_tomb")

    def __init__(self, txns: list):
        self._slots = list(txns)
        self._pos = {id(tx): i for i, tx in enumerate(self._slots)}
        if len(self._pos) != len(self._slots):
            raise ValueError(
                "trace contains the same Txn object more than once; pass "
                "distinct Txn instances (field-identical copies are fine)")
        self._head = 0
        self._n = len(self._slots)
        self._tomb = 0                 # tombstones at index >= _head

    def __len__(self) -> int:
        return self._n

    def __bool__(self) -> bool:
        return self._n > 0

    def _skip_tombstones(self) -> None:
        slots, h = self._slots, self._head
        while h < len(slots) and slots[h] is None:
            h += 1
            self._tomb -= 1
        self._head = h

    def head(self) -> Txn:
        """Oldest outstanding transaction."""
        self._skip_tombstones()
        return self._slots[self._head]

    def first(self, depth: int) -> list:
        """The scheduler window: up to `depth` oldest live transactions."""
        self._skip_tombstones()
        slots, h, tomb = self._slots, self._head, self._tomb
        if tomb == 0:
            return slots[h:h + depth]
        # Every tombstone index t satisfies t < h + depth + tomb (removals
        # only happen inside the window), so this slice is guaranteed to
        # contain the full window; filter/islice keep the scan in C.
        return list(islice(filter(None, slots[h:h + depth + tomb]), depth))

    def remove(self, tx: Txn) -> None:
        self._slots[self._pos.pop(id(tx))] = None
        self._n -= 1
        self._tomb += 1


class ChannelRunState:
    """One channel's in-flight simulation: the event loop, suspended.

    Everything :meth:`ChannelSimCore.run` used to keep in local variables
    lives here, so a run can be advanced incrementally —
    :meth:`advance` executes up to ``max_iters`` loop iterations and
    returns whether the channel finished. This is the batched state-step
    the vectorized multi-channel driver (:mod:`.vectorized`) interleaves
    across all channels of a cube; because the scalar path
    (:meth:`ChannelSimCore.run`) drives the *same* state machine to
    completion in one call, and channels share no state, any interleaving
    of ``advance`` calls is bit-identical to the scalar result.
    """

    __slots__ = ("core", "policy", "pending", "finish", "counts",
                 "idx_in_finish", "period", "next_ref_t", "next_ref_unit",
                 "ref_backlog", "now", "n_txns", "trace", "_counts_base",
                 "_trace_base", "samples", "next_sample_t", "_samples_base")

    def __init__(self, core: "ChannelSimCore", txns: list[Txn]):
        pol = core.policy
        order = sorted(range(len(txns)), key=lambda i: txns[i].arrival_ns)
        ordered = [txns[i] for i in order]
        self.core = core
        self.policy = pol
        self.idx_in_finish = {id(tx): order[k]
                              for k, tx in enumerate(ordered)}
        self.pending = _PendingQueue(ordered)
        self.finish = np.zeros(len(txns))
        self.counts = {k: 0 for k in pol.count_keys}
        self.counts["ref_backlog_max"] = 0
        # The trace list is handed to the policy *before* begin() so a
        # policy may cache it in per-run state; None keeps every emission
        # site a single attribute test (zero-cost when off).
        self.trace = [] if core.emit_trace else None
        pol.trace = self.trace
        pol.begin(self.counts)
        # Telemetry sampling (repro.obs.MetricsProbe): with a sample
        # window set, the event loop appends one state sample per
        # window-boundary crossing. When off, next_sample_t = +inf makes
        # the hot-loop guard a single always-false float compare — the
        # same zero-cost-when-off contract as the trace sink above. The
        # leading sample is the baseline snapshot deltas diff against.
        w = core.sample_window_ns
        self.samples = [] if w else None
        self.next_sample_t = float(w) if w else float("inf")
        if self.samples is not None:
            self.samples.append((0.0, len(txns), 0, False,
                                 dict(self.counts)))
        self.period = pol.ref_period
        self.next_ref_t = self.period
        self.next_ref_unit = 0
        self.ref_backlog = 0
        self.now = 0.0
        self.n_txns = len(txns)
        self._counts_base = None       # set by feed(): warm per-batch deltas
        self._trace_base = 0           # trace length at the last feed()
        self._samples_base = 0         # sample count at the last feed()

    @property
    def finished(self) -> bool:
        return not self.pending

    def feed(self, txns: list[Txn]) -> None:
        """Load the next transaction batch into a *drained* state without
        resetting any warm channel state.

        This is the suspend/resume seam warm cross-step replay
        (:meth:`SystemSim.run_steps` with ``warm=True``) is built on: the
        policy FSMs (open rows, per-PC timing clocks), the refresh
        governor (absolute due cadence, rotation unit, backlog) and the
        event clock all carry over — only the queue, the finish array and
        the per-batch command-count baseline are renewed. Arrivals are on
        the same absolute clock as every previous batch; arrivals in a
        gap after the last drain are reached through the normal
        idle-advance, which issues the refreshes due *inside* the gap at
        their own anchors. Feeding an undrained state is an error — the
        single event loop cannot interleave two batches' accounting.
        """
        if self.pending:
            raise RuntimeError(
                f"feed() on an undrained channel: {len(self.pending)} of "
                f"{self.n_txns} transactions outstanding")
        order = sorted(range(len(txns)), key=lambda i: txns[i].arrival_ns)
        ordered = [txns[i] for i in order]
        self.idx_in_finish = {id(tx): order[k]
                              for k, tx in enumerate(ordered)}
        self.pending = _PendingQueue(ordered)
        self.finish = np.zeros(len(txns))
        self.n_txns = len(txns)
        self._counts_base = dict(self.counts)
        if self.trace is not None:
            self._trace_base = len(self.trace)
        if self.samples is not None:
            # Per-feed baseline marker: the first sample of a feed slice
            # carries the cumulative snapshot window deltas start from.
            self._samples_base = len(self.samples)
            self.samples.append((self.now, len(self.pending),
                                 self.ref_backlog,
                                 bool(getattr(self.policy, "draining",
                                              False)),
                                 dict(self.counts)))

    def advance(self, max_iters: int = 1) -> bool:
        """Execute up to ``max_iters`` event-loop iterations; returns True
        once the channel has drained. Hot path: every per-iteration
        attribute is hoisted into locals so a batched advance amortizes
        the Python dispatch cost across the whole batch."""
        core = self.core
        pol = self.policy
        pending = self.pending
        finish = self.finish
        counts = self.counts
        idx_in_finish = self.idx_in_finish
        refresh = core.refresh
        max_post = core.max_ref_postpone
        depth = core.queue_depth
        period = self.period
        next_ref_t = self.next_ref_t
        next_ref_unit = self.next_ref_unit
        ref_backlog = self.ref_backlog
        now = self.now
        issue = pol.issue
        issue_refresh = pol.issue_refresh
        n_ref_units = pol.n_ref_units
        samples = self.samples
        next_sample_t = self.next_sample_t
        sample_w = core.sample_window_ns
        evals = pol.ready_evals

        iters = 0
        for iters in range(max_iters):
            if not pending:
                break
            # Telemetry sampling: one state snapshot per window-boundary
            # crossing. next_sample_t is +inf when sampling is off, so
            # the disabled cost is this single float compare; sampling
            # itself only *observes* (appends), never changes loop state
            # — results stay bit-identical either way.
            if now >= next_sample_t:
                samples.append((now, len(pending), ref_backlog,
                                bool(getattr(pol, "draining", False)),
                                dict(counts)))
                next_sample_t += sample_w
                if next_sample_t <= now:     # idle jump skipped windows
                    next_sample_t = (now // sample_w + 1.0) * sample_w
            qwin = pending.first(depth)

            # -- refresh governor: rotating per-unit refresh with
            # demand-aware bounded postponement, each issue anchored at its
            # own due time so refreshes of different units may overlap. --
            while refresh and next_ref_t <= now:
                ref_backlog += 1
                next_ref_t += period
            if ref_backlog > counts["ref_backlog_max"]:
                counts["ref_backlog_max"] = ref_backlog
            while ref_backlog > 0:
                demanded = any(tx.bank == next_ref_unit for tx in qwin)
                if demanded and ref_backlog < max_post:
                    break
                due = next_ref_t - ref_backlog * period
                issue_refresh(next_ref_unit, due)
                next_ref_unit = (next_ref_unit + 1) % n_ref_units
                ref_backlog -= 1

            window = [tx for tx in qwin if tx.arrival_ns <= now]
            if not window:
                # Idle: jump to the next event — arrival OR refresh due —
                # so refreshes due during a sparse-arrival gap are issued
                # in the gap (bounded postponement) instead of piling up
                # behind the next arrival.
                cand = pending.head().arrival_ns
                if refresh:
                    cand = min(cand, next_ref_t)
                now = max(now + 1e-9, cand)
                continue

            now, issued, completions = issue(window, now)
            for tx, fin in completions:
                finish[idx_in_finish[id(tx)]] = fin
                pending.remove(tx)

            if not issued:
                # Nothing issueable: jump to the next event (refresh or
                # arrival) to guarantee progress.
                nxt = [tx.arrival_ns for tx in qwin if tx.arrival_ns > now]
                cand = min(nxt) if nxt else now + period
                if refresh:
                    cand = min(cand, next_ref_t)
                now = max(now + 1e-9, cand)
        else:
            iters = max_iters
        host.count("cycle.iters", iters)
        if evals is not None:
            host.count("cycle.ready_evals", pol.ready_evals - evals)

        self.next_ref_t = next_ref_t
        self.next_ref_unit = next_ref_unit
        self.ref_backlog = ref_backlog
        self.now = now
        self.next_sample_t = next_sample_t
        return not pending

    def result(self) -> SimResult:
        """The drained batch's :class:`SimResult`. After a :meth:`feed`
        the command counts are the *delta* since that feed and the trace
        and telemetry samples are the per-feed slices. The one exception
        is ``ref_backlog_max``: it is a session-cumulative **high-water
        mark**, not a counter — it is *never* reset at a feed boundary,
        and attaching telemetry sampling (``sample_window_ns``) does not
        change that: the per-window backlog series comes from the
        sampled ``ref_backlog`` scalar, while the counts key keeps
        reporting the worst backlog the whole warm session has ever
        seen. A later feed's result can therefore report a
        ``ref_backlog_max`` reached during an *earlier* feed — that is
        the intended semantics (pinned by tests/test_obs.py), so warm
        step results stay comparable with fresh per-step runs on every
        true counter while the refresh high-water stays an invariant of
        the session. Finish times are always on the state's absolute
        clock."""
        if self.pending:
            raise RuntimeError(
                f"channel not drained: {len(self.pending)} of "
                f"{self.n_txns} transactions outstanding")
        bytes_moved = self.n_txns * self.policy.bytes_per_txn
        counts, trace, samples = self.counts, self.trace, self.samples
        if self._counts_base is not None:
            base = self._counts_base
            counts = {k: (v if k == "ref_backlog_max"
                          else v - base.get(k, 0))
                      for k, v in counts.items()}
            if trace is not None:
                trace = trace[self._trace_base:]
            if samples is not None:
                samples = samples[self._samples_base:]
        else:
            # Snapshot: a later feed() keeps mutating the live dict/list,
            # and the first batch's result must not grow with the session.
            counts = dict(counts)
            if trace is not None:
                trace = trace[:]
            if samples is not None:
                samples = samples[:]
        return SimResult(self.finish,
                         float(self.finish.max(initial=0.0)),
                         bytes_moved, counts, trace=trace, samples=samples)


class ChannelSimCore:
    """Policy-driven event loop for one memory channel.

    The loop body is the invariant part of both controllers:

    1. take the scheduler window (`queue_depth` oldest pending txns),
    2. accrue refresh debt (one unit per elapsed ``policy.ref_period``),
    3. drain the debt — a refresh due for a unit with queued demand is
       postponed (JEDEC bounded postponement) until the backlog hits
       ``max_ref_postpone``, each issue anchored at its own due time,
    4. let the policy issue command work for the arrived window,
    5. if nothing arrived / nothing issued, jump the clock to the next
       event (arrival or refresh due) so progress is guaranteed and
       refreshes fire *inside* idle gaps instead of piling up behind the
       next arrival.

    Policies mutate their own FSM state and the shared ``counts`` dict;
    the loop state (clock, queue, refresh debt, finish array) lives in a
    :class:`ChannelRunState` — :meth:`run` drives one state to
    completion, :meth:`start_run` hands the state out for incremental
    (batched / vectorized multi-channel) advancing.
    """

    def __init__(self, policy, queue_depth: int, refresh: bool = True,
                 max_ref_postpone: int = 8, emit_trace: bool = False,
                 sample_window_ns: float | None = None):
        self.policy = policy
        self.queue_depth = queue_depth
        self.refresh = refresh
        self.max_ref_postpone = max_ref_postpone
        self.emit_trace = emit_trace
        if sample_window_ns is not None and sample_window_ns <= 0:
            raise ValueError(
                f"sample_window_ns must be positive, got {sample_window_ns}")
        #: telemetry sampling cadence (ns); None disables sampling and
        #: keeps the event loop bit-identical to the pre-telemetry core.
        self.sample_window_ns = sample_window_ns

    def start_run(self, txns: list[Txn]) -> ChannelRunState:
        """Begin a run without driving it: the returned state advances
        under caller control (see :mod:`repro.core.sched.vectorized`)."""
        return ChannelRunState(self, txns)

    def run(self, txns: list[Txn]) -> SimResult:
        state = ChannelRunState(self, txns)
        while not state.advance(4096):
            pass
        return state.result()
