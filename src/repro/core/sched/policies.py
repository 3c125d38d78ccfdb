"""Scheduler policies: the pluggable half of the channel simulator.

Each policy packages one controller architecture's *decision logic and
state* — bank/VBA FSMs, per-resource clocks, command selection — behind
the interface :class:`ChannelSimCore` drives:

``count_keys``
    Command-count stat keys the policy maintains (the core adds
    ``ref_backlog_max``).
``ref_period`` / ``n_ref_units``
    Refresh cadence and rotation length for the core's governor.
``begin(counts)``
    (Re)initialize all per-run state; stash the shared counts dict.
``issue_refresh(unit, due)``
    Perform one rotating refresh for `unit`, anchored at `due`.
``issue(window, now) -> (now, issued, completions)``
    One scheduling step over the arrived window. `completions` is a list
    of ``(txn, finish_ns)``; `issued` False tells the core to advance the
    clock to the next event.
``bytes_per_txn``
    Data moved per transaction (MC access granularity).
``state_footprint()``
    The Table IV census of what the policy must physically track — FSM
    instances, states per FSM, managed timing parameters, page policy —
    so MC-complexity claims are introspected from the code that *is* the
    scheduler rather than asserted in prose.
"""
from __future__ import annotations

from ..command_generator import CommandGenerator
from ..timing import (ChannelGeometry, HBM4_BANK_STATES, HBM4Timing,
                      ROME_BANK_STATES, RoMeTiming)
from .core import CmdRecord, Txn


class SchedulerPolicy:
    """Interface; see the module docstring for the contract."""

    count_keys: tuple = ()
    ref_period: float = 0.0
    n_ref_units: int = 1
    bytes_per_txn: int = 0

    #: Command-trace sink, set by :class:`ChannelRunState` before
    #: ``begin()``: a list of :class:`CmdRecord` when the run was started
    #: with ``emit_trace=True``, else None. Every emission site guards on
    #: it so the hot path pays one attribute test when tracing is off.
    #: The trace exists so `repro.analysis.timing_checker` can verify the
    #: command stream against the JEDEC / Table III rule tables without
    #: trusting any of the readiness math below.
    trace: list | None = None

    #: Readiness evaluations made by the policy's command picks, where
    #: the policy counts them (FR-FCFS); None where it does not (RoMe).
    ready_evals: int | None = None

    def begin(self, counts: dict) -> None:
        raise NotImplementedError

    def issue_refresh(self, unit: int, due: float) -> None:
        raise NotImplementedError

    def issue(self, window: list[Txn], now: float):
        raise NotImplementedError

    def state_footprint(self) -> dict:
        raise NotImplementedError


# ===========================================================================
# Conventional HBM4: FR-FCFS
# ===========================================================================

class _BankState:
    __slots__ = ("open_row", "t_act", "t_last_rd", "t_last_wr_data",
                 "t_rp_done", "t_ref_done")

    def __init__(self) -> None:
        self.open_row: int | None = None
        self.t_act = -1e18
        self.t_last_rd = -1e18
        self.t_last_wr_data = -1e18
        self.t_rp_done = 0.0
        self.t_ref_done = 0.0


class FRFCFSOpenPagePolicy(SchedulerPolicy):
    """FR-FCFS over a bounded CAM queue, open-page, 7-state bank FSMs.

    One HBM4 channel = 2 pseudo channels simulated jointly. Each PC owns
    half the DQ pins and its own banks; the two PCs share C/A but we
    assume C/A is never the bottleneck for the baseline (it has 18 pins).
    Bank ids 0..127: pc = bank // 64, bank group = (bank % 64) // 4.
    """

    count_keys = ("ACT", "RD", "WR", "PRE", "REFpb", "ca_commands")
    page_policy = "open"

    #: Open-page keeps a row open while queued hits still target it; the
    #: closed-page subclass flips this (always precharge after access).
    keep_open_for_hits = True

    def __init__(self, timing: HBM4Timing | None = None,
                 geometry: ChannelGeometry | None = None):
        self.t = timing or HBM4Timing()
        self.g = geometry or ChannelGeometry()
        self.banks_per_pc = self.g.banks_per_pc
        self.n_banks = self.g.banks_per_channel
        self.burst_ns = self.g.burst_ns  # 32 B over one PC's pins
        self.ref_period = self.t.tREFIpb
        self.n_ref_units = self.n_banks
        self.bytes_per_txn = self.g.col_bytes
        self.ready_evals = 0

    # -- helpers -----------------------------------------------------------

    def _bg(self, bank: int) -> int:
        return (bank % self.banks_per_pc) // self.g.banks_per_group

    def _pc(self, bank: int) -> int:
        return bank // self.banks_per_pc

    # -- per-run state -----------------------------------------------------

    def begin(self, counts: dict) -> None:
        self.counts = counts
        self.banks = [_BankState() for _ in range(self.n_banks)]
        # Per-PC shared resources.
        self.pc_bus_free = [0.0, 0.0]              # DQ bus next-free
        self.pc_last_burst = [-1e18, -1e18]        # last RD/WR cmd time (tCCDS)
        self.pc_last_burst_bg = [dict(), dict()]   # bg -> last cmd time (tCCDL)
        self.pc_last_burst_sid = [dict(), dict()]  # sid -> last cmd time (tCCDR)
        self.pc_last_was_write = [False, False]
        self.pc_last_rd_cmd = [-1e18, -1e18]
        self.pc_last_wr_data_end = [-1e18, -1e18]
        self.pc_last_wr_data_end_bg = [dict(), dict()]  # bg -> data end (tWTRL)
        self.ch_last_ref = -1e18                   # REFpb spacing (tRREFpb)
        self.pc_act_times = [[], []]               # for tFAW (per PC)
        self.pc_last_act = [-1e18, -1e18]          # tRRDS
        self.pc_last_act_bg = [dict(), dict()]     # tRRDL

    # -- readiness clocks --------------------------------------------------

    def act_ready(self, bank_id: int, b: _BankState, at: float) -> float:
        t = self.t
        pc = self._pc(bank_id)
        bg = self._bg(bank_id)
        r = max(at, b.t_rp_done, b.t_ref_done,
                self.pc_last_act[pc] + t.tRRDS,
                self.pc_last_act_bg[pc].get(bg, -1e18) + t.tRRDL)
        acts = self.pc_act_times[pc]
        if len(acts) >= 4:
            r = max(r, acts[-4] + t.tFAW)
        return r

    def col_ready(self, bank_id: int, b: _BankState, is_write: bool,
                  sid: int, at: float) -> float:
        t = self.t
        pc = self._pc(bank_id)
        bg = self._bg(bank_id)
        trcd = t.tRCDWR if is_write else t.tRCDRD
        r = max(at, b.t_act + trcd, b.t_ref_done,
                self.pc_last_burst[pc] + t.tCCDS,
                self.pc_last_burst_bg[pc].get(bg, -1e18) + t.tCCDL)
        # tCCDR: RD/WR to RD/WR spacing across SIDs (ranks) sharing the PC.
        for other_sid, t_cmd in self.pc_last_burst_sid[pc].items():
            if other_sid != sid:
                r = max(r, t_cmd + t.tCCDR)
        if is_write and not self.pc_last_was_write[pc]:
            r = max(r, self.pc_last_rd_cmd[pc] + t.tRTW)
        if not is_write:
            if self.pc_last_was_write[pc]:
                r = max(r, self.pc_last_wr_data_end[pc] + t.tWTRS)
            # tWTRL binds same-bank-group reads against the *last write
            # to that group* even when interleaved reads already flipped
            # the turnaround direction — the per-PC gate above would
            # skip it (found by the trace sanitizer).
            wbg = self.pc_last_wr_data_end_bg[pc].get(bg)
            if wbg is not None:
                r = max(r, wbg + t.tWTRL)
        return r

    def pre_ready(self, b: _BankState, at: float) -> float:
        t = self.t
        return max(at, b.t_act + t.tRAS, b.t_last_rd + t.tRTP,
                   b.t_last_wr_data + t.tWR)

    # -- refresh -----------------------------------------------------------

    def issue_refresh(self, unit: int, due: float) -> None:
        t = self.t
        b = self.banks[unit]
        tr = self.trace
        # tRREFpb: REFpb commands to *different* banks still share the
        # C/A path — successive refresh starts keep their spacing even
        # when backdated due anchors and bank-busy pushes collide
        # (found by the trace sanitizer).
        start = max(due, b.t_rp_done, b.t_ref_done,
                    self.ch_last_ref + t.tRREFpb)
        if b.open_row is not None:
            pr = self.pre_ready(b, start)
            b.t_rp_done = pr + t.tRP
            b.open_row = None
            self.counts["PRE"] += 1
            if tr is not None:
                tr.append(CmdRecord(pr, "PRE", unit, self._pc(unit), -1, -1,
                                    -1.0, -1.0))
            start = b.t_rp_done
        b.t_ref_done = start + t.tRFCpb
        self.ch_last_ref = start
        self.counts["REFpb"] += 1
        if tr is not None:
            tr.append(CmdRecord(start, "REF", unit, self._pc(unit), -1, -1,
                                -1.0, -1.0))

    # -- one scheduling step -----------------------------------------------

    def issue(self, window: list[Txn], now: float):
        t = self.t
        counts = self.counts
        banks = self.banks
        tr = self.trace
        issued = False
        completions: list = []

        # Row-bus work (runs concurrently with the column bus): progress
        # the oldest row-miss whose bank's open row is no longer needed by
        # any queued hit. This is what deep queues buy the conventional
        # MC — lookahead to overlap ACT/PRE of upcoming rows with the
        # bursts of the current ones.
        prepared: set[int] = set()
        for tx in window:
            b = banks[tx.bank]
            if b.open_row == tx.row or tx.bank in prepared:
                continue
            if b.open_row is not None:
                # Keep a row open while queued hits still target it
                # (open-page only).
                if self.keep_open_for_hits and \
                        any(h.bank == tx.bank and h.row == b.open_row
                            for h in window):
                    prepared.add(tx.bank)
                    continue
                pr = self.pre_ready(b, max(tx.arrival_ns, b.t_ref_done))
                b.t_rp_done = pr + t.tRP
                b.open_row = None
                counts["PRE"] += 1
                counts["ca_commands"] += 1
                if tr is not None:
                    tr.append(CmdRecord(pr, "PRE", tx.bank,
                                        self._pc(tx.bank), tx.sid, -1,
                                        -1.0, -1.0))
                now = max(now, pr)
            else:
                ar = self.act_ready(tx.bank, b,
                                    max(tx.arrival_ns, b.t_ref_done))
                pc = self._pc(tx.bank)
                bg = self._bg(tx.bank)
                b.t_act = ar
                b.open_row = tx.row
                self.pc_last_act[pc] = ar
                self.pc_last_act_bg[pc][bg] = ar
                self.pc_act_times[pc].append(ar)
                if len(self.pc_act_times[pc]) > 8:
                    self.pc_act_times[pc] = self.pc_act_times[pc][-8:]
                counts["ACT"] += 1
                counts["ca_commands"] += 1
                if tr is not None:
                    tr.append(CmdRecord(ar, "ACT", tx.bank, pc, tx.sid,
                                        tx.row, -1.0, -1.0))
                now = max(now, ar)
            prepared.add(tx.bank)
            issued = True

        # Column-bus work: earliest-ready row hit (FR), oldest on ties.
        # Issue times are governed by per-resource clocks (bank readiness,
        # per-PC burst spacing, DQ bus) — the column C/A path sustains one
        # command per PC per tCCDS, so a pick may legally land before
        # `now` (commands ride independent buses).
        best, best_t = self._pick_column(window, now)
        if best is not None:
            tx, r = best, best_t
            b = banks[tx.bank]
            pc = self._pc(tx.bank)
            bg = self._bg(tx.bank)
            lat = t.tCWL if tx.is_write else t.tCL
            data_start = max(r + lat, self.pc_bus_free[pc])
            # If the bus is the constraint, push the command time too.
            cmd_t = data_start - lat
            data_end = data_start + self.burst_ns
            self.pc_bus_free[pc] = data_end
            self.pc_last_burst[pc] = cmd_t
            self.pc_last_burst_bg[pc][bg] = cmd_t
            self.pc_last_burst_sid[pc][tx.sid] = cmd_t
            self.pc_last_was_write[pc] = tx.is_write
            counts["ca_commands"] += 1
            if tx.is_write:
                b.t_last_wr_data = data_end
                self.pc_last_wr_data_end[pc] = data_end
                self.pc_last_wr_data_end_bg[pc][bg] = data_end
                counts["WR"] += 1
            else:
                b.t_last_rd = cmd_t
                self.pc_last_rd_cmd[pc] = cmd_t
                counts["RD"] += 1
            if tr is not None:
                tr.append(CmdRecord(cmd_t, "WR" if tx.is_write else "RD",
                                    tx.bank, pc, tx.sid, tx.row,
                                    data_start, data_end))
            self._after_column(tx, b, cmd_t)
            completions.append((tx, data_end))
            now = max(now, cmd_t)
            issued = True

        return now, issued, completions

    # -- subclass hooks ----------------------------------------------------

    def _column_groups(self, window: list[Txn],
                       now: float) -> list[list[Txn]]:
        """Candidate groups for the column bus, in preference order: the
        pick comes from the first group with an issuable row hit.
        Write-drain narrows the head group to one kind at a time but
        keeps the other kind as a fallback — a group with no issuable
        transaction must never stall the bus while a lower-preference
        one could issue (liveness: row-prep keeps rows open for *queued*
        hits regardless of kind, so a kind-filtered head group can be
        blocked behind the very rows the fallback group holds open)."""
        return [window]

    def _pick_column(self, window: list[Txn], now: float):
        """Earliest-ready activated row hit from the first non-empty
        candidate group; oldest (window order) on ties. Returns
        ``(txn, ready_ns)`` or ``(None, None)``."""
        for group in self._column_groups(window, now):
            best, best_t = self._earliest_hit(group)
            if best is not None:
                return best, best_t
        return None, None

    def _earliest_hit(self, txns: list[Txn]):
        """Earliest-ready activated row hit of `txns`, first in list order
        on ties: ``(txn, ready_ns)`` or ``(None, None)``.

        :meth:`col_ready` is evaluated once per ``(bank, is_write, sid)``
        key, for the key's first row hit. Its result is
        ``max(tx.arrival_ns, X)`` with ``X`` a function of the key and the
        controller state alone, and every candidate list keeps window
        (arrival) order within a key — the window itself, the
        write-drain groups (``reads + aged writes`` keeps each direction
        in order) and the same-SID list. So within a key readiness is
        non-decreasing in list order, the first hit has the smallest
        readiness of its key and wins its ties, and a later hit of the
        same key can never satisfy ``r < best_t - 1e-12``: skipping it
        leaves the pick unchanged. A non-hit marks no key.
        ``ready_evals`` counts the evaluations."""
        banks = self.banks
        col_ready = self.col_ready
        seen = set()
        best = None
        best_t = None
        for tx in txns:
            b = banks[tx.bank]
            if b.open_row == tx.row and b.t_act <= 1e17:
                key = (tx.bank, tx.is_write, tx.sid)
                if key in seen:
                    continue
                seen.add(key)
                r = col_ready(tx.bank, b, tx.is_write, tx.sid, tx.arrival_ns)
                if best_t is None or r < best_t - 1e-12:
                    best, best_t = tx, r
        self.ready_evals += len(seen)
        return best, best_t

    def _after_column(self, tx: Txn, b: _BankState, cmd_t: float) -> None:
        """Open-page: the row stays open after a column access."""

    # -- introspection -----------------------------------------------------

    def state_footprint(self) -> dict:
        scheduling = ("bank group interleaving", "PC interleaving")
        if self.keep_open_for_hits:
            scheduling = ("row-buffer locality",) + scheduling
        return {
            "name": "frfcfs_open" if self.keep_open_for_hits else
                    "frfcfs_closed",
            "timing_params": self.t.n_managed(),
            "fsm_instances": self.banks_per_pc,   # one per bank per PC
            "states_per_fsm": len(HBM4_BANK_STATES),
            "page_policy": self.page_policy,
            "scheduling": scheduling,
        }


class HBM4ClosedPagePolicy(FRFCFSOpenPagePolicy):
    """Closed-page HBM4 variant: auto-precharge after every column access.

    A comparison point between open-page FR-FCFS and RoMe: the scheduler
    sheds the row-buffer-locality bookkeeping (every access pays
    ACT + RD/WR + PRE), so it degrades far less with shallow queues but
    caps stream bandwidth at the tRC-limited random-row rate. The
    difference from the open-page policy is exactly two hooks — the
    keep-open-for-hits check and the post-access precharge — everything
    else (bank FSMs, per-PC clocks, refresh) is shared.
    """

    page_policy = "closed (auto-precharge after access)"
    keep_open_for_hits = False

    def _after_column(self, tx: Txn, b: _BankState, cmd_t: float) -> None:
        pr = self.pre_ready(b, cmd_t)
        b.t_rp_done = pr + self.t.tRP
        b.open_row = None
        self.counts["PRE"] += 1
        self.counts["ca_commands"] += 1
        if self.trace is not None:
            self.trace.append(CmdRecord(pr, "PRE", tx.bank,
                                        self._pc(tx.bank), tx.sid, -1,
                                        -1.0, -1.0))


class FRFCFSWriteDrainPolicy(FRFCFSOpenPagePolicy):
    """FR-FCFS with watermark-based write draining (posted writes).

    Conventional HBM controllers treat writes as *posted* traffic: they
    sit in a write buffer and are released in batches, so the tRTW/tWTRS
    bus turnarounds are paid once per burst instead of once per write.
    The state machine here:

    * *Drain entry*: queued-write occupancy >= ``high_watermark`` (and,
      under sustained mixed load, only after at least ``high_watermark``
      reads were serviced since the last drain — symmetric batching, so
      a 50/50 backlog alternates read and write bursts instead of
      re-triggering drains back to back).
    * *Drain exit* (hysteresis with a hard cap): occupancy fell to
      ``low_watermark``, or ``drain_budget`` writes were drained this
      batch. The cap is the read-starvation bound the tests pin: reads
      are blocked by at most ``drain_budget`` writes per drain.
    * *Outside drain*: reads own the column bus. A write becomes
      individually eligible only once aged past ``write_age_ns`` (and
      only while occupancy is below the watermark) — which is what
      stops the plain-FR-FCFS pathology of slotting a lone write into
      every read-stream gap and paying both turnaround penalties for a
      single burst. Writes remain the *fallback* group throughout:
      row-prep keeps rows open for queued hits of either kind, so a
      kind-filtered head group must never stall a bus the fallback
      could use (liveness).

    Table IV cost over plain FR-FCFS: a 2-state drain FSM, two occupancy
    comparators, drained/serviced batch counters, and a write-age
    timestamp compare — reported via ``state_footprint()`` so the
    complexity census stays honest.
    """

    count_keys = FRFCFSOpenPagePolicy.count_keys + ("drain_entries",)

    def __init__(self, timing: HBM4Timing | None = None,
                 geometry: ChannelGeometry | None = None,
                 high_watermark: int = 8, low_watermark: int = 2,
                 drain_budget: int = 16, write_age_ns: float = 400.0):
        super().__init__(timing, geometry)
        if not 0 < low_watermark <= high_watermark:
            raise ValueError(
                f"need 0 < low_watermark <= high_watermark, got "
                f"{low_watermark}/{high_watermark}")
        if drain_budget < 1:
            raise ValueError(f"drain_budget must be >= 1, got {drain_budget}")
        self.high_watermark = high_watermark
        self.low_watermark = low_watermark
        self.drain_budget = drain_budget
        self.write_age_ns = write_age_ns

    def begin(self, counts: dict) -> None:
        super().begin(counts)
        self.draining = False
        self._drained = 0            # writes issued in the current batch
        self._reads_since = self.high_watermark   # allow an initial drain

    def _column_groups(self, window: list[Txn],
                       now: float) -> list[list[Txn]]:
        writes = [tx for tx in window if tx.is_write]
        reads = [tx for tx in window if not tx.is_write]
        if self.draining and (self._drained >= self.drain_budget
                              or len(writes) <= self.low_watermark):
            self.draining = False
            self._reads_since = 0
        if (not self.draining and len(writes) >= self.high_watermark
                and (not reads
                     or self._reads_since >= self.high_watermark)):
            self.draining = True
            self._drained = 0
            self.counts["drain_entries"] += 1
        if self.draining:
            return [writes, reads]
        if not reads:
            # Pure posted traffic: only aged writes issue — young ones
            # wait for a batch (or for the core's idle-advance to age
            # them). No reads queued means nothing can deadlock behind
            # the held writes.
            return [[tx for tx in writes
                     if now - tx.arrival_ns >= self.write_age_ns]]
        head = reads
        if len(writes) < self.high_watermark:
            # Overdue trickle writes ride along with the reads; at or
            # above the watermark they wait for the (imminent) batch
            # drain instead of fragmenting it.
            head = reads + [tx for tx in writes
                            if now - tx.arrival_ns >= self.write_age_ns]
        return [head, writes]

    def _after_column(self, tx: Txn, b: _BankState, cmd_t: float) -> None:
        if tx.is_write:
            if self.draining:
                self._drained += 1
        else:
            self._reads_since += 1

    def state_footprint(self) -> dict:
        fp = super().state_footprint()
        fp["name"] = "frfcfs_writedrain"
        fp["scheduling"] = fp["scheduling"] + (
            "write draining (hi/lo watermark)",)
        fp["aux_state"] = ("drain-mode FSM (2 states)",
                           "write-occupancy hi/lo comparators",
                           "drained / reads-serviced batch counters",
                           "write-age timestamp compare")
        return fp


class HBM4SIDGroupPolicy(FRFCFSOpenPagePolicy):
    """FR-FCFS with tCCDR-aware cross-SID burst grouping.

    Column bursts addressed to different SIDs (stack levels) of the same
    pseudo channel must be spaced by tCCDR > tCCDS. This policy keeps a
    last-issued-SID register per PC and prefers a same-SID candidate
    whenever it is ready within the ``tCCDR - tCCDS`` window a switch
    would forfeit, coalescing bursts into same-SID runs (the
    rank-grouping trick of conventional multi-rank controllers).

    Measured honestly (benchmarks/policy_sweep.py): with the Table V
    timings, FR-FCFS's readiness-driven pick already encodes the tCCDR
    penalty, so explicit grouping is bandwidth-*neutral* (bounded by the
    margin rule) — what it buys is fewer SID switch *events*
    (``sid_switches`` stat; rank-switch IO/ODT stress) and a guaranteed
    bound rather than a greedy accident. That neutrality is itself a
    design-space result the sweep reports: conventional-MC scheduling
    tricks buy margins, not multiples — RoMe's granularity change is
    what moves the needle (Table IV / Fig 9).

    Table IV cost over plain FR-FCFS: one SID register per PC plus a
    readiness comparator — see ``state_footprint()``.
    """

    count_keys = FRFCFSOpenPagePolicy.count_keys + ("sid_switches",)

    def begin(self, counts: dict) -> None:
        super().begin(counts)
        self.pc_cur_sid = [-1] * self.g.pseudo_channels

    def _pick_column(self, window: list[Txn], now: float):
        best, best_t = super()._pick_column(window, now)
        if best is None:
            return best, best_t
        pc = self._pc(best.bank)
        cur = self.pc_cur_sid[pc]
        if cur < 0 or best.sid == cur:
            return best, best_t
        # Switching SIDs forfeits tCCDR - tCCDS of the next same-SID
        # burst; take a same-SID candidate if one is ready inside that
        # window.
        margin = self.t.tCCDR - self.t.tCCDS
        same, same_t = self._earliest_hit(
            [tx for tx in window
             if tx.sid == cur and self._pc(tx.bank) == pc])
        if same is not None and same_t <= best_t + margin + 1e-12:
            return same, same_t
        return best, best_t

    def _after_column(self, tx: Txn, b: _BankState, cmd_t: float) -> None:
        pc = self._pc(tx.bank)
        if 0 <= self.pc_cur_sid[pc] != tx.sid:
            self.counts["sid_switches"] += 1
        self.pc_cur_sid[pc] = tx.sid

    def state_footprint(self) -> dict:
        fp = super().state_footprint()
        fp["name"] = "frfcfs_sidgroup"
        fp["scheduling"] = fp["scheduling"] + (
            "cross-SID burst grouping (tCCDR-aware)",)
        fp["aux_state"] = ("last-SID register per PC",
                           "same-SID readiness comparator")
        return fp


# ===========================================================================
# RoMe
# ===========================================================================

class RoMeRowPolicy(SchedulerPolicy):
    """RoMe MC: oldest-first with VBA interleaving (§V-A).

    Three commands (RD_row, WR_row, REF), 4-state VBA FSM. All intra-row
    sequencing is delegated to the command generator (statically timed),
    so the policy only enforces the ten Table III row-to-row gaps; per-VBA
    busy-until and refresh-until complete the FSM
    (Idle / Reading / Writing / Refreshing).
    """

    count_keys = ("ACT", "RD", "WR", "PRE", "REFpb", "row_commands",
                  "ca_commands")
    page_policy = "none (always precharge after row access)"

    #: Refresh priorities a variant may select. "demand" is the paper MC
    #: (refresh postponed under queued demand, bounded by the core's
    #: ``max_ref_postpone``); "eager" never postpones — the channel
    #: binding maps it to ``max_ref_postpone=1``.
    REFRESH_PRIORITIES = ("demand", "eager")

    def __init__(self, timing: RoMeTiming | None = None,
                 geometry: ChannelGeometry | None = None,
                 n_vbas: int = 16,
                 variant: str | None = None,
                 refresh_priority: str = "demand"):
        if refresh_priority not in self.REFRESH_PRIORITIES:
            raise ValueError(
                f"refresh_priority must be one of {self.REFRESH_PRIORITIES}, "
                f"got {refresh_priority!r}")
        self.t = timing or RoMeTiming()
        self.g = geometry or ChannelGeometry()
        self.variant = variant
        self.refresh_priority = refresh_priority
        self.n_vbas = n_vbas
        self.row_bytes = self.g.row_bytes * 2 * self.g.pseudo_channels  # 4 KB
        self._cg = CommandGenerator()
        self._sched_rd = self._cg.expand(is_write=False)
        self._sched_wr = self._cg.expand(is_write=True)
        self._bursts = 2 * self._cg.bursts_per_bank()
        # VBA-paired refresh every 2*tREFIpb, rotating (§V-B).
        self.ref_period = 2 * self.t.tREFIpb
        self.n_ref_units = n_vbas
        self.bytes_per_txn = self.row_bytes
        self._ref_cap = self.t.max_concurrent_refreshing()

    def begin(self, counts: dict) -> None:
        self.counts = counts
        self.vba_busy_until = [0.0] * self.n_vbas  # Reading/Writing/Refreshing
        self.last_cmd_t = -1e18
        self.last_cmd_write = False
        self.last_cmd_vba = -1
        self.last_cmd_sid = -1
        self.ch_last_ref = -1e18       # cross-VBA REFpb release spacing
        self._ref_ends = []            # active refresh windows (FSM cap)

    def start_time(self, tx: Txn, at: float) -> float:
        t = self.t
        r = max(at, tx.arrival_ns, self.vba_busy_until[tx.bank])
        if self.last_cmd_t > -1e17:
            gap = t.gap_ns(self.last_cmd_write, tx.is_write,
                           same_vba=(tx.bank == self.last_cmd_vba),
                           same_sid=(tx.sid == self.last_cmd_sid))
            r = max(r, self.last_cmd_t + gap)
        return r

    def issue_refresh(self, unit: int, due: float) -> None:
        # VBA-paired refresh, anchored at due time (may overlap across
        # VBAs — the paper's "up to three refreshing simultaneously").
        # Each VBA-refresh is two REFpb commands tRREFpb apart, so
        # successive VBA-refresh *starts* keep 2*tRREFpb on the C/A
        # path, and at most max_concurrent_refreshing() windows overlap
        # (the MC provisions exactly that many refresh FSMs) — both
        # found by the trace sanitizer.
        t = self.t
        start = max(due, self.vba_busy_until[unit],
                    self.ch_last_ref + 2 * t.tRREFpb)
        window = t.tRFCpb + t.tRREFpb
        cap = self._ref_cap
        in_flight = sorted(e for e in self._ref_ends if e > start)
        if len(in_flight) >= cap:
            # Wait until enough windows end that ours is the cap-th.
            start = in_flight[len(in_flight) - cap]
        self.vba_busy_until[unit] = start + window
        self.ch_last_ref = start
        self._ref_ends.append(start + window)
        if len(self._ref_ends) > 8:
            del self._ref_ends[0]
        self.counts["REFpb"] += 2
        self.counts["row_commands"] += 1
        self.counts["ca_commands"] += 1
        if self.trace is not None:
            self.trace.append(CmdRecord(start, "REF", unit, 0, -1, -1,
                                        -1.0, -1.0))

    def issue(self, window: list[Txn], now: float):
        t = self.t
        counts = self.counts
        # Oldest-first with VBA interleaving: prefer a request whose VBA
        # differs from the last-issued one if it is ready no later.
        cands = [(self.start_time(tx, now), i, tx)
                 for i, tx in enumerate(window)]
        cands.sort(key=lambda c: (c[0], c[1]))
        best_t, _, best = cands[0]
        for ct, _, tx in cands:
            if tx.bank != self.last_cmd_vba and ct <= best_t + 1e-9:
                best_t, best = ct, tx
                break

        sched = self._sched_wr if best.is_write else self._sched_rd
        svc = t.tWR_row if best.is_write else t.tRD_row
        self.vba_busy_until[best.bank] = best_t + svc
        self.last_cmd_t = best_t
        self.last_cmd_write = best.is_write
        self.last_cmd_vba = best.bank
        self.last_cmd_sid = best.sid
        counts["ACT"] += 2
        counts["PRE"] += 2
        counts["WR" if best.is_write else "RD"] += self._bursts
        counts["row_commands"] += 1
        counts["ca_commands"] += 1
        if self.trace is not None:
            self.trace.append(CmdRecord(
                best_t, "WR_row" if best.is_write else "RD_row",
                best.bank, 0, best.sid, best.row,
                best_t + sched.first_data_ns, best_t + sched.last_data_ns))
        completions = [(best, best_t + sched.last_data_ns)]
        now = max(now, best_t)
        return now, True, completions

    # -- introspection -----------------------------------------------------

    def state_footprint(self) -> dict:
        name = "rome_oldest_first"
        if self.variant:
            name += f"_{self.variant}"
        fp = {
            "name": name,
            "timing_params": self.t.n_managed(),
            # 2 VBAs operating + up to 3 refreshing simultaneously.
            "fsm_instances": 2 + self.t.max_concurrent_refreshing(),
            "states_per_fsm": len(ROME_BANK_STATES),
            "page_policy": self.page_policy,
            "scheduling": ("VBA interleaving",),
        }
        if self.refresh_priority != "demand":
            # The census is invariant across variants — the MC sheds no
            # FSM state by refreshing eagerly; only the governor knob
            # differs, and the footprint says so.
            fp["scheduling"] = fp["scheduling"] + (
                f"refresh priority: {self.refresh_priority}",)
        return fp
