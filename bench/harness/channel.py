"""A plain channel model for the reference, written from the
configuration's stated controller and timing tables.

:func:`decompose` splits a step's extent records into per-channel
transactions by the stated stripe map; :func:`simulate` schedules one
channel's transactions from an empty, refreshed-on-time state under the
stated policy and returns each transaction's finish time (its last data
beat), in input order:

* ``hbm4``: FR-FCFS over a ``queue_depth`` window, open page. Row work
  (PRE of a row no queued hit needs, else ACT) proceeds for the oldest
  miss of each bank; the column bus takes the earliest-ready row hit,
  the oldest on ties. Bank, bank-group and pseudo-channel clocks follow
  the DRAM table (tRCD, tRAS, tRP, tRTP, tWR, tRRD, tFAW, tCCD, tRTW,
  tWTR, CL, CWL) and the data bus of each pseudo channel.
* ``rome``: oldest-first over a ``queue_depth`` window, preferring a
  VBA other than the last one when it is ready no later; one row
  command per transaction, spaced by the Table III row-to-row gaps, its
  last data beat at the command generator's offset.

Both rotate per-unit refresh at the stated interval, postponed
while the unit has queued demand, up to ``max_ref_postpone`` due.

The model imports nothing of the program; it holds the program's cycle
engine to the configuration's semantics, transaction by transaction.
"""
from __future__ import annotations

import numpy as np

NEVER = -1e18


# ---------------------------------------------------------------------------
# Stripe map: extent records -> per-channel transactions
# ---------------------------------------------------------------------------

def decompose(addr, nbytes, is_write, arrival, mem: dict) -> dict:
    """``{channel: (arrival, bank, row, col, is_write)}`` arrays, each
    channel's transactions in record order, units ascending within a
    record. ``mem`` is the configuration's ``memory`` group with
    ``n_channels``."""
    g, nch = mem["stripe_bytes"], mem["n_channels"]
    first = addr // g
    count = (addr + nbytes - 1) // g - first + 1
    rec = np.repeat(np.arange(addr.size), count)
    unit = first[rec] + (np.arange(rec.size)
                         - np.repeat(np.cumsum(count) - count, count))
    ch, u = unit % nch, unit // nch
    ctl = mem["controller"]
    if ctl["policy"] == "rome":
        nv = ctl["n_vbas"]
        bank, row, col = u % nv, u // nv, np.zeros_like(u)
    else:
        geo = ctl["geometry"]
        pcs, nbg, bpg = (geo["pseudo_channels"], geo["bank_groups"],
                         geo["banks_per_group"])
        cols = geo["row_bytes"] // geo["col_bytes"]
        pc, j = u % pcs, u // pcs
        bg, k = j % nbg, j // nbg
        col, rseq = k % cols, k // cols
        row = rseq // bpg
        bank = pc * nbg * bpg + bg * bpg + rseq % bpg
    out = {}
    for c in np.unique(ch).tolist():
        m = ch == c
        r = rec[m]
        out[c] = (arrival[r], bank[m], row[m], col[m], is_write[r])
    return out


# ---------------------------------------------------------------------------
# The event loop
# ---------------------------------------------------------------------------

def simulate(txns, mem: dict) -> np.ndarray:
    """Finish time of each transaction of one channel, in input order."""
    ctl = mem["controller"]
    arr, bank, row, _, wr = (np.asarray(x).tolist() for x in txns)
    n = len(arr)
    ctrl = (_FRFCFS if ctl["policy"] == "hbm4" else _RoMe)(ctl, arr, bank,
                                                           row, wr)
    period = ctrl.period
    depth, max_post = ctl["queue_depth"], ctl["max_ref_postpone"]
    finish = [0.0] * n
    live = sorted(range(n), key=arr.__getitem__)
    now = 0.0
    next_ref, unit, backlog = period, 0, 0
    while live:
        qwin = live[:depth]
        while next_ref <= now:
            backlog += 1
            next_ref += period
        while backlog > 0:
            if (backlog < max_post
                    and any(bank[i] == unit for i in qwin)):
                break
            ctrl.refresh(unit, next_ref - backlog * period)
            unit = (unit + 1) % ctrl.n_units
            backlog -= 1
        window = [i for i in qwin if arr[i] <= now]
        if not window:
            now = max(now + 1e-9, min(arr[live[0]], next_ref))
            continue
        now, issued, done = ctrl.issue(window, now)
        for i, fin in done:
            finish[i] = fin
            live.remove(i)
        if not issued:
            later = [arr[i] for i in qwin if arr[i] > now]
            cand = min(later) if later else now + period
            now = max(now + 1e-9, min(cand, next_ref))
    return np.array(finish, np.float64)


class _FRFCFS:
    """Open-page FR-FCFS over 2 pseudo channels of one HBM4 channel."""

    def __init__(self, ctl: dict, arr, bank, row, wr):
        geo, t = ctl["geometry"], ctl["timing"]
        self.t = t
        self.arr, self.bank, self.row, self.wr = arr, bank, row, wr
        self.banks_per_pc = geo["bank_groups"] * geo["banks_per_group"]
        self.bpg = geo["banks_per_group"]
        pc_gbps = geo["data_pins"] * geo["data_rate_gbps"] / 8.0 \
            / geo["pseudo_channels"]
        self.burst = geo["col_bytes"] / pc_gbps
        self.period = t["tREFI"] / t["refresh_rotation_banks"]
        nb = self.n_units = self.banks_per_pc * geo["pseudo_channels"]
        npc = geo["pseudo_channels"]
        self.open = [None] * nb
        self.t_act = [NEVER] * nb
        self.t_rd = [NEVER] * nb
        self.t_wr_data = [NEVER] * nb
        self.rp_done = [0.0] * nb
        self.ref_done = [0.0] * nb
        self.bus_free = [0.0] * npc
        self.last_col = [NEVER] * npc
        self.last_col_bg = [{} for _ in range(npc)]
        self.last_write = [False] * npc
        self.last_rd_cmd = [NEVER] * npc
        self.last_wr_end = [NEVER] * npc
        self.last_wr_end_bg = [{} for _ in range(npc)]
        self.last_act = [NEVER] * npc
        self.last_act_bg = [{} for _ in range(npc)]
        self.acts = [[] for _ in range(npc)]
        self.last_ref = NEVER

    def _where(self, b):
        return b // self.banks_per_pc, (b % self.banks_per_pc) // self.bpg

    def _pre_ready(self, b, at):
        t = self.t
        return max(at, self.t_act[b] + t["tRAS"], self.t_rd[b] + t["tRTP"],
                   self.t_wr_data[b] + t["tWR"])

    def refresh(self, b, due):
        t = self.t
        start = max(due, self.rp_done[b], self.ref_done[b],
                    self.last_ref + t["tRREFpb"])
        if self.open[b] is not None:
            self.rp_done[b] = self._pre_ready(b, start) + t["tRP"]
            self.open[b] = None
            start = self.rp_done[b]
        self.ref_done[b] = start + t["tRFCpb"]
        self.last_ref = start

    def _col_ready(self, i):
        t, b, w = self.t, self.bank[i], self.wr[i]
        pc, bg = self._where(b)
        r = max(self.arr[i],
                self.t_act[b] + (t["tRCDWR"] if w else t["tRCDRD"]),
                self.ref_done[b], self.last_col[pc] + t["tCCDS"],
                self.last_col_bg[pc].get(bg, NEVER) + t["tCCDL"])
        if w and not self.last_write[pc]:
            r = max(r, self.last_rd_cmd[pc] + t["tRTW"])
        if not w:
            if self.last_write[pc]:
                r = max(r, self.last_wr_end[pc] + t["tWTRS"])
            wbg = self.last_wr_end_bg[pc].get(bg)
            if wbg is not None:
                r = max(r, wbg + t["tWTRL"])
        return r

    def issue(self, window, now):
        t, arr, bank, row = self.t, self.arr, self.bank, self.row
        issued = False
        prepared = set()
        for i in window:                     # row work
            b = bank[i]
            if self.open[b] == row[i] or b in prepared:
                continue
            if self.open[b] is not None:
                if any(bank[h] == b and row[h] == self.open[b]
                       for h in window):
                    prepared.add(b)
                    continue
                pr = self._pre_ready(b, max(arr[i], self.ref_done[b]))
                self.rp_done[b] = pr + t["tRP"]
                self.open[b] = None
                now = max(now, pr)
            else:
                pc, bg = self._where(b)
                acts = self.acts[pc]
                ar = max(arr[i], self.ref_done[b], self.rp_done[b],
                         self.last_act[pc] + t["tRRDS"],
                         self.last_act_bg[pc].get(bg, NEVER) + t["tRRDL"])
                if len(acts) >= 4:
                    ar = max(ar, acts[-4] + t["tFAW"])
                self.t_act[b] = ar
                self.open[b] = row[i]
                self.last_act[pc] = ar
                self.last_act_bg[pc][bg] = ar
                acts.append(ar)
                del acts[:-8]
                now = max(now, ar)
            prepared.add(b)
            issued = True
        best = best_t = None                 # column work
        for i in window:
            b = bank[i]
            if self.open[b] == row[i] and self.t_act[b] <= 1e17:
                r = self._col_ready(i)
                if best_t is None or r < best_t - 1e-12:
                    best, best_t = i, r
        if best is None:
            return now, issued, []
        b, w = bank[best], self.wr[best]
        pc, bg = self._where(b)
        lat = t["tCWL"] if w else t["tCL"]
        data_start = max(best_t + lat, self.bus_free[pc])
        cmd = data_start - lat
        end = data_start + self.burst
        self.bus_free[pc] = end
        self.last_col[pc] = cmd
        self.last_col_bg[pc][bg] = cmd
        self.last_write[pc] = w
        if w:
            self.t_wr_data[b] = end
            self.last_wr_end[pc] = end
            self.last_wr_end_bg[pc][bg] = end
        else:
            self.t_rd[b] = cmd
            self.last_rd_cmd[pc] = cmd
        return max(now, cmd), True, [(best, end)]


def row_data_offsets(dram: dict, cols_per_row: int) -> tuple[float, float]:
    """Last data beat of a read and of a write row command, after its
    issue: two ACTs tRRDS apart (the first delayed by tRRDS - tCCDS),
    ``cols_per_row`` bursts to each bank alternating at tCCDS, then
    CL/CWL and one burst."""
    act0 = dram["tRRDS"] - dram["tCCDS"]
    act1 = act0 + dram["tRRDS"]
    out = []
    for trcd, cl in ((dram["tRCDRD"], dram["tCL"]),
                     (dram["tRCDWR"], dram["tCWL"])):
        s = max(act0 + trcd, act1 + trcd - dram["tCCDS"])
        last = s + 2 * (cols_per_row - 1) * dram["tCCDS"] + dram["tCCDS"]
        out.append(last + cl + dram["tCCDS"])
    return out[0], out[1]


class _RoMe:
    """Oldest-first row controller with VBA interleaving."""

    def __init__(self, ctl: dict, arr, bank, row, wr):
        t = self.t = ctl["timing"]
        self.arr, self.bank, self.wr = arr, bank, wr
        self.n_units = ctl["n_vbas"]
        self.period = 2 * t["tREFIpb"]
        self.rd_last, self.wr_last = row_data_offsets(ctl["dram"],
                                                      ctl["cols_per_row"])
        self.ref_window = t["tRFCpb"] + t["tRREFpb"]
        self.ref_cap = ctl["max_refreshing"]
        self.busy = [0.0] * self.n_units
        self.last_t = NEVER
        self.last_w = False
        self.last_vba = -1
        self.last_ref = NEVER
        self.ref_ends: list = []

    def _gap(self, w, same_vba):
        t = self.t
        if same_vba:
            return t["tWR_row"] if self.last_w else t["tRD_row"]
        return t["t" + ("W" if self.last_w else "R") + "2"
                 + ("W" if w else "R") + "S"]

    def refresh(self, v, due):
        start = max(due, self.busy[v], self.last_ref + 2 * self.t["tRREFpb"])
        later = sorted(e for e in self.ref_ends if e > start)
        if len(later) >= self.ref_cap:
            start = later[len(later) - self.ref_cap]
        self.busy[v] = start + self.ref_window
        self.last_ref = start
        self.ref_ends.append(start + self.ref_window)
        del self.ref_ends[:-8]

    def issue(self, window, now):
        cands = []
        for k, i in enumerate(window):
            v, w = self.bank[i], self.wr[i]
            r = max(now, self.arr[i], self.busy[v])
            if self.last_t > -1e17:
                r = max(r, self.last_t + self._gap(w, v == self.last_vba))
            cands.append((r, k, i))
        cands.sort()
        best_t, _, best = cands[0]
        for r, _, i in cands:
            if self.bank[i] != self.last_vba and r <= best_t + 1e-9:
                best_t, best = r, i
                break
        v, w = self.bank[best], self.wr[best]
        self.busy[v] = best_t + (self.t["tWR_row"] if w
                                 else self.t["tRD_row"])
        self.last_t, self.last_w, self.last_vba = best_t, w, v
        return (max(now, best_t), True,
                [(best, best_t + (self.wr_last if w else self.rd_last))])


def peak_channel_gbps(mem: dict) -> float:
    """One channel's peak data bandwidth from its pins and pin rate."""
    geo = mem["controller"]["geometry"]
    return geo["data_pins"] * geo["data_rate_gbps"] / 8.0
