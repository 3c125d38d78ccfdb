"""A whole run with the timed path broken underneath reads
``correct: false``, once for each fault the cells can have. (They run
on one chip and exchange nothing between chips.)"""
from dataclasses import replace

import pytest

from _cell import fails_on, run

HBM4 = "dsv3-hbm4-x8.decode-poisson"
DECODE = "dsv3-rome-x9.decode-poisson"
FLEET = "dsv3-rome-x9.fleet8-bursty"


def test_answer_altered_where_produced_cycle(monkeypatch):
    from repro.core.system_sim import SystemSim
    orig = SystemSim._run_cycle

    def late(self, *a, **k):
        res = orig(self, *a, **k)
        return replace(res, total_ns=res.total_ns + 1.0)
    monkeypatch.setattr(SystemSim, "_run_cycle", late)
    assert "sched_bad" in fails_on(run(DECODE))


def test_answer_altered_where_produced_analytic(monkeypatch):
    from repro.core.system_sim import SystemSim
    orig = SystemSim._analytic_result

    def cheap(self, *a, **k):
        res = orig(self, *a, **k)
        return replace(res, total_ns=res.total_ns * 0.95)
    monkeypatch.setattr(SystemSim, "_analytic_result", cheap)
    assert "floor_bad" in fails_on(run(FLEET))


def test_census_altered_where_produced(monkeypatch):
    import numpy as np
    from repro.core.system_sim import SystemSim
    orig = SystemSim._analytic_result

    def shifted(self, *a, **k):
        res = orig(self, *a, **k)
        return replace(res, channel_bytes=np.roll(res.channel_bytes, 1))
    monkeypatch.setattr(SystemSim, "_analytic_result", shifted)
    assert "census_bad" in fails_on(run(FLEET))


def test_transaction_finishing_early(monkeypatch):
    import repro.core.system_sim as ss
    orig = ss.run_channels

    def early(*a, **k):
        sims = orig(*a, **k)
        for r in sims:
            if r.finish_ns.size > 1:
                r.finish_ns[r.finish_ns.size // 2] -= 0.5
        return sims
    monkeypatch.setattr(ss, "run_channels", early)
    assert "sched_bad" in fails_on(run(DECODE))


def _oldest_hit(self, window, now):
    """FR-FCFS without first-ready: the oldest activated row hit."""
    for tx in window:
        b = self.banks[tx.bank]
        if b.open_row == tx.row and b.t_act <= 1e17:
            return tx, self.col_ready(tx.bank, b, tx.is_write, tx.sid,
                                      tx.arrival_ns)
    return None, None


def _stalled(orig):
    def start_time(self, tx, at):
        return orig(self, tx, at) + 1.0
    return start_time


@pytest.mark.parametrize("fault", ["oldest_hit", "closed_page",
                                   "row_stall"])
def test_controller_departs_from_its_policy(monkeypatch, fault):
    from repro.core.sched import policies
    if fault == "oldest_hit":
        monkeypatch.setattr(policies.FRFCFSOpenPagePolicy, "_pick_column",
                            _oldest_hit)
    elif fault == "closed_page":
        # every column access closes its row: the batching of row-hit
        # columns is lost
        monkeypatch.setattr(policies.FRFCFSOpenPagePolicy, "_after_column",
                            policies.HBM4ClosedPagePolicy._after_column)
    else:
        monkeypatch.setattr(policies.RoMeRowPolicy, "start_time",
                            _stalled(policies.RoMeRowPolicy.start_time))
    cell = DECODE if fault == "row_stall" else HBM4
    assert "sched_bad" in fails_on(run(cell, seconds=3.0))


def test_transactions_misplaced(monkeypatch):
    import repro.core.system_sim as ss
    monkeypatch.setattr(ss, "rome_unit_location",
                        lambda u, n: ((u + 1) % n, u // n, 0))
    assert "txn_bad" in fails_on(run(DECODE))


@pytest.mark.parametrize("cell", [DECODE, FLEET])
def test_half_the_batch_left_out(monkeypatch, cell):
    from repro.serve.replay.recorder import ServeTraceRecorder
    from repro.workloads import ExtentStream
    orig = ServeTraceRecorder.step

    def half(self, now_ns):
        st = orig(self, now_ns)
        if st is None or len(st.active) < 2:
            return st
        drop = set(st.active[::2])
        return replace(st, stream=ExtentStream(
            r for r in st.stream if r.stream_id not in drop))
    monkeypatch.setattr(ServeTraceRecorder, "step", half)
    assert "bytes_bad" in fails_on(run(cell))


def test_kv_state_left_unchanged(monkeypatch):
    from repro.serve.kv_cache import RowPagedKVCache
    orig = RowPagedKVCache.append_token

    def stuck(self, seq_id):
        before = int(self.seq_lens[seq_id])
        out = orig(self, seq_id)
        self.seq_lens[seq_id] = before
        return out
    monkeypatch.setattr(RowPagedKVCache, "append_token", stuck)
    assert "bytes_bad" in fails_on(run(DECODE))


def test_replica_clock_left_unchanged(monkeypatch):
    from repro.serve.cluster.sim import Replica
    orig = Replica.finish_step

    def stuck(self, st, dur_ns):
        clock = self.clock
        end = orig(self, st, dur_ns)
        if self.n_steps % 7 == 0:
            self.clock = clock
        return end
    monkeypatch.setattr(Replica, "finish_step", stuck)
    assert "clock_bad" in fails_on(run(FLEET))
