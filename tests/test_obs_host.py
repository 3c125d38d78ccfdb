"""repro.obs.host: host-clock spans and counters.

Off, the tracer is a shared null context and an empty snapshot; on, it
accounts self and total time over nested spans, refuses undeclared
names, never changes a replay's or a fleet's result, and every name its
table declares is emitted by a small RoMe or HBM4 replay or fleet run.
"""
from __future__ import annotations

import pytest

from repro.core import sched
from repro.obs import host
from repro.serve.cluster import ClusterSim
from repro.serve.replay import build_replay

REPLAY_KW = dict(policy="rome_qd2", rate_rps=2e5, n_requests=3, seed=0,
                 scale=2 ** -14, length_scale=1 / 32, n_channels=2,
                 sim_mode="cycle", kind="bursty", burst_size=3)
HBM4_KW = REPLAY_KW | dict(policy="hbm4_frfcfs")
FLEET_KW = dict(policy="rome_qd2", n_replicas=2, n_requests=6,
                rate_rps=2e5, kind="poisson", seed=0, scale=2 ** -12,
                sim_mode="hybrid", n_channels=2, length_scale=1 / 32,
                router="round_robin")


@pytest.fixture(autouse=True)
def tracing_off():
    host.disable()
    yield
    host.disable()


class Clock:
    """A settable integer-nanosecond clock."""

    def __init__(self):
        self.ns = 0

    def __call__(self) -> int:
        return self.ns


class Steps:
    """Collector that keeps every priced step's result."""

    probe = None

    def __init__(self):
        self.results = []

    def on_step(self, st, res, now, dur, replica: int = 0):
        self.results.append(res)

    def fold_reports(self, reports):
        pass

    def add_request(self, *args, **kwargs):
        pass


def _run(kind: str, traced: bool):
    steps = Steps()
    if traced:
        host.enable()
    if kind in ("replay", "hbm4"):
        kw = REPLAY_KW if kind == "replay" else HBM4_KW
        eng, _ = build_replay(collector=steps, **kw)
        out = eng.run()
    else:
        out = ClusterSim(collector=steps, **FLEET_KW).run()
    return out, steps.results, host.disable()


@pytest.fixture(scope="module")
def runs():
    return {kind: {"bare": _run(kind, False), "traced": _run(kind, True)}
            for kind in ("replay", "fleet", "hbm4")}


def test_off_is_a_shared_null_span_and_an_empty_snapshot():
    assert host.span("recorder.step") is host.NULL_SPAN
    assert host.span("no.such.span") is host.NULL_SPAN
    with host.span("census"):
        host.count("cycle.txns", 5)
    assert host.snapshot() == {"spans": {}, "counters": {}}


def test_nested_spans_self_and_total_on_a_fake_clock():
    clock = Clock()
    host.enable(clock=clock)
    with host.span("pricing"):
        clock.ns = 10
        with host.span("census"):
            clock.ns = 30
        clock.ns = 35
        inside = host.snapshot()          # pricing still open
        with host.span("pricing"):        # nested in itself
            clock.ns = 40
        clock.ns = 50
    clock.ns = 70                         # outside every span: uncharged
    after = host.disable()
    s = inside["spans"]
    assert s["pricing"] == {"self_s": pytest.approx(15e-9),
                            "total_s": pytest.approx(35e-9), "n": 1}
    assert s["census"] == {"self_s": pytest.approx(20e-9),
                           "total_s": pytest.approx(20e-9), "n": 1}
    s = after["spans"]
    assert s["pricing"] == {"self_s": pytest.approx(30e-9),
                            "total_s": pytest.approx(50e-9), "n": 2}
    d = host.diff(inside, after)
    assert d["spans"] == {"pricing": {"self_s": pytest.approx(15e-9),
                                      "total_s": pytest.approx(15e-9),
                                      "n": 1}}
    assert host.span_s(d, "census") is None
    assert host.span_s(d, "pricing", "total_s") == pytest.approx(15e-9)
    assert host.counter(d, "cycle.txns") == 0


def test_counters_add_and_diff():
    host.enable()
    host.count("cycle.txns", 7)
    first = host.snapshot()
    host.count("cycle.txns", 5)
    host.count("cycle.iters", 3)
    d = host.diff(first, host.snapshot())
    assert d["counters"] == {"cycle.txns": 5, "cycle.iters": 3}
    assert host.counter(d, "cycle.txns") == 5


def test_undeclared_names_raise_when_on():
    host.enable()
    with pytest.raises(host.UndeclaredName):
        host.span("no.such.span")
    with pytest.raises(host.UndeclaredName):
        host.count("no.such.counter")
    with pytest.raises(host.UndeclaredName):
        host.count("census")              # a span, not a counter
    with pytest.raises(host.UndeclaredName):
        host.span_s(host.snapshot(), "cycle.txns")
    with pytest.raises(host.UndeclaredName):
        host.counter(host.snapshot(), "no.such.counter")


@pytest.mark.parametrize("kind", ["replay", "fleet", "hbm4"])
def test_tracing_never_changes_a_result(runs, kind):
    bare, bare_steps, off = runs[kind]["bare"]
    traced, traced_steps, on = runs[kind]["traced"]
    assert off == {"spans": {}, "counters": {}} and on["spans"]
    assert bare.summary() == traced.summary()
    assert ([(r.total_ns, r.mode, r.bytes_moved) for r in bare_steps]
            == [(r.total_ns, r.mode, r.bytes_moved) for r in traced_steps])


def test_every_declared_name_is_emitted_and_nothing_else(runs):
    seen = set()
    for kind in ("replay", "fleet", "hbm4"):
        snap = runs[kind]["traced"][2]
        seen |= set(snap["spans"]) | set(snap["counters"])
        for name in snap["spans"]:
            assert host.DECLARED[name][0] == host.SPAN
        for name in snap["counters"]:
            assert host.DECLARED[name][0] == host.COUNTER
    assert seen == set(host.DECLARED)


def test_cycle_counters_match_the_cycle_steps(runs):
    _, results, snap = runs["replay"]["traced"]
    cycle = [r for r in results if r.mode == "cycle"]
    assert cycle and len(cycle) == len(results)
    txns = sum(len(v) for r in cycle for v in r.channel_txns.values())
    assert host.counter(snap, "cycle.txns") == txns
    assert host.counter(snap, "steps.cycle") == len(cycle)
    # every transaction takes at least one event-loop iteration
    assert host.counter(snap, "cycle.iters") >= txns
    _, results, snap = runs["fleet"]["traced"]
    assert host.counter(snap, "steps.analytic") == sum(
        r.mode == "analytic" for r in results)


def test_ready_evals_count_one_readiness_per_key(runs):
    # an FR-FCFS pick evaluates readiness once per (bank, direction, SID)
    # of its queued row hits: a 64-deep window of a striped stream holds
    # about 16 such keys, against 64 transactions
    host.enable()
    sched.make_channel_sim("hbm4", queue_depth=64).run(
        sched.sequential_read_txns_hbm4(1 << 15))
    snap = host.disable()
    evals = host.counter(snap, "cycle.ready_evals")
    assert 0 < evals <= 17 * host.counter(snap, "cycle.iters")
    snap = runs["hbm4"]["traced"][2]
    assert 0 < host.counter(snap, "cycle.ready_evals") <= \
        64 * host.counter(snap, "cycle.iters")
    # RoMe's row policy makes no readiness pick and reports nothing
    host.enable()
    sched.make_channel_sim("rome").run(
        sched.sequential_read_txns_rome(1 << 20))
    assert "cycle.ready_evals" not in host.disable()["counters"]
    assert host.counter(runs["replay"]["traced"][2], "cycle.ready_evals") == 0


def test_annotations_open_and_close_in_nesting_order():
    events = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("open", self.name))

        def __exit__(self, *exc):
            events.append(("close", self.name))

    host.enable(annotate=Annotation)
    with host.span("fleet.run"):
        with host.span("recorder.step"):
            with host.span("recorder.interleave"):
                pass
        with host.span("pricing"):
            pass
    assert events == [("open", "fleet.run"), ("open", "recorder.step"),
                      ("open", "recorder.interleave"),
                      ("close", "recorder.interleave"),
                      ("close", "recorder.step"), ("open", "pricing"),
                      ("close", "pricing"), ("close", "fleet.run")]
    # a whole replay: every annotation closes in stack order
    events.clear()
    eng, _ = build_replay(**REPLAY_KW)
    eng.run()
    stack = []
    for what, name in events:
        if what == "open":
            stack.append(name)
        else:
            assert stack.pop() == name
    assert not stack and len(events) > 20
