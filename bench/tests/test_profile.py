import json
from pathlib import Path

import pytest

from harness import profile

DATA = Path(__file__).resolve().parent / "data" / "tpu_trace_events.json"
LAYERS = {"recorder + batcher", "cycle engine"}


def synthetic():
    # span 0..1000 ns; device ops [100,200] and [150,300] (overlapping,
    # union 200 ns) and [600,700]; idle gaps [0,100], [300,600] and
    # [700,1000], covered by the two host layers
    return [
        ("/host:CPU", "python", profile.SPAN, 0.0, 1000.0),
        ("/host:CPU", "python", "recorder + batcher", 0.0, 100.0),
        ("/host:CPU", "python", "cycle engine", 300.0, 700.0),
        ("/device:TPU:0", "XLA Ops", "fusion", 100.0, 100.0),
        ("/device:TPU:0", "XLA Ops", "copy", 150.0, 150.0),
        ("/device:TPU:0", "XLA Modules", "jit_f", 100.0, 600.0),
        ("/device:TPU:0", "XLA Ops", "fusion", 600.0, 100.0),
        ("/device:TPU:0", "XLA Ops", "late", 1200.0, 50.0),
    ]


def test_busy_is_the_union_of_device_ops_inside_the_span():
    r = profile.reduce(synthetic(), LAYERS)
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["idle_share"] == pytest.approx(0.7)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["cycle engine", pytest.approx(300e-9)]
    assert [g[0] for g in gaps] == ["cycle engine", "cycle engine",
                                    "recorder + batcher"]
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion"] == pytest.approx(200e-9) and "late" not in ops


def test_gaps_are_labelled_by_the_innermost_layer():
    events = [
        ("/host:CPU", "python", profile.SPAN, 0.0, 1000.0),
        ("/host:CPU", "python", "router / fleet", 0.0, 1000.0),
        ("/host:CPU", "python", "recorder + batcher", 100.0, 300.0),
        ("/host:CPU", "python", "recorder + batcher", 500.0, 400.0),
        ("/device:TPU:0", "XLA Ops", "fusion", 0.0, 10.0),
    ]
    r = profile.reduce(events, {"router / fleet", "recorder + batcher"})
    assert r["breakdown"]["idle_gaps"] == [
        ["recorder + batcher", pytest.approx(990e-9)]]


def test_no_span_is_an_error():
    with pytest.raises(ValueError):
        profile.reduce(synthetic()[1:], LAYERS)


@pytest.mark.skipif(not DATA.exists(), reason="no recorded TPU trace")
def test_recorded_tpu_trace():
    events = [tuple(e) for e in json.loads(DATA.read_text())]
    r = profile.reduce(events, LAYERS)
    assert r["n_device_planes"] >= 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0.5 < r["idle_share"] < 1.0
    labels = {g[0] for g in r["breakdown"]["idle_gaps"]}
    assert labels <= LAYERS | {profile.UNCOVERED}
    assert "cycle engine" in labels
