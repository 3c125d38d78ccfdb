"""Sound runs pass and the control fails, on every cell at its own
size.

On the chip the same readings come from ``bench/control.py`` over a
dozen seeds and more (``PERF.md`` gives them); here one seed per cell
keeps the test short."""
import pytest

from _cell import failing, run

DECODE = ["dsv3-hbm4-x8.decode-poisson", "dsv3-rome-x9.decode-poisson"]
FLEET = ["dsv3-rome-x9.fleet8-bursty", "dsv3-hbm4-x8.fleet8-bursty"]


@pytest.mark.parametrize("cell", DECODE + FLEET)
def test_program_passes_and_float32_control_fails(cell):
    # an HBM4 decode step takes about two seconds on the CPU
    out = run(cell, control="float32", seconds=4.0)
    assert out["correct"], out["checks"]
    assert out["checked_steps"] > 0
    ctrl = out["control"]
    assert not ctrl["correct"] and ctrl["failed"] >= 1
    # a float32 clock cannot hold the step starts a float64 one does
    assert "clock_bad" in failing(ctrl["readings"])


@pytest.mark.parametrize("cell", ["dsv3-rome-x9.decode-poisson",
                                  "dsv3-rome-x9.fleet8-bursty"])
@pytest.mark.parametrize("size_seed", [1, 2])
def test_program_passes_on_other_request_sets(cell, size_seed):
    out = run(cell, size_seed=size_seed)
    assert out["correct"], out["checks"]
