"""Drive a whole run of a cell on the CPU, past the harness's look for
a chip."""
import time
from dataclasses import replace

from harness import device, runner
from harness.manifest import Manifest

SECONDS = 1.5


def run(cell_name: str, seed: int = 2 ** 31 + 5, control=None,
        seconds: float = SECONDS, size_seed: int | None = None) -> dict:
    jax = device.init_jax()
    cell = Manifest.load().cell(cell_name)
    if size_seed is not None:
        # another request set than the traffic file's
        arrivals = dict(cell.traffic["arrivals"], size_seed=size_seed)
        cell = replace(cell, traffic=dict(cell.traffic, arrivals=arrivals))
    return runner.run(cell, seed, seconds, False, time.perf_counter(), None,
                      jax=jax, control=control, report=False)


def failing(readings: dict) -> set:
    limits = runner.load_limits()
    return {k for k, v in readings.items() if v > limits[k]}


def fails_on(out: dict) -> set:
    """The numbers a run failed; empty when it came out correct."""
    return set() if out["correct"] else failing(
        {k: v["value"] for k, v in out["checks"].items()})
