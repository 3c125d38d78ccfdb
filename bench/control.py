"""Readings of the numbers that decide ``correct``, for the program and
for its control, over many seeds in one process:
``python3 bench/control.py --workload <cell> --seconds <s> --seeds <n>...``.

The control puts the plain reference, computed in float32 (the
precision below the float64 event clocks the configuration states), in
the program's place: finish times, step durations and the clock they
advance. Its verdict uses the same limits and the same count of failed
numbers as ``run.py``; it has to come out not correct. One JSON line
per seed; runs on the cell's own chips, like ``run.py``. The
benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    from harness import device, runner
    from harness.manifest import Manifest
    cell = Manifest.load(ROOT).cell(args.workload)
    jax = device.init_jax()
    try:
        devices = device.require_tpu(jax, cell.chips)
    except device.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for seed in args.seeds:
        out = runner.run(cell, seed, args.seconds, False, time.perf_counter(),
                         devices, jax=jax, control="float32", report=False)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checked_steps": out["checked_steps"],
                          "reference_s": out["reference_s"],
                          "program": {k: v["value"]
                                      for k, v in out["checks"].items()},
                          "control": out["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
