"""Run one benchmark cell: ``python3 bench/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`` from the checkout's root.

The last line of standard output is the result (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``; ``checks`` last); the last lines of standard error
are each number compared beside its limit. Without the TPU chips the
cell asks for, the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from harness import device, runner
    from harness.manifest import Manifest
    cell = Manifest.load(ROOT).cell(args.workload)
    jax = device.init_jax()
    try:
        devices = device.require_tpu(jax, cell.chips)
    except device.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    import repro  # noqa: F401  (a checkout without the program fails here)
    out = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                     T_START, devices, jax=jax)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
