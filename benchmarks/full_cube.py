"""Full-cube cycle-level runs at paper system width (ROADMAP item).

32-channel HBM4 vs 36-channel RoMe (§IV-E: the C/A pins RoMe frees fund
4 extra channels per cube, +12.5 % peak bandwidth), simulated
cycle-level via ``SystemSim.run(stream, workers=N)`` — the process-pool
path is what makes cube-width runs practical, and this benchmark is the
standing proof plus its wall-time tracker (the ``--json`` record CI
keeps as an artifact).

Two regimes:

* ``bulk`` — contiguous read stream loading every channel: the paper
  headline band. RoMe's aggregate bandwidth must exceed HBM4's by
  ~12.5 % (channel count; per-channel efficiency is a wash at row
  granularity).
* ``decode`` — the scaled DeepSeek-V3 ``from_layer_ops`` decode trace
  at cube width, cross-checked against the TPOT memory-time model
  (``perfmodel.tpot.stream_mem_ns``) and the address map's load
  balance.
"""
from __future__ import annotations

import os
import time

from repro.configs.paper_workloads import PAPER_WORKLOADS
from repro.core.system_sim import SystemSim
from repro.core.timing import hbm4_config, rome_config
from repro.perfmodel.tpot import stream_mem_ns, xval_decode_stream
from repro.workloads import bulk_stream

BULK_BYTES_PER_CHANNEL = 256 << 10
DECODE_WORKLOAD = "deepseek-v3"
DECODE_SCALE = 2 ** -9
DECODE_OPS = 16


def default_workers() -> int:
    return max(1, os.cpu_count() or 1)


def run(workers: int | None = None) -> dict:
    workers = workers or default_workers()
    t_all = time.time()
    cfgs = {"hbm4": hbm4_config(), "rome": rome_config()}

    bulk = {}
    for name, cfg in cfgs.items():
        nch = cfg.channels_per_cube
        t0 = time.time()
        sim = SystemSim(cfg, n_channels=nch)
        stream = bulk_stream(nch * BULK_BYTES_PER_CHANNEL)
        res = sim.run(stream, workers=workers)
        bulk[name] = {
            "n_channels": nch,
            "makespan_ns": round(res.total_ns, 1),
            "bandwidth_gbps": round(res.bandwidth_gbps, 1),
            "peak_cube_gbps": round(cfg.cube_bw_gbps, 1),
            "lbr": round(res.load_balance_ratio, 4),
            "wall_s": round(time.time() - t0, 2),
        }

    # Paper headline: +12.5 % aggregate bandwidth from the 4 extra
    # channels (36/32); per-channel efficiency is a wash, so the
    # measured ratio must sit in the headline band.
    ratio = bulk["rome"]["bandwidth_gbps"] / bulk["hbm4"]["bandwidth_gbps"]
    assert 1.08 < ratio < 1.18, (ratio, bulk)

    decode = {}
    w = PAPER_WORKLOADS[DECODE_WORKLOAD]
    for name, cfg in cfgs.items():
        nch = cfg.channels_per_cube
        stream, acc = xval_decode_stream(w, name, n_channels=nch,
                                         scale=DECODE_SCALE,
                                         n_ops=DECODE_OPS)
        t0 = time.time()
        res = SystemSim(acc.mem_cfg, n_channels=acc.n_channels).run(
            stream, workers=workers)
        model_ns = stream_mem_ns(stream, acc)
        rel = abs(res.total_ns - model_ns) / model_ns
        decode[name] = {
            "n_channels": nch,
            "stream_records": len(stream),
            "stream_mb": round(stream.total_bytes / 2 ** 20, 1),
            "makespan_ns": round(res.total_ns, 1),
            "tpot_mem_ns": round(model_ns, 1),
            "rel_err": round(rel, 4),
            "lbr": round(res.load_balance_ratio, 4),
            "wall_s": round(time.time() - t0, 2),
        }
        # The TPOT cross-validation band holds at full cube width, and
        # the address map keeps the cube balanced.
        assert rel < 0.15, (name, res.total_ns, model_ns, rel)
        assert decode[name]["lbr"] > 0.95, decode[name]

    return {
        "workers": workers,
        "bulk": bulk,
        "bulk_bw_ratio": round(ratio, 4),
        "decode": decode,
        "total_wall_s": round(time.time() - t_all, 2),
    }


if __name__ == "__main__":
    import argparse
    import json
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workers", type=int, default=None,
                   help="process-pool width (default: cpu count)")
    p.add_argument("--json", metavar="PATH", default=None,
                   help="also write the results to PATH")
    args = p.parse_args()
    out = run(workers=args.workers)
    text = json.dumps(out, indent=1, default=str)
    print(text)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text)
