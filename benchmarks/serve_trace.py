"""Serving-trace replay sweep: offered load x policy -> SLO metrics
(ROADMAP: serving traces end to end).

End to end from *generated requests* — no hand-built Txn lists anywhere:
a seeded Poisson :class:`~repro.serve.replay.ArrivalProcess` feeds the
real :class:`~repro.serve.batching.ContinuousBatcher` +
:class:`~repro.serve.kv_cache.RowPagedKVCache`; every decode step's
multi-tenant extent stream runs through
:class:`~repro.core.system_sim.SystemSim` under the policy under test,
and the measured makespans fold back into request timelines
(:mod:`repro.serve.replay`). Cells are {FR-FCFS open-page HBM4, RoMe row
policy} x {near-zero load, rho=0.7, rho=1.4} of an estimated saturation
throughput, reporting per-request TTFT/TPOT p50/p99, occupancy, and
goodput vs offered load.

Reproduction bands asserted:

* near-zero-load TPOT matches the analytic ``perfmodel.tpot`` path
  (``stream_mem_ns`` over the same recorded streams) within the
  established 15 % engine_xval band, for both families;
* KV byte conservation on the recorded near-zero trace (every admitted
  request's appends/reads appear exactly once);
* queueing physics: goodput grows with offered load, the rho=1.4 point
  is saturated (offered > goodput), occupancy rises with load;
* at *equal channel width* the granularity change alone is p99-TPOT
  neutral (within 10 %) — the serving-side echo of the policy sweep's
  margins-not-multiples finding, with RoMe's whole-row append overfetch
  visibly taxing ``bytes_moved``;
* the SLO headline: at *equal CA-pin budget* — HBM4 x 8 channels vs
  RoMe x 9, the paper's 32:36 full-cube ratio scaled down — RoMe wins
  p99 TPOT at the saturated load point. This is the +12.5 % bandwidth
  mechanism (pin savings reinvested as channels,
  benchmarks/full_cube.py) cashed out as a measured tail-latency delta
  under serving load.

The load sweep uses the band-valid step scale (2^-12, data-bound steps;
see ``build_replay``). The equal-pin pair spreads the same steps over
4x the channels (per-channel load below the analytic band's regime), so
it carries the headline delta but no xval assertion. ``--reduced`` runs
a structurally identical ACT-bound miniature for CI smoke — bands that
assume the analytic regime are skipped there.

Beyond the Poisson axis, the ``arrival_kinds`` section sweeps the other
two :class:`~repro.serve.replay.ArrivalProcess` disciplines — bursty
(burst admissions co-schedule tenants in one window) and closed-loop
(load self-regulates with service time) — and the ``unscaled`` section
replays the *unscaled* (``scale=1.0``) weight slice end to end through
the hybrid SystemSim: GB-scale decode steps priced by the calibrated
queue-window model (``hybrid_fraction`` reported), the CI-feasibility
proof for production-size traces.

The ``prefill`` section turns prompt ingestion on
(``prefill_chunk_tokens``): prompts stream through the memory system in
chunks — chunk-attention prefix reads plus row-granular K/V appends —
either packed into the concurrent decode step
(``prefill_overlap=True``, packing-prefetch) or claiming dedicated
prefill-only steps that stall decode. Steps run warm
(:meth:`SystemSim.warm_session`): saturated prefill leaves channel
queues draining across step boundaries. Gated claim: at rho >= 1.5,
overlap measurably reduces p99 TTFT vs stalling, per policy. The
full run adds the equal-pin prefill headline — HBM4 x 8 vs RoMe x 9
channels under bursty arrivals with chunked prefill — answering
whether the paper's goodput edge survives prefill contending with
decode (``prefill_headline``).
"""
from __future__ import annotations

import time

import numpy as np

from repro.configs.paper_workloads import REPLAY_SWEEP_MIX
from repro.obs import MetricsProbe, ObsCollector
from repro.obs.export import chrome_trace_events, trace_total_bytes
from repro.perfmodel.tpot import stream_mem_ns
from repro.serve.replay import build_replay

WORKLOAD = "deepseek-v3"
POLICIES = ("hbm4_frfcfs", "rome_qd2")
# Scaled serving mix: median-32-token prompts, mean-8-token outputs at
# the 1/16 length scale (shared with examples/serve_replay.py).
MIX = REPLAY_SWEEP_MIX
LENGTH_SCALE = 1 / 16
NEAR_ZERO_RPS = 1e3          # inter-arrival ~1 ms >> service: serial regime
RHOS = (0.7, 1.4)            # offered load as a fraction of estimated cap
N_SLOTS = 4                  # batch slots per cell (passed to build_replay)
SEED = 0


#: Equal-pin channel widths: the paper's 32 HBM4 vs 36 RoMe channels per
#: cube (same CA-pin budget, fig10_ca_pins) at quarter scale.
EQUAL_PIN_CHANNELS = {"hbm4_frfcfs": 8, "rome_qd2": 9}


def _cell(policy: str, rate_rps: float, n_requests: int, *,
          scale: float, n_channels: int = 2, keep_traces: bool = False,
          kind: str = "poisson", sim_mode: str = "cycle", **arrival_kw):
    eng, acc = build_replay(
        workload=WORKLOAD, policy=policy, rate_rps=rate_rps,
        n_requests=n_requests, kind=kind, seed=SEED, mix=MIX,
        length_scale=LENGTH_SCALE, scale=scale, n_slots=N_SLOTS,
        n_channels=n_channels, keep_traces=keep_traces,
        sim_mode=sim_mode, **arrival_kw)
    return eng.run(), acc


def _check_conservation(res) -> int:
    """Recorded KV bytes == what the request lengths dictate; returns the
    total KV bytes for the report."""
    total = 0
    assert res.requests
    for r in res.requests:
        recs = [rec for tr in res.traces for rec in tr.stream
                if rec.stream_id == r.rid]
        writes = sum(rec.nbytes for rec in recs if rec.is_write)
        reads = sum(rec.nbytes for rec in recs if not rec.is_write)
        total += writes + reads
        assert r.n_out == r.max_new_tokens, r
        # the cache geometry is not carried on the result; KV reads are
        # whole pages by construction, so the smallest read is one page
        pb = min((rec.nbytes for rec in recs if not rec.is_write),
                 default=0)
        assert pb > 0 and reads % pb == 0, (r.rid, reads, pb)
        assert writes > 0 and writes % (2 * r.n_out) == 0, (r.rid, writes)
    return total


def _obs_section(scale: float, n_requests: int) -> dict:
    """Observation-is-free check on the full serving loop: the same
    seeded replay with the repro.obs stack attached must be
    bit-identical to the bare run, and the exported Chrome-trace
    counter tracks must conserve bytes (integral == the result's
    ``bytes_moved``). Complements benchmarks/obs_overhead.py, which
    gates the same contract at the channel-engine level."""
    out: dict = {}
    for policy in POLICIES:
        kw = dict(scale=scale, kind="bursty", burst_size=4)
        bare, _ = _cell(policy, 2e5, n_requests, **kw)
        col = ObsCollector(probe=MetricsProbe(window_ns=200.0))
        obs, _ = _cell(policy, 2e5, n_requests, collector=col, **kw)
        assert bare.summary() == obs.summary(), policy
        assert ([s.dur_ns for s in bare.steps]
                == [s.dur_ns for s in obs.steps]), policy
        trace = {"traceEvents": chrome_trace_events(col, col.probe)}
        s = obs.summary()
        tb = trace_total_bytes(trace)
        assert tb == s["bytes_moved"], (policy, tb, s["bytes_moved"])
        spans = col.request_spans()
        assert len(spans) == n_requests, (policy, len(spans))
        out[policy] = {"identity": 1, "trace_bytes": tb,
                       "row_hit_rate": round(col.probe.row_hit_rate(), 4),
                       "n_spans": len(spans)}
    assert out["hbm4_frfcfs"]["row_hit_rate"] > 0.5, out
    assert out["rome_qd2"]["row_hit_rate"] == 0.0, out
    return out


def run(reduced: bool = False) -> dict:
    scale = 2 ** -13 if reduced else 2 ** -12
    n_req = {"near": 2, "sweep": 5} if reduced else {"near": 4, "sweep": 10}

    out: dict = {"config": {
        "workload": WORKLOAD, "policies": list(POLICIES),
        "length_scale": LENGTH_SCALE, "step_scale_log2": int(np.log2(scale)),
        "reduced": reduced,
    }}

    # --- near-zero load: the analytic cross-validation anchor -------------
    xval = {}
    near = {}
    for policy in POLICIES:
        res, acc = _cell(policy, NEAR_ZERO_RPS, n_req["near"],
                         scale=scale, keep_traces=True)
        assert res.completed == n_req["near"], (policy, res.completed)
        assert max(s.n_active for s in res.steps) == 1, policy
        meas = float(np.mean([s.dur_ns for s in res.steps]))
        model = float(np.mean([stream_mem_ns(tr.stream, acc)
                               for tr in res.traces]))
        rel = abs(meas - model) / model
        kv_bytes = _check_conservation(res)
        xval[policy] = {"mean_step_ns": round(meas, 1),
                        "analytic_step_ns": round(model, 1),
                        "rel_err": round(rel, 4),
                        "kv_bytes": kv_bytes}
        if not reduced:
            # The established engine_xval band, now reached from a full
            # serving loop instead of a hand-built decode slice.
            assert rel < 0.15, (policy, meas, model, rel)
        near[policy] = res
    out["xval"] = xval

    # --- offered-load sweep ----------------------------------------------
    # Capacity estimate from the near-zero HBM4 TPOT: slots / (TPOT x
    # mean output tokens). Both policies sweep the same absolute loads.
    tpots0 = near["hbm4_frfcfs"].tpots_ns
    tpot0 = (float(np.mean(tpots0)) if tpots0
             else xval["hbm4_frfcfs"]["mean_step_ns"])
    mean_out = MIX.out_mean * LENGTH_SCALE
    cap_rps = N_SLOTS / (tpot0 * 1e-9 * mean_out)
    out["capacity_rps_est"] = round(cap_rps, 1)

    cells = {}
    for policy in POLICIES:
        res0 = near[policy]
        cells[f"{policy}/near_zero"] = dict(
            offered_rps=NEAR_ZERO_RPS, **res0.summary())
        for rho in RHOS:
            rate = rho * cap_rps
            res, _ = _cell(policy, rate, n_req["sweep"], scale=scale)
            assert res.completed == n_req["sweep"], (policy, rho)
            cells[f"{policy}/rho{rho}"] = dict(
                offered_rps=round(rate, 1), **res.summary())
    out["cells"] = cells

    # --- bursty / closed-loop arrival disciplines --------------------------
    # The other two ArrivalProcess generators, swept at the same absolute
    # load as the rho sweep's lower point (closed-loop load self-regulates;
    # rate_rps only seeds its think-time scale).
    kinds = {}
    for policy in POLICIES:
        rate = RHOS[0] * cap_rps
        res, _ = _cell(policy, rate, n_req["sweep"], scale=scale,
                       kind="bursty", burst_size=4)
        assert res.completed == n_req["sweep"], (policy, "bursty")
        # A whole burst lands in one admission window: the batch fills
        # deeper than the near-zero (serial) regime ever does.
        assert max(s.n_active for s in res.steps) > 1, (policy, "bursty")
        kinds[f"{policy}/bursty"] = dict(
            offered_rps=round(rate, 1), **res.summary())
        res, _ = _cell(policy, rate, n_req["sweep"], scale=scale,
                       kind="closed", n_users=N_SLOTS,
                       think_ns=1e9 / rate)
        assert res.completed == n_req["sweep"], (policy, "closed")
        # Closed loop seeds n_users at t=0: the batch starts full.
        assert res.steps[0].n_active == min(N_SLOTS, n_req["sweep"]), \
            (policy, "closed")
        kinds[f"{policy}/closed"] = dict(
            offered_rps=round(rate, 1), **res.summary())
    out["arrival_kinds"] = kinds

    # --- observability: attach-and-compare (repro.obs) ---------------------
    out["obs"] = _obs_section(scale, n_req["near"])

    # --- unscaled replay via the hybrid fast path --------------------------
    # scale=1.0: each decode step reads the full (tens-of-GB) weight
    # slice — ~1e9 decomposed transactions per step, unrunnable by the
    # cycle engine. The hybrid SystemSim prices every step with the
    # calibrated queue-window model; completing here (in seconds) IS the
    # CI-feasibility result.
    unscaled = {}
    for policy in POLICIES:
        res, _ = _cell(policy, NEAR_ZERO_RPS, n_req["near"],
                       scale=1.0, sim_mode="hybrid")
        assert res.completed == n_req["near"], (policy, "unscaled")
        s = res.summary()
        assert s["hybrid_fraction"] == 1.0, (policy, s["hybrid_fraction"])
        unscaled[policy] = s
    out["unscaled"] = unscaled

    # --- chunked prefill + packing-prefetch (warm sessions) ----------------
    # Prompts stream through the memory system in chunks; steps carry
    # channel state across boundaries (warm=True) — saturated prefill
    # leaves queues draining when the next step launches. Cells run the
    # band-validated *hybrid* path at the run scale: the packing-
    # prefetch effect is that every dedicated prefill-only step re-pays
    # the full weight-slice read without emitting a token, which only
    # bites when the weight slice dominates the step — the run-scale
    # regime, minutes per cell in the cycle engine but ~1 s priced by
    # the queue-window model (cross-checked against the cycle engine at
    # this exact operating point in tests/test_serve_replay.py's scaled
    # smoke and by benchmarks/hybrid_xval.py's band).
    chunks = (4, 16) if reduced else (8, 32)
    n_pf = 24 if reduced else 32
    prefill = {}
    for policy in POLICIES:
        res0, _ = _cell(policy, NEAR_ZERO_RPS, n_req["near"],
                        scale=scale, sim_mode="hybrid", warm=True,
                        prefill_chunk_tokens=chunks[0])
        tpot0p = (float(np.mean(res0.tpots_ns)) if res0.tpots_ns
                  else float(np.mean([s.dur_ns for s in res0.steps])))
        rate = 1.5 * N_SLOTS / (tpot0p * 1e-9 * mean_out)
        for chunk in chunks:
            for overlap in (False, True):
                res, _ = _cell(policy, rate, n_pf, scale=scale,
                               sim_mode="hybrid", warm=True,
                               prefill_chunk_tokens=chunk,
                               prefill_overlap=overlap)
                assert res.completed == n_pf, (policy, chunk, overlap)
                # Every request clears prefill before its first token.
                assert all(r.prefill_done_ns >= 0 for r in res.requests)
                assert all(r.first_token_ns >= r.prefill_done_ns
                           for r in res.requests), (policy, chunk, overlap)
                s = res.summary()
                assert s["n_prefill_steps"] + s["n_mixed_steps"] > 0, \
                    (policy, chunk, overlap)
                key = (f"{policy}/chunk{chunk}/"
                       f"{'overlap' if overlap else 'stall'}")
                prefill[key] = dict(offered_rps=round(rate, 1), **s)
        # Packing-prefetch gate: at rho >= 1.5, overlapping prefill chunk
        # fetch with decode compute beats stalling decode on the TTFT
        # tail — dedicated prefill-only steps serialize the queue.
        ov = prefill[f"{policy}/chunk{chunks[0]}/overlap"]
        st = prefill[f"{policy}/chunk{chunks[0]}/stall"]
        assert ov["ttft_p99_ns"] < st["ttft_p99_ns"], \
            (policy, chunks[0], ov["ttft_p99_ns"], st["ttft_p99_ns"])
    out["prefill"] = prefill

    # --- bands -------------------------------------------------------------
    for policy in POLICIES:
        lo = cells[f"{policy}/rho{RHOS[0]}"]
        hi = cells[f"{policy}/rho{RHOS[1]}"]
        nz = cells[f"{policy}/near_zero"]
        # goodput rises with offered load; the top point is saturated
        assert hi["goodput_rps"] > lo["goodput_rps"] > nz["goodput_rps"], \
            policy
        assert hi["offered_rps"] > 1.05 * hi["goodput_rps"], (policy, hi)
        # queueing shows up in the TTFT tail, occupancy in the slots
        assert hi["ttft_p99_ns"] > nz["ttft_p99_ns"], policy
        assert hi["occupancy"] > nz["occupancy"], policy

    # Equal channel width: granularity alone is a margin, not a multiple
    # (cf. policy_sweep) — and RoMe pays whole-row append overfetch.
    hbm4_hi = cells[f"hbm4_frfcfs/rho{RHOS[1]}"]
    rome_hi = cells[f"rome_qd2/rho{RHOS[1]}"]
    eq_width_delta = hbm4_hi["tpot_p99_ns"] / rome_hi["tpot_p99_ns"] - 1
    out["equal_width"] = {
        "p99_tpot_hbm4_ns": hbm4_hi["tpot_p99_ns"],
        "p99_tpot_rome_ns": rome_hi["tpot_p99_ns"],
        "p99_tpot_delta_frac": round(eq_width_delta, 4),
    }
    if not reduced:
        assert abs(eq_width_delta) < 0.10, out["equal_width"]

    # --- equal-pin headline (HBM4 x 8ch vs RoMe x 9ch) ---------------------
    if reduced:
        return out
    pin = {}
    for policy, nch in EQUAL_PIN_CHANNELS.items():
        res0, _ = _cell(policy, NEAR_ZERO_RPS, n_req["near"],
                        scale=scale, n_channels=nch)
        tpot_nz = (float(np.mean(res0.tpots_ns)) if res0.tpots_ns
                   else float(np.mean([s.dur_ns for s in res0.steps])))
        rate = RHOS[1] * N_SLOTS / (tpot_nz * 1e-9 * mean_out)
        res, _ = _cell(policy, rate, n_req["sweep"], scale=scale,
                       n_channels=nch)
        assert res.completed == n_req["sweep"], (policy, nch)
        pin[policy] = dict(n_channels=nch, offered_rps=round(rate, 1),
                           tpot_nz_ns=round(tpot_nz, 1), **res.summary())
        cells[f"{policy}/equal_pin_rho{RHOS[1]}"] = pin[policy]
    delta = (pin["hbm4_frfcfs"]["tpot_p99_ns"]
             / pin["rome_qd2"]["tpot_p99_ns"] - 1)
    out["headline"] = {
        "p99_tpot_hbm4_ns": pin["hbm4_frfcfs"]["tpot_p99_ns"],
        "p99_tpot_rome_ns": pin["rome_qd2"]["tpot_p99_ns"],
        "p99_tpot_delta_frac": round(delta, 4),
        "goodput_hbm4_rps": pin["hbm4_frfcfs"]["goodput_rps"],
        "goodput_rome_rps": pin["rome_qd2"]["goodput_rps"],
    }
    # The pin-equivalent system must cash the bandwidth edge out as a
    # positive, bounded tail-latency win under load.
    assert 0.0 < delta < 0.5, out["headline"]

    # --- equal-pin goodput with bursty chunked prefill ---------------------
    # The ISSUE's equal-pin question: does the reinvested-pins goodput
    # edge survive once bursty prefill contends with decode? Same
    # 8-vs-9-channel budget, bursty arrivals, chunked prefill with
    # packing-prefetch on, warm sessions.
    pinp = {}
    for policy, nch in EQUAL_PIN_CHANNELS.items():
        res0, _ = _cell(policy, NEAR_ZERO_RPS, n_req["near"],
                        scale=scale, n_channels=nch, sim_mode="hybrid",
                        warm=True, prefill_chunk_tokens=chunks[0])
        tpot0p = (float(np.mean(res0.tpots_ns)) if res0.tpots_ns
                  else float(np.mean([s.dur_ns for s in res0.steps])))
        rate = 1.5 * N_SLOTS / (tpot0p * 1e-9 * mean_out)
        res, _ = _cell(policy, rate, n_pf, scale=scale,
                       n_channels=nch, sim_mode="hybrid", warm=True,
                       prefill_chunk_tokens=chunks[0],
                       prefill_overlap=True,
                       kind="bursty", burst_size=4)
        assert res.completed == n_pf, (policy, nch, "prefill_pin")
        pinp[policy] = dict(n_channels=nch, offered_rps=round(rate, 1),
                            **res.summary())
        prefill[f"{policy}/equal_pin"] = pinp[policy]
    pdelta = (pinp["rome_qd2"]["goodput_rps"]
              / pinp["hbm4_frfcfs"]["goodput_rps"] - 1)
    out["prefill_headline"] = {
        "goodput_rome_rps": pinp["rome_qd2"]["goodput_rps"],
        "goodput_hbm4_rps": pinp["hbm4_frfcfs"]["goodput_rps"],
        "goodput_delta_frac": round(pdelta, 4),
        "ttft_p99_rome_ns": pinp["rome_qd2"]["ttft_p99_ns"],
        "ttft_p99_hbm4_ns": pinp["hbm4_frfcfs"]["ttft_p99_ns"],
    }
    # Sanity bound only: the *direction* of the answer is the result the
    # baseline records, not an assumption the gate bakes in.
    assert abs(pdelta) < 0.5, out["prefill_headline"]

    return out


if __name__ == "__main__":
    import argparse
    import json
    import traceback
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--reduced", action="store_true",
                   help="CI-smoke miniature (skips analytic-regime bands)")
    p.add_argument("--json", metavar="PATH", default=None,
                   help="write a benchmarks.run-shaped payload to PATH "
                        "(gateable by scripts/bench_compare.py)")
    args = p.parse_args()
    name = "serve_trace_reduced" if args.reduced else "serve_trace"
    t0 = time.time()
    try:
        results = run(reduced=args.reduced)
        status = "PASS"
    except AssertionError as e:
        results = {"error": str(e)}
        status = "FAIL"
    except Exception:
        results = {"error": traceback.format_exc()[-800:]}
        status = "ERROR"
    wall = round(time.time() - t0, 2)
    print(json.dumps(results, indent=1, default=str))
    print(f"[{status}] {name} ({wall:.1f}s)", flush=True)
    if args.json:
        payload = {"status": "pass" if status == "PASS" else "fail",
                   "benchmarks": {name: {"status": status, "wall_s": wall,
                                         "results": results}},
                   "total_wall_s": wall,
                   "failures": int(status != "PASS"),
                   "completed": True}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, default=str)
        print(f"wrote {args.json}")
    raise SystemExit(0 if status == "PASS" else 1)
