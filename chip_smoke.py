#!/usr/bin/env python3
"""Smoke run of the system on one TPU chip.

    python chip_smoke.py

Three phases, in this one process, each checked against a reference:

1. **Serve.** ``repro.launch.serve`` serves 4 seeded requests on 2 slots
   (8 new tokens each) at the full width of ``h2o-danube-1.8b`` with
   random seeded weights. The first decode step's logits are recomputed
   by the same jitted step on the host CPU backend, from the same
   parameters and inputs; the largest difference must be within
   ``LOGIT_TOL``.
2. **Kernels.** The three Pallas kernels, compiled by Mosaic
   (``interpret=False``) at the widths of ``repro.kernels.REAL_WIDTHS``,
   against their ``ref.py`` oracles on the host CPU backend; each call
   must lower to a ``tpu_custom_call``.
3. **Simulator.** The RoMe equal-pin ``deepseek-v3`` serving replay of
   ``benchmarks/serve_trace.py`` at its full (non-reduced) size; every
   simulated statistic must equal the committed baseline. Then one
   cycle-path ``SystemSim.run(workers=2)`` that must equal the serial
   run, with a check that the spawned pool workers never import JAX.

The device must be a TPU before and after every phase: there is no CPU
path, and under ``JAX_PLATFORMS=cpu`` the script fails. Any failure
raises and exits non-zero. Times, rates and memory printed here are
smoke readings, not benchmark metrics. The last line of standard output
is one JSON object naming the device.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

# JAX stays out of module scope: the simulator's spawned pool workers
# re-import this file, and they must never load JAX or open the TPU.
ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

SEED = 0
SERVE_ARGS = ["--arch", "h2o-danube-1.8b", "--requests", "4", "--slots",
              "2", "--max-new", "8", "--seed", str(SEED)]
#: TPU vs host-CPU bound on the first decode step's logits. Both run the
#: bf16 model with different accumulation orders. The largest logits at
#: this seeded init are about 4.8, in the octave [4, 8) where one bf16
#: ulp is 2**-5; 0.25 is eight such ulps. (A bf16 vs float32 run of the
#: same step differs by at most 0.037 at 4 layers on the CPU.)
LOGIT_TOL = 0.25
#: (rtol, atol) of each kernel against its oracle, as in
#: tests/test_kernels.py for the kernel's dtype.
KERNEL_TOL = {"flash_decode": (3e-2, 3e-2),
              "rowstream_matmul": (2e-2, 0.16),
              "rwkv_scan": (1e-3, 1e-3)}
SIM_POLICY = "rome_qd2"
SIM_SCALE = 2 ** -12          # serve_trace.py's non-reduced step scale
SIM_REQUESTS = {"near": 4, "sweep": 10}


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    """Fail the run; unlike ``assert``, also under ``python -O``."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def require_tpu():
    """The first device, which must be a TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r}; there is no CPU path")
    return dev


def host_cpu():
    import jax
    return jax.devices("cpu")[0]


# --- 1. serve ----------------------------------------------------------

def phase_serve() -> dict:
    import jax
    import numpy as np

    from repro.launch import serve

    args = serve.parse_args(SERVE_ARGS)
    run = serve.run(args)
    check(run.completed == args.requests, f"completed {run.completed}")
    check(run.tokens_out == args.requests * args.max_new,
          f"served {run.tokens_out} tokens")

    cpu = host_cpu()
    cache0 = run.adapter.init_decode_state(args.slots, args.max_seq)
    _, logits_cpu, _ = run.decode_step(
        jax.device_put(run.params, cpu),
        jax.device_put(run.first_tokens, cpu),
        jax.device_put(cache0, cpu),
        jax.device_put(np.int32(0), cpu))
    tpu = run.first_logits.astype(np.float32)
    ref = np.asarray(logits_cpu, np.float32)
    check(tpu.shape == ref.shape, f"logits {tpu.shape} vs {ref.shape}")
    check(np.isfinite(tpu).all(), "non-finite TPU logits")
    err = float(np.abs(tpu - ref).max())
    argmax_agree = float((tpu.argmax(-1) == ref.argmax(-1)).mean())
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    out = dict(compile_s=run.compile_s, init_s=run.init_s,
               tokens_per_s=run.tokens_per_s, tokens=run.tokens_out,
               steps=run.steps, peak_bytes_in_use=peak,
               max_abs_logit_diff=err, logit_tol=LOGIT_TOL,
               max_abs_logit=float(np.abs(ref).max()),
               argmax_agreement=argmax_agree)
    log(f"serve (chip_smoke reading): {json.dumps(out)}")
    check(err <= LOGIT_TOL, f"max |logit diff| {err} > {LOGIT_TOL}")
    return out


# --- 2. kernels --------------------------------------------------------

def kernel_calls(seed: int) -> dict:
    """name -> (kernel, oracle, args) at ``REAL_WIDTHS``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import REAL_WIDTHS
    from repro.kernels.flash_decode.kernel import flash_decode
    from repro.kernels.flash_decode.ref import flash_decode_ref
    from repro.kernels.rowstream_matmul.kernel import rowstream_matmul
    from repro.kernels.rowstream_matmul.ref import rowstream_matmul_ref
    from repro.kernels.rwkv_scan.kernel import rwkv_scan
    from repro.kernels.rwkv_scan.ref import rwkv_scan_ref

    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 10))
    normal = lambda shape, dt=jnp.float32: jax.random.normal(
        next(ks), shape, dt)

    fd = REAL_WIDTHS["flash_decode"]
    b, h, hkv, s, d = fd["b"], fd["h"], fd["hkv"], fd["s"], fd["d"]
    flash_args = (normal((b, h, d), jnp.bfloat16),
                  normal((b, hkv, s, d), jnp.bfloat16),
                  normal((b, hkv, s, d), jnp.bfloat16),
                  jnp.int32(s * 3 // 4))

    mm = REAL_WIDTHS["rowstream_matmul"]
    mm_args = (normal((mm["m"], mm["k"]), jnp.bfloat16),
               normal((mm["k"], mm["n"]), jnp.bfloat16))

    rw = REAL_WIDTHS["rwkv_scan"]
    shape = (rw["b"], rw["s"], rw["H"], rw["hd"])
    rwkv_args = (normal(shape), normal(shape), normal(shape),
                 jax.nn.sigmoid(normal(shape)) * 0.5 + 0.4,
                 normal((rw["H"], rw["hd"])) * 0.1)

    return {"flash_decode": (flash_decode, flash_decode_ref, flash_args),
            "rowstream_matmul": (rowstream_matmul, rowstream_matmul_ref,
                                 mm_args),
            "rwkv_scan": (rwkv_scan, rwkv_scan_ref, rwkv_args)}


def check_kernel(name: str, kernel, oracle, args) -> float:
    """Run one compiled kernel and its oracle (on the host CPU backend);
    check they agree within ``KERNEL_TOL`` and return the largest
    error."""
    import jax
    import numpy as np

    got = jax.block_until_ready(kernel(*args))
    want = oracle(*jax.device_put(args, host_cpu()))
    rtol, atol = KERNEL_TOL[name]
    err = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        check(g.shape == w.shape, f"{name}: {g.shape} vs {w.shape}")
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol,
                                   err_msg=name)
        err = max(err, float(np.abs(g - w).max()))
    return err


def phase_kernels() -> dict:
    out = {}
    for name, (kernel, oracle, args) in kernel_calls(SEED).items():
        compiled = kernel.lower(*args, interpret=False).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"{name} did not lower to Mosaic")
        t0 = time.perf_counter()
        err = check_kernel(name, compiled, oracle, args)
        out[name] = dict(max_abs_err=err, tol=KERNEL_TOL[name],
                         wall_s=time.perf_counter() - t0)
        log(f"kernel {name} (chip_smoke reading): {json.dumps(out[name])}")
    return out


# --- 3. simulator ------------------------------------------------------

def _child_probe() -> tuple:
    """Runs in a pool worker: its PID and whether it has imported JAX."""
    return os.getpid(), "jax" in sys.modules or "jaxlib" in sys.modules


def equal_pin_replay() -> tuple:
    """The RoMe equal-pin cell of benchmarks/serve_trace.py at full size:
    the near-zero replay that sets the capacity estimate, then the
    rho=1.4 replay. Returns (summary in the baseline's keys, result)."""
    import numpy as np

    from benchmarks import serve_trace as st
    from repro.serve.replay import build_replay

    nch = st.EQUAL_PIN_CHANNELS[SIM_POLICY]
    kw = dict(workload=st.WORKLOAD, policy=SIM_POLICY, kind="poisson",
              seed=st.SEED, mix=st.MIX, length_scale=st.LENGTH_SCALE,
              scale=SIM_SCALE, n_slots=st.N_SLOTS, n_channels=nch)
    eng, _ = build_replay(rate_rps=st.NEAR_ZERO_RPS,
                          n_requests=SIM_REQUESTS["near"], **kw)
    res0 = eng.run()
    tpot_nz = (float(np.mean(res0.tpots_ns)) if res0.tpots_ns
               else float(np.mean([s.dur_ns for s in res0.steps])))
    mean_out = st.MIX.out_mean * st.LENGTH_SCALE
    rate = st.RHOS[1] * st.N_SLOTS / (tpot_nz * 1e-9 * mean_out)
    eng, _ = build_replay(rate_rps=rate, n_requests=SIM_REQUESTS["sweep"],
                          keep_traces=True, **kw)
    res = eng.run()
    summary = dict(n_channels=nch, offered_rps=round(rate, 1),
                   tpot_nz_ns=round(tpot_nz, 1), **res.summary())
    return summary, res


def phase_simulator() -> dict:
    import numpy as np

    from benchmarks.serve_trace import RHOS
    from repro.core.pool import get_pool, shutdown_pool
    from repro.core.sched.registry import policy_spec

    t0 = time.perf_counter()
    summary, res = equal_pin_replay()
    replay_s = time.perf_counter() - t0
    base = json.loads((ROOT / "benchmarks" / "baselines"
                       / "serve_trace.json").read_text())["metrics"]
    prefix = f"cells.{SIM_POLICY}/equal_pin_rho{RHOS[1]}."
    expect = {k[len(prefix):]: v for k, v in base.items()
              if k.startswith(prefix)}
    check(bool(expect), f"no {prefix}* cell in the baseline")
    diff = {k: (summary.get(k), v) for k, v in expect.items()
            if summary.get(k) is None or float(summary[k]) != float(v)}
    check(not diff, f"replay differs from the baseline: {diff}")

    # One cycle-path run on the shared spawn pool, against the serial run.
    stream = max((tr.stream for tr in res.traces), key=len)
    sim = policy_spec(SIM_POLICY).system_sim(
        n_channels=summary["n_channels"])
    t1 = time.perf_counter()
    serial = sim.run(stream, workers=1)
    parallel = sim.run(stream, workers=2)
    cycle_s = time.perf_counter() - t1
    check(len(parallel.channel_results) > 1, "pool path not taken")
    check(parallel.total_ns == serial.total_ns
          and parallel.bytes_moved == serial.bytes_moved
          and np.array_equal(parallel.channel_bytes, serial.channel_bytes)
          and np.array_equal(parallel.channel_finish_ns,
                             serial.channel_finish_ns),
          "workers=2 differs from the serial run")
    probes = [f.result() for f in
              [get_pool(2).submit(_child_probe) for _ in range(4)]]
    shutdown_pool()
    check(all(pid != os.getpid() for pid, _ in probes),
          f"pool ran in this process: {probes}")
    check(not any(has_jax for _, has_jax in probes),
          f"a pool worker imported JAX: {probes}")

    out = dict(replay_s=replay_s, cycle_run_s=cycle_s,
               wall_s=time.perf_counter() - t0,
               tpot_p99_ns=summary["tpot_p99_ns"],
               goodput_rps=summary["goodput_rps"],
               cycle_total_ns=parallel.total_ns,
               channels=len(parallel.channel_results),
               pool_workers_without_jax=len({p for p, _ in probes}))
    log(f"simulator (chip_smoke reading): {json.dumps(out)}")
    return out


def main() -> int:
    # The host CPU backend is the reference of phases 1 and 2; keep it
    # available where the platform list names only the TPU.
    if os.environ.get("JAX_PLATFORMS") == "tpu":
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    import jax

    from repro.launch.compile_cache import setup_compile_cache

    dev = require_tpu()
    log(f"compile cache: {setup_compile_cache()}")
    log(f"device: {dev.device_kind}, {len(jax.devices())} device(s)")
    for phase in (phase_serve, phase_kernels, phase_simulator):
        t0 = time.perf_counter()
        phase()
        dev = require_tpu()
        log(f"{phase.__name__} done in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
