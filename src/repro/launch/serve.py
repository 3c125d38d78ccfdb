"""Serving driver: continuous-batching decode over the row-paged KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
        --requests 12 --slots 4

Iteration-level scheduling (Orca-style): new requests join the running
batch at token boundaries; the jit'd decode step is shape-stable over a
fixed slot array. Each slot owns a contiguous region of the shared KV
cache; the serve layer accounts pages at 4 KB DRAM-row granularity
(repro.serve.kv_cache) — the software contract of the RoMe interface.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import reduced
from ..configs.registry_configs import ALL_ARCHS
from ..models.registry import get_adapter
from ..serve.batching import ContinuousBatcher, Request
from ..serve.kv_cache import ROW_BYTES
from .compile_cache import setup_compile_cache
from .mesh import make_mesh
from ..compat import set_mesh


def greedy_sample(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)


def make_decode_step(adapter) -> Callable:
    """The jit'd serving step: (params, tokens, cache, pos) ->
    (next tokens, logits, cache)."""
    @jax.jit
    def decode_step(params, tokens, cache, pos):
        logits, cache = adapter.decode(params, {"tokens": tokens},
                                       cache, pos)
        return greedy_sample(logits), logits, cache
    return decode_step


@dataclass
class ServeRun:
    """What one serving run built and measured."""

    adapter: Any
    params: Any
    decode_step: Callable
    first_tokens: np.ndarray      # (slots, 1) tokens fed to the first step
    first_logits: np.ndarray      # logits the first step produced
    completed: int
    steps: int
    occupancy: float
    tokens_out: int
    init_s: float                 # parameter init, on the host clock
    compile_s: float              # decode-step lower + compile
    decode_s: float               # the serving loop, compile excluded

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_out / max(self.decode_s, 1e-9)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b", choices=sorted(ALL_ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def run(args: argparse.Namespace) -> ServeRun:
    """Serve ``args.requests`` seeded requests with continuous batching
    and report what the run measured."""
    cfg = ALL_ARCHS[args.arch]
    if args.reduced:
        cfg = reduced(cfg)
    adapter = get_adapter(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))

    rng = np.random.default_rng(args.seed)
    batcher = ContinuousBatcher(args.slots)
    for rid in range(args.requests):
        prompt = rng.integers(1, cfg.vocab, size=(args.prompt_len,),
                              dtype=np.int32)
        batcher.submit(Request(rid, prompt,
                               max_new_tokens=args.max_new))

    with set_mesh(mesh):
        t0 = time.perf_counter()
        # One jitted program: eager init dispatches (and compiles) every
        # op and keeps float32 transients of each stacked weight alive.
        init = jax.jit(adapter.init, static_argnames="tp")
        params = jax.block_until_ready(
            init(jax.random.PRNGKey(args.seed), tp=1))
        cache = adapter.init_decode_state(args.slots, args.max_seq)
        init_s = time.perf_counter() - t0

        # Slot state: current token and per-slot position.
        cur = np.zeros((args.slots, 1), np.int32)
        pos = 0
        decode_step = make_decode_step(adapter)
        t0 = time.perf_counter()
        step = decode_step.lower(params, jnp.asarray(cur), cache,
                                 jnp.asarray(pos, jnp.int32)).compile()
        compile_s = time.perf_counter() - t0

        first_tokens = first_logits = None
        t0 = time.perf_counter()
        while not batcher.idle():
            admitted = batcher.schedule()
            for slot, req in admitted:
                # Prefill-as-decode: feed prompt tokens one at a time into
                # the slot (a production server would run a prefill kernel;
                # the cache/page accounting is identical).
                cur[slot, 0] = req.prompt[0]
            step_tokens, logits, cache = step(
                params, jnp.asarray(cur), cache,
                jnp.asarray(pos, jnp.int32))
            if first_logits is None:
                first_tokens = cur.copy()
                first_logits = np.asarray(logits)
            out = np.asarray(step_tokens)
            finished = batcher.record_tokens(out)
            for slot in range(args.slots):
                if batcher.active[slot] is not None:
                    cur[slot, 0] = out[slot]
            pos = min(pos + 1, args.max_seq - 1)
            for req in finished:
                print(f"[serve] request {req.rid} done "
                      f"({len(req.out_tokens)} tokens)")
        decode_s = time.perf_counter() - t0
    tokens_out = sum(len(r.out_tokens) for r in batcher.completed)

    result = ServeRun(
        adapter=adapter, params=params, decode_step=decode_step,
        first_tokens=first_tokens, first_logits=first_logits,
        completed=len(batcher.completed), steps=batcher.steps,
        occupancy=batcher.occupancy, tokens_out=tokens_out,
        init_s=init_s, compile_s=compile_s, decode_s=decode_s)
    print(f"[serve] {result.completed} requests, {result.steps} decode "
          f"steps, occupancy {result.occupancy:.2f}, "
          f"{result.tokens_per_s:.1f} tok/s (compile {compile_s:.1f} s)")
    kv_bytes_tok = 2 * cfg.n_layers * cfg.n_kv_heads \
        * cfg.resolved_head_dim * 2
    print(f"[serve] KV bytes/token/all-layers = {kv_bytes_tok} "
          f"({kv_bytes_tok/ROW_BYTES:.2f} DRAM rows)")
    return result


def main(argv=None) -> int:
    setup_compile_cache()
    run(parse_args(argv))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
