"""GQA flash-decode Pallas TPU kernel with row-granularity KV streaming.

One grid instance per (batch, kv-head); the KV sequence is visited in
blocks whose byte size is a whole number of 4 KB DRAM rows (block_s tokens
x head_dim x itemsize ≡ 0 mod 4096) — each KV DMA is one RD_row burst
train, the serving-side contract of the RoMe memory system (the paged KV
cache in repro.serve allocates at exactly this granularity).

Online softmax: running (max, sum, acc) scratch in VMEM across the
sequential S-blocks; the query group (all q heads sharing the kv head)
rides along so the MXU sees a (g x block_s) matmul instead of a GEMV.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...compat.pallas import tpu_compiler_params

DRAM_ROW_BYTES = 4096
NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
    s_idx = pl.program_id(2)
    block_s = k_ref.shape[0]

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # (g, d)
    k = k_ref[0, 0].astype(jnp.float32)                 # (block_s, d)
    v = v_ref[0, 0].astype(jnp.float32)                 # (block_s, d)
    d = q.shape[-1]
    logits = jnp.dot(q, k.T) / jnp.sqrt(float(d))       # (g, block_s)
    token_idx = s_idx * block_s + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    logits = jnp.where(token_idx <= pos_ref[0], logits, NEG_INF)

    m_prev = m_ref[...]                                  # (g, 1)
    m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)                          # (g, block_s)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v)
    m_ref[...] = m_new

    @pl.when(s_idx == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def pick_block_s(s: int, d: int, itemsize: int,
                 target_bytes: int = 1 << 16) -> int:
    """KV block length: a whole number of DRAM rows, a multiple of 8
    sublanes, and a divisor of the (padded) sequence. Falls back to the
    largest multiple of 8 dividing ``s`` when no row-aligned block does."""
    token_bytes = d * itemsize               # bytes per token per head
    quantum = max(8, DRAM_ROW_BYTES // math.gcd(DRAM_ROW_BYTES, token_bytes))
    cap = max(quantum, target_bytes // token_bytes)
    for step in (quantum, 8):                # whole DRAM rows, then sublanes
        bs = cap - cap % step
        while bs > step and s % bs:
            bs -= step
        if s % bs == 0:
            return bs
    return 8


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 pos: jax.Array, block_s: int | None = None, *,
                 interpret: bool) -> jax.Array:
    """q: (b, h, d); caches: (b, h_kv, s, d); pos: scalar int32 (slots >
    pos are unwritten). Returns (b, h, d). ``interpret=True`` runs the
    Pallas interpreter (CPU tests); ``False`` compiles with Mosaic."""
    b, h, d = q.shape
    _, hkv, s, _ = k_cache.shape
    g = h // hkv
    if block_s is None:
        block_s = pick_block_s(s, d, k_cache.dtype.itemsize)
    assert s % block_s == 0, (s, block_s)
    qg = q.reshape(b, hkv, g, d)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (1,))

    grid = (b, hkv, s // block_s)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda i, j, k, pos: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, block_s, d),
                             lambda i, j, k, pos: (i, j, k, 0)),
                pl.BlockSpec((1, 1, block_s, d),
                             lambda i, j, k, pos: (i, j, k, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda i, j, k, pos: (i, j, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),    # running max
                pltpu.VMEM((g, 1), jnp.float32),    # running sum
                pltpu.VMEM((g, d), jnp.float32),    # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, qg, k_cache, v_cache)
    return out.reshape(b, h, d)
