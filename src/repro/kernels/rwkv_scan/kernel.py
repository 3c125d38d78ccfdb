"""Chunked RWKV6 time-mix Pallas TPU kernel — row-granularity streaming of
the attention-free arch's hot loop.

The recurrence  S_t = diag(w_t) S_{t-1} + k_t^T v_t,
               o_t = r_t (diag(u) k_t^T v_t + S_{t-1})
is evaluated chunk-parallel: within a chunk of C tokens all cross-token
terms are contracted with per-channel cumulative decay products, and
only the (hd x hd) state crosses chunk boundaries (VMEM scratch). Every
contraction runs in float32 on the VPU, one row at a time on 2-D tiles,
so the kernel matches the float32 oracle on the chip. Decays are only
ever multiplied, never divided, so a vanishing decay underflows to zero
and nothing can overflow, whatever the data-dependent w in (0, 1).

Chunk size is chosen so one operand chunk (C x hd x 4 B) is a whole number
of 4 KB DRAM rows — each r/k/v/w DMA is one RD_row burst train (C=16,
hd=64 -> exactly one row), the RoMe contract.

Grid: (b, H, n_chunks); the chunk axis is sequential ("arbitrary") and
carries the state in scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...compat.pallas import tpu_compiler_params

DRAM_ROW_BYTES = 4096


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_final_ref, S, O, RD):
    c_idx = pl.program_id(2)
    C, hd = r_ref.shape[2], r_ref.shape[3]

    @pl.when(c_idx == 0)
    def _init():
        S[...] = jnp.zeros_like(S)

    r = r_ref[0, 0].astype(jnp.float32)              # (C, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)              # decay in (0, 1)
    u = u_ref[0].astype(jnp.float32)                 # (1, hd)

    # Intra-chunk term, one output row i at a time (2-D tiles only):
    #   o_i = sum_{j<i} (sum_d r[i,d] k[j,d] dec_i[j,d]) v_j
    #         + (sum_d r[i,d] u[d] k[i,d]) v_i,
    #   dec_i[j] = prod_{t=j+1}^{i-1} w_t   (rows j >= i stay 0).
    # Decays are running products, as in the recurrence itself: no log or
    # exp (the TPU's log is off by up to 1e-4) and no division, so a
    # vanishing decay underflows to zero and nothing can overflow.
    j_col = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    dec = jnp.zeros((C, hd), jnp.float32)
    cum = jnp.ones((1, hd), jnp.float32)             # prod_{t<i} w_t
    for i in range(C):
        if i:
            dec = jnp.where(j_col == i - 1, 1.0, dec * w[i - 1:i])
        a = jnp.sum(r[i:i + 1] * k * dec, axis=1,
                    keepdims=True)                   # (C, 1): A[i, :]
        bonus = jnp.sum(r[i:i + 1] * u * k[i:i + 1], axis=1,
                        keepdims=True)               # (1, 1)
        O[i:i + 1, :] = jnp.sum(a * v, axis=0, keepdims=True) \
            + bonus * v[i:i + 1]
        RD[i:i + 1, :] = r[i:i + 1] * cum           # r_i decayed to chunk start
        cum = cum * w[i:i + 1]
    dec = jnp.where(j_col == C - 1, 1.0, dec * w[C - 1:C])
    k_dec = k * dec                                  # k_j decayed to chunk end

    # State contribution, output and state update, float32 on the VPU (the
    # MXU rounds float32 operands even at HIGHEST precision):
    #   o_i += sum_d r_dec[i,d] S[d,:]
    #   S'[d,:] = cum[d] S[d,:] + sum_j k_dec[j,d] v_j
    r_dec = RD[...]
    s_old = S[...]
    o = O[...]
    for d in range(hd):
        row = s_old[d:d + 1]                         # (1, hd)
        o = o + r_dec[:, d:d + 1] * row
        S[d:d + 1, :] = cum[:, d:d + 1] * row + jnp.sum(
            k_dec[:, d:d + 1] * v, axis=0, keepdims=True)
    o_ref[0, 0] = o.astype(o_ref.dtype)

    @pl.when(c_idx == pl.num_programs(2) - 1)
    def _finish():
        s_final_ref[0, 0] = S[...]


def pick_chunk(s: int, hd: int, itemsize: int = 4) -> int:
    """Chunk length: whole DRAM rows per operand chunk and divides s."""
    c = max(8, DRAM_ROW_BYTES // (hd * itemsize))
    while (c * hd * itemsize) % DRAM_ROW_BYTES and c > 8:
        c -= 8
    while s % c and c > 1:
        c //= 2
    return max(1, c)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
              u: jax.Array, chunk: int | None = None, *,
              interpret: bool):
    """r/k/v/w: (b, s, H, hd); u: (H, hd).
    Returns (o (b, s, H, hd), final state (b, H, hd, hd)).
    ``interpret=True`` runs the Pallas interpreter (CPU tests); ``False``
    compiles with Mosaic."""
    b, s, H, hd = r.shape
    if chunk is None:
        chunk = pick_chunk(s, hd, 4)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    # (b, H, s, hd) layout so the chunk dim is contiguous per (b, H).
    tr = lambda x: x.transpose(0, 2, 1, 3)
    rr, kk, vv, ww = tr(r), tr(k), tr(v), tr(w)

    spec = pl.BlockSpec((1, 1, chunk, hd), lambda i, j, c: (i, j, c, 0))
    o, s_final = pl.pallas_call(
        _kernel,
        grid=(b, H, nc),
        in_specs=[spec, spec, spec,
                  spec,
                  pl.BlockSpec((1, 1, hd), lambda i, j, c: (j, 0, 0))],
        out_specs=[spec,
                   pl.BlockSpec((1, 1, hd, hd), lambda i, j, c: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, H, s, hd), r.dtype),
                   jax.ShapeDtypeStruct((b, H, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32),    # state
                        pltpu.VMEM((chunk, hd), jnp.float32),  # intra out
                        pltpu.VMEM((chunk, hd), jnp.float32)], # decayed r
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rr, kk, vv, ww, u.reshape(H, 1, hd))
    return o.transpose(0, 2, 1, 3), s_final
