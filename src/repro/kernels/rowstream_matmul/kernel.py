"""Row-stream matmul Pallas TPU kernel — the RD_row analogue on TPU.

RoMe's insight adapted to the TPU memory hierarchy: every HBM->VMEM DMA of
the weight operand is one large *contiguous* block — a multiple of the 4 KB
DRAM row along the streamed (K) dimension with the full N extent — so the
HBM controller sees pure row-granularity streaming (one descriptor ≡ one
RD_row burst train), never strided cache-line gather. Block shapes are
MXU-aligned (multiples of 128 on the contraction/output dims).

Grid: (K // bk,) sequential; the fp32 accumulator lives in the output ref
(revisited each step — Pallas keeps it resident in VMEM across grid steps).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...compat.pallas import tpu_compiler_params

DRAM_ROW_BYTES = 4096
MXU = 128


def _kernel(x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                          preferred_element_type=jnp.float32)


def pick_bk(k: int, n: int, itemsize: int, vmem_budget: int = 1 << 21) -> int:
    """Largest K-block that (a) keeps the weight block under the VMEM
    budget, (b) is a multiple of the MXU tile, and (c) makes the block a
    whole number of DRAM rows (bk * n * itemsize ≡ 0 mod 4096)."""
    bk = min(k, max(MXU, vmem_budget // max(1, n * itemsize)))
    bk -= bk % MXU
    bk = max(MXU, bk)
    while (bk * n * itemsize) % DRAM_ROW_BYTES and bk > MXU:
        bk -= MXU
    while k % bk and bk > MXU:
        bk -= MXU
    return max(MXU, bk)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def rowstream_matmul(x: jax.Array, w: jax.Array, bk: int | None = None, *,
                     interpret: bool) -> jax.Array:
    """x: (m, k) @ w: (k, n) -> (m, n). Weight streamed in row-aligned
    K-blocks of the full N width. ``interpret=True`` runs the Pallas
    interpreter (CPU tests); ``False`` compiles with Mosaic."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    if bk is None:
        bk = pick_bk(k, n, w.dtype.itemsize)
    assert k % bk == 0, (k, bk)
    grid = (k // bk,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, bk), lambda i: (0, i)),
            pl.BlockSpec((bk, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((m, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        # The K axis is a reduction into the resident output block.
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, w)
    return out.astype(x.dtype)
