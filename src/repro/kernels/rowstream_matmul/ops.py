"""jit'd public wrapper for the row-stream matmul."""
from __future__ import annotations

import jax

from .kernel import pick_bk, rowstream_matmul
from .ref import rowstream_matmul_ref


def matmul(x: jax.Array, w: jax.Array, use_kernel: bool = True, *,
           interpret: bool) -> jax.Array:
    """Row-granularity streaming matmul. `interpret=True` runs the kernel
    body in the Pallas interpreter (CPU); `False` compiles the same
    pallas_call with Mosaic (TPU). `use_kernel=False` runs the jnp oracle
    instead."""
    if not use_kernel:
        return rowstream_matmul_ref(x, w)
    return rowstream_matmul(x, w, interpret=interpret)


__all__ = ["matmul", "pick_bk"]
