"""jit'd public wrapper for GQA flash decode."""
from __future__ import annotations

import jax

from .kernel import flash_decode, pick_block_s
from .ref import flash_decode_ref


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos, use_kernel: bool = True, *,
                     interpret: bool) -> jax.Array:
    """Row-granularity GQA decode attention; `use_kernel=False` runs the
    jnp oracle instead. `interpret` selects the Pallas interpreter (CPU)
    or the Mosaic-compiled kernel (TPU); callers always name it."""
    if not use_kernel:
        return flash_decode_ref(q, k_cache, v_cache, pos)
    return flash_decode(q, k_cache, v_cache, pos, interpret=interpret)


__all__ = ["decode_attention", "pick_block_s"]
