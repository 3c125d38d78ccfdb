"""jit'd public wrapper for the chunked RWKV6 scan."""
from __future__ import annotations

import jax

from .kernel import pick_chunk, rwkv_scan
from .ref import rwkv_scan_ref


def time_mix(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
             u: jax.Array, use_kernel: bool = True, *,
             interpret: bool):
    """Chunk-parallel RWKV6 recurrence; `use_kernel=False` runs the
    sequential jnp oracle instead. `interpret` selects the Pallas
    interpreter (CPU) or the Mosaic-compiled kernel (TPU)."""
    if not use_kernel:
        return rwkv_scan_ref(r, k, v, w, u)
    return rwkv_scan(r, k, v, w, u, interpret=interpret)


__all__ = ["time_mix", "pick_chunk"]
