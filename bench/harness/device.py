"""The accelerator a run is measured on.

A run names the device JAX reports and refuses to measure without the
chips its cell asks for: no CPU fallback, no result.
"""
from __future__ import annotations

import os
from pathlib import Path

from .manifest import ROOT

#: Fixed, inside the checkout: the path is part of the cache's key.
CACHE_DIR = ROOT / ".jax_cache"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def init_jax(cache_dir: Path = CACHE_DIR):
    """Import JAX with its persistent compilation cache in the checkout,
    handed to the program too through ``JAX_COMPILATION_CACHE_DIR``."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def require_tpu(jax, chips: int) -> list:
    """The first ``chips`` TPU devices, or :class:`NoAccelerator`."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoAccelerator(f"JAX found no backend: {e}") from e
    tpus = [d for d in devices if d.platform == "tpu"]
    if len(tpus) < chips:
        raise NoAccelerator(
            f"cell needs {chips} TPU chip(s); JAX reports "
            f"{[f'{d.platform}:{d.device_kind}' for d in devices]}")
    return tpus[:chips]


def describe(devices: list) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peak_bytes(devices: list) -> int:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)
