"""Pallas-TPU Mosaic compiler parameters (``pltpu.CompilerParams``)."""
from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu


def tpu_compiler_params(*, dimension_semantics: tuple | None = None, **kw):
    """Build the Mosaic compiler-params object."""
    if dimension_semantics is not None:
        kw["dimension_semantics"] = dimension_semantics
    return pltpu.CompilerParams(**kw)
