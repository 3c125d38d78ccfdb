"""Pytree map (``jax.tree.map``, including ``is_leaf``)."""
from __future__ import annotations

import jax

tree_map = jax.tree.map
