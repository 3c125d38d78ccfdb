"""Compiled-artifact cost analysis as a flat ``{metric: value}`` dict."""
from __future__ import annotations


def normalize_cost_analysis(cost) -> dict:
    """dict | None -> flat {metric: value} dict."""
    return dict(cost) if cost else {}


def xla_cost_analysis(compiled) -> dict:
    """Cost analysis of a ``jax.stages.Compiled``; {} when the backend
    provides none."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    return normalize_cost_analysis(cost)
