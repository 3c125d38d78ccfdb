"""Lockstep multi-channel advance for the cycle engine.

A cube's channels are independent once :meth:`SystemSim.decompose` has
split the stream into per-channel transaction lists — the scalar path
simply runs one Python event loop per channel to completion. That leaves
two costs on the table for wide cubes (32–36 channels):

1. per-run dispatch overhead — ``N`` separate ``run()`` calls, each
   paying attribute-lookup and frame setup per event-loop iteration, and
2. no opportunity to stop early as channels drain at different times.

:func:`run_channels` instead starts a :class:`~.core.ChannelRunState`
per channel and advances **all unfinished channels together** in batched
state-steps: each sweep gives every live channel a ``batch``-iteration
slice of its event loop, with a numpy boolean mask tracking which
channels are still live so drained channels drop out of the sweep
immediately. Because channels share no state and each state-step runs
the *same* loop body as :meth:`~.core.ChannelSimCore.run`, the result is
bit-identical to the scalar path by construction — and asserted so on
the facade trace suite (:func:`facade_trace_suite`,
``benchmarks/hybrid_xval.py``, ``tests/test_hybrid.py``).

Telemetry sampling (``sample_window_ns`` on the underlying cores — the
:class:`repro.obs.MetricsProbe` seam) rides *inside* ``advance``: each
state appends its own window samples as its slice of the loop runs, so
the lockstep driver needs no coordination, sweep order cannot affect
the sampled series, and the bit-identity guarantee extends unchanged
to sampled runs (``benchmarks/obs_overhead.py`` gates both directions:
off-mode identity and ≤5 % on-mode overhead).
"""
from __future__ import annotations

import numpy as np

from ...obs import host
from .channels import make_channel_sim
from .core import SimResult, Txn


@host.spanned("cycle.advance")
def advance_states(states, batch: int = 2048) -> None:
    """Drain a set of live :class:`~.core.ChannelRunState`\\ s in lockstep
    ``batch``-iteration slices (the same sweep loop as
    :func:`run_channels`, over caller-owned states). This is the warm
    cross-step driver: :class:`~repro.core.system_sim.WarmRunState` feeds
    each step's transactions into persistent per-channel states and calls
    this to drain them — channels share no state, so any interleaving of
    ``advance`` calls is bit-identical to per-channel loops."""
    live = np.array([not s.finished for s in states], dtype=bool)
    while live.any():
        for i in np.flatnonzero(live):
            if states[i].advance(batch):
                live[i] = False


def run_channels(kind: str, kwargs: dict, txns_per_channel: list[list[Txn]],
                 batch: int = 2048) -> list[SimResult]:
    """Simulate every channel of a cube in lockstep batches.

    ``kind``/``kwargs`` name a :data:`~.channels.CHANNEL_SIM_KINDS` entry
    (one fresh simulator — hence one fresh policy FSM — is built per
    channel; policies are stateful and must never be shared). Returns one
    :class:`SimResult` per channel, in input order, bit-identical to
    ``[make_channel_sim(kind, **kwargs).run(t) for t in txns_per_channel]``.
    """
    n = len(txns_per_channel)
    host.count("cycle.txns", sum(map(len, txns_per_channel)))
    with host.span("cycle.setup"):
        states = [make_channel_sim(kind, **kwargs).start_run(txns)
                  for txns in txns_per_channel]
    advance_states(states, batch)
    return [states[i].result() for i in range(n)]


__all__ = ["run_channels", "advance_states"]
