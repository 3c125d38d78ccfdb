"""Address mapping: software addresses -> (channel, bank/VBA, row, col).

The paper sweeps address mappings for both baseline and RoMe and picks the
bandwidth-maximizing one (§VI-A). For bulk-sequential LLM traffic that is a
channel-interleaved stripe: consecutive AG_MC-sized units rotate across
channels, then across banks/VBAs (RoMe) or bank groups/banks (HBM4), then
rows. This module provides the stripe math plus the channel load-balance
ratio (LBR, Fig 13) used throughout the perf model.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..obs import host
from .timing import MemSystemConfig


@dataclass(frozen=True)
class AddressMap:
    """Stripe-interleaved address map over a multi-cube memory system."""

    n_channels: int            # total channels (cubes * channels_per_cube)
    stripe_bytes: int          # interleave granularity == AG_MC
    banks_per_channel: int     # banks (HBM4) or VBAs (RoMe)
    row_bytes: int             # bytes per effective row

    def channel_of(self, addr: np.ndarray | int):
        return (np.asarray(addr) // self.stripe_bytes) % self.n_channels

    def unit_of(self, addr: np.ndarray | int):
        """Index of the stripe unit within its channel."""
        return (np.asarray(addr) // self.stripe_bytes) // self.n_channels

    def bank_of(self, addr: np.ndarray | int):
        return self.unit_of(addr) % self.banks_per_channel

    def row_of(self, addr: np.ndarray | int):
        units_per_row = max(1, self.row_bytes // self.stripe_bytes)
        return (self.unit_of(addr) // self.banks_per_channel) // units_per_row


def make_address_map(cfg: MemSystemConfig, n_cubes: int = 8) -> AddressMap:
    if cfg.ag_mc_bytes >= cfg.row_bytes:
        banks = cfg.vbas_per_channel            # RoMe: interleave over VBAs
    else:
        banks = cfg.banks_per_channel
    return AddressMap(
        n_channels=cfg.channels_per_cube * n_cubes,
        stripe_bytes=cfg.ag_mc_bytes,
        banks_per_channel=banks,
        row_bytes=cfg.row_bytes,
    )


# ---------------------------------------------------------------------------
# Channel load balance (Fig 13) & the vectorized extent census
# ---------------------------------------------------------------------------
#
# All three censuses below (exact bytes, stripe-unit/transaction counts,
# record touches) share the same cyclic-window stripe math: an extent
# covers `full` complete rotations of the channel ring plus one window
# of `rem` consecutive channels starting at its first unit's channel.
# The batched kernel (`extent_census`) computes every census for a whole
# batch of extents — optionally segmented into per-stream rows — in a
# fixed number of numpy passes: full rotations reduce to per-segment
# sums, and the remainder windows become difference-array updates
# (+w at window start, -w at window end, wrapped tails folded to
# channel 0) resolved by one cumulative sum per segment. That is what
# lets the queue-window model price a fleet of decode steps
# array-at-a-time instead of looping Python over every record.


def extent_arrays(extents) -> tuple[np.ndarray, np.ndarray]:
    """(starts, sizes) int64 arrays from ``[(addr, nbytes)]`` (or any
    (n, 2)-shaped array-like); non-positive sizes dropped, matching the
    scalar loops' skip."""
    a = np.asarray(extents, dtype=np.int64)
    if a.size == 0:
        z = np.zeros(0, np.int64)
        return z, z.copy()
    starts, sizes = a[:, 0], a[:, 1]
    keep = sizes > 0
    if not bool(keep.all()):
        starts, sizes = starts[keep], sizes[keep]
    return starts, sizes


def _windowed_add(acc: np.ndarray, seg: np.ndarray | None, ch0: np.ndarray,
                  length: np.ndarray, weight) -> None:
    """Add ``weight`` to the cyclic channel window ``[ch0, ch0+length)``
    (mod n_channels) of each extent, accumulated into ``acc`` of shape
    (n_segs, n_channels) via difference arrays + one cumsum. ``length``
    must be in [0, n_channels]; ``weight`` is a scalar or per-extent
    array. ``seg`` selects each extent's row (None == row 0)."""
    n_segs, nch = acc.shape
    if ch0.size == 0:
        return
    w = np.broadcast_to(np.asarray(weight, dtype=acc.dtype), ch0.shape)
    row = np.zeros(ch0.shape, np.int64) if seg is None else seg
    # One spare slot per row absorbs -w at window ends that land exactly
    # on nch (never read back by the per-row cumsum).
    d = np.zeros(n_segs * (nch + 1), dtype=acc.dtype)
    base = row * (nch + 1)
    end = ch0 + length
    np.add.at(d, base + ch0, w)
    np.add.at(d, base + np.minimum(end, nch), -w)
    wrap = end - nch
    wrapped = wrap > 0
    if bool(wrapped.any()):
        np.add.at(d, base[wrapped], w[wrapped])          # [0, end-nch)
        np.add.at(d, base[wrapped] + wrap[wrapped], -w[wrapped])
    acc += np.cumsum(d.reshape(n_segs, nch + 1), axis=1)[:, :nch]


@host.spanned("census")
def extent_census(amap: AddressMap, starts: np.ndarray, sizes: np.ndarray,
                  seg: np.ndarray | None = None, n_segs: int = 1
                  ) -> dict[str, np.ndarray]:
    """Every per-channel census of a batch of extents in one vectorized
    pass. Returns ``{"bytes", "units", "touches"}``, each an
    ``(n_segs, n_channels)`` int64 array:

    * ``bytes`` — exact per-channel byte counts (partial first/last
      stripes trimmed), the :func:`channel_bytes` census;
    * ``units`` — stripe-unit (MC transaction) counts, duplicates kept,
      the :func:`channel_unit_counts` census;
    * ``touches`` — extents touching each channel at least once, the
      :func:`record_touch_counts` census.

    ``seg`` (per-extent segment/stream index into ``n_segs`` rows) is
    the batching axis: the queue-window model passes one segment per
    decode step and prices a whole fleet round in a single call.
    """
    g = amap.stripe_bytes
    nch = amap.n_channels
    out = {k: np.zeros((n_segs, nch), np.int64)
           for k in ("bytes", "units", "touches")}
    if starts.size == 0:
        return out
    first_unit = starts // g
    last_unit = (starts + sizes - 1) // g
    n_units = last_unit - first_unit + 1
    full, rem = np.divmod(n_units, nch)
    ch0 = first_unit % nch
    # Full rotations load every channel of the segment equally.
    if seg is None:
        full_sum = np.array([full.sum()])
    else:
        full_sum = np.bincount(seg, weights=full, minlength=n_segs
                               ).astype(np.int64)
    out["units"] += full_sum[:, None]
    out["bytes"] += full_sum[:, None] * g
    sel = rem > 0
    sseg = None if seg is None else seg[sel]
    _windowed_add(out["units"], sseg, ch0[sel], rem[sel], 1)
    _windowed_add(out["bytes"], sseg, ch0[sel], rem[sel], g)
    # Trim the partial first/last stripes to exact byte counts.
    head_excess = starts - first_unit * g
    tail_excess = (last_unit + 1) * g - (starts + sizes)
    row = np.zeros(starts.shape, np.int64) if seg is None else seg
    flat = out["bytes"].reshape(-1)
    np.subtract.at(flat, row * nch + ch0, head_excess)
    np.subtract.at(flat, row * nch + last_unit % nch, tail_excess)
    # Touches: extents spanning a whole rotation touch every channel
    # once; shorter ones touch their n_units-wide window.
    big = n_units >= nch
    if seg is None:
        big_sum = np.array([np.count_nonzero(big)])
    else:
        big_sum = np.bincount(seg[big], minlength=n_segs)
    out["touches"] += big_sum[:, None]
    small = ~big
    sseg = None if seg is None else seg[small]
    _windowed_add(out["touches"], sseg, ch0[small], n_units[small], 1)
    return out


def channel_bytes(amap: AddressMap, extents) -> np.ndarray:
    """Per-channel byte counts for a set of (start_addr, nbytes) extents.

    Exact stripe accounting (vectorized): each extent contributes
    floor/ceil stripes to a cyclic window of channels, with the partial
    first/last stripes trimmed to exact byte counts.
    """
    starts, sizes = extent_arrays(extents)
    return extent_census(amap, starts, sizes)["bytes"][0]


def channel_unit_counts(amap: AddressMap, extents) -> np.ndarray:
    """Per-channel *stripe-unit* counts for a set of (addr, nbytes)
    extents — the exact number of MC transactions
    :meth:`repro.core.system_sim.SystemSim.decompose` would create per
    channel (one txn per touched unit, duplicates counted per extent),
    without materializing any of them. Same cyclic-window stripe math as
    :func:`channel_bytes`, but counting whole units instead of trimming
    partial stripes: this is the O(n_extents) transaction census the
    queue-window model (:mod:`repro.core.queue_model`) and the hybrid
    fast path price unscaled streams with.
    """
    starts, sizes = extent_arrays(extents)
    return extent_census(amap, starts, sizes)["units"][0]


def record_touch_counts(amap: AddressMap, extents) -> np.ndarray:
    """Per-channel *record* counts: how many of the given extents touch
    each channel at least once (each record contributes at most 1 per
    channel). This is the per-extent cost census — a record opening a
    channel pays that channel's fixed row-open/ACT path once regardless
    of how many units it then streams, which is the term the queue-window
    model's ``ext_ns_per_rec`` coefficient prices. Vectorized, same
    cyclic-window stripe math as :func:`channel_unit_counts`.
    """
    starts, sizes = extent_arrays(extents)
    return extent_census(amap, starts, sizes)["touches"][0]


def load_balance_ratio(amap: AddressMap,
                       extents: list[tuple[int, int]]) -> float:
    """LBR = mean(channel bytes) / max(channel bytes); 1.0 == perfectly
    balanced. The effective bandwidth of a bulk transfer scales with LBR
    because the slowest (most loaded) channel gates completion."""
    cb = channel_bytes(amap, extents)
    mx = cb.max()
    if mx == 0:
        return 1.0
    return float(cb.mean() / mx)


def effective_bandwidth_fraction(amap: AddressMap,
                                 extents: list[tuple[int, int]]) -> float:
    """Fraction of peak system bandwidth achievable for these extents,
    limited by the most-loaded channel."""
    return load_balance_ratio(amap, extents)
