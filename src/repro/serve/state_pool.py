"""Recurrent-state pool: one fixed, row-aligned block per sequence and
linear-attention layer.

A linear-attention layer (KDA, Kimi Linear) keeps no per-token cache.
Each sequence holds a state of fixed size per layer, which a decode step
reads whole and rewrites in place. The pool gives every (slot, layer) a
block at a fixed address, so the memory system sees the state as
whole-row reads and writes of the same rows, step after step, beside the
per-token pages of :class:`~repro.serve.kv_cache.RowPagedKVCache`.

Accounting is host-only: no device array backs the blocks.
"""
from __future__ import annotations

from ..obs import host
from ..trace.layergraph import ROW
from ..workloads.stream import ExtentRecord, ExtentStream


class RecurrentStatePool:
    """``n_layers`` state blocks of ``state_bytes`` for each of
    ``n_slots`` sequences, from the row-aligned ``base_addr`` on. A slot
    holds its states from :meth:`alloc` (admission) to :meth:`free`
    (finish); only a slot that holds them can step."""

    def __init__(self, n_slots: int, n_layers: int, state_bytes: int,
                 base_addr: int):
        if base_addr % ROW:
            raise ValueError(f"state pool base {base_addr} is not "
                             f"row-aligned")
        if min(n_slots, n_layers, state_bytes) <= 0:
            raise ValueError("a state pool needs slots, layers and bytes")
        self.n_slots = n_slots
        self.n_layers = n_layers
        self.state_bytes = state_bytes
        self.base_addr = base_addr
        #: each block starts on a row, so every state is whole rows
        self.block_bytes = -(-state_bytes // ROW) * ROW
        self._live = [False] * n_slots

    def addr(self, slot: int, layer: int) -> int:
        return (self.base_addr
                + (slot * self.n_layers + layer) * self.block_bytes)

    @property
    def live(self) -> int:
        """Slots holding states."""
        return sum(self._live)

    def alloc(self, slot: int) -> None:
        if self._live[slot]:
            raise ValueError(f"slot {slot} already holds its states")
        self._live[slot] = True

    def free(self, slot: int) -> None:
        if not self._live[slot]:
            raise ValueError(f"slot {slot} holds no states")
        self._live[slot] = False

    @host.spanned("recorder.state_streams")
    def step_streams(self, slot: int, arrival_ns: float,
                     stream_id: int) -> tuple[ExtentStream, ExtentStream]:
        """One decode step of the slot's states: ``(reads, writes)``,
        each layer's state read whole, then rewritten in place."""
        if not self._live[slot]:
            raise ValueError(f"slot {slot} holds no states")
        host.count("recorder.state_bytes",
                   2 * self.n_layers * self.state_bytes)
        return tuple(
            ExtentStream(ExtentRecord(self.addr(slot, layer), self.state_bytes,
                                      kind, arrival_ns, stream_id)
                         for layer in range(self.n_layers))
            for kind in ("read", "write"))


__all__ = ["RecurrentStatePool"]
