"""Serve-trace recording: continuous batching -> per-step extent streams.

:class:`ServeTraceRecorder` is the bridge between the serving layer and
the memory system. It owns a :class:`~repro.serve.batching.ContinuousBatcher`
and a :class:`~repro.serve.kv_cache.RowPagedKVCache`, drives them one
decode step at a time, and emits each step as one multi-tenant
:class:`~repro.workloads.ExtentStream`:

* **weight reads** — a scaled weights-only decode slice built once via
  :func:`weight_step_stream` (``from_layer_ops`` pacing, so intra-step
  op serialization survives), shifted to the step's start time and
  tagged with *negative* stream ids (``-1 - op_index``);
* **KV reads** — one whole-page :meth:`~RowPagedKVCache.read_stream`
  per active slot, retagged with the request id;
* **KV appends** — one :meth:`~RowPagedKVCache.append_stream` per
  active slot (the decoded token's K/V write), retagged likewise;
* **prefill extents** (``prefill_chunk_tokens`` set) — per prefill
  chunk, the chunk-attention *prefix read* (whole-page reads of the
  context prefilled so far) plus the chunk's prompt-scale K/V appends
  coalesced to row-granular page runs
  (:meth:`~RowPagedKVCache.append_chunk_stream`). With
  ``prefill_overlap=True`` (packing-prefetch) the chunk's fetch is
  packed into the concurrent decode step's stream — hidden under the
  decode compute window; with ``prefill_overlap=False`` a pending chunk
  claims a dedicated prefill-only step and decode stalls for its
  duration (classic prefill-priority alternation).

The negative-vs-nonnegative stream-id split is the tagging contract:
consumers can always separate weight traffic from request traffic, and
``of_stream(rid)`` recovers exactly one request's KV records — the
conservation property tests/test_serve_replay.py pins.

Admission control reserves the *worst case* — ``pages_for(prompt +
max_new)`` — against the pool before a request joins the batch, so a
recorded run can never hit ``MemoryError`` mid-decode (the batcher's
FIFO admission check would otherwise only cover the prompt).
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import numpy as np

from ...obs import host
from ...trace.layergraph import LayerOp, decode_ops
from ...workloads import (ExtentStream, from_layer_ops, layer_ops_span_ns,
                          scale_layer_ops)
from ..batching import ContinuousBatcher, Request
from ..kv_cache import RowPagedKVCache, tokens_per_row
from .arrivals import ArrivalProcess, RequestSpec

#: Weight records are tagged ``WEIGHT_STREAM_BASE - op_index`` — negative,
#: so they can never collide with request ids (which are >= 0).
WEIGHT_STREAM_BASE = -1

#: Default KV-pool base address: beyond any scaled weight slice's
#: allocator cursor, so weights and KV never alias.
KV_BASE_ADDR = 64 << 20


def weight_ops(w, n_ops: int = 4, n_devices: int = 8) -> list[LayerOp]:
    """The first ``n_ops`` decode layer ops reduced to their *weight*
    reads: KV-read extents and activation/KV writes are stripped (live KV
    traffic comes from the paged cache at replay time). For attention
    ops the weight tensor is the first extent; FFN/MoE ops read only
    weights to begin with."""
    ops = decode_ops(w, batch=1, seq_len=1, n_devices=n_devices)[:n_ops]
    return [LayerOp(op.name, op.kind, op.flops,
                    op.extents[:1] if op.kind == "attn"
                    else list(op.extents))
            for op in ops]


def weight_step_stream(w, acc, n_ops: int = 4,
                       scale: float = 2 ** -15) -> tuple[ExtentStream, float]:
    """One decode step's weight-read stream, byte-scaled for cycle-level
    tractability (cf. ``perfmodel.tpot.xval_decode_stream``) and tagged
    with negative stream ids. Built once per replay and shifted to each
    step's start time.

    Returns ``(stream, chain_ns)`` — the records plus the modeled
    roofline span of the whole op chain
    (:func:`repro.workloads.layer_ops_span_ns`, the same pacing rule
    ``from_layer_ops`` applies between ops). ``chain_ns`` is the natural
    ``kv_offset_ns`` for the recorder: the per-slot KV gather/append
    group then becomes visible exactly like the op *following* the
    slice, which is the serialized-group regime the analytic TPOT model
    (``stream_mem_ns``) is valid in.
    """
    ops = scale_layer_ops(weight_ops(w, n_ops), scale)
    s = from_layer_ops(ops, acc)
    return ExtentStream(
        replace(r, stream_id=WEIGHT_STREAM_BASE - r.stream_id)
        for r in s), layer_ops_span_ns(ops, acc)


def make_kv_cache(n_slots: int, max_seq_tokens: int,
                  n_kv_heads: int = 2, head_dim: int = 64,
                  rows_per_page: int = 1, headroom: int = 2,
                  dtype: str = "bfloat16") -> RowPagedKVCache:
    """A row-paged KV pool sized so ``n_slots`` concurrent sequences of up
    to ``max_seq_tokens`` always fit (plus ``headroom`` spare pages). The
    scaled-down KV geometry mirrors the byte-scaling of the weight slice:
    what the memory system sees is whole-row K/V page streams either way.
    """
    pt = tokens_per_row(head_dim, n_kv_heads, rows_per_page=rows_per_page)
    pages_per_seq = -(-max_seq_tokens // pt)
    return RowPagedKVCache(
        n_pages=n_slots * pages_per_seq + headroom, page_tokens=pt,
        n_kv_heads=n_kv_heads, head_dim=head_dim, max_seqs=n_slots,
        max_pages_per_seq=pages_per_seq, dtype=dtype)


@dataclass(frozen=True)
class StepTrace:
    """One recorded step (decode, prefill, or both)."""

    index: int                     # batcher step index (0-based)
    start_ns: float                # step start on the replay clock
    stream: ExtentStream           # weights + per-slot KV, absolute times
    admitted: tuple[int, ...]      # rids admitted at this step's start
    active: tuple[int, ...]        # rids that decoded this step
    finished: tuple[int, ...]      # rids that produced their last token
    prefilled: tuple = ()          # (rid, n_tokens) prefill chunks packed
    prefill_done: tuple = ()       # rids whose prompt completed this step
    kind: str = "decode"           # "decode" | "prefill" | "mixed"

    @property
    def rids(self) -> tuple:
        """Every request this step served (active decoders followed by
        prefill-chunk owners, deduplicated, order-stable) — the
        participant set :class:`repro.obs.ObsCollector` splits the
        step's memory time across."""
        seen = dict.fromkeys(self.active)
        for rid, _ in self.prefilled:
            seen.setdefault(rid)
        return tuple(seen)


class ServeTraceRecorder:
    """Steps batcher + KV cache and emits per-step extent streams.

    The recorder is clock-agnostic: the caller (normally
    :class:`~repro.serve.replay.engine.ReplayEngine`) advances simulated
    time, feeds it to :meth:`submit_due` / :meth:`step`, and decides how
    long each recorded step took. That keeps the serving trace
    *policy-dependent in the right way* — admission windows shift with
    the measured memory makespans of the policy under test.
    """

    def __init__(self, arrivals: ArrivalProcess, cache: RowPagedKVCache,
                 n_slots: int | None = None,
                 weight_stream: ExtentStream = ExtentStream(),
                 kv_offset_ns: float = 0.0,
                 kv_base_addr: int = KV_BASE_ADDR,
                 prefill_chunk_tokens: int | None = None,
                 prefill_overlap: bool = True):
        n_slots = cache.max_seqs if n_slots is None else n_slots
        if n_slots > cache.max_seqs:
            raise ValueError(
                f"n_slots={n_slots} exceeds cache.max_seqs={cache.max_seqs}")
        w_end = max((r.end for r in weight_stream), default=0)
        if w_end > kv_base_addr:
            # Silent aliasing would make the sim see weight and KV reads
            # hitting the same rows — every SLO metric quietly wrong.
            raise ValueError(
                f"weight slice spans to {w_end} B, past kv_base_addr="
                f"{kv_base_addr}; shrink the slice scale or raise the "
                f"KV base")
        self.arrivals = arrivals
        self.cache = cache
        self.weight_stream = weight_stream
        self.kv_offset_ns = kv_offset_ns
        self.kv_base_addr = kv_base_addr
        self.prefill_overlap = prefill_overlap
        self.batcher = ContinuousBatcher(
            n_slots, admit=self._admit,
            prefill_chunk_tokens=prefill_chunk_tokens)
        self.requests: dict[int, Request] = {}
        self.specs: dict[int, RequestSpec] = {}
        self._committed_pages = 0          # worst-case pages of live reqs
        self._worst_pages: dict[int, int] = {}

    # -- admission -----------------------------------------------------------

    def _worst_case_pages(self, req: Request) -> int:
        return self.cache.pages_for(req.prompt_len + req.max_new_tokens)

    def _admit(self, req: Request) -> bool:
        """Check-and-commit: the reservation is taken the moment the
        batcher's admission predicate says yes. ContinuousBatcher pops
        the request exactly when this returns True, so a True return and
        an admission are one-to-one — committing here (rather than after
        ``schedule()`` returns) is what keeps several admissions in one
        scheduling iteration from each passing against the same stale
        count and overcommitting the pool."""
        worst = self._worst_case_pages(req)
        if self._committed_pages + worst > self.cache.n_pages:
            return False
        self._committed_pages += worst
        self._worst_pages[req.rid] = worst
        return True

    @host.spanned("recorder.submit")
    def submit_due(self, now_ns: float) -> list[Request]:
        """Move every arrived spec into the batcher's wait queue."""
        out = []
        for spec in self.arrivals.due(now_ns):
            worst = self.cache.pages_for(spec.prompt_len
                                         + spec.max_new_tokens)
            # Both limits matter: a request over max_pages_per_seq would
            # pass the pool check, then crash in alloc_seq/append_token
            # mid-replay once its page-table row overflows.
            limit = min(self.cache.n_pages, self.cache.max_pages_per_seq)
            if worst > limit:
                raise ValueError(
                    f"request {spec.rid} needs {worst} pages but the cache "
                    f"allows {limit} per sequence "
                    f"(n_pages={self.cache.n_pages}, max_pages_per_seq="
                    f"{self.cache.max_pages_per_seq}); size it with "
                    f"make_kv_cache(max_seq_tokens=...)")
            req = Request(spec.rid,
                          np.zeros(spec.prompt_len, np.int32),
                          max_new_tokens=spec.max_new_tokens)
            self.requests[spec.rid] = req
            self.specs[spec.rid] = spec
            self.batcher.submit(req)
            out.append(req)
        return out

    # -- one decode step -----------------------------------------------------

    @host.spanned("recorder.step")
    def step(self, now_ns: float) -> StepTrace | None:
        """Run one scheduling iteration + step at ``now_ns``.

        Returns the recorded :class:`StepTrace`, or None when no request
        is active (the caller should jump the clock to the next arrival).
        Per decoding slot the emitted order is read-then-append: the
        attention gather sees the pre-append sequence length, the decoded
        token's K/V write lands after it. All slots' KV groups arrive at
        ``now + kv_offset_ns`` — with the offset set to the weight
        chain's span (:func:`weight_step_stream`), the gather behaves
        like the op following the slice; tenants still contend with each
        other inside that window.

        With chunked prefill enabled, each step also carries up to one
        prefill pack (chunk-attention prefix reads + coalesced K/V page
        appends per chunk, at the same KV window). Under
        ``prefill_overlap=True`` the pack rides in the decode step
        (packing-prefetch: the fetch hides under the decode window);
        under ``prefill_overlap=False`` a pending pack claims the whole
        step and decode stalls (``kind="prefill"``). Either way a chunk
        committed during step *i* makes its request decode-eligible at
        step *i + 1*.
        """
        admitted = []
        chunked = self.batcher.prefill_chunk_tokens is not None
        for slot, req in self.batcher.schedule():
            # Pages were reserved in _admit; allocating the prompt here
            # can therefore never exhaust the pool. Chunked prefill
            # starts the sequence empty — its pages arrive chunk by
            # chunk through append_chunk_stream.
            self.cache.alloc_seq(slot, 0 if chunked else req.prompt_len)
            admitted.append(req.rid)
        active = [(slot, req) for slot, req in enumerate(self.batcher.active)
                  if req is not None]
        if not active:
            return None
        pack = self.batcher.prefill_pack()
        prefill_only = bool(pack) and not self.prefill_overlap
        index = self.batcher.steps
        with host.span("recorder.kv_streams"):
            streams = [self.weight_stream.shifted(now_ns)] \
                if self.weight_stream else []
            kv_ns = now_ns + self.kv_offset_ns
            slot_of = {}
            decoding = []
            for slot, req in active:
                slot_of[req.rid] = slot
                if prefill_only or not req.prefill_done:
                    continue
                decoding.append(req.rid)
                streams.append(
                    self.cache.read_stream(slot, self.kv_base_addr,
                                           arrival_ns=kv_ns).retagged(req.rid)
                    + self.cache.append_stream(slot, self.kv_base_addr,
                                               arrival_ns=kv_ns)
                    .retagged(req.rid))
            for slot, req, n in pack:
                # Chunk attention reads the context prefilled so far (empty
                # on the first chunk), then the chunk's K/V lands as
                # row-granular page runs.
                streams.append(
                    (self.cache.read_stream(slot, self.kv_base_addr,
                                            arrival_ns=kv_ns)
                     + self.cache.append_chunk_stream(slot, n,
                                                      self.kv_base_addr,
                                                      arrival_ns=kv_ns))
                    .retagged(req.rid))
        with host.span("recorder.interleave"):
            stream = ExtentStream.interleave(streams)
        host.count("recorder.records", len(stream))
        finished = self.batcher.record_tokens(
            np.zeros(self.batcher.n_slots, np.int32),
            decode=not prefill_only)
        prefill_done = self.batcher.apply_prefill(pack)
        for req in finished:
            self.cache.free_seq(slot_of[req.rid])
            self._committed_pages -= self._worst_pages.pop(req.rid)
        if not decoding:
            kind = "prefill"       # decode stalled or nothing decodable
        else:
            kind = "mixed" if pack else "decode"
        return StepTrace(
            index=index, start_ns=now_ns, stream=stream,
            admitted=tuple(admitted),
            active=tuple(decoding),
            finished=tuple(req.rid for req in finished),
            prefilled=tuple((req.rid, n) for _, req, n in pack),
            prefill_done=tuple(req.rid for req in prefill_done),
            kind=kind)

    def idle(self) -> bool:
        """No queued or active work (arrivals may still be pending)."""
        return self.batcher.idle()

    def drained(self) -> bool:
        """Every request this replay will ever see has completed."""
        return self.batcher.idle() and self.arrivals.exhausted()


__all__ = ["ServeTraceRecorder", "StepTrace", "weight_ops",
           "weight_step_stream", "make_kv_cache",
           "WEIGHT_STREAM_BASE", "KV_BASE_ADDR"]
