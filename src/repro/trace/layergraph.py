"""Per-layer operation census: FLOPs, bytes, and memory extents.

Builds the per-device layer-op list for a decode or prefill step, honouring
the paper's parallelism mapping (§VI-A): TP for attention (1/8/8 for
DeepSeek/Grok/Llama), expert parallelism for MoE, full DP for MLA
attention. Each op carries its memory *extents* — (base_addr, nbytes)
ranges in a row-aligned virtual address space — which drive the LBR model
(Fig 13) and the RoMe/HBM4 service-time comparison (Fig 12).

The allocator aligns every tensor to the 4 KB DRAM row — the software-side
contract of a RoMe system (and what repro.serve's paged KV cache enforces
at runtime).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

from ..configs.paper_workloads import PaperWorkload

ROW = 4096
BF16 = 2


@dataclass
class LayerOp:
    name: str
    kind: str                      # "attn" | "ffn" | "embed" | "head"
    flops: float                   # per device
    extents: list = field(default_factory=list)   # [(addr, nbytes)] reads
    write_bytes: int = 0           # streamed writes (KV append, activations)
    write_extents: list = field(default_factory=list)  # [(addr, nbytes)]

    @property
    def read_bytes(self) -> int:
        return sum(n for _, n in self.extents)


class RowAllocator:
    """Row-aligned bump allocator for the virtual address space."""

    def __init__(self) -> None:
        self.cursor = 0

    def alloc(self, nbytes: int) -> tuple[int, int]:
        base = self.cursor
        self.cursor += math.ceil(nbytes / ROW) * ROW
        return (base, nbytes)


def _expected_active_experts(n_experts: int, top_k: int, tokens: int,
                             experts_per_device: int) -> float:
    """Expected number of distinct experts activated on one device when
    `tokens` tokens each pick top_k of n_experts uniformly."""
    if tokens <= 0:
        return 0.0
    p_unused = (1.0 - top_k / n_experts) ** tokens
    return experts_per_device * (1.0 - p_unused)


def _attention(w: PaperWorkload, kind: str, b_local: int,
               seq_len: int) -> tuple[int, int, int, float]:
    """``(weight bytes, cache read bytes, cache write bytes, flops)`` of
    one attention layer of ``kind`` on one device, for ``b_local``
    sequences of ``seq_len`` tokens."""
    d, hd, bpp = w.d_model, w.head_dim, w.bytes_per_param
    if kind == "mla":               # MLA (DeepSeek): DP attention
        q_dim = w.n_heads * (hd + w.mla_rope_dim)
        wq = (d * w.mla_q_lora + w.mla_q_lora * q_dim if w.mla_q_lora
              else d * q_dim)       # without q-LoRA q projects directly
        wkv_d = d * (w.mla_kv_lora + w.mla_rope_dim)
        wkv_u = w.mla_kv_lora * w.n_heads * (2 * hd)
        wo = w.n_heads * hd * d
        attn_w = (wq + wkv_d + wkv_u + wo) * bpp
        kv_per_tok = w.kv_bytes_per_token_per_layer
        flops = 2.0 * b_local * (attn_w / bpp) \
            + 2.0 * b_local * seq_len * (w.mla_kv_lora + w.mla_rope_dim) \
            * (1 + w.n_heads)
        return (attn_w, b_local * seq_len * kv_per_tok, b_local * kv_per_tok,
                flops)
    if kind == "gqa":               # GQA with TP
        tp = w.attn_tp
        wq = d * (w.n_heads * hd) // tp
        wkv = 2 * d * (w.n_kv_heads * hd) // tp
        wo = (w.n_heads * hd) * d // tp
        attn_w = (wq + wkv + wo) * bpp
        kv_per_tok = w.kv_bytes_per_token_per_layer // tp
        flops = 2.0 * b_local * (attn_w / bpp) \
            + 4.0 * b_local * seq_len * (w.n_heads // tp) * hd
        return (attn_w, b_local * seq_len * kv_per_tok, b_local * kv_per_tok,
                flops)
    if kind == "kda":               # KDA (Kimi Linear): DP attention
        width = w.kda_heads * w.kda_head_dim
        rank = w.kda_gate_rank
        params = (3 * d * width                     # q, k, v
                  + width * d                       # o
                  + 2 * (d * rank + rank * width)   # forget and output gates
                  + d * w.kda_heads                 # beta
                  + 3 * width * w.kda_conv)         # depthwise short conv
        # per head and token, 7 dk*dv flops: decay the state (1), read
        # it with k (2), the rank-one delta update (2), read it with q
        # (2); plus the conv's multiply-adds
        flops = 2.0 * b_local * params + b_local * (
            7.0 * w.kda_heads * w.kda_head_dim ** 2
            + 2.0 * 3 * width * w.kda_conv)
        state = b_local * w.kda_state_bytes
        return params * bpp, state, state, flops
    raise ValueError(f"unknown attention kind {kind!r}")


# ---------------------------------------------------------------------------
# Paper workloads (decode step; per device)
# ---------------------------------------------------------------------------

def decode_ops(w: PaperWorkload, batch: int, seq_len: int,
               n_devices: int = 8) -> list[LayerOp]:
    """One decode step on one device. `batch` = global batch size."""
    alloc = RowAllocator()
    ops: list[LayerOp] = []
    d = w.d_model

    b_local = batch // (n_devices // w.attn_tp) \
        if w.attn_tp < n_devices else batch
    attn = {k: _attention(w, k, b_local, seq_len)
            for k in {w.attn_kind(i) for i in range(w.n_layers)}}

    # --- FFN weights --------------------------------------------------------
    if w.is_moe:
        e_dev = w.n_experts // w.moe_ep
        expert_bytes = 3 * d * w.d_ff * w.bytes_per_param
        active = _expected_active_experts(w.n_experts, w.top_k, batch, e_dev)
        shared_bytes = w.n_shared_experts * expert_bytes
        ffn_tokens = batch * w.top_k / n_devices  # routed tokens per device
        ffn_flops = 2.0 * 3 * d * w.d_ff * ffn_tokens \
            + 2.0 * 3 * d * w.d_ff * (batch / n_devices) * w.n_shared_experts
    else:
        ffn_w = 3 * d * w.d_ff // n_devices * w.bytes_per_param
        ffn_flops = 2.0 * batch * (3 * d * w.d_ff) / n_devices

    act_bytes = b_local * d * w.bytes_per_param

    def walloc(*sizes: int) -> list:
        """Row-aligned write extents (KV append / activation stores) from
        the same allocator as the reads, so the two never overlap."""
        return [alloc.alloc(s) for s in sizes if s > 0]

    for layer in range(w.n_layers):
        # attention
        kind = w.attn_kind(layer)
        attn_w, cache_read, cache_write, attn_flops = attn[kind]
        extents = [alloc.alloc(attn_w)]
        for s in range(min(b_local, 64)):   # cap extent count; scale below
            extents.append(alloc.alloc(cache_read // max(1, min(b_local, 64))))
        if kind == "kda":       # the recurrent state is rewritten in place
            wx = extents[1:] + walloc(act_bytes, act_bytes)
        else:
            wx = walloc(cache_write, act_bytes, act_bytes)
        ops.append(LayerOp(
            name=f"L{layer}.attn", kind="attn",
            flops=attn_flops,
            extents=extents,
            write_bytes=sum(n for _, n in wx),
            write_extents=wx,
        ))
        # ffn
        if w.is_moe and layer >= w.n_dense_layers:
            ex: list = []
            n_active = max(1, round(active))
            for e in range(n_active):
                ex.append(alloc.alloc(expert_bytes))
            if shared_bytes:
                ex.append(alloc.alloc(shared_bytes))
            wx = walloc(act_bytes, act_bytes)
            ops.append(LayerOp(
                name=f"L{layer}.moe", kind="ffn",
                flops=ffn_flops, extents=ex,
                write_bytes=sum(n for _, n in wx), write_extents=wx))
        elif w.is_moe:                                # leading dense layers
            nb = 3 * d * w.dense_d_ff // n_devices * w.bytes_per_param
            wx = walloc(act_bytes, act_bytes)
            ops.append(LayerOp(
                name=f"L{layer}.ffn", kind="ffn",
                flops=2.0 * batch * 3 * d * w.dense_d_ff / n_devices,
                extents=[alloc.alloc(nb)],
                write_bytes=sum(n for _, n in wx), write_extents=wx))
        else:
            wx = walloc(act_bytes, act_bytes)
            ops.append(LayerOp(
                name=f"L{layer}.ffn", kind="ffn",
                flops=ffn_flops,
                extents=[alloc.alloc(ffn_w)],
                write_bytes=sum(n for _, n in wx), write_extents=wx))

    # LM head (TP over all devices)
    head_b = d * w.vocab // n_devices * w.bytes_per_param
    wx = walloc(batch * w.vocab // n_devices * 4)
    ops.append(LayerOp(name="lm_head", kind="head",
                       flops=2.0 * batch * d * w.vocab / n_devices,
                       extents=[alloc.alloc(head_b)],
                       write_bytes=sum(n for _, n in wx),
                       write_extents=wx))
    return ops


def prefill_ops(w: PaperWorkload, batch: int, seq_len: int,
                n_devices: int = 8) -> list[LayerOp]:
    """Prefill processes batch*seq tokens; same weight extents, token count
    multiplied — the workload turns compute-bound (paper: <0.1 % memory
    sensitivity)."""
    tokens = batch * seq_len
    ops = decode_ops(w, batch, seq_len, n_devices)
    scaled = []
    for op in ops:
        f = op.flops * seq_len
        # Writes scale with the token count; the per-token addresses of the
        # decode trace no longer apply, so prefill ops carry byte counts
        # only (the perf model falls back to its address-less write path).
        wb = op.write_bytes * seq_len
        scaled.append(LayerOp(op.name, op.kind, f, op.extents, wb))
    return scaled
