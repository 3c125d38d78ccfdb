"""Layer-op trace census: bytes/flops bookkeeping for the perf model."""
import pytest

from repro.configs.paper_workloads import (KIMI_LINEAR, PAPER_WORKLOADS,
                                          SERVING_WORKLOADS)
from repro.trace.layergraph import RowAllocator, decode_ops, prefill_ops


def test_row_allocator_alignment():
    a = RowAllocator()
    b1, n1 = a.alloc(100)
    b2, n2 = a.alloc(5000)
    assert b1 % 4096 == 0 and b2 % 4096 == 0
    assert b2 >= b1 + 4096            # rounded up to whole rows


@pytest.mark.parametrize("name", sorted(SERVING_WORKLOADS))
def test_decode_ops_structure(name):
    w = SERVING_WORKLOADS[name]
    ops = decode_ops(w, batch=64, seq_len=8192)
    kinds = {o.kind for o in ops}
    assert {"attn", "ffn", "head"} <= kinds
    assert len([o for o in ops if o.kind == "attn"]) == w.n_layers
    for o in ops:
        assert o.flops > 0
        assert o.read_bytes >= 0


def test_prefill_scales_flops_not_extents():
    w = PAPER_WORKLOADS["grok-1"]
    d = decode_ops(w, 8, 8192)
    p = prefill_ops(w, 8, 8192)
    # weights are read once either way; flops scale with tokens
    assert sum(o.flops for o in p) > 1000 * sum(o.flops for o in d)
    assert p[0].extents == d[0].extents


def test_moe_extents_sparser_than_dense():
    """Small batch activates few experts -> few (large) extents; large
    batch touches all experts (the Fig 13 LBR_FFN mechanism)."""
    w = PAPER_WORKLOADS["deepseek-v3"]
    small = decode_ops(w, 1, 8192)
    big = decode_ops(w, 256, 8192)
    s_moe = [o for o in small if o.kind == "ffn" and len(o.extents) > 1]
    b_moe = [o for o in big if o.kind == "ffn" and len(o.extents) > 1]
    assert s_moe and b_moe
    assert len(b_moe[0].extents) > len(s_moe[0].extents)


# (ops, read bytes, write bytes, flops, end of the last extent) of one
# decode step at batch 64 and 8,192 tokens: the paper's three models
# keep the numbers they had before layers could differ in kind
PAPER_TOTALS = {
    "deepseek-v3": (123, 176_112_336_896, 32_683_008, 1_178_380_271_616.0,
                    176_145_207_296),
    "grok-1": (129, 96_099_893_248, 207_618_048, 1_443_109_011_456.0,
               96_307_511_296),
    "llama-3-405b": (253, 134_759_841_792, 1_065_197_568,
                     7_001_132_236_800.0, 135_825_039_360),
}


@pytest.mark.parametrize("name", sorted(PAPER_TOTALS))
def test_paper_decode_ops_unchanged(name):
    ops = decode_ops(PAPER_WORKLOADS[name], batch=64, seq_len=8192)
    assert (len(ops), sum(o.read_bytes for o in ops),
            sum(o.write_bytes for o in ops), sum(o.flops for o in ops),
            max(a + n for o in ops for a, n in o.extents + o.write_extents)
            ) == PAPER_TOTALS[name]


def test_kimi_linear_layer_pattern_and_weights():
    """27 attention ops, KDA at the published 1-indexed kda_layers and
    MLA at the other 7; each kind's weight extent is a plain count of
    its published projections, in bf16."""
    w = KIMI_LINEAR
    ops = decode_ops(w, batch=64, seq_len=1024)
    attn = [o for o in ops if o.kind == "attn"]
    kinds = [w.attn_kind(i) for i in range(w.n_layers)]
    assert len(attn) == 27
    assert [i + 1 for i, k in enumerate(kinds) if k == "kda"] == [
        1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25,
        26]
    assert [i + 1 for i, k in enumerate(kinds) if k == "mla"] == [
        4, 8, 12, 16, 20, 24, 27]
    assert w.period_kinds == ("kda", "kda", "kda", "mla")
    kda = (3 * 2304 * 4096 + 4096 * 2304        # q, k, v; o
           + 2 * (2304 * 128 + 128 * 4096)      # forget and output gates
           + 2304 * 32 + 3 * 4096 * 4)          # beta; short conv
    mla = (2304 * 32 * 192 + 2304 * 576         # q (no q-LoRA); kv down
           + 512 * 32 * 256 + 32 * 128 * 2304)  # kv up; o
    assert kda == 39_510_016
    per_kind = {"kda": 2 * kda, "mla": 2 * mla}
    for op, kind in zip(attn, kinds):
        assert op.extents[0][1] == per_kind[kind]
    assert sum(o.extents[0][1] for o in attn) == 20 * 2 * kda + 7 * 2 * mla
    # batch 64 over 8 data-parallel devices: 8 sequences here, each
    # KDA layer reading their whole states and rewriting them in place
    state = 32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2
    assert w.kda_state_bytes == state == 530 * 4096
    first = attn[0]
    assert [n for _, n in first.extents[1:]] == [state] * 8
    assert first.write_extents[:8] == first.extents[1:]
    assert first.write_bytes == 8 * state + 2 * 8 * 2304 * 2
