"""Kimi-Linear's layer equations (``bench/models/kimi-linear.py``) and its
cell: pinned numbers, a whole run that reads every check 0, and a fault
planted in the recurrent-state traffic that reads ``bytes_bad``."""
import pytest

from _cell import fails_on, run
from harness.manifest import Manifest
from harness.reference import Request

CELL = "kimi-linear-rome-x9.decode-poisson-full"
#: one KDA layer's state per sequence: 530 rows of 4 KB
STATE = 2_170_880


@pytest.fixture(scope="module")
def cell():
    return Manifest.load().cell(CELL)


def test_weights_state_and_pools(cell):
    eq, cfg, tr = cell.equations, cell.config, cell.traffic
    m = cfg["model"]
    assert eq.state_bytes(cfg) == STATE
    assert eq.kda_weight_params(m) == 39_510_016
    assert eq.mla_weight_params(m) == 29_114_368
    # layers 1-4 at 2^-12, each extent at least a row: KDA 19,292 B
    # three times, the dense FFN's 3,888 and each expert's 3,456 raised
    # to 4,096 (one routed and the shared one, three layers), MLA 14,216
    assert eq.weight_bytes(cfg, tr) == 3 * 19_292 + 4_096 + 6 * 4_096 \
        + 14_216 == 100_764
    assert eq.pool_geometry(cfg) == {"n_kv_heads": 1, "head_dim": 288,
                                     "rows_per_page": 9}


# 2 MLA pools, 576 B per token per pool, 64 tokens per page; three KDA
# states of the period read whole and rewritten whole
@pytest.mark.parametrize("context,pages", [(1, 73_728), (64, 73_728),
                                           (65, 147_456),
                                           (4096, 4_718_592)])
def test_request_bytes(cell, context, pages):
    eq = cell.equations
    for rid, seed in [(0, 0), (7, 2 ** 31 + 5)]:
        assert eq.request_bytes(cell.config, cell.traffic,
                                Request(rid, context, seed)) == (
            pages + 3 * STATE, 1152 + 3 * STATE)


def test_model_section_is_the_published_config(cell):
    """The ``model`` section the equations read says what the copied
    config.json keys of the same file say."""
    c = cell.config
    m, lin = c["model"], c["linear_attn_config"]
    assert (m["n_layers"], m["d_model"], m["n_heads"], m["vocab"]) == (
        c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
        c["vocab_size"])
    assert m["head_dim"] == c["qk_nope_head_dim"] == c["v_head_dim"]
    assert (m["mla_kv_lora"], m["mla_rope_dim"]) == (
        c["kv_lora_rank"], c["qk_rope_head_dim"])
    assert c["q_lora_rank"] is None and m["mla_q_lora"] == 0
    assert (m["d_ff"], m["dense_d_ff"], m["n_dense_layers"]) == (
        c["moe_intermediate_size"], c["intermediate_size"],
        c["first_k_dense_replace"])
    assert (m["n_experts"], m["top_k"], m["n_shared_experts"]) == (
        c["num_experts"], c["num_experts_per_token"],
        c["num_shared_experts"])
    assert (m["kda_layers"], m["kda_heads"], m["kda_head_dim"],
            m["kda_conv"]) == (lin["kda_layers"], lin["num_heads"],
                               lin["head_dim"], lin["short_conv_kernel_size"])
    assert m["kda_gate_rank"] == lin["head_dim"]


def test_cell_reads_every_check_zero():
    out = run(CELL)
    assert out["checked_steps"] > 0
    assert out["correct"], out["checks"]
    assert all(c["value"] == 0 for c in out["checks"].values())


def _drop_last_write(orig):
    def step_streams(self, slot, arrival_ns, stream_id):
        reads, writes = orig(self, slot, arrival_ns, stream_id)
        return reads, writes[:-1]
    return step_streams


def _write_once_per_request(orig):
    def step_streams(self, slot, arrival_ns, stream_id):
        from repro.workloads import ExtentStream
        reads, writes = orig(self, slot, arrival_ns, stream_id)
        seen = self.__dict__.setdefault("_written", set())
        if stream_id in seen:
            return reads, ExtentStream()
        seen.add(stream_id)
        return reads, writes
    return step_streams


@pytest.mark.parametrize("fault", [_drop_last_write,
                                   _write_once_per_request])
def test_state_traffic_fault_reads_bytes_bad(monkeypatch, fault):
    from repro.serve.state_pool import RecurrentStatePool
    monkeypatch.setattr(RecurrentStatePool, "step_streams",
                        fault(RecurrentStatePool.step_streams))
    out = run(CELL)
    assert out["checks"]["bytes_bad"]["value"] > 0
    assert "bytes_bad" in fails_on(out)
