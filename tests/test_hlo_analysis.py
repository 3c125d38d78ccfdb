"""HLO analyzer: synthetic-module parses + the pinned cost_analysis
deficiency that motivates it (while bodies counted once)."""
import jax
import jax.numpy as jnp
import pytest

from repro.compat.hlo import normalize_cost_analysis
from repro.launch.hlo_analysis import (HloModule, analyze_hlo, shape_bytes,
                                       xla_cost_analysis, _parse_instr_line)


def test_shape_bytes():
    assert shape_bytes("f32[2,3]") == 24
    assert shape_bytes("bf16[128]") == 256
    assert shape_bytes("(f32[2], s32[4])") == 8 + 16
    assert shape_bytes("pred[]") == 1
    assert shape_bytes("token[]") == 0


def test_parse_tuple_result_instruction():
    line = ("  %while.15 = (s32[], bf16[8,1,3584]{2,1,0}, "
            "f32[28,16]{1,0}) while(%tuple.20), condition=%c, body=%b")
    name, rtype, op = _parse_instr_line(line)
    assert name == "while.15" and op == "while"
    assert shape_bytes(rtype) == 4 + 8 * 3584 * 2 + 28 * 16 * 4


SYNTH = """
HloModule synth

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups={}
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%ni, %ar)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,8]{1,0}) tuple(%z, %a)
  %w = (s32[], f32[8,8]{1,0}) while(%t0), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_while_trip_count_from_condition():
    st = analyze_hlo(SYNTH)
    # 7 iterations x (2*8*8*8) flops
    assert st.flops == 7 * 2 * 8 * 8 * 8
    # 7 all-reduces of 256 B
    assert st.collective_bytes == 7 * 256
    assert st.coll_by_kind == {"all-reduce": 7 * 256}
    assert st.n_collectives == 7


def test_cost_analysis_counts_while_once():
    """Pin the deficiency: XLA's cost_analysis does NOT multiply while
    bodies by trip count — the reason hlo_analysis exists. If this ever
    starts failing, cost_analysis got fixed and the analyzer can defer."""
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    xs = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = jax.jit(f).lower(xs, xs).compile()
    xla_flops = xla_cost_analysis(c)["flops"]
    ours = analyze_hlo(c.as_text()).flops
    per_iter = 2 * 64 ** 3
    assert xla_flops < 2 * per_iter          # counted once
    assert ours == pytest.approx(10 * per_iter, rel=0.01)


def test_normalize_cost_analysis_shapes():
    """Compiled.cost_analysis() returns a flat dict (or nothing); both
    normalize to a flat dict."""
    assert normalize_cost_analysis({"flops": 2.0}) == {"flops": 2.0}
    assert normalize_cost_analysis({}) == {}
    assert normalize_cost_analysis(None) == {}


def test_real_module_collective_symbols():
    """Collective operand sizes resolve through the symbol table even when
    operands print as bare %names."""
    hlo = """
HloModule m

ENTRY %main (a: f32[16,32]) -> f32[16,32] {
  %a = f32[16,32]{1,0} parameter(0)
  %d = f32[16,32]{1,0} add(%a, %a)
  ROOT %ar = f32[16,32]{1,0} all-reduce(%d), replica_groups={}
}
"""
    st = analyze_hlo(hlo)
    assert st.collective_bytes == 16 * 32 * 4
