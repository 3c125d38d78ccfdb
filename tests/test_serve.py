"""Serving layer: row-paged KV cache invariants + continuous batching."""
import numpy as np
import pytest
from _proptest import given, settings, strategies as st

from repro.serve.batching import ContinuousBatcher, Request
from repro.serve.kv_cache import ROW_BYTES, RowPagedKVCache, tokens_per_row


def _cache(**kw):
    base = dict(n_pages=16, page_tokens=tokens_per_row(64, 2),
                n_kv_heads=2, head_dim=64, max_seqs=4,
                max_pages_per_seq=8)
    base.update(kw)
    return RowPagedKVCache(**base)


def test_page_is_whole_rows():
    c = _cache()
    assert c.page_bytes % ROW_BYTES == 0
    assert c.rows_per_page() >= 1


def test_tokens_per_row_exact():
    assert tokens_per_row(64, 2, 2) == 4096 // (64 * 2 * 2)
    with pytest.raises(ValueError):
        tokens_per_row(96, 5, 2)        # no integral packing in one row


def test_alloc_append_free_cycle():
    c = _cache()
    c.alloc_seq(0, 10)
    used0 = c.utilization()
    pg, slot = c.append_token(0)
    assert 0 <= pg < c.n_pages
    c.free_seq(0)
    assert c.utilization() == 0.0
    assert used0 > 0


def test_append_crosses_page_boundary():
    c = _cache()
    tp = c.page_tokens
    c.alloc_seq(0, tp)                   # exactly one full page
    pg2, slot2 = c.append_token(0)       # must grab a fresh page
    assert slot2 == 0
    assert c.page_table[0, 1] == pg2


def test_pool_exhaustion_raises():
    c = _cache(n_pages=2, max_pages_per_seq=8)
    with pytest.raises(MemoryError):
        c.alloc_seq(0, c.page_tokens * 3)


def test_gather_matches_writes():
    import jax.numpy as jnp
    c = _cache()
    c.alloc_seq(1, 3)
    for t in range(3):
        pg, slot = divmod(t, c.page_tokens)
        page_id = int(c.page_table[1, pg])
        c.write(page_id, slot,
                jnp.full((2, 64), float(t)), jnp.full((2, 64), -float(t)))
    k, v = c.gather_seq(1)
    assert k.shape == (3, 2, 64)
    np.testing.assert_allclose(np.asarray(k)[:, 0, 0], [0.0, 1.0, 2.0])
    np.testing.assert_allclose(np.asarray(v)[:, 0, 0], [0.0, -1.0, -2.0])


def test_kv_cache_emits_unified_records():
    """The paged KV cache speaks the same ExtentRecord currency as the
    layer-op traces: whole-page row-aligned reads and in-page writes,
    covering BOTH the K and the V pool."""
    c = _cache()
    c.alloc_seq(2, c.page_tokens + 1)    # spans two pages
    reads = c.read_stream(2, base_addr=1 << 20, arrival_ns=5.0)
    assert len(reads) == 4               # 2 pages x {K, V}
    assert reads.read_bytes == 4 * c.page_bytes
    addrs = {r.addr for r in reads}
    assert len(addrs) == 4               # K and V pages never alias
    for r in reads:
        assert r.kind == "read" and r.arrival_ns == 5.0 and r.stream_id == 2
        assert (r.addr - (1 << 20)) % ROW_BYTES == 0
        assert r.nbytes % ROW_BYTES == 0
    before = int(c.seq_lens[2])
    writes = c.append_stream(2)
    assert int(c.seq_lens[2]) == before + 1   # token accounted exactly once
    per_tok = c.page_bytes // c.page_tokens
    assert len(writes) == 2              # K write + V write
    assert all(w.kind == "write" and w.stream_id == 2
               and w.nbytes == per_tok for w in writes)
    # Each write lands inside the token's page of its own pool.
    page_id, slot = divmod(int(c.seq_lens[2]) - 1, c.page_tokens)
    pool_page = int(c.page_table[2, page_id])
    assert [w.addr for w in writes] == [
        c.page_addr(pool_page, pool="k") + slot * per_tok,
        c.page_addr(pool_page, pool="v") + slot * per_tok]


@settings(deadline=None, max_examples=25)
@given(seed=st.integers(min_value=0, max_value=999))
def test_kv_pool_never_double_allocates(seed):
    """Property: live pages are disjoint across sequences at all times."""
    rng = np.random.default_rng(seed)
    c = _cache(n_pages=12, max_seqs=3, max_pages_per_seq=4)
    lens = [0, 0, 0]
    for _ in range(40):
        sid = int(rng.integers(0, 3))
        if lens[sid] == 0 and rng.random() < 0.5:
            n = int(rng.integers(1, c.page_tokens * 2))
            try:
                c.alloc_seq(sid, n)
                lens[sid] = n
            except MemoryError:
                pass
        elif lens[sid] and rng.random() < 0.3:
            c.free_seq(sid)
            lens[sid] = 0
        elif lens[sid]:
            try:
                c.append_token(sid)
                lens[sid] += 1
            except MemoryError:
                pass
        live = [p for row in c.page_table for p in row if p >= 0]
        assert len(live) == len(set(live))
        assert len(live) + len(c._free) == c.n_pages


# --- continuous batching ------------------------------------------------------

def test_batcher_fifo_and_retire():
    b = ContinuousBatcher(2)
    for rid in range(4):
        b.submit(Request(rid, np.array([1, 2]), max_new_tokens=2))
    adm = b.schedule()
    assert [r.rid for _, r in adm] == [0, 1]
    b.record_tokens(np.array([10, 11]))
    done = b.record_tokens(np.array([12, 13]))
    assert sorted(r.rid for r in done) == [0, 1]
    adm2 = b.schedule()
    assert [r.rid for _, r in adm2] == [2, 3]


def test_batcher_iteration_level_join():
    """A request finishing frees its slot for the next queued request at a
    token boundary (no full-batch drain)."""
    b = ContinuousBatcher(2)
    b.submit(Request(0, np.array([1]), max_new_tokens=1))
    b.submit(Request(1, np.array([1]), max_new_tokens=3))
    b.submit(Request(2, np.array([1]), max_new_tokens=1))
    b.schedule()
    b.record_tokens(np.array([5, 6]))        # r0 done
    adm = b.schedule()
    assert [r.rid for _, r in adm] == [2]
    assert b.active[0].rid == 2 and b.active[1].rid == 1


def test_occupancy_zero_before_first_step():
    """No division by zero (and a defined 0.0) before any decode step."""
    b = ContinuousBatcher(4)
    assert b.occupancy == 0.0
    b.submit(Request(0, np.array([1]), 1))
    assert b.occupancy == 0.0          # still no step recorded


def test_request_timeline_step_indices():
    """submit/admit/first-token/completion step indices as maintained by
    the batcher (the TTFT/TPOT accounting the replay engine folds
    makespans onto)."""
    b = ContinuousBatcher(1)
    r0 = Request(0, np.array([1]), max_new_tokens=2)
    r1 = Request(1, np.array([1]), max_new_tokens=1)
    b.submit(r0)
    b.submit(r1)
    assert r0.timeline.submitted_step == 0 and r1.timeline.submitted_step == 0
    b.schedule()                         # r0 takes the only slot
    assert r0.timeline.admitted_step == 0
    assert r1.timeline.admitted_step == -1
    b.record_tokens(np.array([7]))       # step 0: r0 first token
    assert r0.timeline.first_token_step == 0
    assert r0.timeline.completed_step == -1
    b.schedule()
    b.record_tokens(np.array([8]))       # step 1: r0 completes
    assert r0.timeline.completed_step == 1
    assert r0.timeline.decode_steps == 2 == len(r0.out_tokens)
    b.schedule()                         # r1 admitted at step index 2
    assert r1.timeline.admitted_step == 2
    b.record_tokens(np.array([9]))
    assert r1.timeline.first_token_step == 2
    assert r1.timeline.completed_step == 2
    assert r1.timeline.decode_steps == 1


def test_admission_check_blocks():
    b = ContinuousBatcher(2, admit=lambda req: req.rid != 1)
    b.submit(Request(0, np.array([1]), 1))
    b.submit(Request(1, np.array([1]), 1))
    adm = b.schedule()
    # FIFO order preserved: r0 admitted; r1 blocks the queue head
    assert [r.rid for _, r in adm] == [0]
    assert b.queue[0].rid == 1


# --- serving entry point (repro.launch.serve) ---------------------------

def test_serve_run_completes_every_request():
    from repro.launch import serve
    args = serve.parse_args(["--arch", "h2o-danube-1.8b", "--reduced",
                             "--requests", "3", "--slots", "2",
                             "--max-new", "4"])
    run = serve.run(args)
    assert run.completed == 3
    assert run.tokens_out == 3 * 4
    assert run.first_tokens.shape == (2, 1)
    assert run.first_logits.shape[:2] == (2, 1)
    assert np.isfinite(run.first_logits.astype(np.float32)).all()
    assert run.tokens_per_s > 0 and run.compile_s > 0


def test_compile_cache_left_to_env(monkeypatch, tmp_path):
    import jax
    from repro.launch.compile_cache import setup_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    import os
    import jax
    from repro.launch.compile_cache import REPO_CACHE_DIR, setup_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = setup_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    repo = os.path.join(os.path.dirname(__file__), "..")
    assert os.path.samefile(REPO_CACHE_DIR.parent, repo)
    assert path == str(REPO_CACHE_DIR) and REPO_CACHE_DIR.name == ".jax_cache"
