"""Kimi-Linear on the serving replay: the recurrent-state pool beside the
MLA latent pages, the order of a decoding slot's records, per-request
bytes against a plain count, and DeepSeek-V3's streams exactly as they
were before layers could differ in kind."""
import hashlib

import pytest

from repro.configs.paper_workloads import KIMI_LINEAR
from repro.serve.cluster import ClusterSim
from repro.serve.replay import ServeTraceRecorder, build_replay, make_kv_cache
from repro.serve.state_pool import RecurrentStatePool

#: one KDA layer's state: 32 x 128 x 128 float32 plus 3 x 3 x 4096 bf16
STATE = 2_170_880
KW = dict(policy="rome_qd2", rate_rps=2e5, n_requests=6, seed=3,
          scale=2 ** -14, length_scale=1 / 32, n_channels=2,
          sim_mode="cycle", keep_traces=True)


@pytest.fixture(scope="module")
def kimi():
    eng, _ = build_replay(workload="kimi-linear", **KW)
    return eng, eng.run()


def _slot_records(eng, st, rid):
    """The request's records in one step, split at the state pool."""
    start = eng.recorder.state.base_addr
    recs = list(st.stream.of_stream(rid))
    return [("state" if r.addr >= start else "kv", r.kind) for r in recs], \
        recs


def test_each_decoding_slot_reads_and_rewrites_three_states(kimi):
    eng, res = kimi
    rec = eng.recorder
    pool = rec.state
    assert KIMI_LINEAR.kda_state_bytes == STATE == 530 * 4096
    assert (pool.n_layers, pool.state_bytes) == (3, STATE)
    # past the MLA latent pool, never aliasing it
    assert pool.base_addr >= rec.cache.end_addr(rec.kv_base_addr)
    addrs = {}
    last_step = {}
    for k, st in enumerate(res.traces):
        for rid in st.active:
            shape, recs = _slot_records(eng, st, rid)
            n_pages = len(shape) - 8
            assert shape == ([("kv", "read")] * n_pages
                             + [("state", "read")] * 3
                             + [("kv", "write")] * 2
                             + [("state", "write")] * 3)
            reads, writes = recs[n_pages:n_pages + 3], recs[-3:]
            assert all(r.nbytes == STATE for r in reads + writes)
            assert [r.addr for r in reads] == [r.addr for r in writes]
            # one fixed block per (slot, layer), the same every step
            slot = (reads[0].addr - pool.base_addr) // (3 * pool.block_bytes)
            assert [r.addr for r in reads] == [pool.addr(slot, layer)
                                               for layer in range(3)]
            assert addrs.setdefault(rid, reads[0].addr) == reads[0].addr
            assert len({r.arrival_ns for r in recs}) == 1
            last_step[rid] = k
        for rid in st.finished:
            assert last_step[rid] == k
    # nothing of a request after the step it finished in
    for k, st in enumerate(res.traces):
        assert all(last_step[r.stream_id] >= k
                   for r in st.stream if r.stream_id >= 0)
    assert res.completed == 6 and pool.live == 0


def test_request_bytes_equal_a_plain_count(kimi):
    eng, res = kimi
    cache = eng.recorder.cache
    per_tok = cache.page_bytes // cache.page_tokens
    for rid, req in eng.recorder.requests.items():
        recs = [r for st in res.traces for r in st.stream.of_stream(rid)]
        steps = req.max_new_tokens
        read = sum(2 * -(-(req.prompt_len + k) // cache.page_tokens)
                   * cache.page_bytes + 3 * STATE for k in range(steps))
        write = steps * (2 * per_tok + 3 * STATE)
        assert sum(r.nbytes for r in recs if not r.is_write) == read
        assert sum(r.nbytes for r in recs if r.is_write) == write


def test_state_pool_guards():
    pool = RecurrentStatePool(2, 3, STATE, base_addr=1 << 30)
    assert pool.block_bytes == STATE
    assert pool.addr(1, 2) == (1 << 30) + 5 * STATE
    with pytest.raises(ValueError):
        pool.step_streams(0, 0.0, 7)          # slot holds no states
    pool.alloc(0)
    with pytest.raises(ValueError):
        pool.alloc(0)
    reads, writes = pool.step_streams(0, 5.0, 7)
    assert [r.addr for r in reads] == [r.addr for r in writes] == [
        pool.addr(0, layer) for layer in range(3)]
    assert {r.kind for r in reads} == {"read"}
    assert {r.kind for r in writes} == {"write"}
    pool.free(0)
    with pytest.raises(ValueError):
        pool.free(0)
    with pytest.raises(ValueError):
        RecurrentStatePool(2, 3, STATE, base_addr=100)
    # the recorder refuses a state pool that overlaps the KV pools
    cache = make_kv_cache(2, 64)
    with pytest.raises(ValueError, match="overlaps"):
        ServeTraceRecorder(None, cache, kv_base_addr=1 << 26,
                           state=RecurrentStatePool(2, 3, STATE,
                                                    base_addr=1 << 26))


@pytest.mark.parametrize("kw", [dict(prefill_chunk_tokens=16),
                                dict(warm=True)])
def test_state_prefill_paths_raise(kw):
    """No state prefill traffic is modelled: chunked prefill and warm
    steps must not run silently without it."""
    with pytest.raises(NotImplementedError):
        build_replay(workload="kimi-linear", **(KW | kw))


def test_fleet_runs_kimi_linear():
    sim = ClusterSim(workload="kimi-linear", policy="rome_qd2",
                     n_replicas=2, n_requests=8, rate_rps=2e5, seed=0,
                     scale=2 ** -12, n_channels=2, length_scale=1 / 32)
    out = sim.run()
    assert out.completed == 8
    assert all(r.rec.state is not None and r.rec.state.live == 0
               for r in sim.replicas)


# sha256 of every recorded step (admissions, decoders, finishes, start,
# every record) and the run's summary: DeepSeek-V3's streams and
# simulated statistics as they were before layer kinds existed
DSV3_DIGESTS = {
    "hbm4_frfcfs":
        "21f47d9c8485cd92f7a5969bac92493bcf1448331d34cb5abbb9b7ed329f480c",
    "rome_qd2":
        "fe9fb2f4e83c6982fde4536311e52a8866d01af7cb981f40aa69ef6c7a7c0a39",
}


@pytest.mark.parametrize("policy", sorted(DSV3_DIGESTS))
def test_deepseek_v3_streams_bit_identical(policy):
    eng, _ = build_replay(workload="deepseek-v3", **(KW | dict(policy=policy)))
    assert eng.recorder.state is None
    res = eng.run()
    h = hashlib.sha256()
    for st in res.traces:
        h.update(repr((st.admitted, st.active, st.finished,
                       st.start_ns)).encode())
        for r in st.stream:
            h.update(repr((r.addr, r.nbytes, r.kind, r.arrival_ns,
                           r.stream_id)).encode())
    h.update(repr(res.summary()).encode())
    assert h.hexdigest() == DSV3_DIGESTS[policy]
