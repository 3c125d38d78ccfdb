import numpy as np
import pytest

from harness.traffic import Feed, draw
from repro.configs.paper_workloads import ServingMix
from repro.serve.replay.arrivals import ArrivalProcess, RequestSpec

MIX = {"prompt_median": 512, "prompt_cv": 1.0, "out_mean": 128,
       "prompt_max": 4096, "out_max": 512}


def traffic(kind, block=1, n=300, scale=1 / 16, burst=0):
    arr = {"kind": kind, "rate_rps": 1e6, "n_requests": n, "size_seed": 7,
           "shuffle_block": block, "initial_burst": burst}
    if kind == "bursty":
        arr["burst_size"] = 8
    return {"arrivals": arr, "mix": MIX, "length_scale": scale}


@pytest.mark.parametrize("kind", ["poisson", "bursty"])
def test_unshuffled_stream_is_what_arrival_process_draws(kind):
    t = traffic(kind)
    reqs = draw(t, seed=123)
    kw = {"burst_size": 8} if kind == "bursty" else {}
    ap = ArrivalProcess(kind, 1e6, 300, mix=ServingMix(**MIX),
                        length_scale=1 / 16, seed=7, **kw)
    specs = ap.due(float("inf"))
    assert [s.arrival_ns for s in specs] == reqs.arrival_ns.tolist()
    assert [s.prompt_len for s in specs] == reqs.prompt_len.tolist()
    assert [s.max_new_tokens for s in specs] == reqs.max_new_tokens.tolist()


@pytest.mark.parametrize("kind", ["poisson", "bursty"])
def test_same_seed_same_stream_other_seed_same_blocks(kind):
    t = traffic(kind, block=8)
    a, b, c = draw(t, 5), draw(t, 5), draw(t, 2 ** 31 + 11)
    for x, y in ((a.arrival_ns, b.arrival_ns), (a.prompt_len, b.prompt_len),
                 (a.max_new_tokens, b.max_new_tokens)):
        assert np.array_equal(x, y)
    assert not np.array_equal(a.prompt_len, c.prompt_len)
    for lo in range(0, 300, 8):
        pa = sorted(zip(a.prompt_len[lo:lo + 8], a.max_new_tokens[lo:lo + 8]))
        pc = sorted(zip(c.prompt_len[lo:lo + 8], c.max_new_tokens[lo:lo + 8]))
        assert pa == pc
    assert np.all(np.diff(c.arrival_ns) >= 0)


def test_initial_burst_queues_the_first_requests_and_keeps_the_gaps():
    plain = draw(traffic("poisson", block=4), 9)
    burst = draw(traffic("poisson", block=4, burst=8), 9)
    assert np.all(burst.arrival_ns[:8] == plain.arrival_ns[0])
    assert np.allclose(np.diff(burst.arrival_ns[7:]),
                       np.diff(plain.arrival_ns[7:]))
    assert np.array_equal(burst.prompt_len, plain.prompt_len)


def test_feed_hands_out_due_specs_in_order():
    feed = Feed(draw(traffic("poisson"), 1), RequestSpec)
    t = feed.reqs.arrival_ns
    assert feed.next_arrival_ns() == t[0] and feed.iterations == 1
    first = feed.due(float(t[9]))
    assert [s.rid for s in first] == list(range(10))
    assert feed.due(float(t[9])) == []
    rest = feed.due(float("inf"))
    assert len(rest) == 290 and feed.exhausted()
    assert feed.next_arrival_ns() is None
