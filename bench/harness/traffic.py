"""Seeded request generation, kept with the benchmark.

:func:`draw` is the benchmark's own copy of the sampling arithmetic of
``repro.serve.replay.ArrivalProcess`` (lognormal prompts, geometric
outputs, Poisson or bursty arrivals, drawn from one
``numpy.random.Generator`` in the same order), so a change to the
program's generator cannot move the yardstick.

A traffic file fixes the *set* of requests: lengths and inter-arrival
gaps are drawn once from the file's ``size_seed``. The run's ``--seed``
then shuffles that set inside consecutive blocks of ``shuffle_block``
requests: lengths (as prompt/output pairs) and gaps are permuted
independently. Every seed therefore offers the same work, in another
order, and a window that consumes whole blocks sees the same sizes on
every seed. With ``shuffle_block`` 1 the stream is exactly what
``ArrivalProcess(seed=size_seed)`` draws.

``initial_burst`` k (optional) moves the first k arrivals to the first
one's time and the rest earlier by as much, so the system starts with a
queue and every later gap is kept.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Requests:
    """A generated request stream, in rid order (= arrival order)."""

    arrival_ns: np.ndarray
    prompt_len: np.ndarray
    max_new_tokens: np.ndarray

    def __len__(self) -> int:
        return int(self.arrival_ns.size)


def _lengths(rng, mix: dict, scale: float) -> tuple[int, int]:
    sigma = float(np.sqrt(np.log1p(mix["prompt_cv"] ** 2)))
    p = int(round(float(rng.lognormal(np.log(mix["prompt_median"]), sigma))
                  * scale))
    o = int(round(float(rng.geometric(1.0 / mix["out_mean"])) * scale))
    p_max = max(1, int(round(mix["prompt_max"] * scale)))
    o_max = max(1, int(round(mix["out_max"] * scale)))
    return min(max(p, 1), p_max), min(max(o, 1), o_max)


def draw_base(traffic: dict, seed: int) -> tuple[np.ndarray, np.ndarray,
                                                 np.ndarray, np.ndarray]:
    """Unshuffled draw: ``(gaps_ns, burst_of, prompt, out)`` in the
    order ``ArrivalProcess`` draws them. ``gaps_ns`` holds one gap per
    burst (per request for Poisson); ``burst_of`` maps each request to
    its burst."""
    arr, mix = traffic["arrivals"], traffic["mix"]
    scale = float(traffic["length_scale"])
    n = int(arr["n_requests"])
    rate = float(arr["rate_rps"])
    rng = np.random.default_rng(seed)
    gaps, burst_of, prompt, out = [], [], [], []
    if arr["kind"] == "poisson":
        for i in range(n):
            gaps.append(rng.exponential(1e9 / rate))
            burst_of.append(i)
            p, o = _lengths(rng, mix, scale)
            prompt.append(p)
            out.append(o)
    elif arr["kind"] == "bursty":
        size = int(arr["burst_size"])
        while len(prompt) < n:
            gaps.append(rng.exponential(1e9 * size / rate))
            for _ in range(min(size, n - len(prompt))):
                burst_of.append(len(gaps) - 1)
                p, o = _lengths(rng, mix, scale)
                prompt.append(p)
                out.append(o)
    else:
        raise ValueError(f"arrival kind {arr['kind']!r} is not open-loop "
                         f"(poisson | bursty)")
    return (np.array(gaps, np.float64), np.array(burst_of, np.int64),
            np.array(prompt, np.int64), np.array(out, np.int64))


def _block_permutation(rng, n: int, block: int) -> np.ndarray:
    perm = np.arange(n)
    for lo in range(0, n, block):
        hi = min(n, lo + block)
        perm[lo:hi] = lo + rng.permutation(hi - lo)
    return perm


def draw(traffic: dict, seed: int) -> Requests:
    """The run's request stream: the traffic file's set, shuffled by
    ``seed`` inside blocks (module docstring)."""
    arr = traffic["arrivals"]
    gaps, burst_of, prompt, out = draw_base(traffic,
                                            int(arr["size_seed"]))
    block = int(arr.get("shuffle_block", 1))
    if block > 1:
        rng = np.random.default_rng(seed)
        pairs = _block_permutation(rng, prompt.size, block)
        prompt, out = prompt[pairs], out[pairs]
        gaps = gaps[_block_permutation(rng, gaps.size, block)]
    arrival = np.cumsum(gaps)[burst_of]
    k = int(arr.get("initial_burst", 0))
    if k > 1:
        arrival[k:] -= arrival[k - 1] - arrival[0]
        arrival[:k] = arrival[0]
    return Requests(arrival, prompt, out)


class Feed:
    """The generated stream, handed to the program through its arrival
    seam (duck-typed as ``ArrivalProcess``: ``due``,
    ``next_arrival_ns``, ``on_complete``, ``exhausted``,
    ``n_requests``).

    ``iterations`` counts ``next_arrival_ns`` calls. ``ClusterSim.run``
    makes exactly one per pass of its fleet loop, so steps that see the
    same count were priced in the same fleet round.
    """

    def __init__(self, reqs: Requests, spec_type):
        self.reqs = reqs
        self.n_requests = len(reqs)
        self._spec = spec_type
        self._arrival = reqs.arrival_ns.tolist()
        self._prompt = reqs.prompt_len.tolist()
        self._out = reqs.max_new_tokens.tolist()
        self._next = 0
        self.iterations = 0

    def due(self, now_ns: float) -> list:
        lo = self._next
        hi = bisect.bisect_right(self._arrival, now_ns, lo=lo)
        self._next = hi
        return [self._spec(i, self._arrival[i], self._prompt[i],
                           self._out[i]) for i in range(lo, hi)]

    @property
    def delivered(self) -> int:
        """Requests handed to the program so far."""
        return self._next

    def next_arrival_ns(self) -> float | None:
        self.iterations += 1
        if self._next >= self.n_requests:
            return None
        return self._arrival[self._next]

    def on_complete(self, now_ns: float) -> None:
        """Open loop: completions generate nothing."""

    def exhausted(self) -> bool:
        return self._next >= self.n_requests
