"""The measured window, attached through the program's ``collector=``
seam (an object with ``probe``, ``on_step``, ``fold_reports`` and
``add_request``).

Every step the program prices is logged (its requests and its clock) so
the reference can follow each request from its admission. The window
opens at the first step of a fresh round once ``warmup_steps`` steps are
done (and, for a fleet, once every arrival has been routed); it closes
at the first step whose host timestamp lies past ``seconds``, by raising
:class:`WindowClosed` out of the program's run loop.

Open-loop replay traffic that asks for it (``warmup_until_full``) also
waits until every slot decodes with a queue behind it, so no window
holds the ramp from an empty system, whose length the seed would set.

A round is one replay step, or one fleet round: the steps that
``ClusterSim`` priced in one batched call, which carry the same
``Feed.iterations`` count. A step counts when its host timestamp lies
inside the window and its round began after the window opened.
"""
from __future__ import annotations

import math
import time

import numpy as np

#: A tail is reported only with at least this many samples beyond it.
TAIL_MIN_BEYOND = 10


class WindowClosed(Exception):
    """Raised from ``on_step`` to stop the program at the window's end."""


def rate_per_s(t0: float, stamps: list[float]) -> float:
    """Steps per second over whole steps: every counted step, over the
    host time from the window's start to the end of the last one."""
    if not stamps:
        raise ValueError("no step ended inside the window")
    return len(stamps) / (stamps[-1] - t0)


def tail(samples, q: float = 0.95) -> float:
    """Nearest-rank ``q`` quantile, refused unless at least
    :data:`TAIL_MIN_BEYOND` samples lie beyond it."""
    s = np.sort(np.asarray(samples, np.float64))
    rank = math.ceil(q * s.size)
    if s.size - rank < TAIL_MIN_BEYOND:
        raise ValueError(
            f"p{q * 100:g} of {s.size} samples has {s.size - rank} beyond "
            f"it; need {TAIL_MIN_BEYOND}")
    return float(s[rank - 1])


def round_durations(t0: float, stamps: list[float], rounds: list) -> list:
    """Host seconds per round: from the end of one round to the end of
    the next, a round ending when its first step is handed back."""
    firsts, prev = [], None
    for t, r in zip(stamps, rounds):
        if r != prev:
            firsts.append(t)
            prev = r
    edges = [t0] + firsts
    return [b - a for a, b in zip(edges, edges[1:])]


class Window:
    """Collector that measures the window and keeps what the reference
    needs: the log of every step, and a seeded sample of window steps
    (plus the step with the most records) with their priced results."""

    probe = None

    def __init__(self, seconds: float, warmup_steps: int, sample_steps: int,
                 seed: int, feed, fleet: bool, clock=None, pricer=None,
                 until_full: int = 0):
        self.seconds = float(seconds)
        self.warmup_steps = int(warmup_steps)
        self.until_full = int(until_full)
        self._admitted = 0
        self.sample_steps = int(sample_steps)
        self.feed = feed
        self.fleet = fleet
        self.clock = clock
        self.pricer = pricer
        self._rng = np.random.default_rng([int(seed), 0x5EED])
        self.log: list = []          # (replica, admitted, active, finished,
        #                               start_ns, dur_ns) of every step
        self.t0: float | None = None
        self._open_round = None
        self._prev_round = None
        self._n_seen = 0
        self.stamps: list[float] = []
        self.rounds: list = []
        self.n_analytic = 0
        self.cycle_txns = 0
        self.sample: list = []       # (log index, replica, StepTrace, result)
        self.largest = None
        self.pricer_at_open = None
        self.pricer_at_last = None
        self.clock_at_last = None

    # -- collector seam ------------------------------------------------------

    def on_step(self, st, res, now, dur, replica: int = 0):
        t = time.perf_counter()
        self.log.append((replica, st.admitted, st.active, st.finished,
                         now, dur))
        rnd = self.feed.iterations if self.fleet else len(self.log)
        if self.t0 is None:
            self._admitted += len(st.admitted)
            if (len(self.log) > self.warmup_steps
                    and (not self.fleet or self.feed.exhausted())
                    and (not self.until_full or self._full(st))
                    and rnd != self._prev_round):
                self.t0 = t
                self._open_round = rnd
                if self.clock is not None:
                    self.clock.start(t)
                if self.pricer is not None:
                    self.pricer_at_open = dict(self.pricer.stats)
            self._prev_round = rnd
            return
        self._prev_round = rnd
        if rnd == self._open_round:
            return
        if t - self.t0 > self.seconds:
            raise WindowClosed
        self.stamps.append(t)
        self.rounds.append(rnd)
        if res.mode == "analytic":
            self.n_analytic += 1
        else:
            self.cycle_txns += sum(len(v) for v in res.channel_txns.values())
        self._keep(len(self.log) - 1, replica, st, res)
        if self.clock is not None:
            self.clock_at_last = self.clock.snapshot()
        if self.pricer is not None:
            self.pricer_at_last = dict(self.pricer.stats)

    def fold_reports(self, reports) -> None:
        """The replay never reaches its end inside a run."""

    def add_request(self, *args, **kwargs) -> None:
        """The fleet never reaches its end inside a run."""

    # -- helpers --------------------------------------------------------------

    def _full(self, st) -> bool:
        """Steady state of a replay: every slot decoding and at least as
        many requests queued behind them."""
        queued = self.feed.delivered - self._admitted
        return (len(st.active) == self.until_full
                and queued >= self.until_full)

    def _keep(self, idx: int, replica: int, st, res) -> None:
        """Reservoir sample of counted steps, seeded by the run's seed."""
        item = (idx, replica, st, res)
        n = self._n_seen = self._n_seen + 1
        if len(self.sample) < self.sample_steps:
            self.sample.append(item)
        else:
            j = int(self._rng.integers(n))
            if j < self.sample_steps:
                self.sample[j] = item
        if self.largest is None or len(st.stream) > len(self.largest[2].stream):
            self.largest = item

    def checked_steps(self) -> list:
        """The sample plus the largest step, in log order."""
        items = {i[0]: i for i in self.sample}
        if self.largest is not None:
            items[self.largest[0]] = self.largest
        return [items[k] for k in sorted(items)]

    @property
    def n_steps(self) -> int:
        return len(self.stamps)

    @property
    def n_rounds(self) -> int:
        return len(round_durations(self.t0, self.stamps, self.rounds))

    def sim_steps_per_s(self) -> float:
        return rate_per_s(self.t0, self.stamps)

    def step_host_ms(self) -> list:
        return [1e3 * d for d in np.diff([self.t0] + self.stamps)]

    def round_host_ms(self) -> list:
        return [1e3 * d
                for d in round_durations(self.t0, self.stamps, self.rounds)]
