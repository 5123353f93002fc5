"""Open-loop arrivals and the one-thread driver that submits them on time.

Arrivals belong to the world, not to the server: every request has a due
time fixed before the window opens, and it is submitted when due whether or
not the engine has kept up.  Latency is counted from that due time, so a
driver that falls behind shows as lag (``submit − due``), never as a fast
server.  ``poisson_arrivals`` and the submit-then-tick loop are copies of
``traffic/workload.poisson_arrivals`` and ``OpenLoopDriver.run``.
"""
from __future__ import annotations

import time
from typing import Callable

import numpy as np
from jax.profiler import TraceAnnotation

#: Fixed stream for the multiset of inter-arrival gaps (see `schedule`).
_GAPS_SEED = 0x6761


def poisson_arrivals(rng: np.random.Generator, qps: float,
                     duration_s: float) -> np.ndarray:
    """Sorted arrival times (s) of a Poisson process at rate `qps`."""
    if qps <= 0 or duration_s <= 0:
        return np.empty(0, np.float64)
    n = max(int(qps * duration_s * 2), 16)     # overdraw, then truncate
    t = np.cumsum(rng.exponential(1.0 / qps, size=n))
    while t[-1] < duration_s:                  # rare: overdraw fell short
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / qps, size=n))])
    return t[t < duration_s]


def schedule(seed: int, stream: int, rate: float,
             seconds: float) -> np.ndarray:
    """Due offsets (s) in [0, seconds): a Poisson path at ``rate``.

    The gaps are one draw of the process from a fixed stream, put in an
    order drawn from ``seed``: every seed offers the same number of
    requests with the same gaps, so seeds change which request waits
    behind which, and not how much work the window holds.
    """
    t = poisson_arrivals(np.random.default_rng([_GAPS_SEED, stream]),
                         rate, seconds)
    gaps = np.diff(t, prepend=0.0)
    order = np.random.default_rng([seed, 2, stream]).permutation(len(gaps))
    return np.cumsum(gaps[order])


def drive(tick: Callable[[], object], submit: Callable[[int], None],
          due: np.ndarray, t_end: float,
          clock: Callable[[], float] = time.perf_counter) -> np.ndarray:
    """Submit event ``i`` once ``clock() >= due[i]``, ticking in between.

    Runs until ``t_end`` on ``clock``; an event due before then that a
    long tick kept the driver from submitting is submitted late, at the
    close.  Returns each event's lag (submit time − due time, seconds).
    """
    lag = np.full(len(due), np.nan)
    i = 0
    while True:
        now = clock()
        if now >= t_end:
            now = np.inf
        while i < len(due) and due[i] <= now:
            with TraceAnnotation("bench.submit"):
                submit(i)
            lag[i] = clock() - due[i]
            i += 1
        if now == np.inf:
            return lag
        with TraceAnnotation("bench.tick"):
            tick()

