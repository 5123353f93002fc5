"""95th percentile of RAG-Ready latency over every request of the window.

A request that failed or never came back counts as infinitely late; the
metric is then left out (the run is not correct either).
"""
import math


def read(run):
    v = run.pct(run.latencies_ms(), 95)
    return v if math.isfinite(v) else None
