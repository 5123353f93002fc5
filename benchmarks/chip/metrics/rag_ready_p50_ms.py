"""Median RAG-Ready latency: due time → reranked top-k on the client."""
import math


def read(run):
    v = run.pct(run.latencies_ms(), 50)
    return v if math.isfinite(v) else None
