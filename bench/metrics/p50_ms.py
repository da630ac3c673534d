"""Median client-side latency of all requests in the window (host clock)."""
from bench.readings import latencies_ms, percentile


def read(run):
    return percentile(latencies_ms(run), 50)
