"""Median latency (ms) of every request of the window, due time to result
at the client; a failed request counts as infinitely late."""
from harness.stats import percentile


def read(r):
    lat = r.window.get("latencies_ms")
    return None if lat is None else percentile(lat, 50)
