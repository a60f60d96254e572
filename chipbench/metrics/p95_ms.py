"""95th percentile latency (ms) of every request of the window, due time
to result at the client; a failed request counts as infinitely late."""
import math

from harness.stats import percentile


def read(r):
    lat = r.window.get("latencies_ms")
    if lat is None:
        return None
    v = percentile(lat, 95)
    if not math.isfinite(v):
        raise ValueError("more than 5% of the window's requests failed")
    return v
