"""p99 (ms) of how late the load generator sent each request against its
schedule, on the benchmark's clock: a starved generator is not a fast
server."""
from harness.stats import percentile


def read(r):
    late = r.window.get("gen_late_ms")
    return None if late is None else percentile(late, 99)
