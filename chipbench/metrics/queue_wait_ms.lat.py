"""Mean time (ms) a request waited in the micro-batcher before its launch
started, over the requests launched in the window: the batcher's
``queue_wait_s`` over its ``flushed_requests`` (program counters,
summed over streams)."""


def read(r):
    n = r.window.get("flushed_requests")
    if not n:
        return None
    return r.window["queue_wait_s"] / n * 1e3
