"""Real rows per kernel launch over the window, from the micro-batcher's
counters (summed over streams): how full the frontend keeps each bucket."""


def read(r):
    w = r.window
    if not w.get("launches"):
        return None
    return w["launch_rows"] / w["launches"]
