"""Share of the traced window in which no operation ran on the device, as
a fraction, averaged over the cell's chips."""


def read(r):
    if r.trace is None:
        return None
    busy = sum(r.trace["busy_s"]) / len(r.trace["busy_s"])
    return 1.0 - busy / r.trace["window_s"]
