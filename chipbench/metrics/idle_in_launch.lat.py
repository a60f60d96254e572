"""Share of the device's idle time in the traced window that lies inside
one of the program's ``serving.launch`` spans, as a fraction, over the
cell's chips: the device waiting for the host's launch rather than for a
request."""


def read(r):
    if r.trace is None:
        return None
    s = r.trace["spans"]
    if not s["launch_lines"] or not sum(s["idle_s"]):
        return None
    return sum(s["idle_in_launch_s"]) / sum(s["idle_s"])
