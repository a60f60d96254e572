"""Seconds from process start to the first timed request: loading, making
the weights, building the plan, compiling and warming up."""


def read(r):
    return r.setup_s
