"""Rows whose results reached the client during the window, per second."""


def read(r):
    return r.window["rows_per_s"]
