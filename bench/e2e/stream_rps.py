"""Requests answered ``done`` in the window, over the window (host clock)."""


def read(run):
    n = sum(a["status"] == "done" for a in run["answers"])
    return n / run["window_s"] if n else None
