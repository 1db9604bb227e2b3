"""Set-up: process start to window start, compiles, cache loads, request
generation and warm-up included (host clock)."""


def read(run):
    return run["setup_s"]
