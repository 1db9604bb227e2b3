"""Host time in lane-pool refills (``mbe.refill``: contexts built on the
host and the lane-surgery dispatch) over the window, in %: the delta of
the program's ``stats()`` counter ``refill_s``.  ``None`` where the
program has no such counter."""


def read(run):
    a, b = run["stats_after"], run["stats_before"]
    if "refill_s" not in a or "refill_s" not in b:
        return None
    return 100 * (a["refill_s"] - b["refill_s"]) / run["window_s"]
