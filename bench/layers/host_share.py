"""Host time inside ``MBEServer.poll`` not spent waiting on a round
executable, over the window, in %: the deltas of the program's ``stats()``
counters ``poll_s`` and ``exec_s`` (host clock, spans ``mbe.poll`` and
``mbe.round.wait``).  ``None`` where the program has no such counters."""


def read(run):
    a, b = run["stats_after"], run["stats_before"]
    if not all("poll_s" in s and "exec_s" in s for s in (a, b)):
        return None
    host = (a["poll_s"] - a["exec_s"]) - (b["poll_s"] - b["exec_s"])
    return 100 * host / run["window_s"]
