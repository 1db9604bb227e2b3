"""Host time in lane-pool demuxes (``mbe.demux``: the finished lanes'
done mask and counters taken from the round's host readout of the lanes'
scalar state, decoded into results; a device read only where lane surgery
replaced the state since the round, or a lane's collect buffers are
decoded) over the window, in %: the delta of the program's ``stats()``
counter ``demux_s``.  ``None`` where the program has no such counter."""


def read(run):
    a, b = run["stats_after"], run["stats_before"]
    if "demux_s" not in a or "demux_s" not in b:
        return None
    return 100 * (a["demux_s"] - b["demux_s"]) / run["window_s"]
