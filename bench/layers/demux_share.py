"""Host time in lane-pool demuxes (``mbe.demux``: the done-mask read, the
finished lanes' slices and their counters read back) over the window, in
%: the delta of the program's ``stats()`` counter ``demux_s``.  ``None``
where the program has no such counter."""


def read(run):
    a, b = run["stats_after"], run["stats_before"]
    if "demux_s" not in a or "demux_s" not in b:
        return None
    return 100 * (a["demux_s"] - b["demux_s"]) / run["window_s"]
