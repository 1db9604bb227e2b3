"""Device reads the lane-pool demux made over the window: finished lanes
sliced whole for their collect buffers, and done masks read with no
round readout of the lanes' scalar state.  The delta of the program's
``stats()`` counter ``demux_device_reads``; ``None`` where the program
has no such counter."""


def read(run):
    a, b = run["stats_after"], run["stats_before"]
    if "demux_device_reads" not in a or "demux_device_reads" not in b:
        return None
    return float(a["demux_device_reads"] - b["demux_device_reads"])
