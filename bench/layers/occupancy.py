"""Lane-pool occupancy over the window: engine steps advanced over lane
steps paid for (``stats()`` deltas of ``busy_steps`` / ``total_lane_steps``;
the scheduler's own counters)."""


def read(run):
    a, b = run["stats_after"], run["stats_before"]
    total = a["total_lane_steps"] - b["total_lane_steps"]
    return 100 * (a["busy_steps"] - b["busy_steps"]) / total if total else None
