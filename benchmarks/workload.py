"""Fig.-5 analog: per-worker load distribution with/without work stealing.

Runs the distributed MBE runner in this process, over every device it
has (``jax.devices()``), and reports per-worker busy-step statistics —
min / max / quartiles / std, normalized to the mean — exactly the
quantities behind the paper's Figure 5 box plot.  On a CPU host, give
the process simulated devices before it starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m benchmarks.workload
"""
from __future__ import annotations

import numpy as np
import jax

from repro.core import distributed as dd
from repro.core import engine_dense as ed
from repro.data import dataset_suite


def run() -> list[dict]:
    mesh = jax.make_mesh((jax.device_count(),), ("workers",))
    print(f"[workload] {jax.device_count()} x {jax.devices()[0].device_kind}")
    rows = []
    for name, g in dataset_suite("bench").items():
        cfg = ed.make_config(g)
        for ws in (True, False):
            dist = dd.DistConfig(steps_per_round=512, workers_per_device=2,
                                 work_stealing=ws)
            _, _, driver = dd.make_distributed_runner(
                g, cfg, mesh, ("workers",), dist)
            state, log = driver()
            busy = np.stack([r["busy"] for r in log]).sum(0).astype(float)
            rel = busy / busy.mean()
            q = np.percentile(rel, [0, 25, 50, 75, 100])
            row = dict(dataset=name, work_stealing=ws,
                       n_max=dd.totals(state)["n_max"], rounds=len(log),
                       norm_min=round(q[0], 4), norm_q1=round(q[1], 4),
                       norm_med=round(q[2], 4), norm_q3=round(q[3], 4),
                       norm_max=round(q[4], 4),
                       norm_std=round(float(rel.std()), 4))
            print(row)
            rows.append(row)
    # paired check: stealing must not change the enumeration count
    by = {}
    for row in rows:
        by.setdefault(row["dataset"], {})[row["work_stealing"]] = row
    for name, pair in by.items():
        assert pair[True]["n_max"] == pair[False]["n_max"], name
    return rows


if __name__ == "__main__":
    run()
