"""Distributed MBE with work stealing across every device of the process.

    PYTHONPATH=src python examples/mbe_distributed.py

On a CPU host the process gives itself 8 simulated XLA host devices (the
paper's thread-block grid, scaled down) before JAX starts; on a TPU host
it uses the chips it has.  It enumerates a workload-imbalanced power-law
graph through the unified client (``repro.api.MBEClient``):
``big_graph_threshold=1`` routes the whole graph to the work-stealing
big-graph lane across the serving mesh.  Runs with and without
the round-based work-stealing rebalance (``work_stealing=False`` is the
paper's noWS ablation) and prints the per-worker busy-step distribution
— the live version of the paper's Figure 5.
"""
import os

# host-platform devices only: the flag does not touch a TPU backend
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np          # noqa: E402
import jax                  # noqa: E402

from repro import MBEClient, MBEOptions                 # noqa: E402
from repro.baselines import count_mbea                  # noqa: E402
from repro.data import powerlaw_bipartite               # noqa: E402

g = powerlaw_bipartite(256, 512, m_edges=7000, alpha=1.35, seed=12,
                       name="marvel-like")
print(f"[mbe] {g.name}: |U|={g.n_u} |V|={g.n_v} |E|={len(g.edges)} "
      f"on {jax.device_count()} devices")

oracle = count_mbea(g)

for ws in (False, True):
    client = MBEClient(MBEOptions(
        bucket_mode="exact", big_graph_threshold=1, steps_per_round=512,
        mesh="auto", workers_per_device=2, work_stealing=ws))
    res = client.enumerate(g)
    assert res.n_max == oracle, (res.n_max, oracle)
    st = client.stats()
    busy = np.asarray(st["big_busy_per_worker"], dtype=float)
    rel = busy / busy.mean()
    tag = "work-stealing" if ws else "static       "
    print(f"[{tag}] nMB={res.n_max} rounds={st['batches']} "
          f"busy min/med/max = {rel.min():.2f}/{np.median(rel):.2f}/"
          f"{rel.max():.2f} (x mean)   imbalance={st['big_imbalance']:.3f}")

print("[mbe] both schedules agree with the serial oracle "
      "(benchmarks/workload.py sweeps all dataset families for the "
      "Fig.-5 load-distribution comparison).")
