"""The paper's graph-scale classes for distributed MBE.

A workload is a graph-scale class: padded sizes, generator density, DFS
depth bound and the work-stealing round settings (``DistConfig``).
``CONFIG`` is the production class that ``chip_smoke.py`` serves on the
chip; ``SMOKE`` is its CPU-sized twin, whose worker count
``launch/mbe_run.py`` takes as its default.
"""
from __future__ import annotations

import dataclasses

from repro.core.distributed import DistConfig


@dataclasses.dataclass(frozen=True)
class MBEWorkload:
    name: str
    n_u: int                 # padded |U|
    n_v: int                 # padded |V|
    density: float           # edge density (generator parameter)
    depth: int               # DFS depth bound
    dist: DistConfig = DistConfig()


# Production-scale MBE class. |U|=16384 bitset rows x
# |V|=16384 -> adjacency 16384 x 512 u32 words = 32 MiB resident per device
# (replicated graph, sharded root tasks) — the paper's Table-I scale class.
CONFIG = MBEWorkload(
    name="cumbe-16k", n_u=16_384, n_v=16_384, density=2e-3, depth=64,
    dist=DistConfig(steps_per_round=4096, workers_per_device=1),
)

SMOKE = MBEWorkload(
    name="cumbe-smoke", n_u=64, n_v=64, density=0.1, depth=66,
    dist=DistConfig(steps_per_round=256, workers_per_device=2),
)
