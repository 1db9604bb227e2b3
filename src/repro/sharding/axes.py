"""The MBE serving mesh axis.

The serving executors (``repro.serving.executor``) place graph lanes on a
1-D mesh of their own: ``ShardedExecutor`` shards a bucket's lane pool over
it (one graph per lane, lanes strided across devices) and the big-graph
work-stealing lane spreads ONE graph's root tasks over the same axis.  The
axis is named here so the executors never invent mesh axis names of their
own.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

MBE_LANE_AXIS = "mbe_lanes"


def mbe_serve_mesh(n_devices: Optional[int] = None,
                   axis: str = MBE_LANE_AXIS) -> Mesh:
    """1-D serving mesh over (a prefix of) the local devices.

    ``n_devices=None`` takes every visible device — the multi-device CI leg
    forces 8 host devices via ``XLA_FLAGS=--xla_force_host_platform_
    device_count=8`` and serves the whole pool through them.
    """
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"mbe_serve_mesh: asked for {n_devices} devices but only "
                f"{len(devs)} are visible (force host devices with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))
