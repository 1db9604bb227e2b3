from repro.sharding.axes import MBE_LANE_AXIS, mbe_serve_mesh  # noqa: F401
