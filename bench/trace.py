"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

The harness wraps its measured window in a host span named ``WINDOW`` and
each call into the program in a span whose name starts with ``SPAN``
(``jax.profiler.TraceAnnotation``).  From the trace this module takes

* the window: the first ``WINDOW`` span on a host plane;
* per device plane (``/device:TPU:<n>``), the device operations (the
  ``XLA Ops`` line), clipped to the window;
* busy time: the union of those operations' intervals; idle is the rest
  of the window;
* per-operation device time (the sum of its events' clipped durations),
  and each operation's string-valued stats, which name the HLO op and,
  for a Pallas kernel, the custom call;
* idle gaps: the stretches of the window with no operation, each
  labelled by the host span that overlaps it most (``host.other`` when
  none does).

Everything is averaged over the devices found.  Times are in seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW = "bench.window"
SPAN = "bench."
OTHER = "host.other"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # mean over devices
    op_s: dict[str, float]              # op name -> device s, mean/device
    idle_s: dict[str, float]            # host span -> idle s, mean/device
    n_devices: int
    op_info: dict[str, str] = dataclasses.field(default_factory=dict)
    #                                     op name -> its string stats (the
    #                                     HLO op, category, kernel name...)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[int, int]], lo: int, hi: int
         ) -> list[tuple[int, int]]:
    """The complement of disjoint sorted ``busy`` within ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label_gaps(gap_list, spans) -> dict[str, int]:
    """Sum of gap lengths per host span, each gap going to the span that
    overlaps it most.  ``spans``: ``(start, end, name)``, not nested (the
    harness's spans inside the window follow one another)."""
    spans = sorted(spans)
    out: dict[str, int] = {}
    j = 0
    for s, e in gap_list:
        while j < len(spans) and spans[j][1] <= s:
            j += 1
        best, name = 0, OTHER
        k = j
        while k < len(spans) and spans[k][0] < e:
            ov = min(e, spans[k][1]) - max(s, spans[k][0])
            if ov > best:
                best, name = ov, spans[k][2]
            k += 1
        out[name] = out.get(name, 0) + (e - s)
    return out


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield ev


def reduce_profile(pd) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData``."""
    window, spans = None, []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for ev in _events(plane):
                if ev.name == WINDOW and window is None:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith(SPAN) and ev.name != WINDOW:
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    if not devices:
        raise ValueError("no device plane in the trace")
    lo, hi = window
    busy_ns, op_ns, idle_ns, info = 0, {}, {}, {}
    for plane in devices:
        ivs = []
        for ev in _events(plane, OPS_LINE):
            s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
            if e > s:
                ivs.append((s, e))
                op_ns[ev.name] = op_ns.get(ev.name, 0) + (e - s)
                if ev.name not in info:
                    info[ev.name] = " ".join(
                        f"{k}={v}" for k, v in ev.stats if isinstance(v, str))
        busy = union(ivs)
        busy_ns += sum(e - s for s, e in busy)
        for k, v in label_gaps(gaps(busy, lo, hi), spans).items():
            idle_ns[k] = idle_ns.get(k, 0) + v
    n = len(devices)
    return Reduction(
        window_s=(hi - lo) / 1e9, busy_s=busy_ns / n / 1e9,
        op_s={k: v / n / 1e9 for k, v in op_ns.items()},
        idle_s={k: v / n / 1e9 for k, v in idle_ns.items()}, n_devices=n,
        op_info=info)


def reduce_file(path: str) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def top(d: dict[str, float], k: int = 10) -> list[list]:
    return [[name, s] for name, s in
            sorted(d.items(), key=lambda kv: -kv[1])[:k]]
