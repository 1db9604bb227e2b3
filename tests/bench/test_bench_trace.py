"""``bench/trace.py``: the reduction from a profiler trace to device busy
time, per-operation time and labelled idle gaps, checked against values
counted by hand on a small synthetic trace in the TPU profiler's plane
and line layout."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402
from bench.run import load_reader  # noqa: E402

# Times in ns.  Host: window [0, 12000], bench.poll [0, 4000],
# bench.submit [4000, 5500], bench.poll [5500, 9000].
# TPU:0: fused_select_kernel [1000, 3000] and [6000, 7000], fusion.3
# [2500, 3500], copy [9500, 10500], and one op after the window.
# TPU:1: one op over the whole window.
SYNTHETIC = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 1500000 }
    events { metadata_id: 2 offset_ps: 5500000 duration_ps: 3500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.poll" } }
  event_metadata { key: 3 value { id: 3 name: "bench.submit" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 9 offset_ps: 0 duration_ps: 12000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 8500000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 11500000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fused_select_kernel" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.3" } }
  event_metadata { key: 3 value { id: 3 name: "copy" } }
  event_metadata { key: 4 value { id: 4 name: "late" } }
  event_metadata { key: 9 value { id: 9 name: "jit_round" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "x" } }
}
"""


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData
    return trace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))


def test_union_and_gaps_by_hand():
    busy = trace.union([(1000, 3000), (2500, 3500), (6000, 7000),
                        (9500, 10500), (3500, 3600)])
    assert busy == [(1000, 3600), (6000, 7000), (9500, 10500)]
    assert trace.gaps(busy, 0, 12000) == [(0, 1000), (3600, 6000),
                                          (7000, 9500), (10500, 12000)]
    assert trace.gaps([(0, 5)], 0, 5) == []


def test_gap_labels_by_hand():
    spans = [(0, 4000, "bench.poll"), (4000, 5500, "bench.submit"),
             (5500, 9000, "bench.poll")]
    gap_list = [(0, 1000), (3500, 6000), (7000, 9500), (10500, 12000)]
    # [3500, 6000]: poll 500, submit 1500, poll 500 -> submit
    assert trace.label_gaps(gap_list, spans) == {
        "bench.poll": 1000 + 2500, "bench.submit": 2500,
        trace.OTHER: 1500}


def test_busy_ops_and_idle_by_hand(synthetic):
    r = synthetic
    assert r.n_devices == 2
    assert r.window_s == pytest.approx(12000e-9)
    # TPU:0 busy 2500 + 1000 + 1000 = 4500; TPU:1 busy 12000
    assert r.busy_s == pytest.approx((4500 + 12000) / 2 * 1e-9)
    assert r.idle_share == pytest.approx(1 - 8250 / 12000)
    assert r.op_s == pytest.approx({
        "fused_select_kernel": 1500e-9, "fusion.3": 500e-9,
        "copy": 500e-9, "x": 6000e-9})
    assert r.idle_s == pytest.approx({
        "bench.poll": 1750e-9, "bench.submit": 1250e-9,
        trace.OTHER: 750e-9})


@pytest.mark.parametrize("cell", ["stream", "big"])
def test_layer_readers_by_hand(synthetic, cell):
    run = dict(trace=synthetic)
    idle = load_reader(ROOT, "layers", f"idle_share.{cell}")
    kern = load_reader(ROOT, "layers", f"kernel_share.{cell}")
    assert idle(run) == pytest.approx(100 * (1 - 8250 / 12000))
    assert kern(run) == pytest.approx(100 * 1500 / 8250)
    assert idle(dict(trace=None)) is None and kern(dict(trace=None)) is None
