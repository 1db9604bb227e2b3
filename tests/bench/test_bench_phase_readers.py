"""The per-layer readers of the poll's host time counters
(``host_share``, ``refill_share``, ``demux_share``): deltas of the
program's ``stats()`` over the window, checked by hand, ``None`` where the
program has no such counters, and consistent with each other on counters
a served stream really produced."""
import time

import pytest

from _tiny import ROOT

from bench.run import load_reader

NAMES = ("host_share.stream", "refill_share.stream", "demux_share.stream")


def _run(before, after, window_s=4.0):
    return dict(stats_before=before, stats_after=after, window_s=window_s)


BEFORE = dict(poll_s=1.0, exec_s=0.5, refill_s=0.25, demux_s=0.125,
              busy_steps=0, total_lane_steps=0)
AFTER = dict(poll_s=4.0, exec_s=2.0, refill_s=0.75, demux_s=0.625,
             busy_steps=10, total_lane_steps=20)


@pytest.mark.parametrize("name, want", [
    ("host_share.stream", 100 * ((4.0 - 2.0) - (1.0 - 0.5)) / 4.0),
    ("refill_share.stream", 100 * 0.5 / 4.0),
    ("demux_share.stream", 100 * 0.5 / 4.0),
])
def test_reader_by_hand(name, want):
    assert load_reader(ROOT, "layers", name)(_run(BEFORE, AFTER)) \
        == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_reader_none_without_the_counters(name):
    """A program without the counters (its ``stats()`` lacks the keys)
    gives no reading, and the reader does not raise."""
    read = load_reader(ROOT, "layers", name)
    bare = dict(busy_steps=0, total_lane_steps=0)
    assert read(_run(bare, dict(bare, busy_steps=5))) is None
    assert read(_run(bare, AFTER)) is None


def test_readers_on_a_served_stream():
    """On the counters of a real stream: every share lies in [0, 100],
    and the refill and demux phases fit in the host share, since both
    run inside polls and outside the round executables."""
    from repro import MBEClient, MBEOptions
    from repro.data.generators import dense_small
    client = MBEClient(MBEOptions(max_batch=2, steps_per_round=8))
    client.enumerate_many([dense_small(7, 12, p=0.4, seed=1, name="w")])
    before = client.stats()
    t0 = time.perf_counter()
    futs = [client.submit(dense_small(6 + i % 3, 12, p=0.35, seed=60 + i,
                                      name=f"g{i}")) for i in range(6)]
    while not all(f.done() for f in futs):
        client.poll()
    run = _run(before, client.stats(), time.perf_counter() - t0)
    host, refill, demux = (load_reader(ROOT, "layers", n)(run)
                           for n in NAMES)
    for share in (host, refill, demux):
        assert 0 < share <= 100
    assert refill + demux <= host
