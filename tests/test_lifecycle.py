"""Request lifecycle: pending -> placed -> running -> {done, cancelled,
timed_out} (DESIGN.md §7).

* cancel-pending never compiles (the cache miss counter is unchanged)
  and never builds a context;
* cancel-in-flight frees the lane via row surgery and the next pending
  request refills it;
* higher priority overtakes FIFO order within a bucket;
* an expired deadline returns a result flagged ``timed_out`` without
  poisoning the pool — pending expiry before placement, in-flight expiry
  via eviction with partial progress.
"""
import functools

import pytest
from _graphs import random_graph

from repro import MBEClient, MBEOptions
from repro.core import engine_dense as ed
from repro.data.generators import dense_small
from repro.serving import BucketPolicy, MBEServer

_random_graph = functools.partial(random_graph, canonical=True)


def _heavy():
    return dense_small(14, 28, p=0.55, seed=3, name="heavy")


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------

def test_cancel_pending_never_compiles():
    """A request cancelled while pending must never reach the executable
    cache (no compile) nor a lane; its flagged result is delivered by the
    next poll/reap."""
    srv = MBEServer(BucketPolicy(mode="pow2", steps_per_round=8))
    rid = srv.admit(_random_graph(10, 20, 0.2, 0))
    assert srv.cancel(rid) is True
    assert srv.cache.misses == 0                 # nothing compiled
    got = srv.reap()                             # no scheduling round
    assert got[rid].cancelled and got[rid].status == "cancelled"
    assert got[rid].n_max == 0 and got[rid].steps == 0
    assert got[rid].bicliques is None
    assert srv.stats()["pending"] == 0 and srv.stats()["in_flight"] == 0
    assert srv.stats()["cancelled"] == 1
    assert srv.cancel(rid) is False              # already terminal
    assert srv.drain() == {}                     # server fully idle


def test_cancel_pending_other_buckets_unaffected():
    """Cancelling one bucket's only request must not suppress (or compile
    for) the other buckets' traffic: exactly one executable compiles, for
    the surviving bucket."""
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=2))
    survivor_a = srv.admit(_random_graph(10, 20, 0.2, 1))   # bucket (16,32)
    doomed = srv.admit(_random_graph(4, 60, 0.2, 2))        # bucket (4,64)
    survivor_b = srv.admit(_random_graph(11, 19, 0.2, 3))   # bucket (16,32)
    assert srv.cancel(doomed)
    got = srv.drain()
    assert got[doomed].cancelled
    assert not got[survivor_a].cancelled and not got[survivor_b].cancelled
    assert got[survivor_a].n_max >= 0 and got[survivor_b].n_max >= 0
    # ONLY the (16,32) pool compiles: its round and install executables
    assert srv.cache.misses == 2


def test_cancel_in_flight_frees_lane_and_next_request_refills_it():
    """Cancelling a running request evicts its lane (row surgery) and the
    next pending same-bucket request takes the freed lane on the next
    poll — the pool is never widened (max_batch=1 pins it to one lane)."""
    heavy = _heavy()
    light = _random_graph(10, 20, 0.1, 0)        # same pow2 bucket (16,32)
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=1,
                                 steps_per_round=8))
    rid_h = srv.admit(heavy)
    srv.poll()                                   # heavy placed + running
    assert srv.stats()["in_flight"] == 1
    rid_l = srv.admit(light)                     # queued behind the lane
    assert srv.cancel(rid_h) is True
    assert srv.stats()["in_flight"] == 0         # lane freed immediately
    got = srv.drain()                            # light refills the lane
    assert got[rid_h].cancelled
    assert got[rid_h].steps > 0                  # partial progress reported
    assert got[rid_l].status == "done"
    assert got[rid_l].n_max == int(ed.enumerate_dense(light).n_max)
    # one lane pool, one round and one install executable: the refill
    # reused the evicted slot
    batches = {k[-1] if k[0] == "install" else k[1]
               for k in srv.cache._entries}
    assert batches == {1} and srv.cache.misses == 2
    assert srv.stats()["lanes"] == 2             # two placements, one lane


def test_cancel_in_flight_big_lane():
    """Cancelling the active big-graph request drops the work-stealing
    lane whole; queued big requests are then served normally."""
    heavy = dense_small(18, 36, p=0.5, seed=7, name="big-a")
    heavy2 = dense_small(17, 34, p=0.45, seed=9, name="big-b")
    srv = MBEServer(BucketPolicy(mode="pow2", steps_per_round=16,
                                 big_graph_threshold=16))
    rid_a = srv.admit(heavy)
    rid_b = srv.admit(heavy2)
    srv.poll()                                   # big-a occupies the lane
    assert srv.cancel(rid_a) is True
    got = srv.drain()
    assert got[rid_a].cancelled and got[rid_a].steps > 0
    assert got[rid_b].status == "done"
    assert got[rid_b].n_max == int(ed.enumerate_dense(heavy2).n_max)


# ---------------------------------------------------------------------------
# priority
# ---------------------------------------------------------------------------

def test_priority_overtakes_fifo_within_bucket():
    """With one lane, a high-priority admit placed later must complete
    before earlier FIFO requests of the same bucket (and the FIFO order
    is preserved within a priority level)."""
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=1,
                                 steps_per_round=256))
    g = [_random_graph(10, 20, 0.2, s) for s in range(4)]
    rid0 = srv.admit(g[0])                       # priority 0, first
    rid1 = srv.admit(g[1])                       # priority 0
    rid_hi = srv.admit(g[2], priority=5)         # admitted LAST but highest
    rid2 = srv.admit(g[3])
    order = []
    while srv.has_work():
        order.extend(srv.poll().keys())
    assert set(order) == {rid0, rid1, rid_hi, rid2}
    assert order.index(rid_hi) < order.index(rid0)   # overtook the backlog
    assert order.index(rid0) < order.index(rid1) < order.index(rid2)


def test_priority_respected_at_first_placement():
    """When a pool is first created, the highest-priority request gets
    the lane even though it was admitted after the FIFO backlog."""
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=1,
                                 steps_per_round=512))
    rid_lo = srv.admit(_random_graph(10, 20, 0.2, 0))
    rid_hi = srv.admit(_random_graph(10, 20, 0.2, 1), priority=1)
    first = []
    while not first:
        first = list(srv.poll().keys())
    assert first[0] == rid_hi
    srv.drain()


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------

def test_deadline_pending_expiry_returns_timed_out_without_compiling():
    """A request whose deadline expires while still queued is completed
    as timed_out with zero counters, before any context build or
    compile; later traffic in the same bucket is unaffected."""
    srv = MBEServer(BucketPolicy(mode="pow2", steps_per_round=8))
    rid_t = srv.admit(_heavy(), deadline_s=0.0)      # born expired
    misses_before = srv.cache.misses
    rid_n = srv.admit(_random_graph(10, 20, 0.2, 5))
    got = srv.drain()
    r = got[rid_t]
    assert r.timed_out and r.status == "timed_out"
    assert r.n_max == 0 and r.steps == 0 and r.bicliques is None
    assert r.queue_s > 0 and r.service_s == 0.0 and r.compile_s == 0.0
    # the pool is not poisoned: the normal request completed fine
    assert got[rid_n].status == "done"
    assert got[rid_n].n_max == int(
        ed.enumerate_dense(_random_graph(10, 20, 0.2, 5)).n_max)
    # exactly one pool's executables compiled (its round and install) —
    # for the surviving request's pool
    assert srv.cache.misses == misses_before + 2
    assert srv.stats()["timed_out"] == 1


def test_deadline_in_flight_expiry_evicts_with_partial_progress():
    """An in-flight request whose deadline passes between rounds is
    evicted (lane freed) and completed as timed_out carrying the partial
    counters; the server stays serviceable for the next request."""
    heavy = _heavy()
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=1,
                                 steps_per_round=1))
    # steps_per_round=1: the graph cannot finish inside one round, and
    # the first poll's compile alone outlasts the deadline
    rid = srv.admit(heavy, deadline_s=0.1)
    srv.poll()                                   # placed + first round
    got = dict(srv.poll())
    for _ in range(2000):
        if rid in got:
            break
        got.update(srv.poll())
    r = got[rid]
    assert r.timed_out and r.status == "timed_out"
    assert r.steps >= 1                          # made SOME progress
    assert r.service_s > 0
    assert srv.stats()["in_flight"] == 0
    # pool still serviceable afterwards
    light = _random_graph(10, 20, 0.1, 7)
    rid_l = srv.admit(light)
    got2 = srv.drain()
    assert got2[rid_l].status == "done"
    assert got2[rid_l].n_max == int(ed.enumerate_dense(light).n_max)


# ---------------------------------------------------------------------------
# the same lifecycle through the client/futures facade
# ---------------------------------------------------------------------------

def test_future_cancel_pending_and_in_flight():
    client = MBEClient(MBEOptions(max_batch=1, steps_per_round=8))
    f_run = client.submit(_heavy())
    client.poll()                                # heavy now in flight
    f_pend = client.submit(_random_graph(10, 20, 0.2, 9))
    assert f_pend.cancel() is True               # pending cancel
    assert f_pend.result().status == "cancelled"
    assert f_run.cancel() is True                # in-flight cancel
    assert f_run.result().status == "cancelled"
    assert f_run.cancel() is False               # terminal: too late
    st = client.stats()
    assert st["cancelled"] == 2 and st["in_flight"] == 0


def test_future_deadline_via_client():
    client = MBEClient(MBEOptions(steps_per_round=8))
    fut = client.submit(_heavy(), deadline_s=0.0)
    res = fut.result(timeout=300)
    assert res.status == "timed_out"
    # a later normal submit on the same client is unaffected
    g = _random_graph(10, 20, 0.2, 11)
    assert client.enumerate(g).n_max == int(ed.enumerate_dense(g).n_max)
