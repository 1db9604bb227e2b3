"""Batched multi-graph serving layer: planner, cache, scheduler.

* bucket-planner padding correctness: buckets always contain the graph,
  depth covers the DFS, exact mode is the identity, and enumeration on
  the padded bucket shape is bit-identical to the exact shape;
* executable-cache hit/miss accounting;
* batched-vs-single-graph result equality on a mixed-size request stream
  (counts, fingerprints, and decoded biclique sets);
* continuous-batching scheduler: admit/poll/drain, mid-flight lane refill
  result identity, occupancy lift on a skewed stream, latency/compile
  accounting, truncation flag, and queue preservation under a poisoned
  in-flight batch.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _graphs import random_graph
from _hyp import given, settings, st

from repro.baselines import (bicliques_to_key_set, enumerate_bruteforce,
                             enumerate_mbea)
from repro.core import engine_dense as ed
from repro.core.graph import BipartiteGraph
from repro.data import dataset_suite
from repro.serving import (BucketPolicy, ExecutableCache, MBEServer,
                           plan_batch_size, plan_bucket)

_random_graph = functools.partial(random_graph, canonical=True)


# ---------------------------------------------------------------------------
# bucket planner
# ---------------------------------------------------------------------------

@given(st.integers(1, 40), st.integers(1, 80),
       st.sampled_from(["pow2", "linear", "exact"]), st.integers(0, 1000))
@settings(max_examples=40, deadline=None)
def test_bucket_contains_graph(n_u, n_v, mode, seed):
    g = _random_graph(n_u, n_v, 0.3, seed)
    pol = BucketPolicy(mode=mode)
    b = plan_bucket(g, pol)
    assert b.n_u >= g.n_u and b.n_v >= g.n_v
    assert b.depth >= b.n_u + 2          # DFS stack always covered
    if mode == "exact":
        assert (b.n_u, b.n_v) == (g.n_u, g.n_v)
    # planning is idempotent: a bucket-sized graph maps to itself
    if mode != "exact":
        gb = _random_graph(b.n_u, b.n_v, 0.3, seed + 1)
        b2 = plan_bucket(gb, pol)
        assert (b2.n_u, b2.n_v) == (b.n_u, b.n_v)


def test_bucket_collapses_shapes():
    """The point of bucketing: nearby shapes share one bucket."""
    pol = BucketPolicy(mode="pow2")
    shapes = {(9, 20), (12, 17), (16, 30), (10, 25)}
    buckets = {plan_bucket(_random_graph(u, v, 0.3, 0), pol)
               for u, v in shapes}
    assert len(buckets) == 1
    assert buckets.pop() == plan_bucket(
        _random_graph(16, 32, 0.3, 0), pol)


def test_padded_bucket_enumeration_identical():
    """Engine run at the bucket shape == engine run at the exact shape."""
    g = dataset_suite("test")["ucforum-like"]
    exact = ed.enumerate_dense(g)
    bucket = plan_bucket(g, BucketPolicy(mode="pow2"))
    cfg = bucket.engine_config(collect_cap=1)
    ctx = ed.make_context(g, cfg)
    s0 = ed.init_state(cfg, np.arange(g.n_u, dtype=np.int32))
    import jax
    out = jax.jit(lambda s: ed.run(ctx, cfg, s))(s0)
    assert int(out.n_max) == int(exact.n_max)
    assert int(out.cs) == int(exact.cs)


def test_plan_batch_size():
    pol = BucketPolicy(max_batch=8, pad_batch=True)
    assert plan_batch_size(1, pol) == 1
    assert plan_batch_size(3, pol) == 4
    assert plan_batch_size(8, pol) == 8
    assert plan_batch_size(100, pol) == 8
    nopad = BucketPolicy(max_batch=8, pad_batch=False)
    assert plan_batch_size(3, nopad) == 3


def test_plan_batch_size_non_pow2_max_batch():
    """A non-power-of-two ``max_batch`` with padding must NOT mint batch
    sizes like {1, 2, 4, 6}: every planned size is a power of two capped
    at the previous power of two (the executable-reuse promise)."""
    pol = BucketPolicy(max_batch=6, pad_batch=True)
    assert pol.lane_cap == 4
    sizes = {plan_batch_size(n, pol) for n in range(1, 25)}
    assert sizes == {1, 2, 4}
    for b in sizes:
        assert b & (b - 1) == 0 and b <= pol.max_batch
    # no padding -> the cap is honoured verbatim
    nopad = BucketPolicy(max_batch=6, pad_batch=False)
    assert plan_batch_size(5, nopad) == 5
    assert plan_batch_size(9, nopad) == 6


def test_non_pow2_max_batch_server_end_to_end():
    """Serving through a max_batch=6 policy keeps every cached executable
    at a power-of-two lane count — and still returns correct results."""
    graphs = [_random_graph(9, 13, 0.3, s) for s in range(6)]
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=6))
    results = srv.serve(graphs)
    for g, r in zip(graphs, results):
        assert r.n_max == int(ed.enumerate_dense(g).n_max)
    for key in srv.cache._entries:
        batch = key[-1] if key[0] == "install" else key[1]
        assert batch & (batch - 1) == 0 and batch <= 6


# ---------------------------------------------------------------------------
# executable cache
# ---------------------------------------------------------------------------

def _cstats(hits, misses, entries, evictions=0):
    return dict(hits=hits, misses=misses, entries=entries,
                evictions=evictions)


def test_cache_hit_miss_accounting():
    cache = ExecutableCache()
    g = dataset_suite("test")["corp-leadership"]
    bucket = plan_bucket(g, BucketPolicy(mode="pow2"))
    cfg = bucket.engine_config()
    f1 = cache.get(cfg, 2)
    assert cache.stats() == _cstats(hits=0, misses=1, entries=1)
    f2 = cache.get(cfg, 2)                      # same key -> hit, same fn
    assert f2 is f1
    assert cache.stats() == _cstats(hits=1, misses=1, entries=1)
    cache.get(cfg, 4)                           # new batch size -> miss
    assert cache.stats() == _cstats(hits=1, misses=2, entries=2)
    cfg2 = bucket.engine_config(order_mode="input")   # new config -> miss
    cache.get(cfg2, 2)
    assert cache.stats() == _cstats(hits=1, misses=3, entries=3)
    cache.get(cfg, 2)
    assert cache.stats() == _cstats(hits=2, misses=3, entries=3)


def test_cache_lru_eviction_and_recompile_on_reuse():
    """A bounded cache drops the COLDEST entry past capacity (LRU, so a
    just-hit entry survives) and honestly recompiles a dropped key when it
    returns — a long-lived server with many buckets cannot grow
    executables unboundedly."""
    cache = ExecutableCache(capacity=2)
    g = dataset_suite("test")["corp-leadership"]
    bucket = plan_bucket(g, BucketPolicy(mode="pow2"))
    cfg = bucket.engine_config()
    e1 = cache.get(cfg, 1)
    cache.get(cfg, 2)
    cache.get(cfg, 1)                           # touch: 2 is now coldest
    cache.get(cfg, 4)                           # capacity 2 -> evicts 2
    assert cache.stats() == _cstats(hits=1, misses=3, entries=2,
                                    evictions=1)
    assert cache.get(cfg, 1) is e1              # LRU-touched entry survived
    e2b = cache.get(cfg, 2)                     # evicted key: fresh entry,
    assert cache.stats()["misses"] == 4         # counted as a new compile
    assert not e2b.compiled
    # the recompiled entry still runs (and times its own compile)
    ctx = ed.make_context(g, cfg)
    states = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[ed.init_state(cfg, np.arange(g.n_u, dtype=np.int32))
          for _ in range(2)])
    ctxs = jax.tree.map(lambda x: jnp.stack([x] * 2), ctx)
    out = e2b(ctxs, states)
    assert e2b.compiled and e2b.compile_s > 0
    ref = ed.enumerate_dense(g)
    assert all(int(n) == int(ref.n_max) for n in np.asarray(out.n_max))


def test_cache_capacity_validation():
    with pytest.raises(ValueError, match="capacity"):
        ExecutableCache(capacity=0)
    unbounded = ExecutableCache(capacity=None)   # explicit opt-out works
    g = dataset_suite("test")["corp-leadership"]
    cfg = plan_bucket(g, BucketPolicy(mode="pow2")).engine_config()
    for b in (1, 2, 4, 8):
        unbounded.get(cfg, b)
    assert unbounded.stats()["evictions"] == 0


def test_server_reuses_executables_across_flushes():
    """Second wave of same-bucket traffic must be all cache hits."""
    graphs = [_random_graph(10, 14, 0.3, s) for s in range(4)]
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=4))
    srv.serve(graphs)
    misses_after_first = srv.cache.misses
    srv.serve([_random_graph(11, 15, 0.35, s) for s in range(40, 44)])
    assert srv.cache.misses == misses_after_first
    assert srv.cache.hits >= 1


# ---------------------------------------------------------------------------
# batched vs single-graph equality
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("mode", ["pow2", "linear", "exact"])
def test_mixed_stream_matches_single_graph_runs(mode):
    suite = dataset_suite("test")
    graphs = list(suite.values()) + \
        [_random_graph(6 + s, 9 + 2 * s, 0.25, s) for s in range(5)]
    srv = MBEServer(BucketPolicy(mode=mode, max_batch=4),
                    collect_cap=256, collect=True)
    results = srv.serve(graphs)
    assert len(results) == len(graphs)
    for g, r in zip(graphs, results):
        single = ed.enumerate_dense(g, collect_cap=256)
        assert r.n_max == int(single.n_max), (mode, g.name)
        assert r.cs == int(single.cs), (mode, g.name)
        cfg = ed.make_config(g, collect_cap=256)
        ref = bicliques_to_key_set(
            ed.collected_bicliques(cfg, single, g.n_u, g.n_v))
        assert bicliques_to_key_set(r.bicliques) == ref, (mode, g.name)
        # and the oracle agrees on the count
        assert r.n_max == enumerate_mbea(g, collect=False), (mode, g.name)
    st_ = srv.stats()
    assert st_["pending"] == 0
    assert st_["lanes"] >= len(graphs)


def test_swapped_submission_demuxes_in_caller_orientation():
    """A graph submitted with |U| > |V| is canonicalized internally; the
    demuxed bicliques must still index the CALLER's sides."""
    g = random_graph(11, 7, 0.35, 42)            # non-canonical on purpose
    assert g.n_u > g.n_v
    truth = bicliques_to_key_set(enumerate_bruteforce(g))
    srv = MBEServer(BucketPolicy(mode="pow2"), collect_cap=256,
                    collect=True)
    r = srv.serve([g])[0]
    assert r.n_max == len(truth)
    assert bicliques_to_key_set(r.bicliques) == truth
    assert r.latency_s > 0


def test_dummy_lane_padding_is_inert():
    """A partial flush pads the batch with empty-task lanes; they must not
    change any real lane's result."""
    g = dataset_suite("test")["corp-leadership"]
    ref = ed.enumerate_dense(g)
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=8, pad_batch=True))
    res = srv.serve([g, g, g])                   # 3 requests -> 4 lanes
    assert srv.stats()["pad_lanes"] == 1
    for r in res:
        assert r.n_max == int(ref.n_max)
        assert r.cs == int(ref.cs)


# ---------------------------------------------------------------------------
# continuous scheduler: slot admission + mid-flight lane refill
# ---------------------------------------------------------------------------

def _mixed_stream(n):
    suite = dataset_suite("test")
    out = list(suite.values())
    s = 0
    while len(out) < n:
        out.append(_random_graph(5 + s % 14, 8 + (2 * s) % 25, 0.25, s))
        s += 1
    return out[:n]


def test_continuous_mode_identical_to_flush_on_mixed_stream():
    """Bounded rounds + mid-flight refill must be result-identical to
    whole-batch flush on a 48-graph mixed stream: same (n_max, cs) per
    request and bicliques decoded in the submitted orientation."""
    graphs = _mixed_stream(48)
    flush = MBEServer(BucketPolicy(mode="pow2", max_batch=4),
                      collect_cap=128, collect=True)
    cont = MBEServer(BucketPolicy(mode="pow2", max_batch=4,
                                  steps_per_round=24),
                     collect_cap=128, collect=True)
    rf = flush.serve(graphs)
    rc = cont.serve(graphs)
    assert len(rc) == len(graphs)
    for g, a, b in zip(graphs, rf, rc):
        assert (a.n_max, a.cs) == (b.n_max, b.cs), g.name
        assert bicliques_to_key_set(a.bicliques) == \
            bicliques_to_key_set(b.bicliques), g.name
    # every continuous executable is a round-mode entry, one per
    # (bucket, batch) pair with the round budget in the key, or that
    # pool's install executable
    st_ = cont.stats()
    assert st_["misses"] == st_["entries"]
    assert st_["pending"] == 0 and st_["in_flight"] == 0
    rounds = [k for k in cont.cache._entries if k[0] != "install"]
    installs = [k for k in cont.cache._entries if k[0] == "install"]
    for (_cfg, _batch, budget) in rounds:
        assert budget == 24
    assert sorted((k[3].n_u, k[3].n_v, k[4]) for k in installs) == \
        sorted((c.n_u, c.n_v, b) for (c, b, _s) in rounds)


def test_refill_lifts_occupancy_on_skewed_stream():
    """One heavy + many light same-bucket graphs: refilling finished lanes
    mid-flight must yield strictly higher busy/total lane-step occupancy
    than whole-batch flush, at identical results."""
    from repro.data.generators import dense_small
    heavy = dense_small(14, 28, p=0.55, seed=3, name="heavy")
    lights = [_random_graph(10, 20, 0.1, s) for s in range(7)]
    graphs = [heavy] + lights
    occ, res = {}, {}
    for label, spr in (("flush", 0), ("continuous", 16)):
        srv = MBEServer(BucketPolicy(mode="pow2", max_batch=4,
                                     steps_per_round=spr))
        res[label] = srv.serve(graphs)
        st_ = srv.stats()
        occ[label] = st_["occupancy"]
        assert st_["busy_steps"] + st_["idle_lane_steps"] == \
            st_["total_lane_steps"]
    for a, b in zip(res["flush"], res["continuous"]):
        assert (a.n_max, a.cs) == (b.n_max, b.cs)
    assert occ["continuous"] > occ["flush"]


def test_admit_poll_drain_incremental():
    """poll() advances one bounded round; results dribble out and drain()
    finishes the rest.  Requests admitted mid-stream join the live pool."""
    from repro.data.generators import dense_small
    heavy = dense_small(14, 28, p=0.55, seed=3, name="heavy")
    light = _random_graph(10, 20, 0.1, 0)
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=2,
                                 steps_per_round=8))
    rid_h = srv.admit(heavy)
    rid_l = srv.admit(light)
    got = {}
    got.update(srv.poll())                      # heavy cannot finish in 8
    assert rid_h not in got
    rid_l2 = srv.admit(_random_graph(9, 19, 0.1, 1))   # mid-flight admit
    for _ in range(400):
        got.update(srv.poll())
        if len(got) == 3:
            break
    assert set(got) == {rid_h, rid_l, rid_l2}
    assert srv.stats()["pending"] == 0 and srv.stats()["in_flight"] == 0
    assert got[rid_h].n_max == int(ed.enumerate_dense(heavy).n_max)
    assert got[rid_l].n_max == int(ed.enumerate_dense(light).n_max)
    # drain on an idle server is a no-op
    assert srv.drain() == {}


def test_pool_grows_for_burst_after_trickle():
    """A pool created for a single request must widen (migrating the live
    lane mid-DFS) when a burst of same-bucket graphs lands behind it,
    instead of serializing the backlog one lane at a time."""
    from repro.data.generators import dense_small
    heavy = dense_small(14, 28, p=0.55, seed=3, name="heavy")
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=8,
                                 steps_per_round=8))
    rid_h = srv.admit(heavy)
    srv.poll()                                   # creates a 1-lane pool
    burst = [_random_graph(10, 20, 0.1, s) for s in range(7)]
    rids = [srv.admit(g) for g in burst]
    got = srv.drain()
    batches = {k[1] for k in srv.cache._entries if k[0] != "install"}
    assert max(batches) == 8                     # pool widened for the burst
    assert got[rid_h].n_max == int(ed.enumerate_dense(heavy).n_max)
    for g, rid in zip(burst, rids):
        assert got[rid].n_max == int(ed.enumerate_dense(g).n_max)
        assert got[rid].cs == int(ed.enumerate_dense(g).cs)


def test_truncated_false_when_not_collecting():
    """truncated flags a short bicliques list; with collect=False there is
    no list, so it must stay False even when n_max exceeds the buffer."""
    g = dataset_suite("test")["corp-leadership"]
    srv = MBEServer(BucketPolicy(mode="pow2"), collect_cap=1, collect=False)
    r = srv.serve([g])[0]
    assert r.n_max > 1 and r.bicliques is None
    assert not r.truncated


def test_runaway_chunk_preserves_other_buckets_requests():
    """A batch blowing its step budget must NOT lose the other buckets'
    queued requests (the old flush() cleared the whole pending list up
    front, and the old cap contract raised mid-drain).  With typed
    ``step_capped`` results (PR-10) every request — runaway or not —
    gets a terminal result and the server drains clean."""
    runaway = _random_graph(4, 12, 0.5, 7)       # bucket (4, 16), runs first
    others = [_random_graph(12, 20, 0.3, s) for s in range(3)]  # (16, 32)
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=4,
                                 steps_per_round=4),
                    max_graph_steps=4)
    rid_r = srv.admit(runaway)
    rids_o = [srv.admit(g) for g in others]
    got = srv.drain()
    assert got[rid_r].status == "step_capped"
    assert got[rid_r].step_capped and got[rid_r].bicliques is None
    for rid in rids_o:                 # every request delivered, none lost
        assert rid in got
        assert got[rid].status in ("done", "step_capped")
    st_ = srv.stats()
    assert st_["step_capped"] == sum(
        1 for r in got.values() if r.status == "step_capped") >= 1
    assert st_["pending"] == 0 and st_["in_flight"] == 0


def test_strict_step_cap_restores_the_legacy_raise():
    """``strict_step_cap=True`` is the escape hatch for callers that want
    a blown step budget to be loud: evict the runaway, then raise."""
    runaway = _random_graph(4, 12, 0.5, 7)
    others = [_random_graph(12, 20, 0.3, s) for s in range(3)]
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=4,
                                 steps_per_round=4),
                    max_graph_steps=4, strict_step_cap=True)
    srv.submit(runaway)
    for g in others:
        srv.submit(g)
    with pytest.raises(RuntimeError, match="max_graph_steps"):
        srv.flush()
    st_ = srv.stats()
    assert st_["pending"] == len(others)         # unserved requests survive
    assert st_["in_flight"] == 0                 # the runaway lane evicted


def test_completed_results_survive_step_cap_eviction():
    """A lane finishing in the SAME round another lane blows the step cap
    must not lose its computed result: demux happens before the cap
    check, so the finisher's payload is delivered intact alongside the
    runaway's typed ``step_capped`` result."""
    from repro.data.generators import dense_small
    runaway = dense_small(14, 28, p=0.55, seed=3, name="runaway")
    light = _random_graph(9, 17, 0.08, 1)        # finishes within one round
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=2,
                                 steps_per_round=64),
                    max_graph_steps=64)
    rid_r = srv.admit(runaway)
    rid_l = srv.admit(light)
    got = srv.drain()
    assert srv.stats()["in_flight"] == 0         # runaway evicted
    assert got[rid_r].status == "step_capped"
    assert got[rid_r].steps >= 64                # partial counters kept
    assert got[rid_l].status == "done"
    assert got[rid_l].n_max == int(ed.enumerate_dense(light).n_max)


def test_truncated_flag_on_collect_overflow():
    """More maximal bicliques than collect_cap: the result must say so
    instead of quietly returning a short list."""
    g = dataset_suite("test")["corp-leadership"]
    n_true = int(ed.enumerate_dense(g).n_max)
    assert n_true > 1                            # engineered to overflow
    srv = MBEServer(BucketPolicy(mode="pow2"), collect_cap=1, collect=True)
    r = srv.serve([g])[0]
    assert r.truncated
    assert r.n_max == n_true                     # count is still exact
    assert len(r.bicliques) == 1                 # buffer-capped
    big = MBEServer(BucketPolicy(mode="pow2"), collect_cap=256,
                    collect=True)
    r2 = big.serve([g])[0]
    assert not r2.truncated
    assert len(r2.bicliques) == n_true


def test_submit_empty_graph_raises_value_error():
    """Unservable graphs raise ValueError (a bare assert vanishes under
    ``python -O``)."""
    srv = MBEServer()
    with pytest.raises(ValueError, match="not servable"):
        srv.submit(BipartiteGraph.from_edges(0, 0, []))
    assert srv.stats()["pending"] == 0


def test_latency_and_compile_accounting():
    """perf_counter latencies: compile time is reported separately, not
    folded into service latency; cached second-wave requests pay zero."""
    graphs = [_random_graph(10, 14, 0.3, s) for s in range(2)]
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=2))
    first = srv.serve(graphs)
    for r in first:
        assert r.compile_s > 0                   # first wave compiled
        assert r.service_s > 0
        assert r.queue_s >= 0
        assert abs(r.latency_s
                   - (r.queue_s + r.service_s + r.compile_s)) < 1e-9
    # same bucket, same lane count -> cache hit, zero compile charged
    second = srv.serve([_random_graph(10, 14, 0.3, s) for s in (9, 10)])
    for r in second:
        assert r.compile_s == 0.0
        assert r.service_s > 0
