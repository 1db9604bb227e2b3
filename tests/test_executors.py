"""Pluggable execution backends: Executor interface, ShardedExecutor,
big-graph work-stealing lane, and routing.

Single-device checks run inline; the real multi-device placement runs in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (so the
forced device count doesn't leak into the rest of the session), asserting
``ShardedExecutor`` + big-graph lane results are byte-identical to
``LocalExecutor`` and to per-graph runs, with the heavy graph's root tasks
demonstrably spread across >= 2 workers.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
from _graphs import random_graph

from repro.baselines import bicliques_to_key_set
from repro.core import engine_dense as ed
from repro.data.generators import dense_small, random_graph_stream
from repro.serving import (BucketPolicy, LocalExecutor, MBEServer,
                           ShardedExecutor, plan_route)
from repro.sharding.axes import MBE_LANE_AXIS, mbe_serve_mesh


# ---------------------------------------------------------------------------
# routing policy
# ---------------------------------------------------------------------------

def test_plan_route_thresholds():
    pol = BucketPolicy(big_graph_threshold=12)
    small = random_graph(8, 20, 0.3, 0, canonical=True)
    big = random_graph(14, 30, 0.3, 1, canonical=True)
    edge = random_graph(12, 30, 0.3, 2, canonical=True)
    assert plan_route(small, pol) == "lane"
    assert plan_route(big, pol) == "big"
    assert plan_route(edge, pol) == "big"        # threshold is inclusive
    nothr = BucketPolicy()                       # default: routing disabled
    assert plan_route(big, nothr) == "lane"


def test_routing_log_records_decisions():
    """Every admit leaves a routing entry (which route, why) and every pool
    creation records its lane count and placement — the operator-visible
    trail ``launch/serve.py`` prints."""
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=4,
                                 big_graph_threshold=14))
    heavy = dense_small(16, 32, p=0.5, seed=3, name="heavy")
    light = random_graph(8, 20, 0.25, 0, canonical=True)
    srv.serve([light, heavy])
    routes = [e for e in srv.routing_log if e["event"] == "route"]
    assert [e["route"] for e in routes] == ["lane", "big"]
    assert "big_graph_threshold=14" in routes[1]["reason"]
    pools = [e for e in srv.routing_log if e["event"] == "pool"]
    assert pools and all("placement" in e and e["lanes"] >= 1
                         for e in pools)
    bigs = [e for e in srv.routing_log if e["event"] == "big-lane"]
    assert len(bigs) == 1 and "stealing workers" in bigs[0]["placement"]


# ---------------------------------------------------------------------------
# LocalExecutor: the interface wraps the original path unchanged
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_explicit_local_executor_identical_to_default():
    graphs = random_graph_stream(8, seed=5)
    pol = BucketPolicy(mode="pow2", max_batch=4, steps_per_round=16)
    a = MBEServer(pol).serve(graphs)
    b = MBEServer(pol, executor=LocalExecutor()).serve(graphs)
    for ra, rb in zip(a, b):
        assert (ra.n_max, ra.cs, ra.nodes, ra.steps) == \
            (rb.n_max, rb.cs, rb.nodes, rb.steps)


def test_local_big_lane_work_stealing_on_one_device():
    """Big-graph routing is meaningful without a mesh: LocalExecutor runs
    the routed graph as vmap'd stealing workers on one device, result-
    identical to the plain enumeration, with >= 2 workers doing work."""
    heavy = dense_small(18, 36, p=0.5, seed=7, name="heavy")
    ref = ed.enumerate_dense(heavy, collect_cap=2048)
    assert int(ref.n_max) <= 2048               # reference must not truncate
    cfgref = ed.make_config(heavy, collect_cap=2048)
    ref_set = bicliques_to_key_set(
        ed.collected_bicliques(cfgref, ref, heavy.n_u, heavy.n_v))
    srv = MBEServer(BucketPolicy(mode="pow2", steps_per_round=32,
                                 big_graph_threshold=16),
                    collect_cap=2048, collect=True,
                    executor=LocalExecutor(big_workers=4))
    r = srv.serve([heavy])[0]
    assert (r.n_max, r.cs) == (int(ref.n_max), int(ref.cs))
    assert bicliques_to_key_set(r.bicliques) == ref_set
    assert not r.truncated
    busy = np.array(srv.stats()["big_busy_per_worker"])
    assert busy.shape == (4,)
    assert int((busy > 0).sum()) >= 2            # tasks genuinely spread
    assert srv.stats()["in_flight"] == 0 and srv.stats()["pending"] == 0


def test_big_lane_respects_step_cap():
    """A runaway routed-big graph completes with the same typed
    ``step_capped`` result as lane-pool requests; the server stays
    serviceable and other requests are unaffected."""
    heavy = dense_small(16, 32, p=0.55, seed=3, name="runaway")
    light = random_graph(8, 20, 0.2, 0, canonical=True)
    assert int(ed.enumerate_dense(light).steps) < 256    # light fits the cap
    srv = MBEServer(BucketPolicy(mode="pow2", steps_per_round=64,
                                 big_graph_threshold=14),
                    max_graph_steps=256,
                    executor=LocalExecutor(big_workers=2))
    rid_h = srv.admit(heavy)
    rid_l = srv.admit(light)
    got = srv.drain()
    assert srv.stats()["in_flight"] == 0         # big lane evicted
    assert got[rid_h].status == "step_capped"
    assert got[rid_h].bicliques is None
    assert rid_l in got                          # light request still served
    assert got[rid_l].n_max == int(ed.enumerate_dense(light).n_max)


def test_big_lane_strict_step_cap_raises():
    """``strict_step_cap=True`` keeps the legacy evict-then-raise contract
    on the big-graph route too."""
    heavy = dense_small(16, 32, p=0.55, seed=3, name="runaway")
    srv = MBEServer(BucketPolicy(mode="pow2", steps_per_round=64,
                                 big_graph_threshold=14),
                    max_graph_steps=256, strict_step_cap=True,
                    executor=LocalExecutor(big_workers=2))
    srv.admit(heavy)
    with pytest.raises(RuntimeError, match="max_graph_steps"):
        srv.drain()
    assert srv.stats()["in_flight"] == 0         # big lane evicted


# ---------------------------------------------------------------------------
# ShardedExecutor on a 1-device mesh (placement degenerate, semantics full)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_executor_single_device_mesh_identity():
    graphs = random_graph_stream(10, seed=2)
    pol = BucketPolicy(mode="pow2", max_batch=4, steps_per_round=24)
    ref = MBEServer(pol, collect_cap=64, collect=True).serve(graphs)
    srv = MBEServer(pol, collect_cap=64, collect=True,
                    executor=ShardedExecutor(mbe_serve_mesh(1)))
    got = srv.serve(graphs)
    for a, b in zip(ref, got):
        assert (a.n_max, a.cs) == (b.n_max, b.cs)
        assert bicliques_to_key_set(a.bicliques) == \
            bicliques_to_key_set(b.bicliques)
    st = srv.stats()
    assert st["executor"] == "sharded"
    assert st["pending"] == 0 and st["in_flight"] == 0
    # backend-qualified keys: sharded entries never collide with local ones
    for (head, _batch, _budget) in srv.cache._entries:
        assert isinstance(head, tuple) and head[0] in ("sharded", "ws")


def test_sharded_executor_rejects_missing_axis():
    with pytest.raises(ValueError, match="no axis"):
        ShardedExecutor(mbe_serve_mesh(1), axis="nonexistent")
    assert MBE_LANE_AXIS in mbe_serve_mesh(1).axis_names


# ---------------------------------------------------------------------------
# the real thing: 8 forced host devices, subprocess-isolated
# ---------------------------------------------------------------------------

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from repro.baselines import bicliques_to_key_set
from repro.core import engine_dense as ed
from repro.core import distributed as dd
from repro.data.generators import dense_small, random_bipartite
from repro.serving import BucketPolicy, MBEServer, LocalExecutor, ShardedExecutor
from repro.sharding.axes import mbe_serve_mesh

assert jax.device_count() == 8
mesh = mbe_serve_mesh(8)

# -- telemetry form of the round fn: busy/pending per worker --------------
g = dense_small(16, 32, p=0.4, seed=11, name="telem")
cfg = ed.make_config(g)
dist = dd.DistConfig(steps_per_round=16, workers_per_device=1)
fn, n_workers, T = dd.make_round_fn(cfg, mesh, ("mbe_lanes",), dist,
                                    with_telemetry=True)
ctx = ed.make_context(g, cfg)
per = []
for w in range(n_workers):
    tasks = np.arange(w, g.n_u, n_workers, dtype=np.int32)
    s = ed.init_state(cfg, tasks)
    pad = np.full(T, -1, np.int32); pad[:len(tasks)] = tasks
    per.append(s._replace(tasks=jax.numpy.asarray(pad)))
state = jax.tree.map(lambda *xs: jax.numpy.stack(xs), *per)
state, telem = fn(ctx, state)
busy = np.asarray(telem["busy_steps"]); pend = np.asarray(telem["pending"])
assert busy.shape == (n_workers,) and pend.shape == (n_workers,)
assert (busy > 0).all() and (busy <= dist.steps_per_round).all()
assert np.array_equal(busy, np.asarray(state.steps))   # first round: steps==busy
assert np.array_equal(pend, np.asarray(state.n_tasks) - np.asarray(state.tpos))

# -- mixed stream: 1 heavy routed-big + 17 small, sharded vs local --------
heavy = dense_small(18, 36, p=0.5, seed=7, name="heavy")
rng = np.random.default_rng(0)
smalls = [random_bipartite(int(rng.integers(6, 14)),
                           int(rng.integers(16, 30)), p=0.2,
                           seed=1000 + i, name=f"small{i}")
          for i in range(17)]
assert all(gg.n_u < 16 for gg in smalls)       # all below the threshold
stream = [heavy] + smalls
pol = BucketPolicy(mode="pow2", max_batch=8, steps_per_round=32,
                   big_graph_threshold=16)
CAP = 4096
refs = []
for gg in stream:
    out = ed.enumerate_dense(gg, collect_cap=CAP)
    assert int(out.n_max) <= CAP, gg.name       # reference must not truncate
    c = ed.make_config(gg, collect_cap=CAP)
    refs.append((int(out.n_max), int(out.cs), bicliques_to_key_set(
        ed.collected_bicliques(c, out, gg.n_u, gg.n_v))))

local = MBEServer(pol, collect_cap=CAP, collect=True,
                  executor=LocalExecutor(big_workers=8))
rl = local.serve(stream)
shard = MBEServer(pol, collect_cap=CAP, collect=True,
                  executor=ShardedExecutor(mesh))
rs = shard.serve(stream)
for gg, a, b, (rn, rcs, rset) in zip(stream, rl, rs, refs):
    assert (a.n_max, a.cs) == (rn, rcs), ("local", gg.name)
    assert (b.n_max, b.cs) == (rn, rcs), ("sharded", gg.name)
    assert bicliques_to_key_set(a.bicliques) == rset, ("local", gg.name)
    assert bicliques_to_key_set(b.bicliques) == rset, ("sharded", gg.name)

busy = np.array(shard.stats()["big_busy_per_worker"])
assert busy.shape == (8,), busy
assert int((busy > 0).sum()) >= 2, f"heavy graph not spread: {busy}"
routes = [e for e in shard.routing_log if e["event"] == "route"]
assert [e["route"] for e in routes].count("big") == 1
big = [e for e in shard.routing_log if e["event"] == "big-lane"][0]
assert "8 device(s)" in big["placement"], big
pools = [e for e in shard.routing_log if e["event"] == "pool"]
assert all(e["lanes"] % 8 == 0 for e in pools), pools  # divisible placement
print("EXECUTORS-8DEV-OK")
"""


@pytest.mark.slow
def test_sharded_executor_and_big_lane_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "EXECUTORS-8DEV-OK" in r.stdout
