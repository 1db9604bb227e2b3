"""The compact engine's kernel work counters (``Engine.work_rows``):
``CompactState.sel_rows``/``chk_rows`` count, on the device, the adjacency
rows each candidate step's select and check passes had to read, and the
serving layer reports them as ``stats()['gathered_select_words']`` /
``['gathered_check_words']`` (rows x the pool's ``cfg.wv``).

* A plain recount: stepping ``engine_compact.step`` in a Python loop and
  summing the level pointers at each candidate step gives the served
  totals.
* The totals are of the work, not of the route: the same graphs give the
  same words on the jnp and Pallas paths, in pools of any width, across
  refills and widening, on a sharded pool and on the big-graph route.
* Engines that count no such work report 0 and carry nothing new.
* A request served in a padded bucket takes the steps it takes at its
  own shape: the root's P region holds the graph's own vertices only.
"""
import numpy as np
import pytest
from _graphs import random_graph

import jax

from repro.baselines import enumerate_mbea
from repro.core import engine_compact as ec
from repro.core.engine import get_engine
from repro.serving import BucketPolicy, MBEServer, ShardedExecutor
from repro.serving.buckets import plan_bucket
from repro.serving.executor import LocalExecutor
from repro.sharding.axes import mbe_serve_mesh

GRAPHS = [random_graph(6 + i, 12 + 2 * i, 0.35, 40 + i, canonical=True)
          for i in range(5)]


def recount(g, cfg) -> tuple[int, int]:
    """Rows the select and check passes of every candidate step read,
    summed on the host from the state before each step: the select pass
    reads the ``p`` rows of the live P region (none when the root forces
    the candidate), the check pass the ``q_ptr`` rows of Q plus the
    ``p_work`` rows left in P, and each pass the mask row."""
    ctx = ec.make_context(g, cfg)
    s = ec.init_state(cfg, np.arange(g.n_u, dtype=np.int32))
    step = jax.jit(lambda st: ec.step(ctx, cfg, st))
    sel = chk = 0
    while not bool(ec._done(s)):
        lvl = int(s.lvl)
        if lvl >= 0:
            p, forced = int(s.p_ptr[lvl]), int(s.forced_x) >= 0
            if p > 0 or forced:                     # a candidate step
                sel += 0 if forced else p + 1
                chk += int(s.q_ptr[lvl]) + (p if forced else p - 1) + 1
        s = step(s)
    return sel, chk


def _served(srv: MBEServer, graphs, trickle: bool = False) -> dict:
    rids = []
    for i, g in enumerate(graphs):
        rids.append(srv.admit(g))
        if trickle and i == 0:
            srv.poll()                  # a narrow pool, widened later
    got = srv.drain()
    assert all(got[r].status == "done" for r in rids)
    return srv.stats()


def test_served_words_equal_the_recount():
    srv = MBEServer(BucketPolicy(max_batch=2, steps_per_round=8),
                    engine="compact", kernel_impl="jnp")
    sel = chk = 0
    for g in GRAPHS:
        cfg = srv._engine_config(plan_bucket(g, srv.policy))
        s, c = recount(g, cfg)
        sel, chk = sel + s * cfg.wv, chk + c * cfg.wv
    stats = _served(srv, GRAPHS)
    assert sel > 0 and chk > sel
    assert (stats["gathered_select_words"],
            stats["gathered_check_words"]) == (sel, chk)


def _reference_words() -> tuple[int, int]:
    stats = _served(MBEServer(BucketPolicy(max_batch=2), engine="compact",
                              kernel_impl="jnp"), GRAPHS)
    return stats["gathered_select_words"], stats["gathered_check_words"]


ROUTES = {
    "one-lane-refills": dict(policy=dict(max_batch=1, steps_per_round=8)),
    "four-lanes-refills": dict(policy=dict(max_batch=4, steps_per_round=8)),
    "widening": dict(policy=dict(max_batch=4, steps_per_round=4),
                     trickle=True),
    "pallas": dict(policy=dict(max_batch=2, steps_per_round=16,
                               steps_per_call=4), kernel_impl="pallas"),
    "sharded": dict(policy=dict(max_batch=2, steps_per_round=8),
                    executor=lambda: ShardedExecutor(mbe_serve_mesh(1))),
    "big-graph-2-workers": dict(
        policy=dict(max_batch=2, steps_per_round=8, big_graph_threshold=1),
        executor=lambda: LocalExecutor(big_workers=2)),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_words_same_on_every_route(route):
    r = ROUTES[route]
    kw = dict(engine="compact", kernel_impl=r.get("kernel_impl", "jnp"))
    if "executor" in r:
        kw["executor"] = r["executor"]()
    srv = MBEServer(BucketPolicy(**r["policy"]), **kw)
    stats = _served(srv, GRAPHS, trickle=r.get("trickle", False))
    if route == "big-graph-2-workers":
        assert len(stats["big_busy_per_worker"]) == 2
    if route == "widening":
        assert any(e["event"] == "pool-grow" for e in srv.routing_log)
    assert (stats["gathered_select_words"],
            stats["gathered_check_words"]) == _reference_words()


@pytest.mark.parametrize("engine", ["dense", "count", "mce"])
def test_other_engines_count_nothing(engine):
    """Engines without the counters report 0, carry no counter in their
    state, and compile one round and one install executable per pool
    shape, as before."""
    from test_stats_contract import _graphs_for
    eng = get_engine(engine)
    srv = MBEServer(BucketPolicy(max_batch=2, steps_per_round=8),
                    engine=engine)
    stats = _served(srv, _graphs_for(engine, n=3))
    assert stats["gathered_select_words"] == 0
    assert stats["gathered_check_words"] == 0
    cfg = srv._engine_config(plan_bucket(_graphs_for(engine, n=1)[0],
                                         srv.policy))
    state = eng.fresh_lane_state(cfg, 1)
    assert eng.work_rows(state) is None
    assert not {"sel_rows", "chk_rows"} & set(state._fields)
    rounds = [k for k in srv.cache._entries if k[0] != "install"]
    installs = [k for k in srv.cache._entries if k[0] == "install"]
    assert stats["misses"] == len(rounds) + len(installs)
    assert len(installs) == len(rounds)


def test_padded_bucket_adds_no_steps():
    """Served in a pow2 bucket wider than the graph, a request takes the
    steps and nodes of the exact-shape run and gives the oracle's count:
    padding rows, x itself and the roots already in Q stay out of the
    root's P region."""
    srv = MBEServer(BucketPolicy(max_batch=2, steps_per_round=8),
                    engine="compact", kernel_impl="jnp")
    rids = [srv.admit(g) for g in GRAPHS]
    got = srv.drain()
    assert sum(srv._engine_config(plan_bucket(g, srv.policy)).n_u > g.n_u
               for g in GRAPHS) >= 3
    for g, rid in zip(GRAPHS, rids):
        exact = ec.enumerate_compact(g)
        res = got[rid]
        assert (res.steps, res.nodes) == (int(exact.steps),
                                          int(exact.nodes))
        assert (res.n_max, res.cs) == (int(exact.n_max), int(exact.cs))
        assert res.n_max == enumerate_mbea(g, collect=False)
