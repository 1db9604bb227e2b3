"""The stats()/result-status schema contract (DESIGN.md §12).

``MBEServer.stats()`` is the operational surface dashboards and the
bench artifacts consume; this suite pins it as a CONTRACT: the key set
and value types are exactly ``serving.STATS_SCHEMA`` — across every
registered engine and all three serving routes (local-pool,
sharded-mesh, big-graph) — so a stats key can never silently appear,
vanish, or change type underneath a consumer.  Likewise the result
lifecycle: every terminal result's ``status`` is one of exactly
{done, cancelled, timed_out, rejected, failed, step_capped}, and the
server's counters add up to the delivered statuses (including the
admission ledger, the per-tenant split, and the fault-tolerance
counters of DESIGN.md §13).
"""
import pytest
from _graphs import random_graph

from repro.core.engine import get_engine, list_engines
from repro.data.generators import dense_small, random_unipartite
from repro.serving import (MONOTONIC_STATS, STATS_SCHEMA, BucketPolicy,
                           FaultPlan, MBEServer, RetryPolicy,
                           ShardedExecutor)
from repro.serving.slo import AdmissionPolicy
from repro.sharding.axes import mbe_serve_mesh

STATUSES = {"done", "cancelled", "timed_out", "rejected", "failed",
            "step_capped"}

#: the fault-tolerance counters PR-10 added to the contract
FAULT_COUNTERS = {"retries", "faults_injected", "checkpoints",
                  "quarantined", "failovers", "failed", "step_capped"}


def _graphs_for(engine_name: str, n: int = 3, big: bool = False):
    eng = get_engine(engine_name)
    if eng.unipartite:
        size = (lambda i: 18) if big else (lambda i: 8 + i)
        return [random_unipartite(size(i), 0.3, seed=10 + i,
                                  name=f"uni{i}")
                for i in range(n)]
    if big:
        return [dense_small(18, 30, p=0.4, seed=10 + i, name=f"big{i}")
                for i in range(n)]
    return [random_graph(6 + i, 12, 0.3, 10 + i, canonical=True)
            for i in range(n)]


def _assert_schema(stats: dict) -> None:
    assert set(stats) == set(STATS_SCHEMA), (
        f"stats keys drifted: extra={set(stats) - set(STATS_SCHEMA)}, "
        f"missing={set(STATS_SCHEMA) - set(stats)}")
    for key, typ in STATS_SCHEMA.items():
        assert isinstance(stats[key], typ), \
            f"stats[{key!r}] is {type(stats[key]).__name__}, " \
            f"schema says {typ}"


def test_monotonic_keys_are_schema_keys():
    assert MONOTONIC_STATS <= set(STATS_SCHEMA)


@pytest.mark.parametrize("engine", sorted(list_engines()))
@pytest.mark.parametrize("route", ["local-pool", "sharded-mesh",
                                   "big-graph"])
def test_stats_schema_every_engine_every_route(engine, route):
    """The full cross product: same key set, same types, regardless of
    workload engine or execution route."""
    kw = {}
    pol = dict(max_batch=2)
    if route == "sharded-mesh":
        kw["executor"] = ShardedExecutor(mbe_serve_mesh(1))
    if route == "big-graph":
        pol["big_graph_threshold"] = 16
    srv = MBEServer(BucketPolicy(**pol), engine=engine, **kw)
    _assert_schema(srv.stats())                    # idle server too
    big = route == "big-graph"
    rids = [srv.admit(g) for g in _graphs_for(engine, n=2, big=big)]
    got = srv.drain()
    stats = srv.stats()
    _assert_schema(stats)
    assert all(got[r].status == "done" for r in rids)
    if big:
        routes = [e["route"] for e in srv.routing_log
                  if e["event"] == "route"]
        assert "big" in routes, "stream never exercised the big route"
        assert stats["big_busy_per_worker"], \
            "big route served but the worker ledger is empty"


@pytest.mark.parametrize("engine", sorted(list_engines()))
def test_result_status_schema_and_counter_consistency(engine):
    """One server, all four terminal statuses, every engine: statuses
    come from the closed set, counters and the per-tenant ledger add up
    to the delivered results."""
    srv = MBEServer(BucketPolicy(max_batch=2), engine=engine,
                    admission=AdmissionPolicy(max_pending=3))
    gs = _graphs_for(engine, n=4)
    r_done = srv.admit(gs[0], tenant="t")
    r_dead = srv.admit(gs[1], deadline_s=0.0, tenant="t")
    r_cancel = srv.admit(gs[2], tenant="t")
    r_reject = srv.admit(gs[3], tenant="t")        # queue full: rejected
    assert srv.cancel(r_cancel)
    got = srv.drain()
    statuses = {rid: got[rid].status for rid in got}
    assert set(statuses.values()) == {"done", "cancelled", "timed_out",
                                      "rejected"}
    assert statuses[r_done] == "done"
    assert statuses[r_dead] == "timed_out"
    assert statuses[r_cancel] == "cancelled"
    assert statuses[r_reject] == "rejected"
    eng = get_engine(engine)
    for rid, res in got.items():
        assert isinstance(res, eng.result_type)
        assert res.status in STATUSES
        if res.status != "done":                   # flagged: no payload
            assert res.metric == 0
        if res.status == "rejected":
            assert res.reject_reason in ("backpressure", "fairness",
                                         "shed")
            assert res.steps == 0
    stats = srv.stats()
    _assert_schema(stats)
    assert stats["cancelled"] == 1
    assert stats["timed_out"] == 1
    assert stats["admitted"] == 3
    assert stats["rejected"] == stats["rejected_backpressure"] == 1
    assert stats["shed"] == 0 and stats["rejected_fairness"] == 0
    pt = stats["per_tenant"]["t"]
    assert pt == dict(admitted=3, rejected=1, completed=1, cancelled=1,
                      timed_out=1, failed=0, step_capped=0)


def test_fault_counters_are_contract_keys():
    """PR-10's fault-tolerance counters are part of the schema, counted
    as monotonic (so ``reset_stats`` zeros them), and read 0 on a server
    with no recovery machinery attached."""
    assert FAULT_COUNTERS <= set(STATS_SCHEMA)
    assert FAULT_COUNTERS <= MONOTONIC_STATS
    srv = MBEServer(BucketPolicy(max_batch=2))
    srv.admit(random_graph(6, 12, 0.3, 1, canonical=True))
    srv.drain()
    stats = srv.stats()
    for key in FAULT_COUNTERS:
        assert stats[key] == 0, f"{key} nonzero with recovery disabled"


def test_fault_counters_move_and_reset_under_chaos():
    """Under an injector + retry policy the fault counters move, the
    delivered statuses stay in the closed set, and ``reset_stats``
    rebaselines ``faults_injected`` (the injector's own count keeps
    growing; the stat is per measured phase)."""
    def chaos_server():
        return MBEServer(
            BucketPolicy(max_batch=2, steps_per_round=16),
            retry=RetryPolicy(max_attempts=4, backoff_s=1e-5,
                              checkpoint_interval=2),
            fault_injector=FaultPlan(seed=2, launch_rate=0.25))

    srv = chaos_server()
    gs = [random_graph(6 + i, 12, 0.3, 20 + i, canonical=True)
          for i in range(3)]
    for g in gs:
        srv.admit(g)
    got = srv.drain()
    assert all(r.status in STATUSES for r in got.values())
    stats = srv.stats()
    _assert_schema(stats)
    assert stats["faults_injected"] > 0
    assert stats["retries"] > 0
    assert stats["checkpoints"] > 0
    srv.reset_stats()
    after = srv.stats()
    for key in FAULT_COUNTERS:
        assert after[key] == 0, f"monotonic {key} survived reset"

    # chaos determinism: an identical second run injects the identical
    # fault sequence and delivers identical payloads
    srv2 = chaos_server()
    [srv2.admit(g) for g in gs]
    got2 = srv2.drain()
    srv3 = chaos_server()
    [srv3.admit(g) for g in gs]
    got3 = srv3.drain()
    assert sorted(got2) == sorted(got3)
    for rid in got2:
        assert got2[rid].status == got3[rid].status
        assert got2[rid].metric == got3[rid].metric
        assert got2[rid].steps == got3[rid].steps
    assert srv2._injectors[0].log == srv3._injectors[0].log
    s2, s3 = srv2.stats(), srv3.stats()
    for key in ("faults_injected", "retries", "quarantined", "failovers",
                "failed", "step_capped"):
        assert s2[key] == s3[key], key


def test_reset_stats_covers_exactly_the_monotonic_keys():
    """After ``reset_stats`` every MONOTONIC key reads zero (empty for
    containers); gauges and configuration echoes keep their values."""
    srv = MBEServer(BucketPolicy(max_batch=2),
                    admission=AdmissionPolicy(max_pending=64))
    srv.admit(random_graph(6, 12, 0.3, 1, canonical=True))
    srv.drain()
    before = srv.stats()
    assert before["batches"] > 0 and before["admitted"] == 1
    srv.reset_stats()
    after = srv.stats()
    _assert_schema(after)
    for key in MONOTONIC_STATS:
        assert after[key] == 0, f"monotonic {key} survived reset"
    # derived-from-monotonic ratios read zero too
    assert after["occupancy"] == 0.0
    assert after["steps_per_poll"] == 0.0
    assert after["per_tenant"] == {}
    # gauges/echoes survive
    assert after["entries"] == before["entries"]
    assert after["engine"] == before["engine"]
    assert after["kernel_impl"] == before["kernel_impl"]


#: the lane-placement counters: refills that took a pool's install
#: executable, and lanes placed by row surgery instead
PLACEMENT_COUNTERS = {"installs", "install_fallbacks"}


def test_placement_counters_are_contract_keys():
    assert PLACEMENT_COUNTERS <= set(STATS_SCHEMA)
    assert PLACEMENT_COUNTERS <= MONOTONIC_STATS
    for key in PLACEMENT_COUNTERS:
        assert STATS_SCHEMA[key] is int


def test_local_pools_place_every_lane_through_the_install_executable():
    """On a local executor each refill that places lanes is one install
    call and no lane takes row surgery; warm refills compile nothing."""
    srv = MBEServer(BucketPolicy(max_batch=4, steps_per_round=8))
    gs = [random_graph(6 + i % 2, 12, 0.3, 30 + i, canonical=True)
          for i in range(9)]
    for g in gs[:4]:
        srv.admit(g)
    srv.drain()
    first = srv.stats()
    assert first["install_fallbacks"] == 0
    assert 1 <= first["installs"] <= first["lanes"]
    srv.reset_stats()
    for g in gs[4:]:
        srv.admit(g)
    srv.drain()
    s = srv.stats()
    assert s["install_fallbacks"] == 0
    assert 1 <= s["installs"] <= s["lanes"] == 5
    srv.reset_stats()
    assert srv.stats()["installs"] == 0


def test_sharded_pools_place_lanes_by_row_surgery():
    """A pool whose lane axis is sharded over a mesh keeps the
    shard-by-shard row surgery: every placed lane is a fallback."""
    srv = MBEServer(BucketPolicy(max_batch=2),
                    executor=ShardedExecutor(mbe_serve_mesh(1)))
    for g in _graphs_for("dense", n=3):
        srv.admit(g)
    got = srv.drain()
    assert all(r.status == "done" for r in got.values())
    s = srv.stats()
    assert s["installs"] == 0
    assert s["install_fallbacks"] == s["lanes"] == 3


def test_failover_resume_places_checkpointed_lanes_by_row_surgery():
    """Lanes resumed from a host checkpoint after a failover carry a whole
    state, so they take row surgery; fresh lanes still take the install
    executable."""
    srv = MBEServer(
        BucketPolicy(max_batch=2, steps_per_round=4),
        retry=RetryPolicy(max_attempts=3, backoff_s=1e-5,
                          checkpoint_interval=1),
        fault_injector=FaultPlan(seed=1, device_lost_after=3))
    gs = [random_graph(8 + i, 14, 0.35, 60 + i, canonical=True)
          for i in range(4)]
    for g in gs:
        srv.admit(g)
    got = srv.drain()
    assert all(r.status == "done" for r in got.values())
    s = srv.stats()
    assert s["failovers"] == 1
    assert s["install_fallbacks"] >= 1
    assert s["installs"] >= 1
    assert s["lanes"] >= len(gs) + s["install_fallbacks"]


#: the kernel work counters (``Engine.work_rows``): adjacency words the
#: compact engine's gathered select and check passes had to read
WORK_COUNTERS = {"gathered_select_words", "gathered_check_words"}


def test_work_counters_are_contract_keys():
    """In the schema as ints, monotonic, moved by a compact stream and
    zeroed by ``reset_stats``."""
    assert WORK_COUNTERS <= set(STATS_SCHEMA)
    assert WORK_COUNTERS <= MONOTONIC_STATS
    for key in WORK_COUNTERS:
        assert STATS_SCHEMA[key] is int
    srv = MBEServer(BucketPolicy(max_batch=2, steps_per_round=8),
                    engine="compact")
    for g in _graphs_for("compact", n=2):
        srv.admit(g)
    srv.drain()
    stats = srv.stats()
    _assert_schema(stats)
    for key in WORK_COUNTERS:
        assert stats[key] > 0
    srv.reset_stats()
    for key in WORK_COUNTERS:
        assert srv.stats()[key] == 0
