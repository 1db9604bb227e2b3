"""Fault-injection + recovery subsystem (DESIGN.md §13).

The contract under test, end to end:

* chaos with transient faults + retry delivers results BYTE-IDENTICAL
  to the fault-free run (the functional-launch invariant: a raised
  launch committed nothing, so the retry recomputes nothing),
* the injector's fault schedule is deterministic per seed (two
  identical runs inject the identical sequence),
* a persistent device loss fails over to a fresh executor and resumes
  from host-side checkpoints — zero lost requests, identical payloads,
* a poisoned request is isolated by quarantine bisection and completes
  as a typed ``failed`` result; innocents are unaffected,
* everything is OFF by default: no plan + no policy = byte-identical
  serving and an all-zero fault ledger.
"""
import time

import numpy as np
import pytest
from _graphs import random_graph

import jax
from repro.serving import (BucketPolicy, DeviceLostError, ExecutableCache,
                           FaultInjector, FaultPlan, LocalExecutor,
                           MBEServer, RetryPolicy, ShardedExecutor,
                           TransientLaunchError, verified_read)
from repro.sharding.axes import mbe_serve_mesh

ENGINES = ("dense", "compact", "count", "mce")


def _graphs(engine, n=4):
    if engine == "mce":
        from repro.data.generators import random_unipartite
        return [random_unipartite(8 + i, 0.3, seed=40 + i, name=f"uni{i}")
                for i in range(n)]
    return [random_graph(5 + i, 10 + i, 0.35, 40 + i, canonical=True)
            for i in range(n)]


def _payload(res):
    """The full comparable payload of one result."""
    return (res.status, res.metric, res.steps, res.nodes)


def _serve(graphs, *, executor=None, retry=None, plan=None, engine="dense",
           **kw):
    srv = MBEServer(BucketPolicy(max_batch=2, steps_per_round=16),
                    engine=engine, retry=retry, fault_injector=plan,
                    **({"executor": executor} if executor else {}), **kw)
    rids = [srv.admit(g) for g in graphs]
    got = srv.drain()
    return srv, {r: got[r] for r in rids}


# ---------------------------------------------------------------------------
# determinism + transient-fault byte-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
def test_transient_faults_are_byte_identical(engine):
    """≥20% launch faults + retry: every payload identical to the
    fault-free arm, across every registered engine."""
    gs = _graphs(engine)
    _, base = _serve(gs, engine=engine)
    srv, chaos = _serve(gs, engine=engine,
                        retry=RetryPolicy(max_attempts=5, backoff_s=1e-5),
                        plan=FaultPlan(seed=2, launch_rate=0.25))
    assert {r: _payload(v) for r, v in base.items()} \
        == {r: _payload(v) for r, v in chaos.items()}
    s = srv.stats()
    assert s["faults_injected"] > 0 and s["retries"] > 0
    assert s["failed"] == 0 and s["quarantined"] == 0


def test_fault_schedule_is_deterministic():
    """Same seed, same stream → identical injected-fault log, retry
    count and payloads; different seed → different schedule."""
    gs = _graphs("dense")
    runs = []
    for _ in range(2):
        srv, got = _serve(gs, retry=RetryPolicy(max_attempts=5,
                                                backoff_s=1e-5),
                          plan=FaultPlan(seed=7, launch_rate=0.25))
        runs.append((srv._injectors[0].log, srv.stats()["retries"],
                     {r: _payload(v) for r, v in got.items()}))
    assert runs[0] == runs[1]
    srv3, _ = _serve(gs, retry=RetryPolicy(max_attempts=5, backoff_s=1e-5),
                     plan=FaultPlan(seed=8, launch_rate=0.25))
    assert srv3._injectors[0].log != runs[0][0]


def test_corrupted_done_mask_reads_are_recovered():
    """Transient scoreboard corruption: verified reads keep demux honest
    and the payloads identical to the clean run."""
    gs = _graphs("dense")
    _, base = _serve(gs)
    srv, chaos = _serve(gs, retry=RetryPolicy(max_attempts=3,
                                              backoff_s=1e-5),
                        plan=FaultPlan(seed=2, corrupt_done_rate=0.15))
    assert {r: _payload(v) for r, v in base.items()} \
        == {r: _payload(v) for r, v in chaos.items()}
    assert srv.stats()["faults_injected"] > 0
    assert srv.stats()["retries"] == 0      # reads re-read, never retried


def test_compile_faults_retry_without_poisoning_the_cache():
    """Injected compile failures are retried; the executable cache never
    keeps a failed entry and ``misses`` counts only successful
    compiles (== the clean run's count)."""
    gs = _graphs("dense")
    srv0, base = _serve(gs)
    srv, chaos = _serve(gs, retry=RetryPolicy(max_attempts=5,
                                              backoff_s=1e-5),
                        plan=FaultPlan(seed=3, compile_rate=0.3))
    assert {r: _payload(v) for r, v in base.items()} \
        == {r: _payload(v) for r, v in chaos.items()}
    assert srv.stats()["misses"] == srv0.stats()["misses"]
    assert srv.stats()["entries"] == srv0.stats()["entries"]


# ---------------------------------------------------------------------------
# device-lost failover
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make_exec", [
    pytest.param(lambda: None, id="local"),
    pytest.param(lambda: ShardedExecutor(mbe_serve_mesh(1)), id="sharded"),
])
def test_device_lost_fails_over_with_identical_payloads(make_exec):
    """A persistent device loss mid-stream: the server swaps executors
    once, resumes from checkpoints, and delivers every payload
    identically to the fault-free arm — zero lost requests."""
    gs = _graphs("dense")
    _, base = _serve(gs, executor=make_exec())
    srv, chaos = _serve(gs, executor=make_exec(),
                        retry=RetryPolicy(max_attempts=3, backoff_s=1e-5,
                                          checkpoint_interval=2),
                        plan=FaultPlan(seed=1, device_lost_after=4))
    assert {r: _payload(v) for r, v in base.items()} \
        == {r: _payload(v) for r, v in chaos.items()}
    s = srv.stats()
    assert s["failovers"] == 1
    assert s["checkpoints"] > 0
    assert isinstance(srv.executor, FaultInjector)
    assert isinstance(srv.executor.inner, LocalExecutor)
    fo = [e for e in srv.routing_log if e["event"] == "failover"]
    assert len(fo) == 1 and "device-lost" in fo[0]["reason"]


def test_device_lost_without_retry_policy_raises():
    """No retry policy = no recovery machinery: the injected device loss
    propagates to the caller exactly like any launch error."""
    gs = _graphs("dense")
    with pytest.raises(DeviceLostError):
        _serve(gs, plan=FaultPlan(seed=1, device_lost_after=1))


def test_failover_can_target_an_explicit_executor():
    """``failover_executor`` names the degraded-mode target; the swap is
    recorded in stats and the stream still completes."""
    gs = _graphs("dense")
    _, base = _serve(gs)
    srv, chaos = _serve(
        gs, retry=RetryPolicy(max_attempts=3, backoff_s=1e-5,
                              checkpoint_interval=1),
        plan=FaultPlan(seed=2, device_lost_after=3),
        failover_executor=LocalExecutor(big_workers=2))
    assert {r: _payload(v) for r, v in base.items()} \
        == {r: _payload(v) for r, v in chaos.items()}
    assert srv.stats()["failovers"] == 1
    assert srv.executor.inner.big_workers == 2


# ---------------------------------------------------------------------------
# poison quarantine
# ---------------------------------------------------------------------------

def test_poison_quarantine_isolates_exactly_the_culprit():
    """A request that deterministically kills every round it is resident
    in: bisection isolates it, it completes as ``failed`` with a
    ``fail_reason``, and every innocent payload matches the clean run."""
    gs = _graphs("dense", n=4)
    _, base = _serve(gs)
    srv, chaos = _serve(gs,
                        retry=RetryPolicy(max_attempts=2, backoff_s=1e-5),
                        plan=FaultPlan(seed=1, poison_nth_install=2))
    failed = {r: v for r, v in chaos.items() if v.status == "failed"}
    assert len(failed) == 1
    (rid, res), = failed.items()
    assert "quarantine" in res.fail_reason
    assert res.metric == 0 and res.bicliques is None
    for r, v in chaos.items():
        if r != rid:
            assert _payload(v) == _payload(base[r])
    s = srv.stats()
    assert s["quarantined"] == 1 and s["failed"] == 1
    assert s["failovers"] == 0
    q = [e for e in srv.routing_log if e["event"] == "quarantine"]
    assert q, "quarantine left no routing_log record"


def test_transient_streak_exonerates_all_suspects():
    """max_attempts=1 makes every transient fault look like poison; the
    quarantine's final confirm probe (fresh restart, no fault) must
    exonerate the suspects instead of failing an innocent request."""
    gs = _graphs("dense", n=2)
    _, base = _serve(gs)
    srv, chaos = _serve(gs,
                        retry=RetryPolicy(max_attempts=1, backoff_s=1e-5),
                        plan=FaultPlan(seed=5, launch_rate=0.15))
    assert srv.stats()["failed"] == 0
    assert {r: _payload(v) for r, v in base.items()} \
        == {r: _payload(v) for r, v in chaos.items()}


# ---------------------------------------------------------------------------
# disabled-path byte-identity
# ---------------------------------------------------------------------------

def test_off_by_default_is_byte_identical():
    """No plan, no policy: stats() and payloads identical across two
    fresh servers, and the whole fault ledger reads zero.  The host time
    counters (``poll_s`` etc.) are wall clock, so they alone may differ."""
    gs = _graphs("dense")
    srv1, got1 = _serve(gs)
    srv2, got2 = _serve(gs)
    clock = {"poll_s", "refill_s", "demux_s", "exec_s"}

    def counted(srv):
        return {k: v for k, v in srv.stats().items() if k not in clock}
    assert counted(srv1) == counted(srv2)
    assert {r: _payload(v) for r, v in got1.items()} \
        == {r: _payload(v) for r, v in got2.items()}
    for key in ("retries", "faults_injected", "checkpoints",
                "quarantined", "failovers", "failed", "step_capped"):
        assert srv1.stats()[key] == 0


def test_retry_policy_alone_changes_nothing():
    """A retry policy with no injector and no faults: payloads identical
    to the bare server (checkpointing runs but never restores)."""
    gs = _graphs("dense")
    _, base = _serve(gs)
    srv, got = _serve(gs, retry=RetryPolicy(max_attempts=3,
                                            checkpoint_interval=2))
    assert {r: _payload(v) for r, v in base.items()} \
        == {r: _payload(v) for r, v in got.items()}
    assert srv.stats()["retries"] == 0
    assert srv.stats()["checkpoints"] > 0


# ---------------------------------------------------------------------------
# retry policy mechanics
# ---------------------------------------------------------------------------

def test_retry_backoff_is_deterministic_and_bounded():
    pol = RetryPolicy(backoff_s=0.01, backoff_mult=2.0, max_backoff_s=0.05,
                      jitter=0.5, seed=3)
    a = [pol.delay_s("site", k) for k in range(1, 8)]
    b = [pol.delay_s("site", k) for k in range(1, 8)]
    assert a == b                               # deterministic jitter
    assert a != [pol.delay_s("other", k) for k in range(1, 8)]
    for k, d in enumerate(a, start=1):
        base = min(0.01 * 2.0 ** (k - 1), 0.05)
        assert base * 0.5 <= d <= base * 1.5


def test_retry_is_deadline_aware():
    """A huge backoff must not make a deadlined request wait: the sleep
    is clamped to the earliest live deadline, so the drain finishes in
    deadline-time, not backoff-time."""
    gs = _graphs("dense", n=2)
    srv = MBEServer(BucketPolicy(max_batch=2, steps_per_round=16),
                    retry=RetryPolicy(max_attempts=4, backoff_s=30.0,
                                      jitter=0.0),
                    fault_injector=FaultPlan(seed=1, launch_rate=0.5))
    t0 = time.perf_counter()
    for g in gs:
        srv.admit(g, deadline_s=0.5)
    srv.drain()
    assert time.perf_counter() - t0 < 10.0, \
        "retry slept past the live deadline"


def test_verified_read_recovers_transient_corruption():
    truth = np.array([True, False, True, False])
    seq = iter([truth, np.array([True, True, True, False]), truth,
                truth, truth])
    val, mismatches = verified_read(lambda: next(seq))
    assert np.array_equal(val, truth)
    assert mismatches == 2      # corrupt read disagreed both ways

    clean = iter([truth] * 3)
    val, mismatches = verified_read(lambda: next(clean))
    assert np.array_equal(val, truth) and mismatches == 0


# ---------------------------------------------------------------------------
# cache compile-failure regression (satellite b)
# ---------------------------------------------------------------------------

class _FlakyJit:
    """A jit-alike whose first ``lower`` raises, then behaves."""

    def __init__(self, fails: int = 1):
        self.calls = 0
        self.fails = fails
        self._jit = jax.jit(lambda c, s: s + c)

    def lower(self, ctx, s):
        self.calls += 1
        if self.calls <= self.fails:
            raise TransientLaunchError("injected compile failure")
        return self._jit.lower(ctx, s)


def test_failed_compile_never_poisons_the_cache():
    """A raising AOT compile leaves NO entry behind and rolls the miss
    count back; retrying the same entry object re-commits on success, so
    counters end exactly as if the failure never happened."""
    cache = ExecutableCache()
    flaky = _FlakyJit()
    entry = cache.get_entry("k", lambda: flaky)
    one = np.float32(1.0)
    with pytest.raises(TransientLaunchError):
        entry(one, one)
    st = cache.stats()
    assert st["entries"] == 0, "failed compile left a poisoned entry"
    assert st["misses"] == 0, "failed compile counted as a compile"
    assert not entry.compiled and entry.compile_s == 0.0

    out = entry(one, one)                       # retry: compiles clean
    assert float(out) == 2.0
    st = cache.stats()
    assert st["entries"] == 1 and st["misses"] == 1
    assert cache.get_entry("k", lambda: 1 / 0) is entry   # re-committed
    assert cache.stats()["hits"] == 1


def test_failed_compile_then_fresh_get_builds_anew():
    """After a failure rollback, the next ``get_entry`` for the key
    builds a fresh entry; when IT succeeds the old failed object stays
    out (incumbent wins on the stale re-commit)."""
    cache = ExecutableCache()
    flaky = _FlakyJit()
    bad = cache.get_entry("k", lambda: flaky)
    one = np.float32(1.0)
    with pytest.raises(TransientLaunchError):
        bad(one, one)
    good = cache.get_entry("k", lambda: _FlakyJit(fails=0))
    assert good is not bad
    assert float(good(one, one)) == 2.0
    assert cache.stats()["entries"] == 1 and cache.stats()["misses"] == 1
    # the stale object retrying later must NOT displace the incumbent
    bad(one, one)
    assert cache.get_entry("k", lambda: 1 / 0) is good
    assert cache.stats()["entries"] == 1
