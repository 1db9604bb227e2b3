"""The demux reads finished lanes from the round's host readout of the
lanes' scalar state (``LanePool.readout``), not from device slices.

* Served results equal those of the per-lane-slice demux (done masks
  evaluated on the device state, every finished lane sliced whole) field
  for field, and the same requests finish in the same poll, for every
  engine, both lane-pool executors and both ``collect`` settings.
* With ``collect=False`` no lane is ever sliced and
  ``stats()['demux_device_reads']`` stays 0.
* A readout never outlives the state it was read from: lane surgery
  between a round and the demux sends the done mask and the counters to
  the surgered state.
"""
import dataclasses

import numpy as np
import pytest
from _graphs import random_graph

import jax
from repro.core.engine import get_engine
from repro.data.generators import random_unipartite
from repro.serving import (BucketPolicy, ExecutableCache, LocalExecutor,
                           MBEServer, RetryPolicy, ShardedExecutor)
from repro.serving.executor import Executor
from repro.sharding.axes import mbe_serve_mesh

ENGINES = ("dense", "compact", "count", "mce")
EXECUTORS = {"local": LocalExecutor,
             "sharded": lambda: ShardedExecutor(mbe_serve_mesh(1))}
TIMINGS = {"latency_s", "queue_s", "service_s", "compile_s"}


def _graphs(engine, n=4):
    if get_engine(engine).unipartite:
        return [random_unipartite(7 + i, 0.35, seed=70 + i, name=f"uni{i}")
                for i in range(n)]
    return [random_graph(5 + i, 10 + i, 0.35, 70 + i, canonical=True)
            for i in range(n)]


def _slice_demux(mp):
    """The demux as it read lanes before the readout: the done mask
    evaluated on the device state, every finished lane sliced whole."""
    mp.setattr(Executor, "done_mask",
               lambda self, pool: np.asarray(pool.engine.done(pool.state)))
    mp.setattr(Executor, "lane_scalars", Executor.lane)


def _never_slice(mp):
    def lane(self, pool, i):
        raise AssertionError("a lane was sliced on the collect=False path")
    mp.setattr(Executor, "lane", lane)


def _stream(engine, executor, collect, cache=None, **kw):
    """Serve a stream poll by poll; returns the server, the rids each poll
    delivered and every result's fields but its timings."""
    srv = MBEServer(BucketPolicy(max_batch=2, steps_per_round=8),
                    engine=engine, executor=executor, collect=collect,
                    collect_cap=64, **kw)
    if cache is not None:
        srv.cache = cache           # same executables on both sides
    rids = [srv.admit(g) for g in _graphs(engine)]
    polls, got = [], {}
    while srv.has_work():
        out = srv.poll()
        polls.append(sorted(out))
        got.update(out)
    assert sorted(got) == rids
    fields = {rid: {k: v for k, v in dataclasses.asdict(res).items()
                    if k not in TIMINGS} | {"status": res.status}
              for rid, res in got.items()}
    return srv, polls, fields


@pytest.mark.parametrize("collect", [False, True])
@pytest.mark.parametrize("executor", sorted(EXECUTORS))
@pytest.mark.parametrize("engine", ENGINES)
def test_readout_demux_equals_slice_demux(monkeypatch, engine, executor,
                                          collect):
    with monkeypatch.context() as mp:
        _slice_demux(mp)
        ref_srv, ref_polls, ref = _stream(engine, EXECUTORS[executor](),
                                          collect)
    with monkeypatch.context() as mp:
        if not collect:
            _never_slice(mp)
        srv, polls, got = _stream(engine, EXECUTORS[executor](), collect,
                                  cache=ref_srv.cache)
    assert got == ref
    assert polls == ref_polls
    assert all(f["status"] == "done" for f in got.values())
    if collect and get_engine(engine).collectable:
        assert any(f.get("bicliques") or f.get("cliques")
                   for f in got.values())
    whole = collect and get_engine(engine).collectable
    assert srv.stats()["demux_device_reads"] == (len(got) if whole else 0)


@pytest.mark.parametrize("retry", [
    None,
    # checkpoints slice lanes by design (outside the demux): off here
    RetryPolicy(max_attempts=3, backoff_s=1e-5, checkpoint_interval=0),
], ids=["plain", "verified"])
@pytest.mark.parametrize("executor", sorted(EXECUTORS))
def test_collect_off_stream_never_slices_a_lane(monkeypatch, executor,
                                                retry):
    """A drained stream, refills and verified done-mask reads included,
    reads no lane and no done mask from the device in the demux."""
    _never_slice(monkeypatch)
    srv, _, got = _stream("dense", EXECUTORS[executor](), False,
                          retry=retry)
    s = srv.stats()
    assert s["demux_device_reads"] == 0
    assert s["batches"] > len(got) // 2 and s["lanes"] == len(got)


def _scalar_rows(pool):
    """Each lane's scalar leaves, read from the device state itself."""
    leaves = {k: np.asarray(v) for k, v in pool.state._asdict().items()
              if v.ndim == 1}
    return [{k: v[i] for k, v in leaves.items()} for i in range(pool.B)]


def _evict(ex, pool, cfg, graphs):
    ex.evict(pool, 0)


def _install(ex, pool, cfg, graphs):
    ex.install(pool, [0], [pool.engine.host_context(graphs[0], cfg)],
               [graphs[0].n_u], ExecutableCache())


def _restore(ex, pool, cfg, graphs):
    ex.restore(pool, [0], [pool.engine.host_context(graphs[0], cfg)],
               [pool.engine.fresh_lane_state(cfg, graphs[0].n_u)])


def _assign(ex, pool, cfg, graphs):
    pool.state = jax.tree.map(lambda x: x.at[0].set(x[1]), pool.state)


@pytest.mark.parametrize("surgery", [_evict, _install, _restore, _assign],
                         ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("executor", sorted(EXECUTORS))
def test_no_demux_from_a_stale_readout(executor, surgery):
    """After a round in which lane 0 finished and lane 1 did not, lane
    surgery on lane 0 drops the round's readout: the done mask, steps and
    counters then describe the surgered state."""
    ex = EXECUTORS[executor]()
    eng = get_engine("dense")
    graphs = [random_graph(4, 6, 0.4, 5, canonical=True),
              random_graph(12, 16, 0.4, 6, canonical=True)]
    cfg = eng.config(16, 16, 18, collect_cap=1)
    pool = ex.new_pool(cfg, 2, engine=eng)
    ex.install(pool, [0, 1], [eng.host_context(g, cfg) for g in graphs],
               [g.n_u for g in graphs], ExecutableCache())
    ex.run_round(pool, ExecutableCache(), budget=40)
    assert pool.readout is not None
    assert ex.done_mask(pool).tolist() == [True, False]
    assert ex.lane_scalars(pool, 0).n_max > 0
    surgery(ex, pool, cfg, graphs)
    assert pool.readout is None
    rows = _scalar_rows(pool)
    assert ex.done_mask(pool).tolist() == \
        np.asarray(eng.done(pool.state)).tolist()
    assert ex.steps(pool).tolist() == [r["steps"] for r in rows]
    for i in range(pool.B):
        assert vars(ex.lane_scalars(pool, i)) == rows[i]
        assert eng.counters(ex.lane_scalars(pool, i)) == \
            eng.counters(ex.lane(pool, i))
