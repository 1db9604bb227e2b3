"""The one front door: public import surface, MBEClient/MBEOptions/
MBEFuture semantics, and the engine registry.

* import-surface covenant: every name in ``repro.__all__`` must exist
  (the test fails if a public name disappears);
* ``MBEClient`` drives all three execution paths (single-graph
  enumerate, batched stream, big-graph work-stealing route) with results
  byte-identical to the pre-refactor entry points
  (``enumerate_dense`` / ``enumerate_compact`` / ``MBEServer``), for
  both registered engines;
* the compact engine is servable through the same bucket/cache/executor
  stack as the dense one (the paper's data structure on the production
  path);
* future semantics: done()/result(timeout)/cancel(), unknown rids.
"""
import functools
import os
import subprocess
import sys

import pytest
from _graphs import random_graph

import repro
from repro import (BipartiteGraph, BucketPolicy, MBEClient, MBEOptions,
                   MBEServer, get_engine, list_engines)
from repro.baselines import bicliques_to_key_set
from repro.core import engine_compact as ec
from repro.core import engine_dense as ed
from repro.data import dataset_suite
from repro.data.generators import dense_small

_random_graph = functools.partial(random_graph, canonical=True)

# the public covenant: ``repro`` must keep exporting at least these
PUBLIC_SURFACE = {
    "__version__", "MBEClient", "MBEOptions", "MBEFuture", "MBEResult",
    "BipartiteGraph", "Engine", "get_engine", "register_engine",
    "list_engines", "MBEServer", "BucketPolicy", "imbalance",
}


# ---------------------------------------------------------------------------
# import surface
# ---------------------------------------------------------------------------

def test_public_import_surface():
    """Every covenant name is exported and resolvable; __all__ contains
    nothing dangling."""
    assert PUBLIC_SURFACE <= set(repro.__all__), \
        sorted(PUBLIC_SURFACE - set(repro.__all__))
    for name in repro.__all__:
        assert getattr(repro, name, None) is not None, name
    assert isinstance(repro.__version__, str) and repro.__version__


def test_import_starts_no_backend():
    """Importing ``repro`` (and the oracle module the ParMBE pool's
    spawned workers import) must not start a JAX backend: on a TPU host
    that would claim the chip for every such process."""
    code = ("import repro, repro.baselines.mbea, repro.serving\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]


def test_compile_cache_dir_from_env_else_checkout(monkeypatch, tmp_path):
    """The launchers' persistent compile cache: $JAX_COMPILATION_CACHE_DIR
    when set, else the fixed <checkout>/.jax_cache — never a per-run
    name, which would never hit."""
    import jax
    from repro.launch.compile_cache import CHECKOUT, use_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(CHECKOUT, ".jax_cache")
        assert use_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert os.path.isfile(os.path.join(CHECKOUT, "chip_smoke.py"))
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_engine_registry():
    assert {"dense", "compact", "count", "mce"} <= set(list_engines())
    assert get_engine("dense").name == "dense"
    eng = get_engine("compact")
    assert get_engine(eng) is eng                 # instances pass through
    # unknown names raise ValueError NAMING the available engines
    with pytest.raises(ValueError, match="available engines"):
        get_engine("nonexistent")
    with pytest.raises(ValueError, match="available engines"):
        MBEOptions(engine="nonexistent")


def test_options_subsume_bucket_policy():
    """MBEOptions is the one config: its policy fields map 1:1 onto the
    BucketPolicy the server runs."""
    opts = MBEOptions(bucket_mode="linear", step_u=16, step_v=64,
                      min_u=8, min_v=32, max_batch=6, pad_batch=False,
                      steps_per_round=24, big_graph_threshold=40)
    pol = opts.bucket_policy()
    assert pol == BucketPolicy(mode="linear", step_u=16, step_v=64,
                               min_u=8, min_v=32, max_batch=6,
                               pad_batch=False, steps_per_round=24,
                               big_graph_threshold=40)
    client = MBEClient(opts)
    assert client.server.policy == pol
    assert client.server.engine.name == "dense"
    # keyword overrides build a replaced options value
    c2 = MBEClient(opts, engine="compact")
    assert c2.options.bucket_policy() == pol
    assert c2.server.engine.name == "compact"


# ---------------------------------------------------------------------------
# one client, all three paths, both engines, byte-identical
# ---------------------------------------------------------------------------

def _direct_reference(engine: str, g, collect_cap=256):
    """The PRE-refactor entry point for each engine."""
    if engine == "dense":
        out = ed.enumerate_dense(g, collect_cap=collect_cap)
    else:
        out = ec.enumerate_compact(g, collect_cap=collect_cap)
    cfg = ed.make_config(g, collect_cap=collect_cap)
    return (int(out.n_max), int(out.cs),
            bicliques_to_key_set(
                ed.collected_bicliques(cfg, out, g.n_u, g.n_v)))


@pytest.mark.parametrize("engine", ["dense", "compact"])
def test_one_client_drives_all_three_paths(engine):
    """ONE MBEClient instance serves (1) a sync single-graph enumerate,
    (2) a batched continuous stream, and (3) a big-graph work-stealing
    route — all byte-identical to the pre-refactor single-graph
    functions."""
    client = MBEClient(MBEOptions(
        engine=engine, max_batch=4, steps_per_round=16,
        big_graph_threshold=16, collect=True, collect_cap=2048))
    # (1) single graph, sync
    g1 = _random_graph(10, 20, 0.25, 3)
    r1 = client.enumerate(g1)
    assert (r1.n_max, r1.cs, bicliques_to_key_set(r1.bicliques)) == \
        _direct_reference(engine, g1, 2048)
    assert r1.status == "done"
    # (2) batched stream (mixed shapes below the routing threshold)
    gs = [_random_graph(6 + s, 9 + 2 * s, 0.25, s) for s in range(5)]
    rs = client.enumerate_many(gs)
    for g, r in zip(gs, rs):
        assert (r.n_max, r.cs, bicliques_to_key_set(r.bicliques)) == \
            _direct_reference(engine, g, 2048), g.name
    # (3) big-graph work-stealing route
    heavy = dense_small(18, 36, p=0.5, seed=7, name="heavy")
    rb = client.enumerate(heavy)
    assert (rb.n_max, rb.cs, bicliques_to_key_set(rb.bicliques)) == \
        _direct_reference(engine, heavy, 2048)
    routes = [e["route"] for e in client.routing_log
              if e["event"] == "route"]
    assert routes.count("big") == 1 and routes.count("lane") == 6
    st = client.stats()
    assert st["engine"] == engine
    assert st["pending"] == 0 and st["in_flight"] == 0


def test_client_matches_legacy_server_results():
    """The facade must not change serving results: MBEClient and a
    directly-driven MBEServer with the same knobs are byte-identical."""
    graphs = list(dataset_suite("test").values())
    pol = BucketPolicy(mode="pow2", max_batch=4, steps_per_round=24)
    legacy = MBEServer(pol, collect_cap=256, collect=True).serve(graphs)
    client = MBEClient(MBEOptions(max_batch=4, steps_per_round=24,
                                  collect=True, collect_cap=256))
    got = client.enumerate_many(graphs)
    for a, b in zip(legacy, got):
        assert (a.n_max, a.cs) == (b.n_max, b.cs)
        assert bicliques_to_key_set(a.bicliques) == \
            bicliques_to_key_set(b.bicliques)


def test_compact_engine_served_through_buckets_and_cache():
    """engine='compact' runs through the SAME serving machinery: padded
    buckets, cached round-mode executables (engine-qualified keys), lane
    refill — with dense-identical fingerprints."""
    graphs = [_random_graph(9 + s % 5, 14 + (3 * s) % 11, 0.3, s)
              for s in range(8)]
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=4,
                                 steps_per_round=16), engine="compact")
    results = srv.serve(graphs)
    for g, r in zip(graphs, results):
        ref = ed.enumerate_dense(g)
        assert (r.n_max, r.cs) == (int(ref.n_max), int(ref.cs)), g.name
    st = srv.stats()
    assert st["engine"] == "compact"
    assert st["misses"] < len(graphs)          # bucketing amortized
    for key in srv.cache._entries:
        # compact entries are engine-qualified so they can never collide
        # with a dense executable for the same bucket
        if key[0] == "install":
            assert key[2] == "compact", key
        else:
            assert key[0][0] == "compact", key


# ---------------------------------------------------------------------------
# futures
# ---------------------------------------------------------------------------

def test_future_done_result_and_repeatability():
    client = MBEClient(MBEOptions(steps_per_round=8))
    g = _random_graph(10, 20, 0.2, 1)
    fut = client.submit(g)
    assert not fut.done()
    res = fut.result(timeout=300)
    assert fut.done()
    assert fut.result() is res                 # result() is idempotent
    assert res.n_max == int(ed.enumerate_dense(g).n_max)


def test_future_result_timeout_raises_and_request_survives():
    heavy = dense_small(14, 28, p=0.55, seed=3, name="heavy")
    client = MBEClient(MBEOptions(max_batch=1, steps_per_round=1))
    fut = client.submit(heavy)
    with pytest.raises(TimeoutError, match="not done"):
        fut.result(timeout=0.0)
    # the request keeps running and can still complete afterwards
    res = fut.result(timeout=600)
    assert res.status == "done"
    assert res.n_max == int(ed.enumerate_dense(heavy).n_max)


def test_future_unknown_rid_raises_key_error():
    from repro import MBEFuture
    client = MBEClient(MBEOptions())
    with pytest.raises(KeyError, match="unknown"):
        MBEFuture(client, 999, "ghost").result()


def test_future_survives_direct_server_drain():
    """The docstring promises MBEServer.admit/poll/drain remain a
    supported surface: a result delivered by driving client.server
    directly must still be claimable through the future (the completion
    sink), not lost."""
    client = MBEClient(MBEOptions(steps_per_round=8))
    g = _random_graph(10, 20, 0.2, 4)
    fut = client.submit(g)
    client.server.drain()                  # low-level surface, no client
    assert fut.done()
    assert fut.result().n_max == int(ed.enumerate_dense(g).n_max)


def test_client_mailbox_bounded_by_unclaimed_futures():
    """Claimed results move onto their future: after enumerate_many /
    result() the client retains nothing, so a long-lived client's
    footprint is bounded by the futures the caller still holds."""
    client = MBEClient(MBEOptions(max_batch=4))
    client.enumerate_many([_random_graph(9 + s, 15 + s, 0.25, s)
                           for s in range(6)])
    assert client._mailbox == {} and client._watched == set()
    fut = client.submit(_random_graph(10, 20, 0.2, 8))
    client.drain()
    assert set(client._mailbox) == {fut.rid}   # unclaimed: retained
    res = fut.result()
    assert client._mailbox == {}               # claimed: released
    assert fut.result() is res                 # ...but still idempotent
