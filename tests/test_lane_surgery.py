"""Engine-level lane surgery: ``replace_lane``/``replace_lanes`` and the
pool-widening live-lane migration path, tested DIRECTLY (PR 2 only
exercised them through ``MBEServer``).

The load-bearing invariant for the serving layer's refill correctness:
row surgery on a batched (state, ctx) pair touches ONLY the addressed
rows — every untouched lane is bit-identical before and after, including
mid-DFS (partially-run) state, so a refilled pool resumes as if the other
lanes had never been disturbed.
"""
import numpy as np
import jax
import jax.numpy as jnp

from _graphs import random_graph

from repro.core import engine_dense as ed
from repro.serving import BucketPolicy, plan_bucket
from repro.serving.executor import (LocalExecutor, dummy_context,
                                    fresh_lane_state)


def _bucketed_cfg(graphs, collect_cap=8):
    pol = BucketPolicy(mode="pow2")
    buckets = {plan_bucket(g, pol) for g in graphs}
    assert len(buckets) == 1, "test graphs must share one bucket"
    return buckets.pop().engine_config(collect_cap=collect_cap)


def _stack_lanes(cfg, graphs):
    states = [fresh_lane_state(cfg, g.n_u) for g in graphs]
    ctxs = [ed.make_context(g, cfg) for g in graphs]
    return (jax.tree.map(lambda *xs: jnp.stack(xs), *states),
            jax.tree.map(lambda *xs: jnp.stack(xs), *ctxs))


def _snapshot(tree):
    return jax.tree.map(lambda x: np.asarray(x).copy(), tree)


def _assert_rows_identical(before, after, rows, label):
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        for r in rows:
            assert np.array_equal(a[r], np.asarray(b)[r]), \
                f"{label}: lane {r} changed by surgery on another lane"


def _run_rounds(cfg, state, ctx, max_steps):
    fn = jax.jit(lambda c, s: ed.run_batch(c, cfg, s, max_steps=max_steps,
                                           ctx_batched=True))
    return fn(ctx, state)


def test_replace_lane_untouched_lanes_bit_identical():
    """Single-row surgery mid-flight: every other lane's state AND context
    leaves are byte-for-byte unchanged, and the batch still enumerates
    every lane correctly afterwards."""
    graphs = [random_graph(10 + s, 18 + s, 0.3, s, canonical=True)
              for s in range(4)]
    cfg = _bucketed_cfg(graphs)
    state, ctx = _stack_lanes(cfg, graphs)
    # advance mid-DFS so untouched rows carry live (non-initial) state
    state = _run_rounds(cfg, state, ctx, max_steps=7)
    s_before, c_before = _snapshot(state), _snapshot(ctx)

    fresh_g = random_graph(11, 19, 0.35, 99, canonical=True)
    state, ctx = ed.replace_lane(state, ctx, 2,
                                 fresh_lane_state(cfg, fresh_g.n_u),
                                 ed.make_context(fresh_g, cfg))
    keep = [0, 1, 3]
    _assert_rows_identical(s_before, state, keep, "state")
    _assert_rows_identical(c_before, ctx, keep, "ctx")
    # the replaced row really is the fresh lane
    assert int(np.asarray(state.steps)[2]) == 0
    assert np.array_equal(np.asarray(ctx.adj)[2],
                          np.asarray(ed.make_context(fresh_g, cfg).adj))

    # run everything to completion: per-lane results == per-graph runs
    state = _run_rounds(cfg, state, ctx, max_steps=cfg.max_steps)
    final = [fresh_g if i == 2 else g for i, g in enumerate(graphs)]
    for i, g in enumerate(final):
        ref = ed.enumerate_dense(g)
        assert int(np.asarray(state.n_max)[i]) == int(ref.n_max), g.name
        assert int(np.asarray(state.cs)[i]) == int(ref.cs), g.name


def test_replace_lanes_batched_scatter_matches_sequential():
    """Multi-row surgery (the refill hot path's single scatter) leaves
    non-addressed rows bit-identical and equals row-by-row surgery."""
    graphs = [random_graph(9 + s, 20 + s, 0.25, 10 + s, canonical=True)
              for s in range(6)]
    cfg = _bucketed_cfg(graphs)
    state, ctx = _stack_lanes(cfg, graphs)
    state = _run_rounds(cfg, state, ctx, max_steps=5)

    new_graphs = [random_graph(10, 21, 0.3, 50 + s, canonical=True)
                  for s in range(3)]
    idx = [1, 3, 4]
    ns = [fresh_lane_state(cfg, g.n_u) for g in new_graphs]
    nc = [ed.make_context(g, cfg) for g in new_graphs]

    s_before, c_before = _snapshot(state), _snapshot(ctx)
    s_multi, c_multi = ed.replace_lanes(
        state, ctx, idx,
        jax.tree.map(lambda *xs: jnp.stack(xs), *ns),
        jax.tree.map(lambda *xs: jnp.stack(xs), *nc))
    keep = [0, 2, 5]
    _assert_rows_identical(s_before, s_multi, keep, "state")
    _assert_rows_identical(c_before, c_multi, keep, "ctx")

    s_seq, c_seq = state, ctx
    for i, st_, ct_ in zip(idx, ns, nc):
        s_seq, c_seq = ed.replace_lane(s_seq, c_seq, i, st_, ct_)
    for a, b in zip(jax.tree.leaves(s_multi), jax.tree.leaves(s_seq)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(c_multi), jax.tree.leaves(c_seq)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_sharded_surgery_matches_unsharded_and_keeps_placement():
    """With a mesh ``sharding`` the surgery runs shard by shard: the rows
    equal the single-device scatter's, and every leaf keeps the pool's
    placement (no reshard before the next round)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    graphs = [random_graph(9 + s, 20 + s, 0.25, 40 + s, canonical=True)
              for s in range(4)]
    cfg = _bucketed_cfg(graphs)
    state, ctx = _stack_lanes(cfg, graphs)
    state = _run_rounds(cfg, state, ctx, max_steps=5)
    sh = NamedSharding(Mesh(np.array(jax.devices()), ("lanes",)), P("lanes"))
    new_graphs = [random_graph(10, 21, 0.3, 60 + s, canonical=True)
                  for s in range(2)]
    ns = jax.tree.map(lambda *xs: jnp.stack(xs),
                      *[fresh_lane_state(cfg, g.n_u) for g in new_graphs])
    nc = jax.tree.map(lambda *xs: jnp.stack(xs),
                      *[ed.make_context(g, cfg) for g in new_graphs])
    want = ed.replace_lanes(state, ctx, [3, 0], ns, nc)
    got = ed.replace_lanes(jax.device_put(state, sh), jax.device_put(ctx, sh),
                           [3, 0], ns, nc, sharding=sh)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert b.sharding == sh
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_dummy_eviction_surgery_is_local():
    """Evicting a lane to the dummy (idle, born-done) state must not
    perturb any other lane."""
    graphs = [random_graph(12, 22, 0.3, 70 + s, canonical=True)
              for s in range(3)]
    cfg = _bucketed_cfg(graphs)
    state, ctx = _stack_lanes(cfg, graphs)
    state = _run_rounds(cfg, state, ctx, max_steps=9)
    s_before, c_before = _snapshot(state), _snapshot(ctx)
    state, ctx = ed.replace_lane(state, ctx, 0, fresh_lane_state(cfg, 0),
                                 dummy_context(cfg))
    _assert_rows_identical(s_before, state, [1, 2], "state")
    _assert_rows_identical(c_before, ctx, [1, 2], "ctx")
    done = np.asarray((state.lvl < 0) & (state.tpos >= state.n_tasks))
    assert done[0]                               # evicted lane is born done


def test_pool_widening_migration_preserves_live_rows():
    """The executor's pool-widening path: live mid-DFS rows migrated into
    a wider pool are bit-identical to their source rows, resume where they
    left off, and finish with the same results as uninterrupted runs."""
    ex = LocalExecutor()
    graphs = [random_graph(11 + s, 19 + s, 0.35, 30 + s, canonical=True)
              for s in range(2)]
    cfg = _bucketed_cfg(graphs)
    old = ex.new_pool(cfg, 2)
    ex.install(old, [0, 1],
               [fresh_lane_state(cfg, g.n_u) for g in graphs],
               [ed.make_context(g, cfg) for g in graphs])
    old.state = _run_rounds(cfg, old.state, old.ctx, max_steps=11)
    assert not ex.done_mask(old).all(), "graphs must still be mid-DFS"
    s_rows = _snapshot(old.state)
    c_rows = _snapshot(old.ctx)

    new = ex.new_pool(cfg, 8)
    ex.migrate(old, new, [0, 1])
    for a, b in zip(jax.tree.leaves(s_rows), jax.tree.leaves(new.state)):
        assert np.array_equal(a[:2], np.asarray(b)[:2]), \
            "migrated state rows not bit-identical"
    for a, b in zip(jax.tree.leaves(c_rows), jax.tree.leaves(new.ctx)):
        assert np.array_equal(a[:2], np.asarray(b)[:2]), \
            "migrated ctx rows not bit-identical"
    # the widened pool's padding lanes are born done (inert)
    assert ex.done_mask(new)[2:].all()

    new.state = _run_rounds(cfg, new.state, new.ctx,
                            max_steps=cfg.max_steps)
    for i, g in enumerate(graphs):
        ref = ed.enumerate_dense(g)
        assert int(np.asarray(new.state.n_max)[i]) == int(ref.n_max)
        assert int(np.asarray(new.state.cs)[i]) == int(ref.cs)
        # steps continued from the partial run, not restarted
        assert int(np.asarray(new.state.steps)[i]) == int(ref.steps)
