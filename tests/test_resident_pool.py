"""Differential tests for the multi-lane resident pool kernel
(kernels/resident_pool) and its engine/serving wiring.

The pool kernel advances EVERY lane of a worker batch one multi-step
segment in ONE Pallas launch (grid over lanes).  Its contract is
byte-identity with the legacy vmap-of-single-lane layout: every state
leaf equal at every segment boundary, for shared-context worker pools
(``ctx_batched=False``) and multi-graph batches (``ctx_batched=True``),
ragged pools included.  On top of that sit the scoreboard convention,
the lanes-aware VMEM gate, and the executable-cache key extension
(``("pool", width)`` appended ONLY when the pool path is active, so
legacy keys never change).
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _graphs import random_graph as _random_graph

from repro.core import engine_dense as ed
from repro.kernels.resident_pool import (B_DONE, B_LEFT, BOARD_SLOTS,
                                         resident_pool_segment,
                                         resident_pool_segment_ref,
                                         resident_pool_state_bytes,
                                         resident_pool_supported)
from repro.kernels.resident_step import (resident_segment,
                                         resident_state_bytes)

BIG_BUDGET = 1 << 30


def _pool_state(cfg, chunks):
    """Stack per-lane states over task chunks (equal t_len, ragged
    n_tasks — an empty chunk is a lane born done)."""
    t_len = max(max((len(c) for c in chunks), default=1), 1)
    states = []
    for c in chunks:
        t = np.full(t_len, -1, dtype=np.int32)
        t[: len(c)] = np.asarray(c, dtype=np.int32)
        states.append(ed.init_state(cfg, t)._replace(
            n_tasks=jnp.int32(len(c))))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def _lane(s, i):
    return jax.tree.map(lambda x: x[i], s)


def _assert_leaves_equal(a, b, msg):
    for name, x, y in zip(a._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{msg}:{name}")


def _drive_and_compare(ctxs, cfg, s, *, spc, ctx_batched, max_segments=200):
    """Advance the pool kernel and the per-lane single-lane kernel in
    lockstep, asserting every leaf + the scoreboard at every boundary.
    ``ctxs`` is the stacked context when ``ctx_batched`` else a list of
    per-lane contexts sharing one (the vmap reference indexes it)."""
    B = int(s.lvl.shape[0])
    g_pool = ctxs if ctx_batched else ctxs[0]
    sr = jax.tree.map(lambda x: x, s)
    for seg in range(max_segments):
        prev_steps = np.asarray(sr.steps)
        s, board = resident_pool_segment(
            g_pool, cfg, s, start=0, budget=BIG_BUDGET,
            steps_per_call=spc, ctx_batched=ctx_batched, interpret=True)
        lanes = [resident_segment(ctxs[i], cfg, _lane(sr, i), start=0,
                                  budget=BIG_BUDGET, steps_per_call=spc,
                                  interpret=True)
                 for i in range(B)]
        sr = jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)
        _assert_leaves_equal(s, sr, f"seg{seg}")
        # scoreboard: done flag + unspent segment steps, per lane
        done = np.asarray(ed._done(sr))
        adv = np.asarray(sr.steps) - prev_steps
        board = np.asarray(board)
        assert board.shape == (B, BOARD_SLOTS)
        np.testing.assert_array_equal(board[:, B_DONE],
                                      done.astype(np.int32),
                                      err_msg=f"seg{seg}:board_done")
        np.testing.assert_array_equal(board[:, B_LEFT], spc - adv,
                                      err_msg=f"seg{seg}:board_left")
        if done.all():
            return s, sr
    raise AssertionError("pool did not finish")


@pytest.mark.parametrize("order", ["deg", "deg_nocache", "input"])
def test_pool_boundary_identity_shared_ctx(order):
    """Shared-context worker pool (the distributed runner's layout):
    the pool kernel must equal per-lane single-lane segments on EVERY
    leaf at EVERY boundary — ragged pool included (lane 1 born done)."""
    g = _random_graph(7, 11, 0.35, 5)
    cfg = ed.make_config(g, order_mode=order, collect_cap=8,
                         kernel_impl="pallas")
    assert cfg.resident_active
    ctx = ed.make_context(g, cfg)
    chunks = [np.arange(0, 4), np.arange(0), np.arange(4, 7)]
    s0 = _pool_state(cfg, chunks)
    out, _ = _drive_and_compare([ctx] * len(chunks), cfg, s0, spc=3,
                                ctx_batched=False)
    # the born-done lane never advanced
    assert int(out.steps[1]) == 0 and int(out.n_max[1]) == 0


def test_pool_boundary_identity_batched_ctx():
    """Multi-graph batch (the serving layer's bucket pool): lane b owns
    graph b; the pool streams the stacked context block per grid cell."""
    graphs = [_random_graph(7, 11, d, seed) for d, seed in
              ((0.3, 1), (0.55, 2), (0.15, 3))]
    cfg = ed.make_config(graphs[0], collect_cap=8, kernel_impl="pallas")
    ctxs = [ed.make_context(g, cfg) for g in graphs]
    gb = jax.tree.map(lambda *xs: jnp.stack(xs), *ctxs)
    s0 = _pool_state(cfg, [np.arange(7)] * 3)
    # ctx_batched reference indexes the per-graph contexts
    B = 3
    s, sr = s0, jax.tree.map(lambda x: x, s0)
    for seg in range(200):
        s, board = resident_pool_segment(gb, cfg, s, start=0,
                                         budget=BIG_BUDGET,
                                         steps_per_call=2,
                                         ctx_batched=True, interpret=True)
        sj, bj = resident_pool_segment_ref(gb, cfg, sr, start=0,
                                           budget=BIG_BUDGET,
                                           steps_per_call=2,
                                           ctx_batched=True)
        lanes = [resident_segment(ctxs[i], cfg, _lane(sr, i), start=0,
                                  budget=BIG_BUDGET, steps_per_call=2,
                                  interpret=True) for i in range(B)]
        sr = jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)
        _assert_leaves_equal(s, sr, f"seg{seg}")
        # the jnp pool reference (vmap of the single-lane ref) agrees too,
        # scoreboard included
        _assert_leaves_equal(sj, sr, f"seg{seg}:ref")
        np.testing.assert_array_equal(np.asarray(board), np.asarray(bj),
                                      err_msg=f"seg{seg}:board")
        if bool(np.asarray(ed._done(sr)).all()):
            break
    else:
        raise AssertionError("pool did not finish")


def test_run_batch_pool_end_to_end_parity():
    """run_batch with the pool active must be byte-identical, every
    leaf, to the jnp run_batch — the whole-engine differential the
    serving stack relies on."""
    g = _random_graph(8, 12, 0.4, 9)
    chunks = np.array_split(np.arange(8, dtype=np.int32), 4)
    outs = {}
    for impl in ("pallas", "jnp"):
        cfg = ed.make_config(g, collect_cap=16, kernel_impl=impl)
        if impl == "pallas":
            assert ed.pool_lanes(cfg, 4) == 4
        ctx = ed.make_context(g, cfg)
        s = _pool_state(cfg, chunks)
        outs[impl] = jax.jit(
            lambda st, c=ctx, k=cfg: ed.run_batch(c, k, st, unroll=4))(s)
    _assert_leaves_equal(outs["pallas"], outs["jnp"], "run_batch")


def test_vmem_gate_lanes_arithmetic():
    """The residency budget must scale with the lane count: per-lane
    state/out blocks are linear in ``lanes`` while the streamed context
    is charged once; the pool gate charges only the concurrent grid
    cells, so huge pools pass while huge CONFIGS fail."""
    cfg = ed.make_config(_random_graph(6, 6, 0.5, 0),
                         kernel_impl="pallas")
    b1, b2, b3 = (resident_state_bytes(cfg, lanes=k) for k in (1, 2, 3))
    assert b1 < b2 < b3 and (b2 - b1) == (b3 - b2)
    # pool charge is capped at the concurrent cells, not the pool width
    assert resident_pool_state_bytes(cfg, 2) == \
        resident_pool_state_bytes(cfg, 64) == b2
    assert resident_pool_supported(cfg, 256)
    big = ed.EngineConfig(n_u=4096, n_v=4096, m_real=4096, depth=4098,
                          kernel_impl="pallas")
    assert not resident_pool_supported(big, 2)
    assert ed.pool_lanes(big, 8) == 0


def test_pool_lanes_selection():
    """All-or-nothing width selection: 'auto' admits any supported
    batch; an int cap admits batches up to the cap and pins 0/1 to the
    legacy vmap layout; the jnp path never pools."""
    cfg = ed.make_config(_random_graph(6, 8, 0.4, 1),
                         kernel_impl="pallas")
    assert ed.pool_lanes(cfg, 0) == 0
    assert ed.pool_lanes(cfg, 4) == 4                      # auto
    for cap, batch, want in ((0, 4, 0), (1, 4, 0), (4, 3, 3),
                             (4, 4, 4), (4, 5, 0)):
        c = dataclasses.replace(cfg, resident_lanes=cap)
        assert ed.pool_lanes(c, batch) == want, (cap, batch)
    jnp_cfg = ed.make_config(_random_graph(6, 8, 0.4, 1),
                             kernel_impl="jnp")
    assert ed.pool_lanes(jnp_cfg, 4) == 0


def test_cache_key_pool_extension():
    """Legacy executable-cache keys are untouched when the pool is
    inactive; active pools append ``("pool", width)`` so the two
    compiled layouts never collide in one entry."""
    from repro.serving import BucketPolicy, MBEServer
    stream = [_random_graph(6, 10, 0.3, s, canonical=True)
              for s in range(4)]
    pol = BucketPolicy(mode="pow2", max_batch=4, steps_per_round=32)
    refs = [(int(o.n_max), int(o.cs))
            for o in (ed.enumerate_dense(g) for g in stream)]
    lpp = {}
    for lanes_knob, want_pool in ((0, False), ("auto", True)):
        srv = MBEServer(pol, kernel_impl="pallas",
                        resident_lanes=lanes_knob)
        res = srv.serve(stream)
        for r, ref in zip(res, refs):
            assert (r.n_max, r.cs) == ref
        tails = [k[-1] for k in srv.cache._entries]
        has_pool = any(isinstance(t, tuple) and t and t[0] == "pool"
                       for t in tails)
        assert has_pool == want_pool, (lanes_knob, list(srv.cache._entries))
        st = srv.stats()
        assert st["resident_lanes"] == lanes_knob
        assert st["launches"] > 0
        lpp[want_pool] = st["launches_per_poll"]
    # same trajectory, same segment count: the pool costs ONE launch per
    # segment where the vmap layout costs one per lane
    assert lpp[True] * 4 == lpp[False], lpp


def test_sharded_pool_refill_identity():
    """ShardedExecutor with ``resident_lanes>1``: continuous refill
    through ``replace_lane`` must stay byte-identical to per-graph jnp
    runs while the per-device shard advances through the pool kernel
    (the cache key carries the pool tail).  Device-count aware: the
    multi-device CI leg forces 8 host devices; locally this runs on
    however many are visible."""
    from repro.serving import BucketPolicy, MBEServer, ShardedExecutor
    from repro.sharding.axes import mbe_serve_mesh
    n_dev = jax.device_count()
    mesh = mbe_serve_mesh(n_dev)
    stream = [_random_graph(6, 10, 0.35, 100 + s, canonical=True)
              for s in range(2 * n_dev + 2)]
    refs = [(int(o.n_max), int(o.cs))
            for o in (ed.enumerate_dense(g) for g in stream)]
    srv = MBEServer(BucketPolicy(mode="pow2", max_batch=n_dev,
                                 steps_per_round=24),
                    kernel_impl="pallas", resident_lanes="auto",
                    executor=ShardedExecutor(mesh))
    res = srv.serve(stream)
    for g, r, ref in zip(stream, res, refs):
        assert (r.n_max, r.cs) == ref, g.name
    assert any(isinstance(k[-1], tuple) and k[-1][0] == "pool"
               for k in srv.cache._entries), list(srv.cache._entries)
    assert srv.stats()["executor"] == "sharded"
