"""The dense engine's lane-masked per-step loop (``_run_batch_stepwise``).

``run_batch`` takes it when neither the pool kernel nor B concurrent
resident lanes fit: every jnp batch, and pallas batches too large to
keep resident (the ucforum bucket at B >= 2 on a TPU).  Its contract:

* every state leaf equals per-lane single-lane ``run`` at every round
  boundary, for both batch layouts, across unrolls, budgets that stop
  lanes mid-segment, lanes that finish at different steps and lanes
  born done, on the jnp and the Pallas (interpret) kernels;
* no equation of the round's jaxpr outputs a whole lane stack except
  the loop itself and the row scatters, so no select or copy of a
  stack comes back;
* ``stats()['stepwise_steps']`` counts the lane steps such rounds
  advance, and nothing else.
"""
import dataclasses

import numpy as np
import jax
import jax.extend
import jax.numpy as jnp
import pytest
from _graphs import random_graph

from repro.core import engine_dense as ed
from repro.core.engine import DENSE
from repro.serving import (MONOTONIC_STATS, STATS_SCHEMA, BucketPolicy,
                           MBEServer)

GRAPHS = [random_graph(5, 8, 0.45, 3), random_graph(7, 9, 0.4, 4),
          random_graph(9, 10, 0.35, 5)]
N_U, N_V = 9, 10


def _cfg(kernel_impl):
    # resident=False pins the per-step kernels on the pallas path, so
    # run_batch takes the stepwise loop there too, as on a TPU bucket
    # past the residency gate
    return ed.EngineConfig(n_u=N_U, n_v=N_V, m_real=N_U, depth=N_U + 2,
                           collect_cap=4, kernel_impl=kernel_impl,
                           resident=False)


def _stack(trees):
    return jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                        *trees)


def _lanes(cfg, ctx_batched):
    """Batched (context, state): one graph per lane plus a lane born
    done, or one graph split over workers plus a worker with no task."""
    if ctx_batched:
        ctx = _stack([ed.host_context(g, cfg) for g in GRAPHS]
                     + [jax.device_get(DENSE.dummy_context(cfg))])
        st = _stack([DENSE.fresh_lane_state(cfg, g.n_u) for g in GRAPHS]
                    + [DENSE.fresh_lane_state(cfg, 0)])
        return ctx, st
    g = GRAPHS[2]
    chunks = [np.arange(0, 6), np.arange(6, 8), np.arange(8, 9), []]
    states = []
    for c in chunks:
        t = np.full(N_U, -1, np.int32)
        t[: len(c)] = c
        states.append(ed.init_state(cfg, t)._replace(
            n_tasks=jnp.int32(len(c))))
    return ed.make_context(g, cfg), _stack(states)


@pytest.mark.parametrize("kernel_impl", ["jnp", "pallas"])
@pytest.mark.parametrize("unroll", [1, 8])
@pytest.mark.parametrize("ctx_batched", [True, False])
def test_stepwise_loop_equals_single_lane_run(ctx_batched, unroll,
                                              kernel_impl):
    cfg = _cfg(kernel_impl)
    budget = 13                 # not a multiple of 8: lanes stop mid-segment
    ctx, st = _lanes(cfg, ctx_batched)
    B = st.lvl.shape[0]
    assert ed.stepwise_lanes(cfg, B)
    batch = jax.jit(lambda c, s: ed.run_batch(
        c, cfg, s, max_steps=budget, ctx_batched=ctx_batched,
        unroll=unroll))
    lane = jax.jit(lambda c, s: ed.run(c, cfg, s, max_steps=budget,
                                       unroll=unroll))
    lanes = [jax.tree.map(lambda x, i=i: x[i], st) for i in range(B)]
    lane_ctx = [jax.tree.map(lambda x, i=i: x[i], ctx) if ctx_batched
                else ctx for i in range(B)]
    finished_at = {}
    for rnd in range(60):
        st = batch(ctx, st)
        lanes = [lane(c, s) for c, s in zip(lane_ctx, lanes)]
        ref = _stack(lanes)
        for name, a, b in zip(st._fields, st, ref):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"round {rnd}, leaf {name}")
        done = np.asarray(ed._done(st))
        for i in np.flatnonzero(done):
            finished_at.setdefault(int(i), rnd)
        if done.all():
            break
    assert done.all(), "lanes did not finish in 60 rounds"
    assert finished_at[B - 1] == 0          # born done
    steps = np.asarray(st.steps)
    assert steps[B - 1] == 0
    assert len(set(steps[: B - 1].tolist())) == B - 1   # ragged finishes
    assert max(finished_at.values()) > 1    # budgets cut lanes mid-run


def _sub_jaxprs(params):
    for v in params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jax.extend.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax.extend.core.Jaxpr):
                yield x


def _stack_shaped(jaxpr, shapes, found):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if getattr(v.aval, "shape", None) in shapes:
                found.append(eqn.primitive.name)
        for sub in _sub_jaxprs(eqn.params):
            _stack_shaped(sub, shapes, found)
    return found


@pytest.mark.parametrize("kernel_impl", ["jnp", "pallas"])
def test_stepwise_round_writes_stacks_only_by_row_scatters(kernel_impl):
    """The per-step round holds no equation whose output has a lane
    stack's shape other than the loop and the row scatters: a select,
    cond or copy of a whole stack would be one (``vmap(run)``'s body
    holds dozens of ``select_n`` on them)."""
    cfg = _cfg(kernel_impl)
    ctx, st = _lanes(cfg, True)
    B = st.lvl.shape[0]
    shapes = {(B, cfg.depth, cfg.n_u), (B, cfg.depth, cfg.wv),
              (B, cfg.depth, cfg.wu)}
    jaxpr = jax.make_jaxpr(lambda c, s: ed.run_batch(
        c, cfg, s, max_steps=512, ctx_batched=True, unroll=8))(ctx, st)
    found = _stack_shaped(jaxpr.jaxpr, shapes, [])
    assert found.count("while") == 5          # the loop's five stacks
    assert found.count("scatter") == 8 * 6           # 8 steps x 6 writes
    assert set(found) == {"while", "scatter"}, sorted(set(found))


# ---------------------------------------------------------------------------
# stats()['stepwise_steps']
# ---------------------------------------------------------------------------

STREAM = [random_graph(6 + i % 3, 10, 0.35, 40 + i, canonical=True)
          for i in range(5)]
POLICY = BucketPolicy(mode="pow2", max_batch=4, steps_per_round=16,
                      steps_per_call=4)


def test_stepwise_steps_is_a_monotonic_contract_key():
    assert STATS_SCHEMA["stepwise_steps"] is int
    assert "stepwise_steps" in MONOTONIC_STATS


def test_stepwise_steps_counts_every_step_of_a_per_step_pool():
    srv = MBEServer(POLICY, kernel_impl="jnp")
    assert srv.stats()["stepwise_steps"] == 0
    srv.serve(STREAM[:3])
    first = srv.stats()
    assert first["busy_steps"] > 0
    assert first["stepwise_steps"] == first["busy_steps"]
    srv.reset_stats()
    assert srv.stats()["stepwise_steps"] == 0
    srv.serve(STREAM[3:])
    again = srv.stats()
    assert again["stepwise_steps"] == again["busy_steps"] > 0


@pytest.mark.parametrize("engine,kw", [
    ("compact", dict(kernel_impl="jnp")),
    ("dense", dict(kernel_impl="pallas", resident_lanes="auto"))],
    ids=["compact", "dense-pool-kernel"])
def test_stepwise_steps_zero_off_the_loop(engine, kw):
    srv = MBEServer(POLICY, engine=engine, **kw)
    srv.serve(STREAM[:3])
    st = srv.stats()
    assert st["busy_steps"] > 0
    assert st["stepwise_steps"] == 0


def test_stepwise_lanes_follows_the_residency_gate():
    """The hook names exactly the batches the pool kernel and B resident
    lanes both refuse; engines other than dense never take the loop."""
    from repro.core.engine import get_engine, list_engines
    small = ed.EngineConfig(n_u=16, n_v=16, m_real=16, depth=18,
                            kernel_impl="pallas")
    big = dataclasses.replace(small, n_u=1024, n_v=1024, m_real=1024,
                              depth=1026)
    assert not ed.stepwise_lanes(small, 4)            # pool kernel
    assert not ed.stepwise_lanes(big, 1)              # pool kernel, B = 1
    assert all(ed.stepwise_lanes(big, b) for b in range(2, 9))
    pinned = dataclasses.replace(small, resident_lanes=0)
    assert not ed.stepwise_lanes(pinned, 4)           # B resident lanes
    assert ed.stepwise_lanes(dataclasses.replace(small, kernel_impl="jnp"),
                             4)
    for name in list_engines():
        eng = get_engine(name)
        assert eng.stepwise_lanes(big, 8) == (name == "dense"), name
