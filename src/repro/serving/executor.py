"""Pluggable execution backends for the MBE serving layer (DESIGN.md §6).

``MBEServer`` used to own its execution path outright: single-device
``run_batch`` lane pools, advanced in bounded rounds with ``replace_lane``
row surgery.  That is ONE point in a larger design space — cuMBE's hybrid
parallelism (PAPER.md §IV) pairs the inverse decomposition (many small
graphs, one lane each) with the direct one (one big graph fanned out over
all workers, balanced by work stealing).  This module extracts the
execution path behind an ``Executor`` interface so the scheduler can serve
both shapes of traffic from one mesh:

* ``LocalExecutor``   — today's single-device lane pools, unchanged: one
  vmap lane per graph, one cached ``run_batch`` executable per
  ``(bucket, batch, budget)``.
* ``ShardedExecutor`` — the same lane-pool contract placed across a
  ``jax.sharding.Mesh``: the pool's batch axis is sharded over the serving
  axis (``sharding.axes.MBE_LANE_AXIS``) and each round is ONE
  ``distributed.make_round_fn(ctx_batched=True)`` call, so a single host
  poll advances every device's lanes in lockstep bounded rounds.
* ``BigGraphLane``    — the work-stealing layout for requests above the
  routing threshold (``buckets.plan_route``): ONE graph decomposed into
  root tasks strided across every mesh worker
  (``ctx_batched=False, work_stealing=True``), stealing pending tasks at
  round barriers, so a heavy graph no longer serializes behind one vmap
  lane while small-graph buckets fill the rest of the mesh.  Both
  executors can mint one; ``LocalExecutor`` runs it as a vmap'd worker
  batch on a one-device mesh (cuMBE's many-TBs-per-SM analog),
  ``ShardedExecutor`` spreads it over the whole serving mesh.

The scheduler speaks ONLY this interface: lane planning, pool creation,
refill installation, round execution, demux views, eviction, and pool
migration all go through executor methods — ``MBEServer`` itself contains
no ``run_batch``/``replace_lane`` calls.  Executables are cached in the
scheduler's ``ExecutableCache`` under backend-qualified keys (mesh + axis
+ workers-per-device prepended to the config slot), so one server can mix
backends without entry collisions, and every backend's compile time is
AOT-timed the same way.
"""
from __future__ import annotations

import abc
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import distributed as dd
from repro.core import engine_dense as ed
from repro.core.engine import DENSE, Engine
from repro.serving.buckets import BucketPolicy, plan_batch_size
from repro.serving.cache import ExecutableCache
from repro.sharding.axes import MBE_LANE_AXIS

# Round budget for the big-graph lane when the bucket policy runs
# unbounded rounds (steps_per_round == 0): work stealing only happens at
# round barriers, so the big lane must stay bounded even in flush mode.
DEFAULT_BIG_ROUND_STEPS = 2048


def fresh_lane_state(cfg: ed.EngineConfig, n_tasks: int) -> ed.DenseState:
    """Dense-engine lane state (back-compat alias for
    ``Engine.fresh_lane_state``; pools carry their own engine now)."""
    return DENSE.fresh_lane_state(cfg, n_tasks)


def dummy_context(cfg: ed.EngineConfig) -> ed.GraphContext:
    """Dense-engine idle-lane context (back-compat alias for
    ``Engine.dummy_context``)."""
    return DENSE.dummy_context(cfg)


def _install_fn(engine: Engine, cfg: ed.EngineConfig):
    """A pool's install executable: the rows where ``mask`` is set get a
    fresh state for ``n_tasks`` and the context rows ``rows``; every
    other row keeps its bits.  The pool's state and context are donated,
    so the update happens in place."""
    def install(state, ctx, mask, n_tasks, rows):
        def put(new, old):
            return jnp.where(mask.reshape((-1,) + (1,) * (old.ndim - 1)),
                             new, old)
        return (jax.tree.map(put, engine.fresh_lane_rows(cfg, n_tasks),
                             state),
                jax.tree.map(put, rows, ctx))
    return jax.jit(install, donate_argnums=(0, 1))


class LanePool:
    """Device-side half of a bucket's lane pool: the batched state/context
    pytrees (whatever types ``engine`` mints) plus their static shape.
    Owned and mutated exclusively by an ``Executor``; the scheduler holds
    the host-side slot bookkeeping (which request occupies which lane) and
    never touches the arrays directly."""

    __slots__ = ("cfg", "B", "engine", "state", "ctx")

    def __init__(self, cfg: ed.EngineConfig, n_lanes: int,
                 engine: Engine | None = None):
        self.cfg = cfg
        self.B = n_lanes
        self.engine = engine or DENSE
        self.state = None
        self.ctx = None


@dataclasses.dataclass
class RoundTelemetry:
    """What one bounded round reports back to the scheduler."""
    wall_s: float                 # round wall time (compile included)
    compile_s: float              # XLA compile charged to this round
    adv: np.ndarray               # per-lane/worker engine steps advanced
    pending: np.ndarray | None = None   # per-worker unstarted root tasks
    #                                     (work-stealing lanes only)
    work: dict | None = None      # per-lane/worker rows each kernel pass
    #                               had to read this round
    #                               (``Engine.work_rows`` deltas; None for
    #                               engines that count none)


def _progress(engine: Engine, state):
    """What a round's telemetry reads of a pool's state: the lanes'
    ``steps`` and, for engines that count it, their ``work_rows``, in
    one device-to-host read."""
    work = engine.work_rows(state)
    if work is None:
        return np.asarray(state.steps), None
    return jax.device_get((state.steps, work))


def _widen(work: dict | None) -> dict | None:
    """A round's per-lane int32 row counts as int64."""
    return None if work is None else {
        k: np.asarray(v).astype(np.int64) for k, v in work.items()}


def _delta(after: dict | None, before: dict | None) -> dict | None:
    """Per-lane growth of the int32 work counters over a round, taken
    modulo 2**32 (a round adds far less than 2**31)."""
    return None if after is None else _widen(
        {k: v - before[k] for k, v in after.items()})


class Executor(abc.ABC):
    """Execution backend: owns where lane pools live and how rounds run."""

    name: str = "executor"

    # -- lane planning --------------------------------------------------
    @abc.abstractmethod
    def plan_lanes(self, n_pending: int, policy: BucketPolicy) -> int:
        """Lane count for a pool serving ``n_pending`` same-bucket graphs
        (backend-constrained: e.g. divisible by the mesh size)."""

    # -- pool lifecycle -------------------------------------------------
    def new_pool(self, cfg: ed.EngineConfig, n_lanes: int,
                 engine: Engine | None = None) -> LanePool:
        """Fresh pool of ``n_lanes`` idle (born-done) lanes, placed on this
        backend's devices.  ``engine`` picks the enumeration engine the
        pool's lanes run (default dense)."""
        pool = LanePool(cfg, n_lanes, engine)
        eng = pool.engine
        ds, dc = eng.fresh_lane_state(cfg, 0), eng.dummy_context(cfg)
        pool.state = jax.tree.map(lambda x: jnp.stack([x] * n_lanes), ds)
        pool.ctx = jax.tree.map(lambda x: jnp.stack([x] * n_lanes), dc)
        sh = self._pool_sharding()
        if sh is not None:
            pool.state = jax.device_put(pool.state, sh)
            pool.ctx = jax.device_put(pool.ctx, sh)
        return pool

    def install(self, pool: LanePool, idx: list[int], ctxs: list,
                n_tasks: list[int], cache: ExecutableCache) -> bool:
        """Place fresh lanes into rows ``idx``: row ``idx[j]`` gets the
        host context ``ctxs[j]`` (``Engine.host_context``) and a fresh
        state owning root tasks ``[0, n_tasks[j])`` (the host span
        ``mbe.install``).

        An unsharded pool takes its install executable, one per
        ``(engine, cfg, B)`` in ``cache``: the context rows, padded to
        ``B``, go to the device in one transfer, the fresh states are
        built there (``Engine.fresh_lane_rows``), and the pool's buffers
        are donated, so one dispatch updates the pool in place for any
        number of lanes.  A sharded pool keeps the shard-by-shard row
        surgery of ``replace_lanes(sharding=...)``: a partitioned update
        on a four-chip mesh wrote into other lanes' rows (DESIGN.md §6).
        Returns True when the install executable placed the lanes, False
        when row surgery did."""
        with jax.profiler.TraceAnnotation("mbe.install"):
            if self._pool_sharding() is not None:
                self._set_rows(pool, idx, ctxs,
                               [pool.engine.fresh_lane_state(pool.cfg, n)
                                for n in n_tasks])
                return False
            B = pool.B
            mask = np.zeros(B, bool)
            mask[idx] = True
            nt = np.zeros(B, np.int32)
            nt[idx] = n_tasks

            def pad(*xs):
                out = np.zeros((B, *xs[0].shape), xs[0].dtype)
                out[idx] = np.stack(xs)
                return out

            entry = cache.get_entry(
                ("install", self.name, pool.engine.name, pool.cfg, B),
                lambda: _install_fn(pool.engine, pool.cfg))
            pool.state, pool.ctx = entry(
                pool.state, pool.ctx,
                *jax.device_put((mask, nt, jax.tree.map(pad, *ctxs))))
            return True

    def restore(self, pool: LanePool, idx: list[int], ctxs: list,
                states: list) -> None:
        """Place whole lane states into rows ``idx``: lanes resumed from a
        host checkpoint (failover, quarantine exoneration) carry a state
        part-way through its enumeration, not a fresh one, so they take
        the row surgery of ``replace_lanes``, re-pinned to the backend's
        sharding (the host span ``mbe.install``)."""
        with jax.profiler.TraceAnnotation("mbe.install"):
            self._set_rows(pool, idx, ctxs, states)

    def _set_rows(self, pool: LanePool, idx: list[int], ctxs: list,
                  states: list) -> None:
        def stack(*xs):
            return np.stack(xs)
        pool.state, pool.ctx = ed.replace_lanes(
            pool.state, pool.ctx, idx, jax.tree.map(stack, *states),
            jax.tree.map(stack, *ctxs), sharding=self._pool_sharding())

    def migrate(self, old: LanePool, new: LanePool,
                live_idx: list[int]) -> None:
        """Move live rows of ``old`` into rows [0, len(live_idx)) of
        ``new`` — the pool-widening path: in-flight DFS state resumes
        unchanged in the wider pool.  The wider pool is assembled on the
        host and placed with one transfer: a device gather and scatter
        would take shapes from the number of live lanes, and so compile
        anew for each count a stream happens to reach."""
        ii = np.asarray(live_idx)

        def put(o, n):
            out = np.array(n)
            out[: len(ii)] = np.asarray(o)[ii]
            return out
        new.state, new.ctx = jax.device_put(
            (jax.tree.map(put, old.state, new.state),
             jax.tree.map(put, old.ctx, new.ctx)), self._pool_sharding())

    def evict(self, pool: LanePool, i: int) -> None:
        """Dummy-out lane ``i`` (step-cap eviction, cancellation, deadline
        expiry): the slot is freed and every other lane's rows are
        untouched."""
        pool.state, pool.ctx = ed.replace_lane(
            pool.state, pool.ctx, i, pool.engine.fresh_lane_state(pool.cfg, 0),
            pool.engine.dummy_context(pool.cfg),
            sharding=self._pool_sharding())

    # -- execution ------------------------------------------------------
    @abc.abstractmethod
    def run_round(self, pool: LanePool, cache: ExecutableCache,
                  budget: int | None, unroll: int = 1) -> RoundTelemetry:
        """Advance every lane by one bounded round (``budget`` engine steps
        per lane; None = run to completion) through a cached executable.
        ``unroll`` is the multi-step compiled-segment knob
        (``BucketPolicy.steps_per_call``): candidate steps per while-loop
        iteration inside the round executable (baked into the cache
        key; byte-identical results)."""

    def launches_per_segment(self, pool: LanePool) -> int:
        """Kernel launches one compiled segment of this pool costs on the
        resident pallas path: 1 when the engine's multi-lane pool kernel
        is active for this (cfg, B), else one per lane (the vmap
        layout).  The scheduler's ``launches_per_poll`` stat multiplies
        this by the segments a round actually ran."""
        return 1 if pool.engine.pool_lanes(pool.cfg, pool.B) else pool.B

    def stepwise(self, pool: LanePool) -> bool:
        """Whether this pool's rounds take the engine's lane-masked
        per-step loop (``Engine.stepwise_lanes`` at the width
        ``run_batch`` sees)."""
        return pool.engine.stepwise_lanes(pool.cfg, pool.B)

    # -- demux views ----------------------------------------------------
    def lane(self, pool: LanePool, i: int) -> ed.DenseState:
        """Host-readable view of one lane's state (for demux)."""
        return jax.tree.map(lambda x, i=i: x[i], pool.state)

    def done_mask(self, pool: LanePool) -> np.ndarray:
        return np.asarray(pool.engine.done(pool.state))

    def steps(self, pool: LanePool) -> np.ndarray:
        """Per-lane cumulative engine steps (for step-cap enforcement) —
        part of the interface so the scheduler never reads the
        executor-owned pool arrays directly."""
        return np.asarray(pool.state.steps)

    # -- placement / big-graph lane -------------------------------------
    @abc.abstractmethod
    def placement(self, n_lanes: int) -> str:
        """Human-readable lane placement for the routing log."""

    @abc.abstractmethod
    def big_lane(self, cfg: ed.EngineConfig, ctx, n_roots: int,
                 cache: ExecutableCache, budget: int | None,
                 engine: Engine | None = None,
                 steps_per_call: int = 1) -> "BigGraphLane":
        """Work-stealing lane for one routed-big graph on this backend
        (``engine`` selects the enumeration engine, default dense; the
        executor's ``work_stealing`` flag selects the noWS ablation;
        ``steps_per_call`` is the in-round engine-loop unroll)."""

    def _pool_sharding(self):
        return None                 # single-device backends


class LocalExecutor(Executor):
    """Single-device lane pools — the PR-2 execution path, verbatim, behind
    the interface.  The big-graph lane runs as ``big_workers`` vmap'd
    workers on a one-device mesh (work stealing between vmap lanes — the
    many-thread-blocks-per-SM analog), so big-graph routing is meaningful
    even without a multi-device mesh."""

    name = "local"

    def __init__(self, big_workers: int = 4, work_stealing: bool = True):
        self.big_workers = big_workers
        self.work_stealing = work_stealing

    def plan_lanes(self, n_pending: int, policy: BucketPolicy) -> int:
        return plan_batch_size(n_pending, policy)

    def run_round(self, pool: LanePool, cache: ExecutableCache,
                  budget: int | None, unroll: int = 1) -> RoundTelemetry:
        entry = cache.get_round(pool.cfg, pool.B, budget,
                                engine=pool.engine, unroll=unroll)
        steps0, work0 = _progress(pool.engine, pool.state)
        out, wall, compile_s = entry.timed_call(pool.ctx, pool.state)
        pool.state = out
        steps1, work1 = _progress(pool.engine, out)
        return RoundTelemetry(wall_s=wall, compile_s=compile_s,
                              adv=steps1 - steps0,
                              work=_delta(work1, work0))

    def placement(self, n_lanes: int) -> str:
        return f"1 device x {n_lanes} vmap lanes"

    def big_lane(self, cfg, ctx, n_roots, cache, budget, engine=None,
                 steps_per_call=1):
        mesh = Mesh(np.array(jax.devices()[:1]), (MBE_LANE_AXIS,))
        return BigGraphLane(self.name, cfg, mesh, MBE_LANE_AXIS,
                            self.big_workers, ctx, n_roots, cache, budget,
                            engine=engine, work_stealing=self.work_stealing,
                            steps_per_call=steps_per_call)


class ShardedExecutor(Executor):
    """Lane pools placed across a 1-D serving mesh.

    The pool's batch axis is sharded over ``axis`` (``wpd = B // n_dev``
    lanes per device) and one bounded round is ONE
    ``make_round_fn(ctx_batched=True, work_stealing=False)`` call — the
    per-lane-graphs layout, where stealing is meaningless because root-task
    indices are graph-local; balancing across lanes is the scheduler's
    refill.  Lane counts are therefore padded up to a multiple of the mesh
    size (pow2 meshes compose with the planner's pow2 promise).  Lane
    surgery re-pins the pool to the mesh sharding after every scatter
    (``replace_lanes(sharding=...)``), so rounds never pay a reshard.

    ``big_workers_per_device`` sizes the big-graph lane: total stealing
    workers = mesh size x that (over-decomposition knob)."""

    name = "sharded"

    def __init__(self, mesh: Mesh, axis: str = MBE_LANE_AXIS,
                 big_workers_per_device: int = 1,
                 work_stealing: bool = True):
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {axis!r}: {mesh.axis_names}")
        self.mesh = mesh
        self.axis = axis
        self.n_devices = int(mesh.shape[axis])
        self.big_workers_per_device = big_workers_per_device
        self.work_stealing = work_stealing

    def _pool_sharding(self):
        return NamedSharding(self.mesh, P(self.axis))

    def plan_lanes(self, n_pending: int, policy: BucketPolicy) -> int:
        base = plan_batch_size(n_pending, policy)
        n_dev = self.n_devices
        b = max(base, n_dev)
        return ((b + n_dev - 1) // n_dev) * n_dev   # divisible placement

    def run_round(self, pool: LanePool, cache: ExecutableCache,
                  budget: int | None, unroll: int = 1) -> RoundTelemetry:
        cfg, B = pool.cfg, pool.B
        wpd = B // self.n_devices
        key = ((self.name, pool.engine.name, self.mesh, self.axis, wpd,
                cfg), B, budget)
        if unroll != 1:
            key = key + (unroll,)
        # the per-device shard is what run_batch sees inside shard_map,
        # so the pool path (and the key extension) is per-device-width
        pw = pool.engine.pool_lanes(cfg, wpd)
        if pw:
            key = key + (("pool", pw),)

        def build():
            dist = dd.DistConfig(
                steps_per_round=(budget if budget is not None
                                 else cfg.max_steps),
                workers_per_device=wpd, work_stealing=False,
                steps_per_call=unroll)
            fn, _, _ = dd.make_round_fn(cfg, self.mesh, (self.axis,), dist,
                                        ctx_batched=True,
                                        with_telemetry=True,
                                        engine=pool.engine)
            return fn

        entry = cache.get_entry(key, build)
        (out, telem), wall, compile_s = entry.timed_call(pool.ctx,
                                                         pool.state)
        pool.state = out
        telem = jax.device_get(telem)
        return RoundTelemetry(
            wall_s=wall, compile_s=compile_s,
            adv=np.asarray(telem["busy_steps"]),
            pending=np.asarray(telem["pending"]),
            work=_widen(telem.get("work")))

    def launches_per_segment(self, pool: LanePool) -> int:
        wpd = pool.B // self.n_devices
        per_dev = 1 if pool.engine.pool_lanes(pool.cfg, wpd) else wpd
        return self.n_devices * per_dev

    def stepwise(self, pool: LanePool) -> bool:
        return pool.engine.stepwise_lanes(pool.cfg,
                                          pool.B // self.n_devices)

    def placement(self, n_lanes: int) -> str:
        wpd = n_lanes // self.n_devices
        return (f"{self.n_devices} devices x {wpd} lanes "
                f"(axis {self.axis!r})")

    def big_lane(self, cfg, ctx, n_roots, cache, budget, engine=None,
                 steps_per_call=1):
        return BigGraphLane(self.name, cfg, self.mesh, self.axis,
                            self.big_workers_per_device, ctx, n_roots,
                            cache, budget, engine=engine,
                            work_stealing=self.work_stealing,
                            steps_per_call=steps_per_call)


class BigGraphLane:
    """One heavy graph served cuMBE-style: root tasks strided across every
    mesh worker, pending tasks stolen at round barriers.

    The round function is ``make_round_fn(ctx_batched=False,
    work_stealing=True, with_telemetry=True)`` — one replicated graph, the
    worker state sharded over the serving axis — cached under a
    backend-qualified key so same-bucket big graphs reuse one executable.
    Per-worker busy-step telemetry accumulates in ``busy_per_worker``: the
    scheduler surfaces it so operators can SEE the heavy graph's subtrees
    spread across workers (the paper's Fig.-5 load-distribution view,
    live)."""

    def __init__(self, backend: str, cfg: ed.EngineConfig, mesh: Mesh,
                 axis: str, workers_per_device: int, ctx,
                 n_roots: int, cache: ExecutableCache, budget: int | None,
                 engine: Engine | None = None, work_stealing: bool = True,
                 steps_per_call: int = 1):
        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        self.engine = engine or DENSE
        n_dev = int(mesh.shape[axis])
        self.n_workers = n_dev * workers_per_device
        self.round_steps = (budget if budget and budget > 0
                            else DEFAULT_BIG_ROUND_STEPS)
        dist = dd.DistConfig(steps_per_round=self.round_steps,
                             workers_per_device=workers_per_device,
                             work_stealing=work_stealing,
                             steps_per_call=steps_per_call)
        key = (("ws", backend, self.engine.name, work_stealing, mesh, axis,
                workers_per_device, cfg),
               self.n_workers, self.round_steps)
        if steps_per_call != 1:
            key = key + (steps_per_call,)

        def build():
            fn, _, _ = dd.make_round_fn(cfg, mesh, (axis,), dist,
                                        ctx_batched=False,
                                        with_telemetry=True,
                                        engine=self.engine)
            return fn

        self._entry = cache.get_entry(key, build)
        # strided initial deal of the REAL root tasks (padding vertices
        # own no subtree); queue capacity T = cfg.m_real, the same bound
        # make_round_fn bakes into the steal re-deal
        T = cfg.m_real
        per = []
        for w in range(self.n_workers):
            tasks = np.arange(w, n_roots, self.n_workers, dtype=np.int32)
            s = self.engine.init_state(cfg, tasks)
            pad = np.full(T, -1, np.int32)
            pad[: tasks.shape[0]] = tasks
            per.append(s._replace(tasks=jnp.asarray(pad)))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
        sh = NamedSharding(mesh, P(axis))
        self.state = jax.tree.map(lambda x: jax.device_put(x, sh), stacked)
        self.ctx = jax.device_put(ctx, NamedSharding(mesh, P()))
        self.busy_per_worker = np.zeros(self.n_workers, np.int64)
        self.rounds = 0

    def run_round(self) -> RoundTelemetry:
        (out, telem), wall, compile_s = self._entry.timed_call(self.ctx,
                                                               self.state)
        self.state = out
        telem = jax.device_get(telem)
        adv = np.asarray(telem["busy_steps"], np.int64)
        self.busy_per_worker += adv
        self.rounds += 1
        return RoundTelemetry(
            wall_s=wall, compile_s=compile_s, adv=adv,
            pending=np.asarray(telem["pending"]),
            work=_widen(telem.get("work")))

    @property
    def done(self) -> bool:
        return bool(np.asarray(self.engine.done(self.state)).all())

    def max_worker_steps(self) -> int:
        return int(np.asarray(self.state.steps).max())

    def worker_state(self, w: int) -> ed.DenseState:
        """Host-readable view of one worker's state (for demux merging)."""
        return jax.tree.map(lambda x, w=w: x[w], self.state)

    def placement(self) -> str:
        n_dev = int(self.mesh.shape[self.axis])
        return (f"{self.n_workers} stealing workers on {n_dev} device(s) "
                f"(axis {self.axis!r}, round={self.round_steps} steps)")
