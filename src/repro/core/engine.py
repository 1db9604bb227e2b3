"""Engine protocol + name registry: one contract over every workload.

The serving stack (``repro.serving``) is workload-generic: buckets,
executable cache, executors, the continuous-batching scheduler and the
big-graph work-stealing lane all drive engines exclusively through this
module's ``Engine`` ABC.  An engine declares:

* **constructors** — ``make_context`` (device-resident graph data;
  ``host_context`` builds the same pytree as NumPy arrays),
  ``init_state`` (the worker-state pytree), ``dummy_context`` (idle
  lanes), ``config`` (bucket-shaped ``EngineConfig``, including any
  engine-specific parameters such as the count engine's ``(p, q)``);
* **the resumable stepper** — ``step``/``run``/``run_batch`` (a generic
  ``lax.while_loop`` driver is provided; engines with fused/resident
  kernel paths override ``run``);
* **the result schema** — ``result_type`` (an ``EngineResult`` variant,
  see ``repro.core.results``) plus the payload hooks ``finish`` /
  ``finish_workers`` / ``partial`` / ``counters`` the scheduler calls at
  demux, big-lane merge and cancel/deadline time.  The scheduler never
  names a concrete result class;
* **routing traits** — ``canonicalize`` (whether admission may transpose
  the graph to |U| <= |V|; counting/unipartite workloads keep the
  submitted orientation) and ``unipartite`` (the engine interprets a
  submission as a symmetric unipartite graph, see
  ``repro.core.graph.unipartite_graph``).

Registered engines (all served through the same pools, cache, sharded
mesh and big-graph work-stealing routes):

* ``dense``   — per-level packed bitmask stacks (the TPU-native MBE
  adaptation; P/Q/R are bitsets, candidate counts come from one dense
  AND+popcount pass).
* ``compact`` — the paper-faithful compact array + level pointers +
  lookup table (cuMBE §III-B).
* ``count``   — (p,q)-biclique counting without materialization
  (``engine_count``): scalar accumulator, no collect buffers.
* ``mce``     — maximal clique enumeration on unipartite graphs
  (``engine_mce``): Bron–Kerbosch over the same bitsets and stealing
  layout.

State-pytree contract: every engine state is a NamedTuple pytree whose
*shared* fields are the task queue (``tasks``/``n_tasks``/``tpos``), the
DFS level ``lvl`` (-1 = between tasks) and the counters
``steps``/``nodes``.  Those are the only fields the executors and the
work-stealing re-deal in ``distributed.make_round_fn`` touch: done-masks
come from ``Engine.done``, lane surgery (``replace_lane``/
``replace_lanes``) is a pytree row scatter, and everything else
(bitmask stacks vs compact arrays vs a bare accumulator) stays behind
the engine's own hooks.

The MBE engines share ``EngineConfig`` and the collect-buffer scalar
tail (``n_max``/``cs``/``out_n``/``out_l``/``out_r``); both enumerate
the same maximal bicliques with the same order-independent fingerprint
(``cs``); ``steps``/``nodes`` may differ between the engines, so
"byte-identical" claims compare ``(n_max, cs)`` and decoded biclique
sets, never step counts.

Registry: ``register_engine`` installs an engine under its ``name``
(duplicate names raise — pass ``override=True`` to swap in a tuned
variant deliberately), ``get_engine`` resolves names (``ValueError``
naming the available engines on a miss), ``list_engines`` lists them.
The built-in ``count``/``mce`` engines register lazily on first lookup
so importing this module stays cycle-free.
"""
from __future__ import annotations

import abc
import dataclasses
import importlib

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import engine_compact as ec
from repro.core import engine_dense as ed
from repro.core.engine_dense import EngineConfig
from repro.core.graph import BipartiteGraph
from repro.core.results import (CliqueResult, CountResult, EngineResult,
                                MBEResult)

_U32_MOD = 1 << 32


class Engine(abc.ABC):
    """One workload engine: constructors + resumable stepper + result
    schema.  See the module docstring for the full contract."""

    name: str = "engine"
    result_type: type[EngineResult] = MBEResult
    collectable: bool = True    # engine materializes results into the
    #                             out_* collect buffers (False: ``collect``
    #                             server knobs are inert for this engine)
    canonicalize: bool = True   # admission may transpose to |U| <= |V|
    #                             (False: the workload's semantics depend
    #                             on the submitted orientation)
    unipartite: bool = False    # submissions are symmetric unipartite
    #                             embeds (``graph.unipartite_graph``)

    # -- constructors ---------------------------------------------------
    @abc.abstractmethod
    def make_context(self, g: BipartiteGraph, cfg: EngineConfig):
        """Device-resident graph data (adjacency + orderings)."""

    def host_context(self, g: BipartiteGraph, cfg: EngineConfig):
        """``make_context`` as a pytree of NumPy arrays: the serving
        refill builds lanes' contexts on the host and sends them to the
        device in one transfer.  This default builds on the device and
        copies back; engines that build it in NumPy override it."""
        return jax.device_get(self.make_context(g, cfg))

    @abc.abstractmethod
    def init_state(self, cfg: EngineConfig, tasks: np.ndarray):
        """Fresh worker state owning the given root-task list."""

    @abc.abstractmethod
    def dummy_context(self, cfg: EngineConfig):
        """All-zero context for idle lanes; paired with
        ``fresh_lane_state(cfg, 0)`` the lane is born done and never
        reads it."""

    def config(self, n_u: int, n_v: int, depth: int, *,
               m_real: int | None = None, **kw) -> EngineConfig:
        """Bucket-shaped ``EngineConfig`` — the scheduler's ONE config
        entry point (collect-buffer sizing included).  ``kw`` carries the
        server knobs (``collect_cap``/``order_mode``/``impl``/
        ``kernel_impl``/...) plus any engine-specific parameters; keys
        ``EngineConfig`` does not know are dropped here so one scheduler
        call site can serve every engine (engines consume their own
        params in overrides before delegating)."""
        known = {f.name for f in dataclasses.fields(EngineConfig)}
        kw = {k: v for k, v in kw.items() if k in known}
        return EngineConfig(n_u=n_u, n_v=n_v,
                            m_real=n_u if m_real is None else m_real,
                            depth=depth, **kw)

    def make_config(self, g: BipartiteGraph, **kw) -> EngineConfig:
        """Exact-shape config for one graph (no bucket padding)."""
        return self.config(g.n_u, g.n_v, g.n_u + 2, m_real=g.n_u, **kw)

    def fresh_lane_state(self, cfg: EngineConfig, n_tasks: int):
        """Worker state owning root tasks [0, n_tasks), task queue padded
        to the bucket-wide capacity ``cfg.n_u`` so every serving lane has
        identical shapes (the lane-pool refill unit)."""
        s = self.init_state(cfg, np.arange(n_tasks, dtype=np.int32))
        pad = np.full(cfg.n_u, -1, np.int32)
        pad[:n_tasks] = np.arange(n_tasks, dtype=np.int32)
        return s._replace(tasks=jnp.asarray(pad))

    def fresh_lane_rows(self, cfg: EngineConfig, n_tasks: jax.Array):
        """Traceable ``fresh_lane_state`` over a leading lane axis: row
        ``i`` equals ``fresh_lane_state(cfg, n_tasks[i])``.  That is the
        born-done template ``fresh_lane_state(cfg, 0)`` with the task
        queue and its length set, so the install executable builds fresh
        lanes on the device.  An engine whose fresh state depends on the
        task count in any other field overrides this."""
        rows = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_tasks.shape[0], *x.shape)),
            self.fresh_lane_state(cfg, 0))
        t = jnp.arange(cfg.n_u, dtype=jnp.int32)
        n = n_tasks.astype(jnp.int32)
        return rows._replace(tasks=jnp.where(t < n[:, None], t, -1),
                             n_tasks=n)

    # -- execution ------------------------------------------------------
    @abc.abstractmethod
    def step(self, ctx, cfg: EngineConfig, s):
        """One engine loop iteration."""

    def run(self, ctx, cfg: EngineConfig, s, max_steps: int | None = None,
            unroll: int = 1):
        """Run until done or the (resumable-round) step budget expires.

        Generic ``lax.while_loop`` driver over ``step``; ``unroll``
        advances up to that many engine steps per while-loop iteration
        (multi-step compiled segments; byte-identical — steps 2..unroll
        are guarded by the same done/budget predicate the loop condition
        checks).  Engines with fused/VMEM-resident kernel paths override
        this with their specialized loops."""
        budget = cfg.max_steps if max_steps is None else max_steps
        start = s.steps

        def active(st):
            return (~self.done(st)) & (st.steps - start < budget)

        def body(st):
            st = self.step(ctx, cfg, st)    # cond guarantees the first
            for _ in range(unroll - 1):
                st = jax.lax.cond(active(st),
                                  lambda t: self.step(ctx, cfg, t),
                                  lambda t: t, st)
            return st

        return jax.lax.while_loop(active, body, s)

    def run_batch(self, ctx, cfg: EngineConfig, s,
                  max_steps: int | None = None, ctx_batched: bool = False,
                  unroll: int = 1):
        """``run`` over a leading batch axis (``ctx_batched=True`` = one
        graph per lane — the serving layout; False = one shared graph,
        many workers — the distributed layout)."""
        ax = 0 if ctx_batched else None
        return jax.vmap(
            lambda c, st: self.run(c, cfg, st, max_steps=max_steps,
                                   unroll=unroll),
            in_axes=(ax, 0))(ctx, s)

    def pool_lanes(self, cfg: EngineConfig, batch: int) -> int:
        """Pool width this engine's ``run_batch`` would run ``batch``
        lanes at via a multi-lane resident kernel (one launch per pool),
        or 0 for the legacy one-launch-per-lane layout.  The cache and
        executors extend executable keys with ``("pool", width)`` ONLY
        when this is nonzero, so engines without a pool path keep their
        legacy keys byte-for-byte."""
        return 0

    def stepwise_lanes(self, cfg: EngineConfig, batch: int) -> bool:
        """Whether ``run_batch`` advances ``batch`` lanes on the dense
        engine's lane-masked per-step loop; the serving layer counts
        the steps such rounds advance (``stats()['stepwise_steps']``).
        False for engines that have no such loop."""
        return False

    def work_rows(self, s) -> dict | None:
        """Adjacency rows each kernel pass of this engine has had to
        read so far, per worker: ``{pass: int32 array}`` over the state's
        leading axes, counted on the device whatever kernel path runs.
        The serving layer reads the round's deltas with its ``steps``
        telemetry and reports them as words in ``stats()``
        (``<pass>_words``).  None, the default, for engines that count
        no such work: their state and executables carry nothing extra."""
        return None

    # -- collect / decode hooks ----------------------------------------
    def done(self, s) -> jax.Array:
        """Whether a worker state has finished all its tasks (works
        unbatched or over a leading lane/worker axis)."""
        return (s.lvl < 0) & (s.tpos >= s.n_tasks)

    def collected(self, cfg: EngineConfig, s, n_u: int,
                  n_v: int) -> list[tuple[tuple, tuple]]:
        """Decode the collect buffer into (L members, R members) tuples
        (the MBE engines share the ``out_n``/``out_l``/``out_r``
        layout)."""
        return ed.collected_bicliques(cfg, s, n_u, n_v)

    # -- result schema (the scheduler's ONLY result constructors) -------
    def counters(self, s) -> dict:
        """Host-side scalar progress counters for one worker state (the
        partial-progress payload of cancel/deadline eviction)."""
        return dict(n_max=int(s.n_max), cs=int(s.cs),
                    nodes=int(s.nodes), steps=int(s.steps))

    def stacked_counters(self, stacked) -> dict:
        """``counters`` summed over a leading worker axis (the big-graph
        lane's stacked state).  The fingerprint is an order-independent
        uint32 sum, so worker-wise addition reproduces the serial
        value."""
        return dict(
            n_max=int(np.asarray(stacked.n_max).sum()),
            cs=int(np.asarray(stacked.cs, dtype=np.uint64).sum()
                   % _U32_MOD),
            nodes=int(np.asarray(stacked.nodes).sum()),
            steps=int(np.asarray(stacked.steps).sum()))

    def finish(self, cfg: EngineConfig, s, *, n_u: int, n_v: int,
               swapped: bool = False, collect: bool = False) -> dict:
        """Result payload for ONE completed lane state.  The returned
        dict supplies every ``result_type`` field the scheduler does not
        own (the scheduler adds rid/name/timing/flags and calls
        ``make_result``)."""
        out = self.counters(s)
        out.update(bicliques=None, truncated=False)
        if collect:
            bic = self.collected(cfg, s, n_u, n_v)
            if swapped:     # back to the submitted orientation
                bic = [(R, L) for L, R in bic]
            out["bicliques"] = bic
            out["truncated"] = int(s.n_max) > int(s.out_n)
        return out

    def finish_workers(self, cfg: EngineConfig, stacked, n_workers: int,
                       *, n_u: int, n_v: int, swapped: bool = False,
                       collect: bool = False) -> dict:
        """Result payload for a completed big-graph lane: counters summed
        across the stacked worker states, collect buffers concatenated."""
        out = self.stacked_counters(stacked)
        out.update(bicliques=None, truncated=False)
        if collect:
            bic = []
            truncated = False
            per_n_max = np.asarray(stacked.n_max)
            per_out_n = np.asarray(stacked.out_n)
            for w in range(n_workers):
                ws = jax.tree.map(lambda x, w=w: x[w], stacked)
                bic.extend(self.collected(cfg, ws, n_u, n_v))
                truncated |= int(per_n_max[w]) > int(per_out_n[w])
            if swapped:
                bic = [(R, L) for L, R in bic]
            out["bicliques"] = bic
            out["truncated"] = truncated
        return out

    def partial(self, counters: dict | None,
                cfg: EngineConfig | None = None) -> dict:
        """Result payload for a request that did NOT run to completion
        (cancelled / deadline-expired): the partial counters read from
        the evicted lane (zeros for never-placed requests), nothing
        materialized."""
        c = counters or {}
        return dict(n_max=int(c.get("n_max", 0)), cs=int(c.get("cs", 0)),
                    nodes=int(c.get("nodes", 0)),
                    steps=int(c.get("steps", 0)),
                    bicliques=None, truncated=False)

    def make_result(self, **fields) -> EngineResult:
        """Construct this engine's ``result_type`` from a payload dict
        (``finish``/``finish_workers``/``partial``) merged with the
        scheduler's lifecycle fields."""
        return self.result_type(**fields)

    # -- convenience ----------------------------------------------------
    def enumerate(self, g: BipartiteGraph, order_mode: str = "deg",
                  collect_cap: int = 1, impl: str = "jnp",
                  kernel_impl: str = "auto", **params):
        """Full single-worker run at the exact graph shape; returns the
        final engine state."""
        cfg = self.make_config(g, order_mode=order_mode,
                               collect_cap=collect_cap, impl=impl,
                               kernel_impl=kernel_impl, **params)
        ctx = self.make_context(g, cfg)
        s0 = self.init_state(cfg, np.arange(g.n_u, dtype=np.int32))
        out = jax.jit(lambda st: self.run(ctx, cfg, st))(s0)
        assert bool(self.done(out)), "step budget exhausted"
        return out

    def __repr__(self) -> str:  # registry debugging
        return f"<Engine {self.name!r}>"


class DenseEngine(Engine):
    """TPU-native bitmask-stack engine (``engine_dense``)."""

    name = "dense"

    def make_context(self, g, cfg):
        return ed.make_context(g, cfg)

    def host_context(self, g, cfg):
        return ed.host_context(g, cfg)

    def init_state(self, cfg, tasks):
        return ed.init_state(cfg, tasks)

    def dummy_context(self, cfg):
        return ed.GraphContext(
            adj=jnp.zeros((cfg.n_u, cfg.wv), jnp.uint32),
            order=jnp.zeros((cfg.n_u,), jnp.int32),
            rank=jnp.zeros((cfg.n_u,), jnp.int32),
            l_root=jnp.zeros((cfg.wv,), jnp.uint32),
            root_counts=jnp.zeros((cfg.n_u,), jnp.int32))

    def step(self, ctx, cfg, s):
        return ed.step(ctx, cfg, s)

    def run(self, ctx, cfg, s, max_steps=None, unroll=1):
        return ed.run(ctx, cfg, s, max_steps=max_steps, unroll=unroll)

    def run_batch(self, ctx, cfg, s, max_steps=None, ctx_batched=False,
                  unroll=1):
        return ed.run_batch(ctx, cfg, s, max_steps=max_steps,
                            ctx_batched=ctx_batched, unroll=unroll)

    def pool_lanes(self, cfg, batch):
        return ed.pool_lanes(cfg, batch)

    def stepwise_lanes(self, cfg, batch):
        return ed.stepwise_lanes(cfg, batch)


class CompactEngine(Engine):
    """Paper-faithful compact-array engine (``engine_compact``)."""

    name = "compact"

    def make_context(self, g, cfg):
        return ec.make_context(g, cfg)

    def host_context(self, g, cfg):
        return ec.host_context(g, cfg)

    def init_state(self, cfg, tasks):
        return ec.init_state(cfg, tasks)

    def dummy_context(self, cfg):
        return ec.CompactContext(
            adj=jnp.zeros((cfg.n_u, cfg.wv), jnp.uint32),
            order=jnp.zeros((cfg.n_u,), jnp.int32),
            p_static=jnp.zeros((cfg.n_u,), jnp.int32),
            lk_static=jnp.zeros((cfg.n_u,), jnp.int32),
            q_static=jnp.zeros((cfg.n_u,), jnp.int32),
            l_root=jnp.zeros((cfg.wv,), jnp.uint32))

    def step(self, ctx, cfg, s):
        return ec.step(ctx, cfg, s)

    def run(self, ctx, cfg, s, max_steps=None, unroll=1):
        return ec.run(ctx, cfg, s, max_steps=max_steps, unroll=unroll)

    def work_rows(self, s):
        return dict(gathered_select=s.sel_rows, gathered_check=s.chk_rows)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Engine] = {}

# built-in engines that register themselves on import; loaded lazily so
# this module (which they import) stays cycle-free
_BUILTIN_MODULES = ("repro.core.engine_count", "repro.core.engine_mce")
_builtins_loaded = False


def _load_builtins() -> None:
    global _builtins_loaded
    if _builtins_loaded:
        return
    _builtins_loaded = True
    for mod in _BUILTIN_MODULES:
        importlib.import_module(mod)


def register_engine(engine: Engine, *, override: bool = False) -> Engine:
    """Register an engine under its ``name``.

    Duplicate names raise ``ValueError`` — a silent last-wins overwrite
    turns an accidental name collision into wrong results served under a
    familiar name.  Pass ``override=True`` to deliberately swap in a
    tuned variant; re-registering the SAME instance is a no-op (import
    idempotence)."""
    prev = _REGISTRY.get(engine.name)
    if prev is not None and prev is not engine and not override:
        raise ValueError(
            f"engine {engine.name!r} is already registered ({prev!r}); "
            f"pass override=True to replace it")
    _REGISTRY[engine.name] = engine
    return engine


def get_engine(engine: str | Engine) -> Engine:
    """Resolve a registry name (or pass an ``Engine`` instance through).
    Unknown names raise ``ValueError`` listing the available engines."""
    if isinstance(engine, Engine):
        return engine
    if engine not in _REGISTRY:
        _load_builtins()
    try:
        return _REGISTRY[engine]
    except KeyError:
        raise ValueError(f"unknown engine {engine!r}; available engines: "
                         f"{list_engines()}") from None


def list_engines() -> list[str]:
    """Names of every registered engine (built-ins included)."""
    _load_builtins()
    return sorted(_REGISTRY)


DENSE = register_engine(DenseEngine())
COMPACT = register_engine(CompactEngine())
