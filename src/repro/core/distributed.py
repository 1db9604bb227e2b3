"""Distributed MBE: coarse-grained parallelism + round-based work stealing.

cuMBE's scheduling, mapped to SPMD TPU semantics (DESIGN.md §2):

* **coarse-grained parallelism** — first-level subtrees (root tasks in the
  global degeneracy order) are the unit of work; cuMBE assigns them to
  thread blocks via an atomic counter on a global candidate set P_g. Here
  the workers are mesh devices (× an optional vmap'd worker batch per
  device, standing in for multiple TBs per SM).
* **k-level work stealing** — a TPU is lockstep-SPMD: an idle device cannot
  asynchronously steal. The DFS therefore runs in bounded *rounds*
  (``steps_per_round`` while-loop iterations); at the end of each round all
  workers hit a collective barrier (the `grid.sync()` analog) where the
  pending root-task queues are all-gathered and re-dealt round-robin across
  workers. Thieves are workers that drained their queue mid-round; victims
  donate their *unstarted* tasks — exactly the paper's semantics with the
  steal granularity k=1 plus over-decomposition (several tasks per worker
  per round) standing in for k=2 fine-graining. An in-flight subtree stays
  on its worker (shipping a DFS stack across ICI costs more than finishing
  it).
* the ``noWS`` ablation (benchmarks, paper Fig. 5/6) disables the re-deal:
  static strided assignment only.

The round function is one jitted ``shard_map``; the host driver loops
rounds until every worker reports done, recording per-round per-worker
busy-step counts — the data behind the Fig.-5 load-distribution analysis.

**Batch axis** — the round function is parameterized over a leading batch
axis rather than assuming one graph: per-device execution goes through
``engine_dense.run_batch``, whose ``ctx_batched`` flag selects between one
replicated graph shared by all workers (this module's default, cuMBE's
setting) and one graph *per worker lane* (the multi-graph serving layout,
``repro.serving``).  Work stealing requires the shared-graph layout — root
task indices are graph-local, so stealing across lanes that hold different
graphs would be meaningless; ``make_round_fn`` enforces this.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import engine_dense as ed
from repro.core.graph import BipartiteGraph


@dataclasses.dataclass(frozen=True)
class DistConfig:
    steps_per_round: int = 4096     # work-stealing barrier period
    workers_per_device: int = 1     # vmap'd worker batch (TBs per SM analog)
    work_stealing: bool = True      # False = noWS ablation
    max_rounds: int = 10_000
    steps_per_call: int = 1         # engine-loop inner unroll: steps per
    #                                 while-loop iteration inside the round
    #                                 (multi-step compiled segments; the
    #                                 in-graph early exit is preserved, so
    #                                 results are byte-identical)


def _flatten_pending(all_tasks: jax.Array, all_tpos: jax.Array,
                     all_ntask: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(W, T) queues + cursors -> (W*T,) flat pending list + total count."""
    W, T = all_tasks.shape
    n_pend = all_ntask - all_tpos                    # (W,)
    offs = jnp.cumsum(n_pend) - n_pend               # (W,)
    pos = jnp.arange(T)[None, :]                     # (1, T)
    src_idx = all_tpos[:, None] + pos                # (W, T)
    valid = pos < n_pend[:, None]
    gathered = jnp.take_along_axis(
        all_tasks, jnp.minimum(src_idx, T - 1), axis=1)
    dst = jnp.where(valid, offs[:, None] + pos, W * T)
    flat = jnp.full((W * T,), -1, jnp.int32)
    flat = flat.at[dst.reshape(-1)].set(gathered.reshape(-1), mode="drop")
    return flat, jnp.sum(n_pend)


def _deal_strided(flat: jax.Array, total: jax.Array, w: jax.Array,
                  n_workers: int, T: int) -> tuple[jax.Array, jax.Array]:
    """Worker w takes flat[w::n_workers] — round-robin deal."""
    j = jnp.arange(T)
    src = j * n_workers + w
    take = src < total
    tasks = jnp.where(take, flat[jnp.minimum(src, flat.shape[0] - 1)], -1)
    n = jnp.sum(take).astype(jnp.int32)
    return tasks.astype(jnp.int32), n


def make_round_fn(cfg: ed.EngineConfig, mesh: Mesh,
                  axis_names: tuple[str, ...],
                  dist: DistConfig = DistConfig(),
                  ctx_batched: bool = False,
                  with_telemetry: bool = False,
                  engine=None):
    """The jitted work-stealing round: (ctx, state) -> state.

    Graph context is an explicit argument (replicated over the mesh), so
    no 32 MiB adjacency constant is baked into the HLO.

    ``ctx_batched=False`` (default): one graph, replicated; every worker
    lane runs its task slice of that graph and pending tasks are stolen
    across lanes at the round barrier.  ``ctx_batched=True``: the context
    leaves carry a leading worker axis (one graph per lane, sharded like
    the state) — the multi-graph serving layout; work stealing must be off
    because root-task indices are graph-local.

    ``with_telemetry=True`` changes the signature to
    ``(ctx, state) -> (state, telemetry)`` where telemetry is a dict of
    per-worker ``(W,)`` arrays computed in-graph:

    * ``busy_steps`` — engine steps each worker actually advanced this
      round (its slice of the round's work, the Fig.-5 load data), and
    * ``pending``    — unstarted root tasks left in each worker's queue
      AFTER the steal re-deal (what a scheduler needs to decide whether
      the lane is starving or saturated), and
    * for engines that count kernel work (``Engine.work_rows``),
      ``work`` — the rows each worker's kernel passes had to read this
      round.

    The serving executors consume the telemetry form; the classic driver
    keeps the bare-state form for backward compatibility.

    ``engine`` is an ``repro.core.engine.Engine`` (default: the dense
    engine).  The round works for any registered engine because the
    steal re-deal only touches the task-queue fields (``tasks``/
    ``n_tasks``/``tpos``) and the step counter — part of the shared
    engine contract.
    """
    if engine is None:
        from repro.core.engine import DENSE as engine
    if ctx_batched and dist.work_stealing:
        raise ValueError("work stealing requires a shared graph context: "
                         "task indices are graph-local (set "
                         "work_stealing=False for per-lane graphs)")
    n_dev = int(np.prod([mesh.shape[a] for a in axis_names]))
    wpd = dist.workers_per_device
    n_workers = n_dev * wpd
    T = cfg.m_real  # queue capacity: every worker could end up with all roots

    def _per_device(ctx: ed.GraphContext, s: ed.DenseState):
        # s leaves have leading dim = workers_per_device
        steps_before = s.steps
        work_before = engine.work_rows(s)
        s = engine.run_batch(ctx, cfg, s, max_steps=dist.steps_per_round,
                             ctx_batched=ctx_batched,
                             unroll=dist.steps_per_call)
        busy = s.steps - steps_before                    # (wpd,)
        if dist.work_stealing:
            # ---- work-stealing barrier -------------------------------
            ax = axis_names if len(axis_names) > 1 else axis_names[0]
            all_tasks = jax.lax.all_gather(s.tasks, ax, axis=0, tiled=True)
            all_tpos = jax.lax.all_gather(s.tpos, ax, axis=0, tiled=True)
            all_ntask = jax.lax.all_gather(s.n_tasks, ax, axis=0, tiled=True)
            flat, total = _flatten_pending(
                all_tasks.reshape(n_workers, T),
                all_tpos.reshape(n_workers),
                all_ntask.reshape(n_workers))
            dev_id = jax.lax.axis_index(ax)
            w_ids = dev_id * wpd + jnp.arange(wpd)
            new_tasks, new_n = jax.vmap(
                lambda w: _deal_strided(flat, total, w, n_workers, T))(w_ids)
            s = s._replace(tasks=new_tasks, n_tasks=new_n,
                           tpos=jnp.zeros((wpd,), jnp.int32))
        if not with_telemetry:
            return s
        telem = dict(busy_steps=busy, pending=s.n_tasks - s.tpos)
        if work_before is not None:
            telem["work"] = {k: v - work_before[k]
                             for k, v in engine.work_rows(s).items()}
        return s, telem

    spec_leaf = P(axis_names)
    ctx_spec = spec_leaf if ctx_batched else P()
    out_spec = (spec_leaf, spec_leaf) if with_telemetry else spec_leaf

    @jax.jit
    def round_fn(ctx: ed.GraphContext, state: ed.DenseState):
        return jax.shard_map(
            _per_device, mesh=mesh, in_specs=(ctx_spec, spec_leaf),
            out_specs=out_spec, check_vma=False)(ctx, state)

    return round_fn, n_workers, T


def make_distributed_runner(
        g: BipartiteGraph, cfg: ed.EngineConfig, mesh: Mesh,
        axis_names: tuple[str, ...], dist: DistConfig = DistConfig()):
    """Build (init_states, round_fn, driver) for the given mesh axes.

    ``axis_names`` lists the mesh axes the worker dimension is sharded over
    (their total size = number of devices participating).
    """
    ctx = ed.make_context(g, cfg)
    round_fn_core, n_workers, T = make_round_fn(cfg, mesh, axis_names, dist)
    wpd = dist.workers_per_device

    def init_states() -> ed.DenseState:
        """Strided initial assignment of the m_real root tasks."""
        per = []
        for w in range(n_workers):
            tasks = np.arange(w, cfg.m_real, n_workers, dtype=np.int32)
            s = ed.init_state(cfg, tasks)
            pad = np.full(T, -1, np.int32)
            pad[: tasks.shape[0]] = tasks
            s = s._replace(tasks=jnp.asarray(pad))
            per.append(s)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
        sh = NamedSharding(mesh, P(axis_names))  # dim0 over all named axes
        return jax.tree.map(lambda x: jax.device_put(x, sh), stacked)

    def round_fn(state: ed.DenseState) -> ed.DenseState:
        return round_fn_core(ctx, state)

    def driver(state: ed.DenseState | None = None, verbose: bool = False):
        """Run rounds to completion. Returns (final_state, round_log)."""
        if state is None:
            state = init_states()
        log = []
        prev_steps = np.zeros(n_workers, np.int64)
        for r in range(dist.max_rounds):
            state = round_fn(state)
            steps = np.asarray(state.steps, np.int64)
            busy = steps - prev_steps
            prev_steps = steps
            done = np.asarray((state.lvl < 0) & (state.tpos >= state.n_tasks))
            log.append(dict(round=r, busy=busy.copy(),
                            done=int(done.sum()),
                            n_max=int(np.asarray(state.n_max).sum())))
            if verbose:
                print(f"round {r}: done {int(done.sum())}/{n_workers} "
                      f"nMB={log[-1]['n_max']}")
            if bool(done.all()):
                break
        return state, log

    return init_states, round_fn, driver


def totals(state: ed.DenseState) -> dict:
    """Aggregate counters across the worker dimension."""
    return dict(
        n_max=int(np.asarray(state.n_max, np.int64).sum()),
        cs=int(np.asarray(state.cs, np.uint64).sum() % (1 << 32)),
        nodes=int(np.asarray(state.nodes, np.int64).sum()),
        steps=np.asarray(state.steps, np.int64),
    )
