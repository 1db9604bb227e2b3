"""Dense-bitset MBE engine (TPU-native adaptation of cuMBE).

This is the paper's recursion-free DFS re-expressed for a vector unit:

* cuMBE's **compact array + level pointers** become per-level packed bitmask
  stacks (``lmask/pmask/qmask/rmask``) inside a ``lax.while_loop`` — all
  shapes static, zero dynamic allocation, O(|U|+|V|) words per level and
  O(depth) levels, exactly the paper's space bound.
* cuMBE's **lookup table** becomes an O(1) bit test.
* cuMBE's **reverse scanning** (phases C/E share per-candidate
  |N(v) ∩ L'| counts) becomes ONE dense AND+popcount pass over the whole
  adjacency (the ``intersect_count`` kernel) whose result serves the
  maximality check, the maximal expansion AND the paper's Q' filter at no
  extra cost.
* cuMBE's **early-stop candidate selection** becomes a fused masked argmin
  over the same counts pass (degeneracy order, recomputed per level like the
  paper's per-level re-selection).

**Kernel paths** (``EngineConfig.kernel_impl``, DESIGN.md §8): the
``"jnp"`` path issues the passes above as separate XLA ops
(``intersect_count`` + elementwise/reduce); ``"pallas"`` collapses each
candidate branch into the fused step kernels — ``fused_select`` (counts +
masked argmin, one VMEM-resident pass) and ``fused_check`` (Q-violation
flag + full/partial expansion partition + Q' filter + optional cstack
counts refill in one pass, so a ``deg`` branch costs exactly ONE fused
call).  ``"auto"`` picks pallas on TPU and jnp elsewhere; both paths are
byte-identical (``tests/test_fused_engines.py``).

The engine is *task-driven*: a worker owns a list of first-level subtrees
(root candidates), matching cuMBE's coarse-grained decomposition. Task i of
the global root order sees Q = roots before i and P = roots after i — the
exact state Algorithm 1 has when popping root i, so a single worker running
all tasks in order is bit-identical to the serial enumeration, and disjoint
task lists across workers partition the search space (the distributed
runner's unit of work stealing).

**Serving / batching** (see ``repro.serving``): ``run_batch`` lifts the
engine over a leading batch axis.  The same compiled loop serves two
layouts — many workers sharing one graph (the distributed runner's
per-device worker batch) or one worker per graph across a *shape bucket*
of different graphs padded to a common ``(n_u, n_v, depth)`` (the batched
multi-graph serving layer).  Because every shape is static, the compiled
executable is reusable for any batch of graphs in the same bucket.

Registered as ``"dense"`` in ``repro.core.engine``; the public entry
point is ``repro.api.MBEClient`` —
``MBEClient(MBEOptions()).enumerate(g)`` serves this engine through the
bucketed/cached production path, while ``enumerate_dense`` below remains
the exact-shape direct call.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bitset
from repro.core.graph import BipartiteGraph
from repro.kernels.dispatch import resolve_impl
from repro.kernels.fused_check.ops import fused_check_packed
from repro.kernels.fused_select.ops import fused_select_packed
from repro.kernels.intersect_count.ops import intersect_count
from repro.kernels.resident_pool.ops import (resident_pool_segment,
                                             resident_pool_supported)
from repro.kernels.resident_step.ops import (resident_segment,
                                             resident_supported)

_INF = np.int32(0x7FFFFFFF)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_u: int                    # padded |U| (array dim)
    n_v: int                    # padded |V|
    m_real: int                 # real |U| (= number of root tasks)
    depth: int                  # recursion depth bound (n_u + 2 is safe)
    collect_cap: int = 1        # biclique output buffer rows
    order_mode: str = "deg"     # 'deg' (paper ordering, cached counts)
    #                             | 'deg_nocache' (recompute per node — the
    #                             paper-faithful two-pass baseline)
    #                             | 'input' (noES ablation)
    impl: str = "jnp"           # intersect_count impl on the unfused path
    #                             ('jnp'|'pallas'|'auto')
    kernel_impl: str = "auto"   # step-kernel path: 'jnp' = unfused
    #                             reference ops, 'pallas' = the fused
    #                             fused_select/fused_check kernels (one
    #                             adjacency pass per branch; interpret
    #                             mode off-TPU), 'auto' = pallas on TPU,
    #                             jnp elsewhere (kernels.dispatch)
    max_steps: int = 1 << 30    # safety/round bound on loop iterations
    resident: bool = True       # pallas path only: back run/run_batch
    #                             with the VMEM-resident multi-step
    #                             segment kernel (kernels.resident_step)
    #                             whenever the state fits its VMEM budget;
    #                             False pins the per-step fused kernels
    #                             (DESIGN.md §9)
    count_pq: tuple[int, int] = (2, 2)   # the 'count' engine's (p, q)
    #                             parameters (repro.core.engine_count);
    #                             inert for the enumeration engines but
    #                             part of the shared config so it rides
    #                             the executable-cache key like every
    #                             other semantic knob
    resident_lanes: int | str = "auto"   # pallas+resident path only: back
    #                             run_batch with the multi-lane pool
    #                             kernel (kernels.resident_pool — one
    #                             launch per pool, grid over lanes).
    #                             'auto' = whenever the per-cell gate
    #                             passes; int k >= 2 = only for pools up
    #                             to k lanes; 0/1 = never (legacy
    #                             vmap-of-single-lane)
    resident_rebalance: bool = False     # pool path only: at each segment
    #                             boundary, reassign surplus step budget
    #                             from finished lanes to busy ones via
    #                             the kernel's scoreboard (host-side
    #                             first iteration of in-kernel stealing).
    #                             Off by default — it intentionally
    #                             diverges from the fixed-budget vmap
    #                             trajectory

    @property
    def fused(self) -> bool:
        """Whether branches take the fused Pallas step-kernel path
        (resolved at trace time — 'auto' is backend-dependent)."""
        return resolve_impl(self.kernel_impl) == "pallas"

    @property
    def resident_active(self) -> bool:
        """Whether ``run`` backs its loop with the resident segment
        kernel: pallas path, opted in, and the state fits VMEM."""
        return self.fused and self.resident and resident_supported(self)

    @property
    def wu(self) -> int:
        return bitset.n_words(self.n_u)

    @property
    def wv(self) -> int:
        return bitset.n_words(self.n_v)


class GraphContext(NamedTuple):
    """Device-resident graph data shared by all workers."""
    adj: jax.Array      # (NU, WV) uint32
    order: jax.Array    # (NU,) int32: root order (degree-ascending), -1 pad
    rank: jax.Array     # (NU,) int32: rank[v] = position of v in order;
    #                     padding vertices get rank = 2*NU (never in P/Q)
    l_root: jax.Array   # (WV,) uint32: all real V vertices
    root_counts: jax.Array  # (NU,) int32: |N(v) & l_root| = degree — the
    #                     level-0 entry of the counts cache, free at setup


class DenseState(NamedTuple):
    lmask: jax.Array    # (D, WV) u32
    cstack: jax.Array   # (D, NU) i32: |N(v) & lmask[lvl]| counts cache —
    #                     level lvl's selection reads it; the child level
    #                     inherits the expansion pass (c2) for free, so
    #                     candidate selection costs ZERO adjacency passes
    #                     (beyond-paper: the GPU paper re-scans P with
    #                     early stops every selection)
    pmask: jax.Array    # (D, WU) u32
    qmask: jax.Array    # (D, WU) u32
    rmask: jax.Array    # (D, WU) u32
    xstack: jax.Array   # (D,) i32
    lvl: jax.Array      # i32 (-1 = between tasks)
    forced_x: jax.Array  # i32 (-1 = none): root candidate override
    tasks: jax.Array    # (T,) i32 indices into global root order
    n_tasks: jax.Array  # i32
    tpos: jax.Array     # i32
    steps: jax.Array    # i32 loop iterations (all branches)
    nodes: jax.Array    # i32 candidate visits (search-tree nodes)
    n_max: jax.Array    # i32 maximal bicliques found
    max_fail: jax.Array  # i32 maximality-check failures
    cs: jax.Array       # u32 enumeration fingerprint
    out_n: jax.Array    # i32
    out_l: jax.Array    # (C, WV) u32
    out_r: jax.Array    # (C, WU) u32


# ---------------------------------------------------------------------------
# host-side setup
# ---------------------------------------------------------------------------

def host_context(g: BipartiteGraph, cfg: EngineConfig) -> GraphContext:
    """``make_context`` as NumPy arrays, built on the host with no device
    dispatch (the serving refill stacks these rows and sends them in one
    transfer)."""
    assert g.n_u <= cfg.n_u and g.n_v <= cfg.n_v
    # Packed rows are PREFIX-COMPATIBLE under padding: bit v lives at word
    # v//32 regardless of the total word count, so padding n_v only appends
    # zero words and padding n_u only appends zero rows.  A zero-extended
    # word copy of g.adj_u is therefore byte-identical to re-packing — the
    # old Python edge-list round-trip (BipartiteGraph.from_edges over
    # g.edges) cost O(|E|) interpreted work on EVERY bucketed admission,
    # i.e. nearly every request on the serving path.
    adj = np.zeros((cfg.n_u, cfg.wv), dtype=np.uint32)
    src_rows = np.asarray(g.adj_u, dtype=np.uint32)
    adj[: g.n_u, : src_rows.shape[1]] = src_rows
    # Host-side vectorized degree: one popcount pass over the packed rows
    # (a per-row jnp round-trip here costs O(n_u) device dispatches per
    # admitted graph — a real per-request cost on the serving path).
    deg = np.unpackbits(adj[: g.n_u].view(np.uint8), axis=1) \
        .sum(axis=1, dtype=np.int64)
    order_real = np.argsort(deg, kind="stable").astype(np.int32)
    order = np.full(cfg.n_u, -1, dtype=np.int32)
    order[:g.n_u] = order_real
    rank = np.full(cfg.n_u, 2 * cfg.n_u, dtype=np.int32)
    rank[order_real] = np.arange(g.n_u, dtype=np.int32)
    l_root = np.zeros(cfg.wv, dtype=np.uint32)
    l_root[:] = 0
    fm = bitset.full_mask(g.n_v)
    l_root[: fm.shape[0]] = fm
    rc = np.zeros(cfg.n_u, dtype=np.int32)
    rc[: g.n_u] = deg.astype(np.int32)
    return GraphContext(adj=adj, order=order, rank=rank, l_root=l_root,
                        root_counts=rc)


def make_context(g: BipartiteGraph, cfg: EngineConfig) -> GraphContext:
    return jax.tree.map(jnp.asarray, host_context(g, cfg))


def init_state(cfg: EngineConfig, tasks: np.ndarray) -> DenseState:
    """Fresh worker state with a task list (indices into the root order)."""
    t = np.full(max(len(tasks), 1), -1, dtype=np.int32)
    t[: len(tasks)] = np.asarray(tasks, dtype=np.int32)
    D, WU, WV, C = cfg.depth, cfg.wu, cfg.wv, cfg.collect_cap
    z32 = jnp.int32(0)
    return DenseState(
        lmask=jnp.zeros((D, WV), jnp.uint32),
        cstack=jnp.zeros((D, cfg.n_u), jnp.int32),
        pmask=jnp.zeros((D, WU), jnp.uint32),
        qmask=jnp.zeros((D, WU), jnp.uint32),
        rmask=jnp.zeros((D, WU), jnp.uint32),
        xstack=jnp.full((D,), -1, jnp.int32),
        lvl=jnp.int32(-1), forced_x=jnp.int32(-1),
        tasks=jnp.asarray(t), n_tasks=jnp.int32(len(tasks)),
        tpos=z32, steps=z32, nodes=z32, n_max=z32, max_fail=z32,
        cs=jnp.uint32(0), out_n=z32,
        out_l=jnp.zeros((C, WV), jnp.uint32),
        out_r=jnp.zeros((C, WU), jnp.uint32),
    )


# ---------------------------------------------------------------------------
# the three while-loop branches — emitting row DELTAS, not whole states
#
# A lax.switch whose branches return the full DenseState makes XLA copy
# every (depth x N) stack through each branch (measured: 4 x 8.4 MB per
# engine step on the cumbe-16k config, ~22% of the step's HBM bytes).
# Each branch writes at most one row per stack (two for pmask), so the
# branches emit a fixed-schema Delta and the stacks are updated ONCE
# outside the switch; unmodified stacks flow through the while loop
# aliased, copy-free. (EXPERIMENTS §Perf iter C3.)
# ---------------------------------------------------------------------------

class Delta(NamedTuple):
    l_row: jax.Array    # (WV,) u32   lmask write
    l_idx: jax.Array
    l_en: jax.Array
    c_row: jax.Array    # (NU,) i32   cstack write
    pa_row: jax.Array   # (WU,) u32   pmask write A (current level)
    pa_idx: jax.Array
    pa_en: jax.Array
    pb_row: jax.Array   # (WU,) u32   pmask write B (child / task init)
    q_row: jax.Array    # (WU,) u32   qmask write
    q_idx: jax.Array
    q_en: jax.Array
    r_row: jax.Array    # (WU,) u32   rmask write
    x_val: jax.Array    # xstack scalar write
    x_idx: jax.Array
    x_en: jax.Array
    child: jax.Array    # shared index for l/c/pb/r writes
    lvl: jax.Array      # new scalar state
    forced_x: jax.Array
    tpos: jax.Array
    nodes_inc: jax.Array
    n_max_inc: jax.Array
    max_fail_inc: jax.Array
    cs_inc: jax.Array
    ow_l: jax.Array     # (WV,) u32  collect-buffer write
    ow_r: jax.Array     # (WU,) u32
    ow_en: jax.Array


def _delta_zeros(cfg: EngineConfig, s: DenseState) -> Delta:
    z = jnp.int32(0)
    f = jnp.bool_(False)
    return Delta(
        l_row=jnp.zeros((cfg.wv,), jnp.uint32), l_idx=z, l_en=f,
        c_row=jnp.zeros((cfg.n_u,), jnp.int32),
        pa_row=jnp.zeros((cfg.wu,), jnp.uint32), pa_idx=z, pa_en=f,
        pb_row=jnp.zeros((cfg.wu,), jnp.uint32),
        q_row=jnp.zeros((cfg.wu,), jnp.uint32), q_idx=z, q_en=f,
        r_row=jnp.zeros((cfg.wu,), jnp.uint32),
        x_val=jnp.int32(-1), x_idx=z, x_en=f, child=z,
        lvl=s.lvl, forced_x=s.forced_x, tpos=s.tpos,
        nodes_inc=z, n_max_inc=z, max_fail_inc=z, cs_inc=jnp.uint32(0),
        ow_l=jnp.zeros((cfg.wv,), jnp.uint32),
        ow_r=jnp.zeros((cfg.wu,), jnp.uint32), ow_en=f)


def _row(stack: jax.Array, i: jax.Array) -> jax.Array:
    """``stack[i]`` for a traced level ``i`` (negative ``i`` counts from
    the end, out of range clamps), read as a one-row gather.  Under
    ``vmap`` plain indexing becomes a gather for which a TPU keeps the
    stack in a second layout, so every row write then copied the whole
    stack across (DESIGN.md §11)."""
    i = jnp.where(i < 0, i + stack.shape[0], i)
    return jnp.take(stack, i, axis=0, mode="clip")


def _branch_backtrack(g: GraphContext, cfg: EngineConfig,
                      s: DenseState) -> Delta:
    nl = s.lvl - 1
    safe = jnp.maximum(nl, 0)
    x = _row(s.xstack, safe)
    q_new = bitset.add(_row(s.qmask, safe), jnp.maximum(x, 0))
    return _delta_zeros(cfg, s)._replace(
        q_row=q_new, q_idx=safe, q_en=nl >= 0, lvl=nl)


def _branch_init_task(g: GraphContext, cfg: EngineConfig,
                      s: DenseState) -> Delta:
    idx = s.tasks[jnp.minimum(s.tpos, s.tasks.shape[0] - 1)]
    x = g.order[jnp.clip(idx, 0, cfg.n_u - 1)]
    in_p = (g.rank > idx) & (g.rank < cfg.m_real)
    in_q = g.rank < idx
    t = jnp.bool_(True)
    return _delta_zeros(cfg, s)._replace(
        l_row=g.l_root, l_idx=jnp.int32(0), l_en=t,
        c_row=g.root_counts,
        pb_row=bitset.from_bool(in_p),
        q_row=bitset.from_bool(in_q), q_idx=jnp.int32(0), q_en=t,
        r_row=jnp.zeros((cfg.wu,), jnp.uint32),
        child=jnp.int32(0),
        lvl=jnp.int32(0), forced_x=x, tpos=s.tpos + 1)


def _branch_candidate(g: GraphContext, cfg: EngineConfig,
                      s: DenseState) -> Delta:
    lvl = s.lvl
    L = _row(s.lmask, lvl)
    pm = _row(s.pmask, lvl)
    q_row = _row(s.qmask, lvl)
    forced = s.forced_x >= 0

    # -- Step 1: candidate selection ------------------------------------
    if cfg.order_mode == "deg":
        # counts cache: level lvl holds |N(v) & lmask[lvl]| already —
        # selection is a cheap packed-masked argmin, zero adjacency
        # passes on EITHER kernel path (the cache is refilled by the
        # check pass)
        x_sel = bitset.masked_argmin(_row(s.cstack, lvl), pm)
    elif cfg.order_mode == "deg_nocache":
        if cfg.fused:
            # one VMEM-resident pass: counts + masked argmin, nothing
            # round-trips to HBM and the activity mask travels PACKED
            # (x_sel is -1 when P is empty, which only happens under a
            # forced root where x_sel is overridden)
            x_sel, _ = fused_select_packed(g.adj, L, pm, impl="pallas")
        else:
            c_sel = intersect_count(g.adj, L, impl=cfg.impl)   # (NU,)
            x_sel = bitset.masked_argmin(c_sel, pm)
    else:  # 'input': no ordering heuristic (noES ablation)
        x_sel = bitset.first_member(pm)
    x = jnp.where(forced, s.forced_x, x_sel)
    pm_after = bitset.remove(pm, jnp.maximum(x, 0))

    # -- Step 2: L' construction ----------------------------------------
    Lp = L & g.adj[x]
    nLp = bitset.count(Lp)
    nonempty = nLp > 0

    # -- Steps 3+4 fused: maximality check against Q + maximal expansion
    # over remaining P.  Both need |N(v) & L'| for every v; the jnp path
    # materializes that counts vector once (c2) and derives the flags
    # with separate elementwise/reduce ops, the pallas path emits the
    # violation flag and the partition flags from ONE kernel pass
    # (fused_check_packed: qmask/pmask rows in, flag WORDS out — no
    # to_bool/from_bool expansion per step) — plus the counts themselves
    # only when the 'deg' cache needs refilling.
    if cfg.fused:
        with_counts = cfg.order_mode == "deg"
        viol_f, fullw, partw, nzw, c2 = fused_check_packed(
            g.adj, Lp, nLp, q_row, pm_after,
            impl="pallas", with_counts=with_counts)
        viol = viol_f & nonempty
        c_row = c2 if with_counts else jnp.zeros((cfg.n_u,), jnp.int32)
        q_keep = nzw
        part_row = partw
        has_part = jnp.any(partw != 0)
    else:
        qb = bitset.to_bool(q_row, cfg.n_u)
        pb = bitset.to_bool(pm_after, cfg.n_u)
        c2 = intersect_count(g.adj, Lp, impl=cfg.impl)         # (NU,)
        viol = jnp.any(qb & (c2 == nLp)) & nonempty
        fullw = bitset.from_bool(pb & (c2 == nLp))
        part_row = bitset.from_bool(pb & (c2 > 0) & (c2 < nLp))
        has_part = jnp.any(part_row != 0)
        c_row = c2
        q_keep = bitset.from_bool(c2 > 0)
    is_max = nonempty & ~viol
    Rp = _row(s.rmask, lvl) | bitset.singleton(x, cfg.wu) | fullw
    has_child = is_max & has_part

    # -- descend / finish -------------------------------------------------
    # after a forced (root-task) candidate, the level-0 P must empty so the
    # task terminates once its subtree is done (other roots are other tasks)
    pm_final = jnp.where(forced, jnp.zeros_like(pm_after), pm_after)
    # paper's Q' filter comes free from the shared counts/check pass:
    q_child = q_row & q_keep
    nl = jnp.where(has_child, lvl + 1, lvl)
    child = jnp.minimum(lvl + 1, cfg.depth - 1)
    # no child: x's subtree is finished -> move x to Q at this level
    q_lvl = bitset.add(q_row, jnp.maximum(x, 0))

    return _delta_zeros(cfg, s)._replace(
        l_row=Lp, l_idx=child, l_en=has_child,
        c_row=c_row,
        pa_row=pm_final, pa_idx=lvl, pa_en=jnp.bool_(True),
        pb_row=part_row,
        q_row=jnp.where(has_child, q_child, q_lvl),
        q_idx=jnp.where(has_child, child, lvl), q_en=jnp.bool_(True),
        r_row=Rp,
        x_val=x, x_idx=lvl, x_en=has_child, child=child,
        lvl=nl, forced_x=jnp.int32(-1),
        nodes_inc=jnp.int32(1),
        n_max_inc=is_max.astype(jnp.int32),
        max_fail_inc=(viol & nonempty).astype(jnp.int32),
        cs_inc=jnp.where(is_max, bitset.pair_checksum(Lp, Rp),
                         jnp.uint32(0)),
        ow_l=Lp, ow_r=Rp, ow_en=is_max)


def _apply_delta(cfg: EngineConfig, s: DenseState, d: Delta) -> DenseState:
    def setrow(stack, row, idx, en):
        # a disabled write goes out of range and is dropped
        i = jnp.where(en, jnp.clip(idx, 0, stack.shape[0] - 1),
                      stack.shape[0])
        return stack.at[i].set(row, mode="drop")

    lmask = setrow(s.lmask, d.l_row, d.l_idx, d.l_en)
    cstack = setrow(s.cstack, d.c_row, d.child, d.l_en | (d.tpos > s.tpos))
    pmask = setrow(s.pmask, d.pa_row, d.pa_idx, d.pa_en)
    pmask = setrow(pmask, d.pb_row, d.child, d.l_en | (d.tpos > s.tpos))
    qmask = setrow(s.qmask, d.q_row, d.q_idx, d.q_en)
    rmask = setrow(s.rmask, d.r_row, d.child, d.l_en | (d.tpos > s.tpos))
    xstack = setrow(s.xstack, d.x_val, d.x_idx, d.x_en)
    C = cfg.collect_cap
    w_idx = jnp.minimum(s.out_n, C - 1)
    write = d.ow_en & (s.out_n < C)
    out_l = setrow(s.out_l, d.ow_l, w_idx, write)
    out_r = setrow(s.out_r, d.ow_r, w_idx, write)
    return s._replace(
        lmask=lmask, cstack=cstack, pmask=pmask, qmask=qmask, rmask=rmask,
        xstack=xstack, lvl=d.lvl, forced_x=d.forced_x, tpos=d.tpos,
        nodes=s.nodes + d.nodes_inc, n_max=s.n_max + d.n_max_inc,
        max_fail=s.max_fail + d.max_fail_inc, cs=s.cs + d.cs_inc,
        out_n=s.out_n + write.astype(jnp.int32),
        out_l=out_l, out_r=out_r)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _case_id(cfg: EngineConfig, s: DenseState) -> jax.Array:
    """0 = backtrack, 1 = init next task, 2 = process a candidate."""
    lvl_safe = jnp.maximum(s.lvl, 0)
    p_empty = bitset.count(_row(s.pmask, lvl_safe)) == 0
    return jnp.where(
        s.lvl < 0, 1,
        jnp.where(p_empty & (s.forced_x < 0), 0, 2)).astype(jnp.int32)


def _done(s: DenseState) -> jax.Array:
    return (s.lvl < 0) & (s.tpos >= s.n_tasks)


_BRANCHES = (_branch_backtrack, _branch_init_task, _branch_candidate)


def step(g: GraphContext, cfg: EngineConfig, s: DenseState) -> DenseState:
    s = s._replace(steps=s.steps + 1)
    delta = jax.lax.switch(
        _case_id(cfg, s),
        [lambda st, b=b: b(g, cfg, st) for b in _BRANCHES],
        s)
    return _apply_delta(cfg, s, delta)


def _masked_step(g: GraphContext, cfg: EngineConfig, s: DenseState,
                 en: jax.Array) -> DenseState:
    """``step`` of one lane of a batch, taken only where ``en``.

    Made to run under ``vmap``: the three branches' deltas are combined
    by a row-sized select on the case id (what a batched ``lax.switch``
    computes anyway), and every row write, scalar and counter of the
    delta is gated by ``en``.  So no select reads a whole stack, and each
    row scatter updates its stack in place.  Where ``en`` is false the
    lane's state comes back bit for bit."""
    s = s._replace(steps=s.steps + en.astype(jnp.int32))
    cid = _case_id(cfg, s)
    d = jax.tree.map(lambda *xs: jax.lax.select_n(cid, *xs),
                     *(b(g, cfg, s) for b in _BRANCHES))

    def keep(new, old):
        return jnp.where(en, new, old)
    d = d._replace(
        l_en=d.l_en & en, pa_en=d.pa_en & en, q_en=d.q_en & en,
        x_en=d.x_en & en, ow_en=d.ow_en & en,
        lvl=keep(d.lvl, s.lvl), forced_x=keep(d.forced_x, s.forced_x),
        tpos=keep(d.tpos, s.tpos),
        nodes_inc=keep(d.nodes_inc, 0), n_max_inc=keep(d.n_max_inc, 0),
        max_fail_inc=keep(d.max_fail_inc, 0),
        cs_inc=keep(d.cs_inc, jnp.uint32(0)))
    return _apply_delta(cfg, s, d)


def run(g: GraphContext, cfg: EngineConfig, s: DenseState,
        max_steps: int | None = None, unroll: int = 1) -> DenseState:
    """Run until all tasks are done or the step budget is exhausted.

    The step budget is what makes the distributed runner's bounded *rounds*
    (work-stealing barrier points) possible — state is resumable.

    ``unroll`` (>= 1) is the multi-step compiled-segment knob
    (``BucketPolicy.steps_per_call`` on the serving path): each while-loop
    iteration advances up to ``unroll`` engine steps instead of one, so
    the per-step loop carry/cond overhead is amortized and XLA fuses
    across consecutive steps.  The in-graph early exit is preserved —
    steps 2..unroll are guarded by the same done/budget predicate the
    loop condition checks, so the step trajectory (and therefore every
    counter and result) is byte-identical to ``unroll=1``.

    On the pallas path (``cfg.resident_active``) the whole unrolled
    segment collapses into ONE launch of the VMEM-resident multi-step
    kernel (``kernels.resident_step``): the lane state stays on-chip for
    all ``unroll`` steps instead of round-tripping HBM between per-step
    kernel calls.  The segment guards every internal step with the same
    predicate, so the trajectory stays byte-identical to the jnp path
    (the differential suite checks every state leaf at every segment
    boundary).
    """
    budget = cfg.max_steps if max_steps is None else max_steps
    start = s.steps

    def active(st):
        return (~_done(st)) & (st.steps - start < budget)

    if cfg.resident_active:
        def body(st):
            return resident_segment(g, cfg, st, start=start, budget=budget,
                                    steps_per_call=unroll)
    else:
        def body(st):
            st = step(g, cfg, st)   # loop cond guarantees the first step
            for _ in range(unroll - 1):
                st = jax.lax.cond(active(st),
                                  lambda t: step(g, cfg, t), lambda t: t,
                                  st)
            return st

    return jax.lax.while_loop(active, body, s)


def pool_lanes(cfg: EngineConfig, batch: int) -> int:
    """Pool width the multi-lane resident kernel would run ``batch``
    lanes at, or 0 when the legacy vmap-of-single-lane path applies.

    The pool path needs the resident pallas path active
    (``fused & resident``), an opted-in ``resident_lanes`` (``'auto'``
    or an int cap >= the batch), and the per-grid-cell VMEM gate
    (``resident_pool_supported`` — per-cell state bytes + single-tile
    adjacency).  The width is all-or-nothing: a pool either advances in
    one launch or falls back entirely, so compiled executables never mix
    the two layouts.
    """
    if batch <= 0 or not (cfg.fused and cfg.resident):
        return 0
    rl = cfg.resident_lanes
    if rl != "auto":
        if int(rl) < 2 or batch > int(rl):
            return 0
    return batch if resident_pool_supported(cfg, batch) else 0


# per-lane donations are clamped well under int32 range before summing,
# so a pool of default-budget (1 << 30) finished lanes cannot overflow
# the surplus accumulator
_REBALANCE_CLAMP = np.int32(1 << 24)


def _rebalance_budgets(start: jax.Array, bud: jax.Array, st: DenseState,
                       board: jax.Array) -> jax.Array:
    """Round-boundary budget rebalance from the pool scoreboard.

    Finished lanes donate their unused budget (``bud - used``, clamped);
    the surplus is split evenly (floor) over busy lanes, so the total
    granted never exceeds the total donated — the step budget is
    conserved.  Finished lanes are frozen at ``used``: their remaining
    budget reads zero in every later round (no double donation) and the
    kernel's done guard keeps them from advancing regardless.
    """
    used = st.steps - start
    finished = board[:, 0] > 0
    rem = jnp.clip(bud - used, 0, _REBALANCE_CLAMP)
    surplus = jnp.sum(jnp.where(finished, rem, 0))
    n_busy = jnp.maximum(jnp.sum((~finished).astype(jnp.int32)), 1)
    grant = surplus // n_busy
    new_bud = jnp.where(finished, used, bud + grant)
    return jnp.minimum(new_bud, jnp.int32(1 << 30))


def _run_batch_pool(g: GraphContext, cfg: EngineConfig, s: DenseState,
                    budget: int, ctx_batched: bool,
                    unroll: int) -> DenseState:
    """Pool-kernel backing for ``run_batch``: ONE launch advances every
    lane by an ``unroll``-step segment; the while loop runs until every
    lane is done or out of budget.

    Byte-identity with the vmap path is structural: vmapping ``run``'s
    while loop lifts it to a single loop whose condition is ``any(lane
    active)`` with a masked body, and the pool kernel applies the same
    per-lane ``~done & (steps - start < budget)`` guard internally —
    exactly the predicate below, with per-lane ``start``/``budget``
    columns.  With ``cfg.resident_rebalance`` the budgets become mutable
    loop state fed from the scoreboard (and the trajectory intentionally
    diverges from the fixed-budget vmap path).
    """
    start = s.steps
    bud0 = jnp.full_like(start, jnp.int32(budget))

    def cond(carry):
        st, bud = carry
        return jnp.any((~_done(st)) & (st.steps - start < bud))

    def body(carry):
        st, bud = carry
        st2, board = resident_pool_segment(
            g, cfg, st, start=start, budget=bud, steps_per_call=unroll,
            ctx_batched=ctx_batched)
        if cfg.resident_rebalance:
            bud = _rebalance_budgets(start, bud, st2, board)
        return st2, bud

    out, _ = jax.lax.while_loop(cond, body, (s, bud0))
    return out


def stepwise_lanes(cfg: EngineConfig, batch: int) -> bool:
    """Whether ``run_batch`` advances ``batch`` lanes on the lane-masked
    per-step loop (``_run_batch_stepwise``): neither the pool kernel nor
    ``batch`` concurrent resident lanes fit."""
    if pool_lanes(cfg, batch):
        return False
    return not (cfg.resident_active and resident_supported(cfg, lanes=batch))


def _run_batch_stepwise(g: GraphContext, cfg: EngineConfig, s: DenseState,
                        budget: int, ctx_batched: bool,
                        unroll: int) -> DenseState:
    """Per-step backing for ``run_batch``: ONE unbatched while loop, run
    until no lane is active, whose body takes ``unroll`` lane-masked
    steps (``_masked_step``) under ``vmap``.

    A step is taken by exactly the lanes for which ``~done & (steps -
    start < budget)`` holds before it, the predicate of ``run``'s loop
    and of its guarded unrolled steps, so the trajectory is byte-identical
    to ``vmap(run)``.  ``vmap(run)`` turns the batched loop carry, each
    guarded step's ``lax.cond`` and the step's ``lax.switch`` into
    selects over whole stacks, which a TPU also copied between layouts
    every step; here every select is row-sized and every stack is
    written only by row scatters.
    """
    start = s.steps

    def active(st, st0):
        return (~_done(st)) & (st.steps - st0 < budget)

    def segment(c, st, st0):
        for _ in range(unroll):
            st = _masked_step(c, cfg, st, active(st, st0))
        return st

    seg = jax.vmap(segment, in_axes=(0 if ctx_batched else None, 0, 0))
    return jax.lax.while_loop(lambda st: jnp.any(active(st, start)),
                              lambda st: seg(g, st, start), s)


def run_batch(g: GraphContext, cfg: EngineConfig, s: DenseState,
              max_steps: int | None = None,
              ctx_batched: bool = False, unroll: int = 1) -> DenseState:
    """``run`` over a leading batch axis of worker states.

    Serving/batching model: every leaf of ``s`` carries a leading axis of
    size B.  Two layouts share this one code path:

    * ``ctx_batched=False`` — ONE graph, B workers over disjoint task lists
      (the distributed runner's per-device worker batch, cuMBE's many
      thread blocks per SM).
    * ``ctx_batched=True`` — B *different* graphs padded to the same
      ``(n_u, n_v, depth)`` bucket, one worker each (the serving layer's
      multi-graph batch: lane b enumerates graph b end-to-end).

    On the resident pallas path the batch is advanced by the multi-lane
    pool kernel whenever ``pool_lanes`` admits it — one launch per
    segment for the WHOLE pool instead of B vmapped launches.  When B
    concurrent single-lane resident launches fit instead
    (``resident_supported(cfg, lanes=B)``), ``vmap`` lifts ``run``'s
    while loop to run until every lane is done, masking finished lanes.
    Otherwise (``stepwise_lanes``: the jnp path, or a pallas batch too
    large to keep resident) the batch takes the per-step kernels in one
    unbatched loop of lane-masked steps (``_run_batch_stepwise``).  All
    three are byte-identical.  Either way one jitted call enumerates the
    whole batch, and the compiled executable depends only on the bucket
    shape and ``cfg``, never on the graphs themselves (the serving
    cache's key).
    """
    B = s.lvl.shape[0]
    budget = cfg.max_steps if max_steps is None else max_steps
    if pool_lanes(cfg, B):
        return _run_batch_pool(g, cfg, s, budget, ctx_batched, unroll)
    if stepwise_lanes(cfg, B):
        return _run_batch_stepwise(g, cfg, s, budget, ctx_batched, unroll)
    ax = 0 if ctx_batched else None
    return jax.vmap(
        lambda c, st: run(c, cfg, st, max_steps=max_steps, unroll=unroll),
        in_axes=(ax, 0))(g, s)


def replace_lane(batch_state: DenseState, batch_ctx: GraphContext, i: int,
                 lane_state: DenseState, lane_ctx: GraphContext,
                 sharding=None) -> tuple[DenseState, GraphContext]:
    """Row surgery on a batched (state, context) pair: install one lane's
    fresh ``DenseState``/``GraphContext`` into row ``i``, leaving every
    other lane's rows untouched.

    This is the serving layer's mid-flight refill primitive (the slot model
    applied to graph lanes): a lane that finished its graph between bounded
    rounds is re-initialized in place with a queued same-bucket graph, so
    the SAME compiled ``run_batch`` executable keeps all lanes busy across
    an arbitrary-length request stream — the serving-side analog of cuMBE's
    work stealing for vmap-lane imbalance.

    ``sharding`` (a ``jax.sharding.Sharding``) is the pool's placement,
    for pools whose lane axis lives on a device mesh: see
    ``replace_lanes``.
    """
    def expand(tree):
        return jax.tree.map(lambda x: jnp.asarray(x)[None], tree)
    return replace_lanes(batch_state, batch_ctx, [i], expand(lane_state),
                         expand(lane_ctx), sharding=sharding)


def _set_rows_sharded(b: jax.Array, idx: np.ndarray, rows, sharding):
    """``b.at[idx].set(rows)`` for a pool leaf whose lane axis is sharded
    over ``sharding``'s devices, done shard by shard: each device scatters
    the rows it owns into its own shard with a single-device scatter, and
    untouched shards are reused as they are.  No partitioned scatter runs:
    on a four-chip TPU v5e mesh, the partitioned ``b.at[idx].set(rows)``
    installing two lanes at once also wrote into the other lanes' rows of
    the (B, n) int32 leaves (tasks, order, rank, root_counts), and the
    served lanes enumerated corrupted graphs."""
    if b.sharding != sharding:
        b = jax.device_put(b, sharding)
    rows = np.asarray(rows)
    shards = []
    for sh in b.addressable_shards:
        lo, hi, _ = sh.index[0].indices(b.shape[0])
        mine = (idx >= lo) & (idx < hi)
        data = sh.data
        if mine.any():
            data = data.at[idx[mine] - lo].set(
                jax.device_put(rows[mine], sh.device))
        shards.append(data)
    return jax.make_array_from_single_device_arrays(b.shape, sharding,
                                                    shards)


def replace_lanes(batch_state: DenseState, batch_ctx: GraphContext,
                  idx, lane_states: DenseState, lane_ctxs: GraphContext,
                  sharding=None) -> tuple[DenseState, GraphContext]:
    """Vectorized ``replace_lane``: install ``len(idx)`` lanes (leading
    axis of every ``lane_states``/``lane_ctxs`` leaf) with ONE scatter per
    leaf, instead of one full-batch copy per lane.

    The serving refill places fresh lanes of an unsharded pool through
    the pool's install executable instead (``Executor.install``: one
    dispatch, buffers donated).  This surgery takes the lanes that
    executable cannot: lanes of a sharded pool, lanes resumed from a host
    checkpoint (a whole state, not a fresh one), and eviction.

    ``sharding`` (the pool's ``jax.sharding.Sharding``) switches to
    shard-local surgery (``_set_rows_sharded``): every output leaf keeps
    the mesh placement, so the next round's ``shard_map`` pays no
    reshard."""
    if sharding is not None:
        ii = np.asarray(idx, dtype=np.int64)

        def put(b, lanes):
            return _set_rows_sharded(b, ii, lanes, sharding)
    else:
        ii = jnp.asarray(idx, dtype=jnp.int32)

        def put(b, lanes):
            return b.at[ii].set(lanes)
    return (jax.tree.map(put, batch_state, lane_states),
            jax.tree.map(put, batch_ctx, lane_ctxs))


# ---------------------------------------------------------------------------
# convenience: single-worker full enumeration (tests / Table-I benchmark)
# ---------------------------------------------------------------------------

def make_config(g: BipartiteGraph, **kw) -> EngineConfig:
    return EngineConfig(n_u=g.n_u, n_v=g.n_v, m_real=g.n_u,
                        depth=g.n_u + 2, **kw)


def enumerate_dense(g: BipartiteGraph, order_mode: str = "deg",
                    collect_cap: int = 1, impl: str = "jnp",
                    kernel_impl: str = "auto"):
    """Full single-worker enumeration. Returns the final DenseState."""
    cfg = make_config(g, order_mode=order_mode, collect_cap=collect_cap,
                      impl=impl, kernel_impl=kernel_impl)
    ctx = make_context(g, cfg)
    s0 = init_state(cfg, np.arange(g.n_u, dtype=np.int32))
    runner = jax.jit(lambda st: run(ctx, cfg, st))
    out = runner(s0)
    assert bool(_done(out)), "step budget exhausted"
    return out


def collected_bicliques(cfg: EngineConfig, s: DenseState,
                        n_u: int, n_v: int) -> list[tuple[tuple, tuple]]:
    """Decode the collect buffer into (L members, R members) tuples."""
    n = int(s.out_n)
    assert n <= cfg.collect_cap, "collect buffer overflowed"
    out = []
    ol = np.asarray(s.out_l)
    orr = np.asarray(s.out_r)
    for i in range(n):
        L = tuple(bitset.unpack(ol[i], n_v))
        R = tuple(bitset.unpack(orr[i], n_u))
        out.append((L, R))
    return out
