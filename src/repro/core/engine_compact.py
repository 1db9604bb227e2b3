"""Compact-array MBE engine — the paper-faithful reproduction.

This engine transcribes cuMBE's core data structure (Section III-B, Fig. 3)
into JAX:

* ``P`` is ONE fixed array holding a permutation of U, with a **level
  pointer** per recursion depth: the live candidate set at level l is
  ``P[0 : p_ptr[l]]``. Popping a candidate swaps it to the region end and
  decrements the pointer; building P' stably compacts the surviving
  candidates to the front — every mutation is a permutation *within* the
  current region, which is nested inside all ancestor regions, so ancestor
  sets survive untouched (the paper's key invariant).
* ``lookup`` is the paper's lookup table LT_P: ``lookup[v]`` = position of v
  in P, maintained through every swap; membership is the O(1) comparison
  ``lookup[v] < p_ptr[lvl]``.
* ``Q`` is an append-only compact array with per-level counts. Appends land
  at ``q_ptr[lvl]`` which is >= every ancestor's count, so ancestor regions
  are never clobbered (see DESIGN.md §2 for why the paper's swap-based Q'
  compaction cannot grow back safely, and why skipping the Q' filter is
  semantically identical).
* ``R`` is kept as a per-level bitmask stack: R is write-only context (only
  reported, never scanned), so the bitmask is the cheaper faithful choice.
* recursion is a ``lax.while_loop`` — no recursion, no dynamic allocation;
  space is O(|U| + |V|) words per level, O(depth) levels: the paper's
  O(|V+U| x 2 x T) bound.

Counts are computed through the *gathered* adjacency rows ``adj[P]`` /
``adj[Q]`` — the access pattern the compact array induces. The dense engine
(engine_dense.py) removes the gather; the measured difference between the
two is the repo's "reverse scanning" ablation analog (benchmarks Fig. 6).

**Kernel paths** (``EngineConfig.kernel_impl``, DESIGN.md §8): on the
``"pallas"`` path the three per-branch count passes collapse to two fused
VMEM-resident kernels over the SAME gathered access pattern —
``fused_select_gathered_prefix`` over ``adj[P]`` (counts + first-minimum
argmin in position order, activity = the level pointer scalar) and one
``fused_check_gathered_prefix2`` over the concatenated ``adj[Q ++ P']``
rows (maximality check + expansion partition in one pass, activity = the
``(q_ptr, p_ptr)`` scalar pair).  Byte-identical to ``"jnp"``
(``tests/test_fused_engines.py``).

Registered as ``"compact"`` in ``repro.core.engine``, so the paper's data
structure is servable end to end:
``MBEClient(MBEOptions(engine="compact")).enumerate(g)`` runs it through
the same bucket/cache/executor stack as the dense engine (DESIGN.md §7);
``enumerate_compact`` below remains the exact-shape direct call.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bitset
from repro.core.engine_dense import EngineConfig, make_config  # shared cfg
from repro.core.graph import BipartiteGraph
from repro.kernels.fused_check.ops import fused_check_gathered_prefix2
from repro.kernels.fused_select.ops import fused_select_gathered_prefix
from repro.kernels.intersect_count.ops import intersect_count

_INF = np.int32(0x7FFFFFFF)


class CompactContext(NamedTuple):
    adj: jax.Array        # (NU, WV) uint32
    order: jax.Array      # (NU,) i32 root order (degree ascending)
    p_static: jax.Array   # (NU,) i32 initial P layout (reversed order)
    lk_static: jax.Array  # (NU,) i32 lookup for p_static
    q_static: jax.Array   # (NU,) i32 initial Q layout (= order)
    l_root: jax.Array     # (WV,) u32


class CompactState(NamedTuple):
    P: jax.Array          # (NU,) i32 the compact array
    lookup: jax.Array     # (NU,) i32 the lookup table
    p_ptr: jax.Array      # (D,) i32 level pointers
    Q: jax.Array          # (NU,) i32 append-only compact array
    q_ptr: jax.Array      # (D,) i32
    lmask: jax.Array      # (D, WV) u32
    rmask: jax.Array      # (D, WU) u32
    xstack: jax.Array     # (D,) i32
    lvl: jax.Array
    forced_x: jax.Array
    tasks: jax.Array
    n_tasks: jax.Array
    tpos: jax.Array
    steps: jax.Array
    nodes: jax.Array
    n_max: jax.Array
    max_fail: jax.Array
    cs: jax.Array
    out_n: jax.Array
    out_l: jax.Array
    out_r: jax.Array


def make_context(g: BipartiteGraph, cfg: EngineConfig) -> CompactContext:
    assert g.n_u <= cfg.n_u and g.n_v <= cfg.n_v
    # Zero-extended word copy: packed rows are prefix-compatible under
    # padding (bit v stays at word v//32), so no edge-list round-trip —
    # see engine_dense.make_context.
    adj = np.zeros((cfg.n_u, cfg.wv), dtype=np.uint32)
    src_rows = np.asarray(g.adj_u, dtype=np.uint32)
    adj[: g.n_u, : src_rows.shape[1]] = src_rows
    # one vectorized popcount pass (the per-row Python bin() loop cost
    # O(n_u) interpreted big-int conversions per admitted graph)
    deg = np.unpackbits(adj[: g.n_u].view(np.uint8), axis=1) \
        .sum(axis=1, dtype=np.int64)
    order_real = np.argsort(deg, kind="stable").astype(np.int32)
    m = g.n_u
    order = np.full(cfg.n_u, -1, dtype=np.int32)
    order[:m] = order_real
    p_static = np.arange(cfg.n_u, dtype=np.int32)
    p_static[:m] = order_real[::-1]
    p_static[m:] = np.setdiff1d(np.arange(cfg.n_u, dtype=np.int32),
                                order_real)
    lk_static = np.empty(cfg.n_u, dtype=np.int32)
    lk_static[p_static] = np.arange(cfg.n_u, dtype=np.int32)
    q_static = np.arange(cfg.n_u, dtype=np.int32)
    q_static[:m] = order_real
    l_root = np.zeros(cfg.wv, dtype=np.uint32)
    fm = bitset.full_mask(g.n_v)
    l_root[: fm.shape[0]] = fm
    return CompactContext(
        adj=jnp.asarray(adj), order=jnp.asarray(order),
        p_static=jnp.asarray(p_static), lk_static=jnp.asarray(lk_static),
        q_static=jnp.asarray(q_static), l_root=jnp.asarray(l_root))


def init_state(cfg: EngineConfig, tasks: np.ndarray) -> CompactState:
    t = np.full(max(len(tasks), 1), -1, dtype=np.int32)
    t[: len(tasks)] = np.asarray(tasks, dtype=np.int32)
    D, WU, WV, C, NU = (cfg.depth, cfg.wu, cfg.wv, cfg.collect_cap, cfg.n_u)
    z = jnp.int32(0)
    return CompactState(
        P=jnp.arange(NU, dtype=jnp.int32),
        lookup=jnp.arange(NU, dtype=jnp.int32),
        p_ptr=jnp.zeros((D,), jnp.int32),
        Q=jnp.zeros((NU,), jnp.int32),
        q_ptr=jnp.zeros((D,), jnp.int32),
        lmask=jnp.zeros((D, WV), jnp.uint32),
        rmask=jnp.zeros((D, WU), jnp.uint32),
        xstack=jnp.full((D,), -1, jnp.int32),
        lvl=jnp.int32(-1), forced_x=jnp.int32(-1),
        tasks=jnp.asarray(t), n_tasks=jnp.int32(len(tasks)), tpos=z,
        steps=z, nodes=z, n_max=z, max_fail=z, cs=jnp.uint32(0),
        out_n=z, out_l=jnp.zeros((C, WV), jnp.uint32),
        out_r=jnp.zeros((C, WU), jnp.uint32))


# ---------------------------------------------------------------------------

def _branch_backtrack(g, cfg, s: CompactState) -> CompactState:
    nl = s.lvl - 1
    safe = jnp.maximum(nl, 0)
    do = nl >= 0
    qp = s.q_ptr[safe]
    Q = s.Q.at[jnp.where(do, qp, 0)].set(
        jnp.where(do, s.xstack[safe], s.Q[jnp.where(do, qp, 0)]))
    q_ptr = s.q_ptr.at[safe].set(jnp.where(do, qp + 1, qp))
    return s._replace(lvl=nl, Q=Q, q_ptr=q_ptr)


def _branch_init_task(g: CompactContext, cfg, s: CompactState
                      ) -> CompactState:
    idx = s.tasks[jnp.minimum(s.tpos, s.tasks.shape[0] - 1)]
    x = g.order[jnp.clip(idx, 0, cfg.n_u - 1)]
    return s._replace(
        P=g.p_static, lookup=g.lk_static, Q=g.q_static,
        p_ptr=s.p_ptr.at[0].set(jnp.int32(cfg.m_real) - 1 - idx),
        q_ptr=s.q_ptr.at[0].set(idx),
        lmask=s.lmask.at[0].set(g.l_root),
        rmask=s.rmask.at[0].set(jnp.zeros((cfg.wu,), jnp.uint32)),
        lvl=jnp.int32(0), forced_x=x, tpos=s.tpos + 1)


def _branch_candidate(g: CompactContext, cfg: EngineConfig,
                      s: CompactState) -> CompactState:
    lvl = s.lvl
    L = s.lmask[lvl]
    p = s.p_ptr[lvl]
    pos = jnp.arange(cfg.n_u, dtype=jnp.int32)
    forced = s.forced_x >= 0

    # -- Step 1: candidate selection (through the compact array) ---------
    if cfg.order_mode == "deg":
        if cfg.fused:
            # one VMEM-resident pass over the gathered rows adj[P]:
            # counts + first-minimum argmin in POSITION order (the
            # compact-array order), counts never written to HBM, and the
            # level pointer itself is the activity (a scalar — no (N,)
            # comparison vector materialized per step).  The -1 "no
            # active row" sentinel only occurs when p == 0, where this
            # branch's result is discarded (case_id != 2) or the forced
            # root overrides x — clamp so the swap indexing below stays
            # in range.
            i_x, _ = fused_select_gathered_prefix(
                g.adj, s.P, L, p, impl="pallas")
            i_x = jnp.maximum(i_x, 0)
        else:
            rows_p = g.adj[s.P]                             # gathered rows
            c_sel = intersect_count(rows_p, L, impl=cfg.impl)
            i_x = jnp.argmin(jnp.where(pos < p, c_sel, _INF)) \
                .astype(jnp.int32)
    else:
        i_x = jnp.maximum(p - 1, 0)      # pop from the region end
    # swap selected to region end, decrement pointer (skip when forced)
    a = s.P[i_x]
    b = s.P[jnp.maximum(p - 1, 0)]
    P_sw = s.P.at[i_x].set(b).at[jnp.maximum(p - 1, 0)].set(a)
    lk_sw = s.lookup.at[b].set(i_x).at[a].set(jnp.maximum(p - 1, 0))
    x = jnp.where(forced, s.forced_x, a)
    P1 = jnp.where(forced, s.P, P_sw)
    lookup1 = jnp.where(forced, s.lookup, lk_sw)
    p_work = jnp.where(forced, p, p - 1)

    # -- Step 2: L' construction -----------------------------------------
    Lp = L & g.adj[x]
    nLp = bitset.count(Lp)
    nonempty = nLp > 0

    # -- Steps 3+4: maximality check via the Q compact array + maximal
    # expansion via the P compact array.  The jnp path pays one
    # intersect_count per array (c_q, then c_p); the fused path
    # concatenates the two gathered row sets and emits the violation
    # flag and both partition flag vectors from ONE fused_check pass —
    # the counts never round-trip to HBM.
    if cfg.fused:
        # activity is the (q_ptr, p_ptr) level-pointer pair itself —
        # two scalars instead of two (2N,) comparison vectors built and
        # shipped per step; the kernel rebuilds the position predicates
        # from its iota against the static Q/P split.
        viol_f, full2, part2, _, _ = fused_check_gathered_prefix2(
            g.adj, jnp.concatenate([s.Q, P1]), Lp, nLp,
            s.q_ptr[lvl], p_work, impl="pallas")
        viol = viol_f & nonempty
        fullb = full2[cfg.n_u:]                   # per-position flags
        partb = part2[cfg.n_u:]
    else:
        rows_q = g.adj[s.Q]
        c_q = intersect_count(rows_q, Lp, impl=cfg.impl)
        viol = jnp.any((pos < s.q_ptr[lvl]) & (c_q == nLp)) & nonempty
        rows_p1 = g.adj[P1]
        c_p = intersect_count(rows_p1, Lp, impl=cfg.impl)
        act = pos < p_work
        fullb = act & (c_p == nLp)                # per-position flags
        partb = act & (c_p > 0) & (c_p < nLp)
    is_max = nonempty & ~viol
    fullv = jnp.zeros(cfg.n_u, bool).at[P1].set(fullb)   # per-vertex
    Rp = s.rmask[lvl] | bitset.singleton(x, cfg.wu) \
        | bitset.from_bool(fullv)
    has_child = is_max & jnp.any(partb)

    # -- report ------------------------------------------------------------
    n_max = s.n_max + is_max.astype(jnp.int32)
    cs = s.cs + jnp.where(is_max, bitset.pair_checksum(Lp, Rp),
                          jnp.uint32(0))
    C = cfg.collect_cap
    w_idx = jnp.minimum(s.out_n, C - 1)
    write = is_max & (s.out_n < C)
    out_l = s.out_l.at[w_idx].set(jnp.where(write, Lp, s.out_l[w_idx]))
    out_r = s.out_r.at[w_idx].set(jnp.where(write, Rp, s.out_r[w_idx]))
    out_n = s.out_n + write.astype(jnp.int32)

    # -- descend: stable-compact survivors to the region front -----------
    key = jnp.where(pos < p_work, jnp.where(partb, 0, 1), 2)
    perm = jnp.argsort(key, stable=True)
    P_child = P1[perm]
    lk_child = jnp.zeros_like(s.lookup).at[P_child].set(pos)
    n_part = jnp.sum(partb).astype(jnp.int32)

    P2 = jnp.where(has_child, P_child, P1)
    lookup2 = jnp.where(has_child, lk_child, lookup1)
    child = jnp.minimum(lvl + 1, cfg.depth - 1)
    p_ptr = s.p_ptr.at[lvl].set(jnp.where(forced, 0, p_work))
    p_ptr = p_ptr.at[child].set(
        jnp.where(has_child, n_part, p_ptr[child]))
    q_ptr = s.q_ptr.at[child].set(
        jnp.where(has_child, s.q_ptr[lvl], s.q_ptr[child]))
    lmask = s.lmask.at[child].set(jnp.where(has_child, Lp, s.lmask[child]))
    rmask = s.rmask.at[child].set(jnp.where(has_child, Rp, s.rmask[child]))
    xstack = s.xstack.at[lvl].set(jnp.where(has_child, x, s.xstack[lvl]))
    # finished subtree (no child): move x to Q at this level
    qp = s.q_ptr[lvl]
    Q = s.Q.at[jnp.where(has_child, 0, qp)].set(
        jnp.where(has_child, s.Q[0], x))
    q_ptr = q_ptr.at[lvl].set(jnp.where(has_child, q_ptr[lvl], qp + 1))

    return s._replace(
        P=P2, lookup=lookup2, p_ptr=p_ptr, Q=Q, q_ptr=q_ptr,
        lmask=lmask, rmask=rmask, xstack=xstack,
        lvl=jnp.where(has_child, lvl + 1, lvl),
        forced_x=jnp.int32(-1),
        nodes=s.nodes + 1, n_max=n_max,
        max_fail=s.max_fail + (viol & nonempty).astype(jnp.int32),
        cs=cs, out_n=out_n, out_l=out_l, out_r=out_r)


# ---------------------------------------------------------------------------

def _case_id(s: CompactState) -> jax.Array:
    lvl_safe = jnp.maximum(s.lvl, 0)
    p_empty = s.p_ptr[lvl_safe] == 0
    return jnp.where(
        s.lvl < 0, 1,
        jnp.where(p_empty & (s.forced_x < 0), 0, 2)).astype(jnp.int32)


def _done(s: CompactState) -> jax.Array:
    return (s.lvl < 0) & (s.tpos >= s.n_tasks)


def step(g: CompactContext, cfg: EngineConfig,
         s: CompactState) -> CompactState:
    s = s._replace(steps=s.steps + 1)
    return jax.lax.switch(
        _case_id(s),
        [lambda st: _branch_backtrack(g, cfg, st),
         lambda st: _branch_init_task(g, cfg, st),
         lambda st: _branch_candidate(g, cfg, st)],
        s)


def run(g: CompactContext, cfg: EngineConfig, s: CompactState,
        max_steps: int | None = None, unroll: int = 1) -> CompactState:
    """Run until done or the budget expires; ``unroll`` advances up to
    that many engine steps per while-loop iteration (multi-step compiled
    segments, byte-identical — see ``engine_dense.run``)."""
    budget = cfg.max_steps if max_steps is None else max_steps
    start = s.steps

    def active(st):
        return (~_done(st)) & (st.steps - start < budget)

    def body(st):
        st = step(g, cfg, st)       # loop cond guarantees the first step
        for _ in range(unroll - 1):
            st = jax.lax.cond(active(st),
                              lambda t: step(g, cfg, t), lambda t: t, st)
        return st

    return jax.lax.while_loop(active, body, s)


def enumerate_compact(g: BipartiteGraph, order_mode: str = "deg",
                      collect_cap: int = 1, impl: str = "jnp",
                      kernel_impl: str = "auto"):
    cfg = make_config(g, order_mode=order_mode, collect_cap=collect_cap,
                      impl=impl, kernel_impl=kernel_impl)
    ctx = make_context(g, cfg)
    s0 = init_state(cfg, np.arange(g.n_u, dtype=np.int32))
    runner = jax.jit(lambda st: run(ctx, cfg, st))
    out = runner(s0)
    assert bool(_done(out)), "step budget exhausted"
    return out
