"""Pallas TPU kernel: VMEM-resident multi-step dense enumeration segment.

This is the repo's analogue of cuMBE keeping the compact array in GPU
shared memory (paper §III-B) and of GMBE's one-launch-per-subtree
traversal: ONE ``pallas_call`` holds a lane's entire enumeration state —
the per-level packed mask stacks (lmask/pmask/qmask/rmask), the counts
cache (cstack), the cursor scalars — resident in VMEM and advances up to
``steps_per_call`` engine steps internally.  Candidate selection, L'
construction, the maximality check, the expansion partition and the
state update all happen on-chip; between the fused PR-5 kernels the
state round-tripped through HBM once per *primitive*, here it moves
once per *segment*.

Semantics are EXACTLY ``engine_dense.step`` iterated under the run
loop's done/budget guard — byte-identical in every ``DenseState`` leaf
to the jnp path, which remains the oracle (``ref.py``; the differential
suite asserts identity at every segment boundary).  Three details make
the leaf-for-leaf identity hold:

* every step is guarded by the SAME predicate the ``run`` while-loop
  checks (``~done & (steps - start < budget)``), so a segment never
  advances a finished or budget-exhausted lane;
* the candidate branch writes the freshly computed counts row into
  ``cstack[child]`` on descent for EVERY order mode, matching the jnp
  path (the per-step fused kernels skip the write outside ``"deg"`` —
  a counter-invisible but leaf-visible divergence this kernel avoids);
* packing/expansion between (N,)-flag and packed-word forms reproduces
  ``bitset.from_bool``/``to_bool`` bit-exactly, and the enumeration
  fingerprint reproduces ``bitset.pair_checksum``'s uint32 arithmetic.

Layout: masks and stacks are 2D VMEM blocks; the twelve cursor scalars
travel in one (1, 16) int32 SMEM row (``ops.SCAL_*`` indices; ``cs`` is
bitcast uint32<->int32).  Per-vertex context vectors (order/rank/
root_counts) arrive as (1, N) rows.  The grid is a single cell — the
whole point is that nothing leaves VMEM between steps.

What the TPU compiler (Mosaic) requires, and how the body meets it:

* no scalar stores into VMEM — the cursor scalars live in SMEM;
* no unsigned reductions — popcounts, word packing and the fingerprint
  are reduced in int32 (two's-complement sums wrap exactly like uint32,
  so ``cs`` is bit-identical);
* no dynamic lane index — xstack/tasks/order reads are masked lane
  reductions and the xstack write is a masked whole-row select; dynamic
  access stays on the sublane (row) axis, via ``pl.ds`` ref slices;
* no lane <-> sublane reshape of a (NW, 32) block — bit expansion and
  packing go through row/column transposes (``words_to_bits`` /
  ``bits_to_words``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_select.kernel import bits_to_words, words_to_bits

_INF = 0x7FFFFFFF
_SRL = jax.lax.shift_right_logical

# scalar-vector slots (ops.py builds/unpacks; keep in sync)
S_LVL, S_FORCED, S_TPOS, S_STEPS, S_NODES, S_NMAX, S_MAXFAIL, S_CS, \
    S_OUTN, S_NTASKS, S_START, S_BUDGET = range(12)
SCAL_SLOTS = 16


def _i32(c: int) -> int:
    """A uint32 constant as the int32 with the same bits."""
    return c - (1 << 32) if c >= 1 << 31 else c


def _iota_row(n: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)


def _lane_get(row: jax.Array, i: jax.Array) -> jax.Array:
    """``row[0, i]`` of a (1, n) int32 row, 0 <= i < n, as a masked lane
    reduction (Mosaic has no dynamic lane index)."""
    return jnp.sum(jnp.where(_iota_row(row.shape[1]) == i, row, 0))


def _any(b: jax.Array) -> jax.Array:
    return jnp.max(b.astype(jnp.int32)) > 0


def _expand_row(words: jax.Array, n: int) -> jax.Array:
    """(1, NW) uint32 packed row -> (1, n) bool (bit v of word v//32)."""
    return words_to_bits(words, n).T > 0


def _pack_row(flags: jax.Array, nw: int) -> jax.Array:
    """(1, n) bool -> (1, nw) uint32 words (bitset.from_bool)."""
    return bits_to_words(flags.astype(jnp.int32).T, nw)


def _singleton_row(i: jax.Array, nw: int) -> jax.Array:
    """(1, nw) uint32 packed {i} (empty when i < 0 — bitset.singleton)."""
    bit = jnp.uint32(1) << (i % 32).astype(jnp.uint32)
    return jnp.where(_iota_row(nw) == i // 32, bit, jnp.uint32(0))


def _count(words: jax.Array) -> jax.Array:
    return jnp.sum(jax.lax.population_count(words).astype(jnp.int32))


def _checksum_row(words: jax.Array) -> jax.Array:
    """bitset.checksum over a (1, nw) row, as the int32 with its bits."""
    h = jax.lax.bitcast_convert_type(words, jnp.int32)
    h = h * (_iota_row(words.shape[1]) * _i32(0x9E3779B9)
             + _i32(0x85EBCA6B))
    h = h ^ _SRL(h, 15)
    h = h * _i32(0x2545F491)
    h = h ^ _SRL(h, 13)
    return jnp.sum(h)


def _pair_checksum_row(l_words: jax.Array, r_words: jax.Array) -> jax.Array:
    """bitset.pair_checksum over (1, nw) rows, as an int32 scalar."""
    x = _checksum_row(l_words) * _i32(0x85EBCA6B) \
        ^ (_checksum_row(r_words) * _i32(0xC2B2AE35))
    x = x ^ _SRL(x, 16)
    x = x * _i32(0x7FEB352D)
    return x ^ _SRL(x, 15)


def _min_where(cond: jax.Array, idx: jax.Array) -> jax.Array:
    """First index where cond holds (INT32_MAX when none)."""
    return jnp.min(jnp.where(cond, idx, _INF))


def resident_kernel(scal_in, adj, order, rank, rc, lroot, tasks,
                    lmask_in, cstack_in, pmask_in, qmask_in, rmask_in,
                    xstack_in, outl_in, outr_in,
                    scal, lmask, cstack, pmask, qmask, rmask,
                    xstack, outl, outr, *,
                    nu: int, wu: int, wv: int, depth: int, cap: int,
                    t_len: int, m_real: int, order_mode: str, spc: int,
                    row=0):
    """``row`` is this lane's row of the SMEM scalar blocks (0 for the
    single-lane call; the grid cell's lane in the pool kernel)."""
    # ---- state flows in through inputs, lives in the output refs -------
    for k in range(SCAL_SLOTS):
        scal[row, k] = scal_in[row, k]
    lmask[...] = lmask_in[...]
    cstack[...] = cstack_in[...]
    pmask[...] = pmask_in[...]
    qmask[...] = qmask_in[...]
    rmask[...] = rmask_in[...]
    xstack[...] = xstack_in[...]
    outl[...] = outl_in[...]
    outr[...] = outr_in[...]

    def one_step(_k, carry):
        lvl = scal[row, S_LVL]
        forced_x = scal[row, S_FORCED]
        tpos = scal[row, S_TPOS]
        steps = scal[row, S_STEPS]
        done = (lvl < 0) & (tpos >= scal[row, S_NTASKS])
        act = (~done) & (steps - scal[row, S_START] < scal[row, S_BUDGET])
        lvl_safe = jnp.maximum(lvl, 0)
        pm_cur = pmask[pl.ds(lvl_safe, 1), :]            # (1, WU)
        p_empty = _count(pm_cur) == 0
        case = jnp.where(lvl < 0, 1,
                         jnp.where(p_empty & (forced_x < 0), 0, 2))

        @pl.when(act)
        def _count_step():
            scal[row, S_STEPS] = steps + 1

        # ---- case 0: backtrack ----------------------------------------
        @pl.when(act & (case == 0))
        def _backtrack():
            nl = lvl - 1
            safe = jnp.maximum(nl, 0)
            x = _lane_get(xstack[...], safe)
            qrow = qmask[pl.ds(safe, 1), :]
            qnew = qrow | _singleton_row(jnp.maximum(x, 0), wu)
            qmask[pl.ds(safe, 1), :] = jnp.where(nl >= 0, qnew, qrow)
            scal[row, S_LVL] = nl

        # ---- case 1: init next root task ------------------------------
        @pl.when(act & (case == 1))
        def _init_task():
            ti = jnp.minimum(tpos, t_len - 1)
            idx = _lane_get(tasks[...], ti)
            x = _lane_get(order[...], jnp.clip(idx, 0, nu - 1))
            rk = rank[...]                               # (1, NU)
            in_p = (rk > idx) & (rk < m_real)
            in_q = rk < idx
            lmask[pl.ds(0, 1), :] = lroot[...]
            cstack[pl.ds(0, 1), :] = rc[...]
            pmask[pl.ds(0, 1), :] = _pack_row(in_p, wu)
            qmask[pl.ds(0, 1), :] = _pack_row(in_q, wu)
            rmask[pl.ds(0, 1), :] = jnp.zeros((1, wu), jnp.uint32)
            scal[row, S_LVL] = 0
            scal[row, S_FORCED] = x
            scal[row, S_TPOS] = tpos + 1

        # ---- case 2: process a candidate ------------------------------
        @pl.when(act & (case == 2))
        def _candidate():
            L = lmask[pl.ds(lvl_safe, 1), :]             # (1, WV)
            forced = forced_x >= 0
            col = _iota_row(nu)

            # step 1: candidate selection (order_mode is static)
            if order_mode == "deg":
                c_sel = cstack[pl.ds(lvl_safe, 1), :]    # (1, NU)
                actb = _expand_row(pm_cur, nu)
                masked = jnp.where(actb, c_sel, _INF)
                x_sel = _min_where(masked == jnp.min(masked), col)
            elif order_mode == "deg_nocache":
                pc = jax.lax.population_count(adj[...] & L)
                c_all = jnp.sum(pc.astype(jnp.int32), axis=1,
                                keepdims=True).T         # (1, NU)
                actb = _expand_row(pm_cur, nu)
                masked = jnp.where(actb, c_all, _INF)
                x_sel = _min_where(masked == jnp.min(masked), col)
            else:  # 'input': first member of P
                actb = _expand_row(pm_cur, nu)
                first = _min_where(actb, col)
                x_sel = jnp.where(first == _INF, -1, first)
            x = jnp.where(forced, forced_x, x_sel)
            pm_after = pm_cur & ~_singleton_row(jnp.maximum(x, 0), wu)

            # step 2: L' = L & N(x)
            Lp = L & adj[pl.ds(jnp.clip(x, 0, nu - 1), 1), :]
            nLp = _count(Lp)
            nonempty = nLp > 0

            # steps 3+4: one counts pass serves the maximality check, the
            # expansion partition, the Q' filter and the cstack refill
            c2 = jnp.sum(jax.lax.population_count(adj[...] & Lp)
                         .astype(jnp.int32), axis=1, keepdims=True).T
            qb = _expand_row(qmask[pl.ds(lvl_safe, 1), :], nu)
            pb = _expand_row(pm_after, nu)
            eq = c2 == nLp
            viol = _any(qb & eq) & nonempty
            fullb = pb & eq
            partb = pb & (c2 > 0) & (c2 < nLp)
            is_max = nonempty & ~viol
            Rp = rmask[pl.ds(lvl_safe, 1), :] | _singleton_row(x, wu) \
                | _pack_row(fullb, wu)
            has_child = is_max & _any(partb)

            pm_final = jnp.where(forced, jnp.zeros((1, wu), jnp.uint32),
                                 pm_after)
            q_cur = qmask[pl.ds(lvl_safe, 1), :]
            q_child = q_cur & _pack_row(c2 > 0, wu)      # paper's Q' filter
            q_lvl = q_cur | _singleton_row(jnp.maximum(x, 0), wu)
            child = jnp.minimum(lvl + 1, depth - 1)
            nl = jnp.where(has_child, lvl + 1, lvl)

            # ---- apply the delta (write order = _apply_delta) ---------
            lmask[pl.ds(child, 1), :] = jnp.where(
                has_child, Lp, lmask[pl.ds(child, 1), :])
            cstack[pl.ds(child, 1), :] = jnp.where(
                has_child, c2, cstack[pl.ds(child, 1), :])
            pmask[pl.ds(lvl_safe, 1), :] = pm_final
            pmask[pl.ds(child, 1), :] = jnp.where(
                has_child, _pack_row(partb, wu), pmask[pl.ds(child, 1), :])
            q_idx = jnp.where(has_child, child, lvl_safe)
            qmask[pl.ds(q_idx, 1), :] = jnp.where(has_child, q_child, q_lvl)
            rmask[pl.ds(child, 1), :] = jnp.where(
                has_child, Rp, rmask[pl.ds(child, 1), :])
            xstack[...] = jnp.where(
                has_child & (_iota_row(depth) == lvl_safe), x, xstack[...])

            out_n = scal[row, S_OUTN]
            w_idx = jnp.minimum(out_n, cap - 1)
            write = is_max & (out_n < cap)
            outl[pl.ds(w_idx, 1), :] = jnp.where(
                write, Lp, outl[pl.ds(w_idx, 1), :])
            outr[pl.ds(w_idx, 1), :] = jnp.where(
                write, Rp, outr[pl.ds(w_idx, 1), :])

            # cs holds the uint32 fingerprint's bits: int32 adds wrap alike
            scal[row, S_CS] = scal[row, S_CS] + jnp.where(
                is_max, _pair_checksum_row(Lp, Rp), 0)
            scal[row, S_LVL] = nl
            scal[row, S_FORCED] = -1
            scal[row, S_NODES] = scal[row, S_NODES] + 1
            scal[row, S_NMAX] = scal[row, S_NMAX] + is_max.astype(jnp.int32)
            scal[row, S_MAXFAIL] = scal[row, S_MAXFAIL] \
                + viol.astype(jnp.int32)
            scal[row, S_OUTN] = out_n + write.astype(jnp.int32)

        return carry

    jax.lax.fori_loop(0, spc, one_step, 0)


def make_resident_call(*, nu: int, wu: int, wv: int, depth: int, cap: int,
                       t_len: int, m_real: int, order_mode: str, spc: int,
                       interpret: bool):
    """Build the pallas_call for one (cfg, steps_per_call) identity.

    Single grid cell; every operand is a full-array block.  Inputs: scal
    (1,16) i32 in SMEM, adj (NU,WV) u32, order/rank/root_counts (1,NU) i32,
    l_root (1,WV) u32, tasks (1,T) i32, then the nine state blocks.
    Outputs: the updated scal + state blocks (tasks/ctx are read-only).
    """
    kern = functools.partial(
        resident_kernel, nu=nu, wu=wu, wv=wv, depth=depth, cap=cap,
        t_len=t_len, m_real=m_real, order_mode=order_mode, spc=spc)

    def spec(shape):
        return pl.BlockSpec(shape, lambda: tuple(0 for _ in shape))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_shapes = [
        ((1, SCAL_SLOTS), jnp.int32),    # scal
        ((nu, wv), jnp.uint32),          # adj
        ((1, nu), jnp.int32),            # order
        ((1, nu), jnp.int32),            # rank
        ((1, nu), jnp.int32),            # root_counts
        ((1, wv), jnp.uint32),           # l_root
        ((1, t_len), jnp.int32),         # tasks
        ((depth, wv), jnp.uint32),       # lmask
        ((depth, nu), jnp.int32),        # cstack
        ((depth, wu), jnp.uint32),       # pmask
        ((depth, wu), jnp.uint32),       # qmask
        ((depth, wu), jnp.uint32),       # rmask
        ((1, depth), jnp.int32),         # xstack
        ((cap, wv), jnp.uint32),         # out_l
        ((cap, wu), jnp.uint32),         # out_r
    ]
    out_shapes = [in_shapes[0]] + in_shapes[7:]
    return pl.pallas_call(
        kern,
        grid=(),
        in_specs=[smem] + [spec(s) for s, _ in in_shapes[1:]],
        out_specs=[smem] + [spec(s) for s, _ in out_shapes[1:]],
        out_shape=[jax.ShapeDtypeStruct(s, d) for s, d in out_shapes],
        interpret=interpret,
    )
