"""Pallas TPU kernel: multi-lane VMEM-resident enumeration segments.

PR 6's ``resident_step`` keeps ONE lane's state on-chip per launch and
lets ``jax.vmap`` bolt the pool axis on from outside — a 16-lane bucket
pool pays 16 kernel dispatches per segment.  This kernel moves the lane
dimension INTO the grid (cuMBE's many-thread-blocks layout; the paper's
persistent workers): ``grid=(lanes,)``, each grid cell owning one lane's
full state block — mask stacks, counts cache, cursor, scalar slots — in
VMEM and advancing it ``steps_per_call`` guarded engine steps.  A whole
pool advances in ONE ``pallas_call`` instead of ``lanes`` launches, and
the shared ``GraphContext`` adjacency streams once per cell (a
grid-constant index map, so Pallas revalidates the same block instead of
refetching per lane).

The per-cell body IS ``resident_step.resident_kernel``, called verbatim:
the lane axis is squeezed off every 3-D operand by ``None``-leading
``BlockSpec``s, so each cell sees exactly the 2-D refs the single-lane
kernel was written against.  There is no second copy of the step
semantics to drift — byte-identity of the pool against
``vmap(resident_segment)`` is structural, and the differential suite
(``tests/test_resident_pool.py``) asserts it leaf-for-leaf at every
segment boundary anyway.

On top of the single-lane semantics each cell publishes a two-word
**scoreboard row** (the only addition): ``board[0] = done`` after the
segment, ``board[1] = steps_per_call - steps_advanced`` (the budget the
lane left on the table — zero for a lane that ran the whole segment).
The host-side rebalance pass in ``engine_dense.run_batch`` reads the
scoreboard at round boundaries to reassign surplus budget from finished
lanes to busy ones — the structural hook for true in-kernel stealing
(cells donating tasks through a shared SMEM scoreboard) later.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.resident_step.kernel import (S_LVL, S_NTASKS, S_STEPS,
                                                S_TPOS, SCAL_SLOTS,
                                                resident_kernel)

# scoreboard columns: one (1, BOARD_SLOTS) int32 row per lane
B_DONE, B_LEFT = range(2)
BOARD_SLOTS = 2


def resident_pool_kernel(scal_in, adj, order, rank, rc, lroot, tasks,
                         lmask_in, cstack_in, pmask_in, qmask_in, rmask_in,
                         xstack_in, outl_in, outr_in,
                         scal, lmask, cstack, pmask, qmask, rmask,
                         xstack, outl, outr, board, *,
                         nu: int, wu: int, wv: int, depth: int, cap: int,
                         t_len: int, m_real: int, order_mode: str,
                         spc: int):
    """One grid cell = one lane: the single-lane resident kernel on the
    cell's squeezed refs and its row of the SMEM scalar blocks, plus the
    scoreboard write."""
    lane = pl.program_id(0)
    resident_kernel(scal_in, adj, order, rank, rc, lroot, tasks,
                    lmask_in, cstack_in, pmask_in, qmask_in, rmask_in,
                    xstack_in, outl_in, outr_in,
                    scal, lmask, cstack, pmask, qmask, rmask,
                    xstack, outl, outr,
                    nu=nu, wu=wu, wv=wv, depth=depth, cap=cap,
                    t_len=t_len, m_real=m_real, order_mode=order_mode,
                    spc=spc, row=lane)
    adv = scal[lane, S_STEPS] - scal_in[lane, S_STEPS]
    done = (scal[lane, S_LVL] < 0) \
        & (scal[lane, S_TPOS] >= scal[lane, S_NTASKS])
    board[lane, B_DONE] = done.astype(jnp.int32)
    board[lane, B_LEFT] = spc - adv


def make_resident_pool_call(*, lanes: int, ctx_batched: bool, nu: int,
                            wu: int, wv: int, depth: int, cap: int,
                            t_len: int, m_real: int, order_mode: str,
                            spc: int, interpret: bool):
    """Build the pool ``pallas_call`` for one (cfg, lanes, steps_per_call,
    ctx_batched) identity.

    ``grid=(lanes,)``; per-lane state operands carry a leading lane axis
    that the BlockSpec strips with a ``None``-squeeze — stacks/buffers
    as (lanes, d0, d1) arrays, the per-lane rows (tasks, xstack, the
    context vectors) as (lanes, 1, w) arrays: a (1, w) block of a
    (lanes, w) array breaks Mosaic's (8, 128)-or-whole-dimension rule.
    The scalar block (lanes, 16) and the scoreboard (lanes, 2) are whole
    SMEM arrays, resident across the grid; each cell owns its row.
    ``ctx_batched`` selects per-lane context blocks (serving pools: lane
    b enumerates graph b) vs grid-constant maps over ONE shared context
    (the distributed worker layout — adjacency streamed once, reused by
    every cell).
    """
    kern = functools.partial(
        resident_pool_kernel, nu=nu, wu=wu, wv=wv, depth=depth, cap=cap,
        t_len=t_len, m_real=m_real, order_mode=order_mode, spc=spc)

    def lane_row(w):
        # (lanes, 1, w) operand -> squeezed (1, w) block for cell l
        return pl.BlockSpec((None, 1, w), lambda l: (l, 0, 0))

    def lane_stack(d0, d1):
        # (lanes, d0, d1) operand -> squeezed (d0, d1) block for cell l
        return pl.BlockSpec((None, d0, d1), lambda l: (l, 0, 0))

    def shared(d0, d1):
        # one (d0, d1) context array, the same block for every cell
        return pl.BlockSpec((d0, d1), lambda l: (0, 0))

    if ctx_batched:
        ctx_specs = [lane_stack(nu, wv),        # adj  (lanes, NU, WV)
                     lane_row(nu),              # order
                     lane_row(nu),              # rank
                     lane_row(nu),              # root_counts
                     lane_row(wv)]              # l_root
        ctx_shapes = [((lanes, nu, wv), jnp.uint32),
                      ((lanes, 1, nu), jnp.int32),
                      ((lanes, 1, nu), jnp.int32),
                      ((lanes, 1, nu), jnp.int32),
                      ((lanes, 1, wv), jnp.uint32)]
    else:
        ctx_specs = [shared(nu, wv),
                     shared(1, nu), shared(1, nu), shared(1, nu),
                     shared(1, wv)]
        ctx_shapes = [((nu, wv), jnp.uint32),
                      ((1, nu), jnp.int32), ((1, nu), jnp.int32),
                      ((1, nu), jnp.int32), ((1, wv), jnp.uint32)]

    state_specs = [
        lane_row(t_len),                        # tasks  (lanes, T)
        lane_stack(depth, wv),                  # lmask
        lane_stack(depth, nu),                  # cstack
        lane_stack(depth, wu),                  # pmask
        lane_stack(depth, wu),                  # qmask
        lane_stack(depth, wu),                  # rmask
        lane_row(depth),                        # xstack (lanes, D)
        lane_stack(cap, wv),                    # out_l
        lane_stack(cap, wu),                    # out_r
    ]
    state_shapes = [
        ((lanes, 1, t_len), jnp.int32),
        ((lanes, depth, wv), jnp.uint32),
        ((lanes, depth, nu), jnp.int32),
        ((lanes, depth, wu), jnp.uint32),
        ((lanes, depth, wu), jnp.uint32),
        ((lanes, depth, wu), jnp.uint32),
        ((lanes, 1, depth), jnp.int32),
        ((lanes, cap, wv), jnp.uint32),
        ((lanes, cap, wu), jnp.uint32),
    ]

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)    # whole array
    scal_shape = ((lanes, SCAL_SLOTS), jnp.int32)

    in_specs = [smem] + ctx_specs + state_specs
    # outputs: scal + the nine mutable state blocks (tasks/ctx read-only)
    # + the scoreboard
    out_specs = [smem] + state_specs[1:] + [smem]
    out_shapes = [scal_shape] + state_shapes[1:] \
        + [((lanes, BOARD_SLOTS), jnp.int32)]
    return pl.pallas_call(
        kern,
        grid=(lanes,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct(s, d) for s, d in out_shapes],
        interpret=interpret,
        name="resident_pool",
    )
