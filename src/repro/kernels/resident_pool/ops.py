"""Dispatch wrapper for the multi-lane resident pool segment kernel.

``resident_pool_segment(g, cfg, s, ...)`` advances a whole pool of
batched lane states (leading axis = lanes, the layout ``run_batch``'s
vmap produces) by up to ``steps_per_call`` guarded steps each in ONE
kernel launch, and returns the updated batched ``DenseState`` plus the
per-lane ``(lanes, 2)`` scoreboard (``B_DONE``, ``B_LEFT``).  Like the
single-lane wrapper it is duck-typed over ``engine_dense``'s pytrees —
importing the engine here would be circular.

The residency gate is per-grid-cell, NOT per-pool: grid cells execute
sequentially on a TPU core, and Pallas prefetches at most the NEXT
cell's blocks while the current one runs, so concurrent VMEM residency
is bounded by TWO lanes' state (plus the shared context, counted once).
That makes the pool kernel's footprint essentially flat in pool width —
strictly smaller than the vmap-of-single-lane path, whose ``lanes``
simultaneous launches each pin a full state block (the batch-aware
``resident_supported(cfg, lanes=B)`` gate in ``run_batch``).

``resident_pool_supported`` additionally requires the adjacency to plan
as ONE resident tile (``plan_blocks``): the pool kernel streams the
shared context per cell through full-array blocks, so a config whose
adjacency would need width-tiling must stay on the fallback path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import default_interpret, plan_blocks
from repro.kernels.resident_pool.kernel import make_resident_pool_call
from repro.kernels.resident_step.kernel import (
    S_BUDGET, S_CS, S_FORCED, S_LVL, S_MAXFAIL, S_NMAX, S_NODES, S_NTASKS,
    S_OUTN, S_START, S_STEPS, S_TPOS, SCAL_SLOTS)
from repro.kernels.resident_step.ops import (RESIDENT_STATE_BYTES,
                                             resident_state_bytes)

# sequential grid cells + single-cell lookahead prefetch: at most two
# lanes' state blocks are VMEM-resident at once, regardless of pool width
_CONCURRENT_CELLS = 2


def resident_pool_state_bytes(cfg, lanes: int, t_len: int | None = None) -> int:
    """Peak VMEM bytes the pool kernel pins for ``cfg`` at ``lanes``
    (shared context once + ``min(lanes, 2)`` concurrent cells' state)."""
    return resident_state_bytes(
        cfg, t_len, lanes=min(max(lanes, 1), _CONCURRENT_CELLS))


def resident_pool_supported(cfg, lanes: int,
                            t_len: int | None = None) -> bool:
    """Whether a ``lanes``-wide pool of ``cfg`` states fits the pool
    kernel: per-cell VMEM budget + single-tile adjacency."""
    if lanes < 1:
        return False
    if resident_pool_state_bytes(cfg, lanes, t_len) > RESIDENT_STATE_BYTES:
        return False
    bn, bw = plan_blocks(cfg.n_u, cfg.wv)
    return bn >= cfg.n_u and bw == cfg.wv


@functools.partial(jax.jit, static_argnames=("cfg", "steps_per_call",
                                             "ctx_batched", "interpret"))
def resident_pool_segment(g, cfg, s, *, start, budget,
                          steps_per_call: int = 1,
                          ctx_batched: bool = False,
                          interpret: bool | None = None):
    """Advance every lane of the batched state ``s`` by up to
    ``steps_per_call`` engine steps in one pool-kernel launch.

    ``start``/``budget`` broadcast to per-lane (lanes,) int32 columns of
    the scalar block, so the round-boundary rebalance pass can hand each
    lane its own budget.  Returns ``(state, board)`` where ``board`` is
    the (lanes, 2) int32 scoreboard: column 0 = done after the segment,
    column 1 = ``steps_per_call`` minus the steps the lane advanced.
    """
    if interpret is None:
        interpret = default_interpret()
    lanes, t_len = s.tasks.shape
    call = make_resident_pool_call(
        lanes=lanes, ctx_batched=ctx_batched, nu=cfg.n_u, wu=cfg.wu,
        wv=cfg.wv, depth=cfg.depth, cap=cfg.collect_cap, t_len=t_len,
        m_real=cfg.m_real, order_mode=cfg.order_mode, spc=steps_per_call,
        interpret=interpret)
    scal = jnp.zeros((lanes, SCAL_SLOTS), jnp.int32)
    full = functools.partial(jnp.broadcast_to, shape=(lanes,))
    sets = [(S_LVL, s.lvl), (S_FORCED, s.forced_x), (S_TPOS, s.tpos),
            (S_STEPS, s.steps), (S_NODES, s.nodes), (S_NMAX, s.n_max),
            (S_MAXFAIL, s.max_fail),
            (S_CS, jax.lax.bitcast_convert_type(s.cs, jnp.int32)),
            (S_OUTN, s.out_n), (S_NTASKS, s.n_tasks),
            (S_START, full(jnp.asarray(start, jnp.int32))),
            (S_BUDGET, full(jnp.asarray(budget, jnp.int32)))]
    for slot, v in sets:
        scal = scal.at[:, slot].set(v)
    if ctx_batched:
        # per-lane rows ride a unit middle axis (kernel.py: lane_row)
        ctx_args = (g.adj, g.order[:, None], g.rank[:, None],
                    g.root_counts[:, None], g.l_root[:, None])
    else:
        ctx_args = (g.adj, g.order[None, :], g.rank[None, :],
                    g.root_counts[None, :], g.l_root[None, :])
    (scal_o, lmask, cstack, pmask, qmask, rmask, xstack2, out_l, out_r,
     board) = call(scal, *ctx_args, s.tasks[:, None], s.lmask, s.cstack,
                   s.pmask, s.qmask, s.rmask, s.xstack[:, None], s.out_l,
                   s.out_r)
    s2 = s._replace(
        lmask=lmask, cstack=cstack, pmask=pmask, qmask=qmask, rmask=rmask,
        xstack=xstack2[:, 0], out_l=out_l, out_r=out_r,
        lvl=scal_o[:, S_LVL], forced_x=scal_o[:, S_FORCED],
        tpos=scal_o[:, S_TPOS], steps=scal_o[:, S_STEPS],
        nodes=scal_o[:, S_NODES], n_max=scal_o[:, S_NMAX],
        max_fail=scal_o[:, S_MAXFAIL],
        cs=jax.lax.bitcast_convert_type(scal_o[:, S_CS], jnp.uint32),
        out_n=scal_o[:, S_OUTN])
    return s2, board
