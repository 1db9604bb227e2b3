"""Dispatch wrapper for the VMEM-resident multi-step segment kernel.

``resident_segment(g, cfg, s, ...)`` advances a dense-engine lane by up
to ``steps_per_call`` guarded steps in ONE kernel launch and returns the
updated ``DenseState``.  It is duck-typed over ``engine_dense``'s
``GraphContext`` / ``EngineConfig`` / ``DenseState`` (field access only —
importing the engine here would be circular: the engine routes its
``"pallas"`` run path through this module).

``resident_supported(cfg)`` is the static residency gate: the whole
state must fit the kernel's VMEM budget (the counts-cache stack is
O(depth * n_u) — the quadratic term that overflows first).  ``run``
falls back to the per-step fused kernels when the gate fails, so
arbitrarily large configs still work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import default_interpret
from repro.kernels.resident_step.kernel import (
    S_BUDGET, S_CS, S_FORCED, S_LVL, S_MAXFAIL, S_NMAX, S_NODES, S_NTASKS,
    S_OUTN, S_START, S_STEPS, S_TPOS, SCAL_SLOTS, make_resident_call)

# VMEM budget for (context + state) blocks, in the padded bytes that
# ``resident_state_bytes`` counts: the TPU compiler's default scoped VMEM
# limit on v5e.  Compiled for v5e, single-lane and pool kernels both fit
# that limit up to a padded charge of about 18 MiB (n_u = 1280 buckets)
# and are refused from about 21 MiB (n_u = 1408), so the gate keeps a
# margin for Mosaic's own scratch; tests/test_tpu_compile.py compiles the
# largest bench/large bucket the gate admits.
RESIDENT_STATE_BYTES = 16 * 1024 * 1024


def _block_bytes(rows: int, cols: int) -> int:
    """Bytes of a (rows, cols) 32-bit VMEM block in (8, 128) tiles; a
    (1, cols) row is laid out in (1, 128) tiles."""
    r = rows if rows == 1 else -(-rows // 8) * 8
    return 4 * r * (-(-cols // 128) * 128)


def resident_state_bytes(cfg, t_len: int | None = None,
                         lanes: int = 1) -> int:
    """Bytes of VMEM the resident kernel pins for ``cfg`` (context +
    state + outputs, each block padded to the TPU's tiles).

    ``lanes`` scales the per-lane state/output terms for paths that hold
    several lanes' residency at once: ``run_batch``'s vmap path launches
    one kernel per lane concurrently (``lanes = pool width``), while the
    pool kernel's sequential grid caps concurrency at two cells
    (``resident_pool_state_bytes``).  The shared context is counted once
    either way.
    """
    t = cfg.n_u if t_len is None else t_len
    ctx = _block_bytes(cfg.n_u, cfg.wv) + 3 * _block_bytes(1, cfg.n_u) \
        + _block_bytes(1, cfg.wv)
    state = _block_bytes(cfg.depth, cfg.wv) \
        + _block_bytes(cfg.depth, cfg.n_u) \
        + 3 * _block_bytes(cfg.depth, cfg.wu) \
        + _block_bytes(1, cfg.depth) + _block_bytes(1, t)
    out = _block_bytes(cfg.collect_cap, cfg.wv) \
        + _block_bytes(cfg.collect_cap, cfg.wu)
    # state/out held as input and output blocks per resident lane
    return ctx + lanes * 2 * (state + out)


def resident_supported(cfg, t_len: int | None = None,
                       lanes: int = 1) -> bool:
    """Whether ``lanes`` concurrent copies of ``cfg``'s enumeration
    state fit the residency budget."""
    return resident_state_bytes(cfg, t_len, lanes) <= RESIDENT_STATE_BYTES


@functools.partial(jax.jit, static_argnames=("cfg", "steps_per_call",
                                             "interpret"))
def resident_segment(g, cfg, s, *, start, budget, steps_per_call: int = 1,
                     interpret: bool | None = None):
    """Advance lane state ``s`` by up to ``steps_per_call`` engine steps
    in one resident-kernel launch.

    ``start``/``budget`` are the run loop's step-budget operands: every
    internal step is guarded by ``~done & (s.steps - start < budget)`` —
    the exact while-loop predicate — so a segment is byte-identical to
    ``steps_per_call`` guarded single steps of the jnp engine.
    """
    if interpret is None:
        interpret = default_interpret()
    t_len = s.tasks.shape[0]
    call = make_resident_call(
        nu=cfg.n_u, wu=cfg.wu, wv=cfg.wv, depth=cfg.depth,
        cap=cfg.collect_cap, t_len=t_len, m_real=cfg.m_real,
        order_mode=cfg.order_mode, spc=steps_per_call, interpret=interpret)
    scal = jnp.zeros((1, SCAL_SLOTS), jnp.int32)
    sets = [(S_LVL, s.lvl), (S_FORCED, s.forced_x), (S_TPOS, s.tpos),
            (S_STEPS, s.steps), (S_NODES, s.nodes), (S_NMAX, s.n_max),
            (S_MAXFAIL, s.max_fail),
            (S_CS, jax.lax.bitcast_convert_type(s.cs, jnp.int32)),
            (S_OUTN, s.out_n), (S_NTASKS, s.n_tasks),
            (S_START, jnp.asarray(start, jnp.int32)),
            (S_BUDGET, jnp.asarray(budget, jnp.int32))]
    for slot, v in sets:
        scal = scal.at[0, slot].set(v)
    (scal_o, lmask, cstack, pmask, qmask, rmask, xstack2, out_l,
     out_r) = call(scal, g.adj, g.order[None, :], g.rank[None, :],
                   g.root_counts[None, :], g.l_root[None, :],
                   s.tasks[None, :], s.lmask, s.cstack, s.pmask, s.qmask,
                   s.rmask, s.xstack[None, :], s.out_l, s.out_r)
    return s._replace(
        lmask=lmask, cstack=cstack, pmask=pmask, qmask=qmask, rmask=rmask,
        xstack=xstack2[0], out_l=out_l, out_r=out_r,
        lvl=scal_o[0, S_LVL], forced_x=scal_o[0, S_FORCED],
        tpos=scal_o[0, S_TPOS], steps=scal_o[0, S_STEPS],
        nodes=scal_o[0, S_NODES], n_max=scal_o[0, S_NMAX],
        max_fail=scal_o[0, S_MAXFAIL],
        cs=jax.lax.bitcast_convert_type(scal_o[0, S_CS], jnp.uint32),
        out_n=scal_o[0, S_OUTN])
