"""Dispatch wrapper for fused candidate selection (pads, picks impl).

``impl`` follows the shared contract (``repro.kernels.dispatch``):
``"jnp"`` delegates to the pure-jnp oracle in ``ref.py``, ``"pallas"``
runs the Pallas kernel (interpret mode off-TPU), ``"auto"`` picks pallas
on TPU backends and jnp elsewhere — matching ``intersect_count/ops.py``.

Blocking defaults to ``dispatch.plan_blocks`` (``block_n=block_w=None``):
one grid cell when the (N, W) tile fits the VMEM budget, width-tiled
otherwise.  Explicit blocks keep the legacy clamp semantics for the
blocking sweeps in tests.

Activity-encoding variants (see kernel.py):

* ``fused_select``         — dense (N,) 0/1 activity (legacy convention).
* ``fused_select_packed``  — packed uint32 activity words (the engines'
  pmask row, no per-step ``to_bool`` expansion).
* ``fused_select_gathered``        — compact-array order, dense activity.
* ``fused_select_gathered_prefix`` — compact-array order with the level
  pointer itself as the activity (rows [0, p) active), no (N,) vector.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import (default_interpret, pad_axis,
                                    plan_blocks, resolve_impl)
from repro.kernels.fused_select.kernel import fused_select_pallas
from repro.kernels.fused_select.ref import (fused_select_packed_ref,
                                            fused_select_prefix_ref,
                                            fused_select_ref)


@functools.partial(jax.jit, static_argnames=("impl", "block_n", "block_w",
                                             "interpret"))
def fused_select(adj: jax.Array, mask: jax.Array, active: jax.Array, *,
                 impl: str = "auto", block_n: int | None = None,
                 block_w: int | None = None, interpret: bool | None = None
                 ) -> tuple[jax.Array, jax.Array]:
    """First active row minimizing popcount(adj & mask); see kernel.py."""
    impl = resolve_impl(impl)
    if impl == "jnp":
        return fused_select_ref(adj, mask, active)
    if interpret is None:
        interpret = default_interpret()
    n, w = adj.shape
    bn, bw = plan_blocks(n, w, block_n, block_w)
    adj_p = pad_axis(pad_axis(adj, 0, bn), 1, bw)
    mask_p = pad_axis(mask, 0, bw)
    act_p = pad_axis(active.astype(jnp.int32), 0, bn)   # pad rows inactive
    idx, val = fused_select_pallas(
        adj_p, mask_p, act_p, block_n=bn, block_w=bw, interpret=interpret)
    return idx, val


@functools.partial(jax.jit, static_argnames=("impl", "block_n", "block_w",
                                             "interpret"))
def fused_select_packed(adj: jax.Array, mask: jax.Array,
                        act_words: jax.Array, *, impl: str = "auto",
                        block_n: int | None = None,
                        block_w: int | None = None,
                        interpret: bool | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """``fused_select`` with PACKED activity: ``act_words`` is the
    (ceil(N/32),) uint32 bitset of active rows (the engine's pmask row,
    passed without ``to_bool`` expansion).  Bits at positions >= N must
    be clear (true for every engine mask)."""
    impl = resolve_impl(impl)
    if impl == "jnp":
        return fused_select_packed_ref(adj, mask, act_words)
    if interpret is None:
        interpret = default_interpret()
    n, w = adj.shape
    bn, bw = plan_blocks(n, w, block_n, block_w, row_mult=32)
    adj_p = pad_axis(pad_axis(adj, 0, bn), 1, bw)
    mask_p = pad_axis(mask, 0, bw)
    np_ = adj_p.shape[0]
    act_p = pad_axis(act_words, 0, np_ // 32)[: np_ // 32]
    idx, val = fused_select_pallas(
        adj_p, mask_p, act_p, block_n=bn, block_w=bw, interpret=interpret,
        act_kind="packed")
    return idx, val


@functools.partial(jax.jit, static_argnames=("impl", "block_n", "block_w",
                                             "interpret"))
def fused_select_prefix(adj: jax.Array, mask: jax.Array, p: jax.Array, *,
                        impl: str = "auto", block_n: int | None = None,
                        block_w: int | None = None,
                        interpret: bool | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """``fused_select`` with PREFIX activity: rows [0, p) are active
    (``p`` a traced scalar — the compact engine's level pointer)."""
    impl = resolve_impl(impl)
    if impl == "jnp":
        return fused_select_prefix_ref(adj, mask, p)
    if interpret is None:
        interpret = default_interpret()
    n, w = adj.shape
    bn, bw = plan_blocks(n, w, block_n, block_w)
    adj_p = pad_axis(pad_axis(adj, 0, bn), 1, bw)
    mask_p = pad_axis(mask, 0, bw)
    # padded rows have global index >= n >= p, hence inactive by the
    # prefix rule itself — nothing to pad on the activity side.
    idx, val = fused_select_pallas(
        adj_p, mask_p, jnp.asarray(p, jnp.int32), block_n=bn, block_w=bw,
        interpret=interpret, act_kind="prefix")
    return idx, val


def fused_select_gathered(adj: jax.Array, idx: jax.Array, mask: jax.Array,
                          active: jax.Array, *, impl: str = "auto",
                          block_n: int | None = None,
                          block_w: int | None = None,
                          interpret: bool | None = None
                          ) -> tuple[jax.Array, jax.Array]:
    """``fused_select`` over the gathered rows ``adj[idx]`` — the
    compact-array access pattern (selection in compact-array position
    order; the returned index is a POSITION into ``idx``)."""
    return fused_select(adj[idx], mask, active, impl=impl, block_n=block_n,
                        block_w=block_w, interpret=interpret)


def fused_select_gathered_prefix(adj: jax.Array, idx: jax.Array,
                                 mask: jax.Array, p: jax.Array, *,
                                 impl: str = "auto",
                                 block_n: int | None = None,
                                 block_w: int | None = None,
                                 interpret: bool | None = None
                                 ) -> tuple[jax.Array, jax.Array]:
    """``fused_select_gathered`` with the compact engine's level-pointer
    activity (positions [0, p) active) passed as a scalar instead of a
    materialized (N,) comparison vector."""
    return fused_select_prefix(adj[idx], mask, p, impl=impl,
                               block_n=block_n, block_w=block_w,
                               interpret=interpret)
