"""Pallas TPU kernel: fused maximality check + maximal-expansion partition.

After candidate selection builds L' = L ∩ N(x), one engine step still
needs, for every vertex/position v with counts c[v] = popcount(adj[v] & L'):

* the **Q-violation flag**  ``any(q_act[v] & (c[v] == |L'|))`` — cuMBE's
  maximality check (paper §III-E phase C),
* the **full flags**        ``p_act[v] & (c[v] == |L'|)``  — candidates
  absorbed into R' (maximal expansion, phase E),
* the **partial flags**     ``p_act[v] & (0 < c[v] < |L'|)`` — the child
  candidate set P',
* the **nonzero flags**     ``c[v] > 0`` — the paper's Q' filter.

The unfused path materializes the counts vector to HBM (one
``intersect_count`` pass per row set) and derives each of these with
separate elementwise/reduction XLA ops.  This kernel computes ALL of them
in ONE pass over the adjacency bitset: per-row partial counts accumulate
in a VMEM scratch and only the flag vectors (plus the scalar flag) are
ever written out — the counts never round-trip to HBM.

``with_counts=True`` additionally emits the counts vector: the dense
engine's ``"deg"`` mode caches child-level counts (``cstack``) so the
NEXT level's candidate selection costs zero adjacency passes; emitting
the cache from the same pass keeps that beyond-paper optimization intact.

Activity/flag encodings (``act_kind``):

* ``"dense"``   — (N,) 0/1 activity inputs and (N,) flag outputs (the
  original convention).
* ``"packed"``  — q/p activity arrive as uint32 BITSET WORDS (the dense
  engine's qmask/pmask rows, no ``to_bool`` expansion) and the
  full/part/nz flags leave as packed words too (no ``from_bool`` on the
  engine side) — 32x less HBM traffic per step on every mask operand.
* ``"prefix2"`` — the compact engine's concatenated [Q ++ P] gathered
  layout: activity is two scalar bounds (q_hi, p_hi) against a static
  row split; positions [0, q_hi) of the first half and [0, p_hi) of the
  second half are active.  Flag outputs stay dense (positions are then
  scattered through the compact array, so packing buys nothing).

TPU mapping
-----------
* grid = (N/BN, W/BW), W innermost: per-row partial counts accumulate in
  a VMEM scratch (BN, 1); at the last W block the flags are emitted and
  the block's Q-violation disjunction is OR-folded into the global (1,1)
  flag output, a whole SMEM array resident across the sequential grid,
  exactly like ``fused_select``.
* |L'| arrives as a (1,1) i32 SMEM input (traced scalar, not a Python
  constant — it changes every step).
* blocking comes from ``dispatch.plan_blocks`` (single cell / row
  stripes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_select.kernel import (SMEM_SCALAR, bits_to_words,
                                              expand_act_words)

ACT_KINDS = ("dense", "packed", "prefix2")


def _kernel(*refs, block_n: int, n_wblocks: int, with_counts: bool,
            act_kind: str, split: int):
    if act_kind == "prefix2":
        (adj_ref, mask_ref, nlp_ref, bounds_ref,
         viol_ref, full_ref, part_ref, nz_ref) = refs[:8]
    else:
        (adj_ref, mask_ref, nlp_ref, qact_ref, pact_ref,
         viol_ref, full_ref, part_ref, nz_ref) = refs[:9]
    counts_ref = refs[-2] if with_counts else None
    acc_ref = refs[-1]
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init_viol():
        viol_ref[0, 0] = jnp.int32(0)

    @pl.when(j == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tile = adj_ref[...] & mask_ref[...]
    pc = jax.lax.population_count(tile).astype(jnp.int32)
    acc_ref[...] += jnp.sum(pc, axis=1, keepdims=True)

    @pl.when(j == n_wblocks - 1)
    def _emit():
        c = acc_ref[...]                               # (BN, 1) int32
        nlp = nlp_ref[0, 0]
        if act_kind == "dense":
            q = qact_ref[...] > 0
            p = pact_ref[...] > 0
        elif act_kind == "packed":
            q = expand_act_words(qact_ref[...], block_n)
            p = expand_act_words(pact_ref[...], block_n)
        else:  # prefix2
            rows_g = i * block_n + jax.lax.broadcasted_iota(
                jnp.int32, (block_n, 1), 0)
            q = (rows_g < split) & (rows_g < bounds_ref[0, 0])
            p = (rows_g >= split) & (rows_g - split < bounds_ref[0, 1])
        eq = c == nlp
        viol_ref[0, 0] = viol_ref[0, 0] | jnp.max(
            jnp.where(q & eq, 1, 0).astype(jnp.int32))
        fullb = p & eq
        partb = p & (c > 0) & (c < nlp)
        nzb = c > 0
        if act_kind == "packed":
            full_ref[...] = bits_to_words(fullb, block_n // 32)
            part_ref[...] = bits_to_words(partb, block_n // 32)
            nz_ref[...] = bits_to_words(nzb, block_n // 32)
        else:
            full_ref[...] = fullb.astype(jnp.int32)
            part_ref[...] = partb.astype(jnp.int32)
            nz_ref[...] = nzb.astype(jnp.int32)
        if with_counts:
            counts_ref[...] = c


@functools.partial(jax.jit, static_argnames=("block_n", "block_w",
                                             "interpret", "with_counts",
                                             "act_kind", "split"))
def fused_check_pallas(adj: jax.Array, mask: jax.Array, n_mask: jax.Array,
                       q_act: jax.Array, p_act: jax.Array, *,
                       block_n: int = 512, block_w: int = 256,
                       interpret: bool = False, with_counts: bool = False,
                       act_kind: str = "dense", split: int = 0):
    """adj: (N, W) u32; mask: (W,) u32; n_mask: () i32 (= popcount(mask));
    activity per ``act_kind``: dense (N,) i32 pair / packed (N/32,) u32
    pair / prefix2 () i32 pair (q_hi, p_hi) against the static ``split``.
    -> (viol () i32, full, part, nz[, counts (N,) i32]) where the flag
    vectors are (N,) i32 (dense/prefix2) or (N/32,) u32 (packed).
    N % block_n == 0 and W % block_w == 0 (ops.py pads)."""
    n, w = adj.shape
    assert n % block_n == 0 and w % block_w == 0, (n, w, block_n, block_w)
    assert act_kind in ACT_KINDS, act_kind
    grid = (n // block_n, w // block_w)
    kern = functools.partial(_kernel, block_n=block_n, n_wblocks=grid[1],
                             with_counts=with_counts, act_kind=act_kind,
                             split=split)
    col_spec = pl.BlockSpec((block_n, 1), lambda i, j: (i, 0))
    col_shape = jax.ShapeDtypeStruct((n, 1), jnp.int32)
    in_specs = [
        pl.BlockSpec((block_n, block_w), lambda i, j: (i, j)),
        pl.BlockSpec((1, block_w), lambda i, j: (0, j)),
        SMEM_SCALAR,
    ]
    args = [adj, mask[None, :], jnp.asarray(n_mask, jnp.int32).reshape(1, 1)]
    if act_kind == "dense":
        in_specs += [col_spec, col_spec]
        args += [q_act.astype(jnp.int32)[:, None],
                 p_act.astype(jnp.int32)[:, None]]
        flag_spec, flag_shape = col_spec, col_shape
    elif act_kind == "packed":
        assert block_n % 32 == 0
        assert q_act.shape == p_act.shape == (n // 32,), \
            (q_act.shape, p_act.shape, n)
        # word rows on a squeezed leading axis (see fused_select)
        word_spec = pl.BlockSpec((None, 1, block_n // 32),
                                 lambda i, j: (i, 0, 0))
        in_specs += [word_spec, word_spec]
        args += [q_act.reshape(n // block_n, 1, block_n // 32),
                 p_act.reshape(n // block_n, 1, block_n // 32)]
        flag_spec = word_spec
        flag_shape = jax.ShapeDtypeStruct((n // block_n, 1, block_n // 32),
                                          jnp.uint32)
    else:  # prefix2: one (1, 2) i32 bounds operand
        in_specs += [SMEM_SCALAR]
        args += [jnp.stack([jnp.asarray(q_act, jnp.int32),
                            jnp.asarray(p_act, jnp.int32)]).reshape(1, 2)]
        flag_spec, flag_shape = col_spec, col_shape
    out_specs = [SMEM_SCALAR, flag_spec, flag_spec, flag_spec]
    out_shape = [jax.ShapeDtypeStruct((1, 1), jnp.int32),
                 flag_shape, flag_shape, flag_shape]
    if with_counts:
        out_specs.append(col_spec)
        out_shape.append(col_shape)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_n, 1), jnp.int32)],
        interpret=interpret,
    )(*args)
    viol = out[0][0, 0]
    if act_kind == "packed":
        full, part, nz = (o.reshape(-1) for o in out[1:4])
    else:
        full, part, nz = (o[:, 0] for o in out[1:4])
    counts = out[4][:, 0] if with_counts else None
    return viol, full, part, nz, counts
