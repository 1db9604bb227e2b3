"""Pallas TPU kernel: fused degeneracy-order candidate selection.

cuMBE's candidate selection scans P for the vertex minimizing |N(v) & L|,
with two early stops (Section III-E). Early-exit of a lockstep VPU scan is
an anti-pattern; the TPU-native form fuses the whole selection into one
pass over the adjacency bitset matrix:

    counts[i] = popcount(adj[i] & maskL)          (the intersect_count op)
    select    = argmin_i { counts[i] : active[i] }

in a single pallas_call — the counts never round-trip to HBM (the paper's
goal, achieved structurally instead of via early exit).

TPU mapping
-----------
* grid = (N/BN, W/BW), W innermost: per-row partial counts accumulate in a
  VMEM scratch (BN,1); at the last W block the masked block-minimum is
  folded into the global (1,1) running (val, idx) outputs, whole SMEM
  arrays that stay resident across the sequential grid.
* first-minimum-wins tie-breaking (strict <) matches jnp.argmin.
* blocking comes from ``dispatch.plan_blocks``: one grid cell whenever the
  (N, W) tile fits its VMEM budget, full-width row stripes otherwise.

Activity encodings (``act_kind``) — how "v ∈ P" reaches the kernel:

* ``"dense"``  — (BN, 1) int32 0/1 rows, the original calling convention.
* ``"packed"`` — uint32 words, 32 activity bits per lane; the engines pass
  their pmask row directly instead of ``to_bool``-expanding it to an (N,)
  vector every step (a 32x HBM-traffic blowup on the hot operand).  The
  kernel expands bits in VMEM (``words_to_bits``, no gather).
* ``"prefix"`` — a single (1, 1) int32 bound ``p``: row i is active iff
  i < p.  The compact engine's level-pointer activity, as a scalar instead
  of a materialized (N,) comparison vector.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INF = 0x7FFFFFFF  # python int: a traced constant may not be captured

ACT_KINDS = ("dense", "packed", "prefix")

# (1, 1) int32 operands that the kernel reads or writes as scalars live in
# SMEM: Mosaic has no scalar stores into VMEM, and a whole-array SMEM block
# stays resident across the sequential grid like a revisited VMEM block.
SMEM_SCALAR = pl.BlockSpec(memory_space=pltpu.SMEM)


def words_to_bits(words: jax.Array, n: int) -> jax.Array:
    """(1, NW) uint32 words -> (n, 1) int32 0/1 column (n <= 32 * NW):
    row v holds bit v % 32 of word v // 32 (``bitset.to_bool`` order).

    Kernel-safe without a gather: the word row is transposed into a
    column, each word is broadcast down 32 sublanes and the (NW, 32, 1)
    block folds into rows — shapes Mosaic lowers for 32-bit data, where a
    (NW, 32) <-> (1, 32 NW) lane reshape is refused."""
    nw = words.shape[1]
    col = words.T
    rep = jnp.reshape(jnp.broadcast_to(col[:, None, :], (nw, 32, 1)),
                      (nw * 32, 1))
    sh = jax.lax.broadcasted_iota(jnp.uint32, (nw * 32, 1), 0) \
        & jnp.uint32(31)
    return ((rep >> sh) & jnp.uint32(1)).astype(jnp.int32)[:n]


def bits_to_words(bits: jax.Array, nw: int) -> jax.Array:
    """(n, 1) 0/1 column -> (1, NW) uint32 words (``bitset.from_bool``),
    the inverse of ``words_to_bits``.  The word sum runs in int32: Mosaic
    has no unsigned reductions, and a sum of distinct powers of two is the
    same 32 bits in either signedness."""
    n = bits.shape[0]
    b = bits.astype(jnp.int32)
    if n < nw * 32:
        b = jnp.concatenate([b, jnp.zeros((nw * 32 - n, 1), jnp.int32)],
                            axis=0)
    sh = jax.lax.broadcasted_iota(jnp.int32, (nw * 32, 1), 0) & 31
    words = jnp.sum(jnp.reshape(b << sh, (nw, 32, 1)), axis=1)   # (NW, 1)
    return jax.lax.bitcast_convert_type(words.T, jnp.uint32)


def expand_act_words(words: jax.Array, block_n: int) -> jax.Array:
    """(1, BN/32) uint32 activity words -> (BN, 1) bool.  BN % 32 == 0."""
    return words_to_bits(words, block_n) > 0


def _kernel(adj_ref, mask_ref, act_ref, val_ref, idx_ref, counts_ref, *,
            block_n: int, n_wblocks: int, act_kind: str):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init_out():
        val_ref[0, 0] = jnp.int32(_INF)
        idx_ref[0, 0] = jnp.int32(-1)

    @pl.when(j == 0)
    def _init_counts():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    tile = adj_ref[...] & mask_ref[...]
    pc = jax.lax.population_count(tile).astype(jnp.int32)
    counts_ref[...] += jnp.sum(pc, axis=1, keepdims=True)

    @pl.when(j == n_wblocks - 1)
    def _fold():
        if act_kind == "dense":
            actb = act_ref[...] > 0                       # (BN, 1)
        elif act_kind == "packed":
            actb = expand_act_words(act_ref[...], block_n)
        else:  # prefix
            rows_g = i * block_n + jax.lax.broadcasted_iota(
                jnp.int32, (block_n, 1), 0)
            actb = rows_g < act_ref[0, 0]
        c = jnp.where(actb, counts_ref[...], _INF)        # (BN, 1)
        bmin = jnp.min(c)
        # first minimum within the block
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_n, 1), 0)
        bidx = jnp.min(jnp.where(c == bmin, rows, _INF))
        better = bmin < val_ref[0, 0]
        val_ref[0, 0] = jnp.where(better, bmin, val_ref[0, 0])
        idx_ref[0, 0] = jnp.where(better, i * block_n + bidx,
                                  idx_ref[0, 0])


@functools.partial(jax.jit, static_argnames=("block_n", "block_w",
                                             "interpret", "act_kind"))
def fused_select_pallas(adj: jax.Array, mask: jax.Array,
                        active: jax.Array, *, block_n: int = 512,
                        block_w: int = 256,
                        interpret: bool = False, act_kind: str = "dense"
                        ) -> tuple[jax.Array, jax.Array]:
    """adj: (N, W) u32; mask: (W,) u32; active per ``act_kind``:
    dense (N,) i32 / packed (N/32,) u32 (N % 32 == 0) / prefix () i32.
    -> (idx i32, val i32): first row minimizing popcount(adj&mask) among
    active rows; (-1, INT32_MAX) if none active.
    N % block_n == 0 and W % block_w == 0 (ops.py pads)."""
    n, w = adj.shape
    assert n % block_n == 0 and w % block_w == 0, (n, w, block_n, block_w)
    assert act_kind in ACT_KINDS, act_kind
    grid = (n // block_n, w // block_w)
    kern = functools.partial(_kernel, block_n=block_n, n_wblocks=grid[1],
                             act_kind=act_kind)
    if act_kind == "dense":
        act_arg = active[:, None].astype(jnp.int32)
        act_spec = pl.BlockSpec((block_n, 1), lambda i, j: (i, 0))
    elif act_kind == "packed":
        assert block_n % 32 == 0 and active.shape == (n // 32,), \
            (block_n, active.shape)
        # one (1, BN/32) word row per row block, on a squeezed leading
        # axis: a (1, BN/32) block of an (N/BN, BN/32) array breaks
        # Mosaic's (8, 128)-or-whole-dimension block rule
        act_arg = active.reshape(n // block_n, 1, block_n // 32)
        act_spec = pl.BlockSpec((None, 1, block_n // 32),
                                lambda i, j: (i, 0, 0))
    else:  # prefix
        act_arg = jnp.asarray(active, jnp.int32).reshape(1, 1)
        act_spec = SMEM_SCALAR
    val, idx = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_w), lambda i, j: (i, j)),
            pl.BlockSpec((1, block_w), lambda i, j: (0, j)),
            act_spec,
        ],
        out_specs=[SMEM_SCALAR, SMEM_SCALAR],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((block_n, 1), jnp.int32)],
        interpret=interpret,
    )(adj, mask[None, :], act_arg)
    return idx[0, 0], val[0, 0]
