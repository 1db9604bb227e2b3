"""Shared impl-dispatch rules for the MBE kernel packages.

Every kernel op takes ``impl`` with the same three values:

* ``"jnp"``    — the pure-jnp oracle (``ref.py``): fast on CPU, the
  byte-identical reference the Pallas path is validated against.
* ``"pallas"`` — the Pallas TPU kernel; on the CPU backend it runs in
  interpret mode so tests exercise the REAL kernel body there.
* ``"auto"``   — ``"pallas"`` on a TPU default backend, ``"jnp"``
  elsewhere (interpret mode is correct but slow, so it is never chosen
  automatically).

The engines resolve ``EngineConfig.kernel_impl`` through the same
function at trace time, so one knob ("auto") gives the fused Pallas hot
path on TPU and the unfused jnp path everywhere else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

IMPLS = ("auto", "jnp", "pallas")


def resolve_impl(impl: str) -> str:
    """Map ``impl`` to a concrete ``"jnp"``/``"pallas"`` choice."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"unknown impl {impl!r}; expected one of {IMPLS}")
    return impl


def default_interpret() -> bool:
    """Whether a pallas_call must run in interpret mode: on the CPU
    backend (the test suite) it must, on a TPU it must not.  Any other
    backend has no Mosaic path, and interpreting there would hide the
    missing device, so it raises."""
    backend = jax.default_backend()
    if backend in ("tpu", "cpu"):
        return backend == "cpu"
    raise RuntimeError(f"Pallas kernels need a TPU (or the CPU backend "
                       f"in interpret mode); default backend is {backend!r}")


def pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    """Zero-pad ``axis`` up to a multiple of ``mult`` (shared by every
    ops wrapper: zero words contribute zero to popcounts and padded rows
    are marked inactive, so padding never changes a result)."""
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# Per-tile budget for the row-accumulate step kernels' (block_n, block_w)
# adjacency tile.  Compiled for TPU v5e, the kernel also holds a second
# (pipelined) copy of the tile, and every (block_n, 1) int32 column — the
# counts scratch, dense activity and flag columns, the counts output — pads
# to 128 lanes, i.e. 512 bytes a row.  A 1 MiB tile over at most
# ``MAX_BLOCK_ROWS`` rows keeps the worst variant (compact prefix2: seven
# such columns) near 9 MiB, inside the compiler's 16 MiB default scoped
# VMEM limit; the compile tests (tests/test_tpu_compile.py) hold it there.
DEFAULT_TILE_BYTES = 1024 * 1024
MAX_BLOCK_ROWS = 2048

_LANE = 128      # TPU lane width (words per vector register row)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def plan_blocks(n: int, w: int, block_n: int | None = None,
                block_w: int | None = None, *, row_mult: int = 8,
                tile_bytes: int = DEFAULT_TILE_BYTES) -> tuple[int, int]:
    """Pick ``(block_n, block_w)`` for an (n, w) row-accumulate kernel.

    Explicit ``block_n``/``block_w`` are honoured (clamped to the array,
    alignment-rounded) — the test sweeps exercise fixed blockings.  The
    ``None`` auto policy:

    * one grid cell whenever all rows fit one tile (``tile_bytes`` and
      ``MAX_BLOCK_ROWS``) — every serving bucket of the bench and large
      suites;
    * otherwise full-width row stripes: the mask row is the only operand
      a row block re-reads, and the sequential grid folds the running
      argmin / violation flag through SMEM between row blocks;
    * width tiles (multiples of 128 lanes) only when a single ``row_mult``
      stripe of full rows overflows the tile budget.

    ``row_mult`` is the row-block alignment (8 sublanes; the packed-mask
    variants need 32 so activity words align with row blocks).
    """
    if block_n is not None or block_w is not None:
        bn = min(block_n or 512, max(row_mult, _round_up(n, row_mult)))
        bw = min(block_w or 256, max(8, w))
        return _round_up(bn, row_mult), bw
    words = tile_bytes // 4
    rows = max(row_mult, min(MAX_BLOCK_ROWS, words // max(w, 1))
               // row_mult * row_mult)
    n_pad = _round_up(n, row_mult)
    if n_pad <= rows:
        return n_pad, w                     # one resident tile
    if row_mult * w <= words:
        return rows, w                      # full-width row stripes
    return row_mult, max(_LANE, (words // row_mult) // _LANE * _LANE)
