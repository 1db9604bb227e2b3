"""Pure-jnp oracle for fused_select (all activity encodings)."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import bitset

_INF = np.int32(0x7FFFFFFF)


def fused_select_ref(adj: jax.Array, mask: jax.Array, active: jax.Array
                     ) -> tuple[jax.Array, jax.Array]:
    counts = jnp.sum(jax.lax.population_count(adj & mask[None, :]),
                     axis=1).astype(jnp.int32)
    masked = jnp.where(active > 0, counts, _INF)
    val = jnp.min(masked)
    idx = jnp.where(val == _INF, jnp.int32(-1),
                    jnp.argmin(masked).astype(jnp.int32))
    return idx, val


def fused_select_packed_ref(adj: jax.Array, mask: jax.Array,
                            act_words: jax.Array
                            ) -> tuple[jax.Array, jax.Array]:
    """Packed-activity oracle: defined as the dense oracle over the
    expanded bitset (the expansion the packed kernel avoids)."""
    n = adj.shape[0]
    return fused_select_ref(
        adj, mask, bitset.to_bool(act_words, n).astype(jnp.int32))


def fused_select_prefix_ref(adj: jax.Array, mask: jax.Array, p: jax.Array
                            ) -> tuple[jax.Array, jax.Array]:
    """Prefix-activity oracle: rows [0, p) active."""
    n = adj.shape[0]
    act = (jnp.arange(n, dtype=jnp.int32) < p).astype(jnp.int32)
    return fused_select_ref(adj, mask, act)
