"""HBM roofline shares of the compact engine's gathered kernels.

A kernel's share is the least bytes its passes had to read over the
window, over what the chip's HBM moves at its published peak in the
device time of the kernel's operations:

    100 * bytes / (kernel device s * HBM bytes/s of the running chip)

The kernels do a few integer operations per 4-byte word, far below any
compute peak, so the HBM bound is the roofline.  The bytes come from the
program's ``stats()`` counters ``gathered_select_words`` and
``gathered_check_words``: for each candidate step, the adjacency rows a
pass had to read (the active rows given by the compact array's level
pointers, plus the mask row), in 32-bit words.  They count what a step
needs, not what the kernel streams, so a kernel that reads padded or
inactive rows reads a lower share, and none can read above 100 %.

A kernel's operations are those whose own HLO name (left of `` = ``)
is the kernel's ``pallas_call`` name, as ``%fused_select.12``: the HLO
text of other operations names the kernel's result among its operands.
"""
from __future__ import annotations

import re

from bench import peaks

WORD_BYTES = 4


def least_bytes(words: int) -> int:
    """Bytes of ``words`` 32-bit adjacency words."""
    return WORD_BYTES * words


def kernel_s(trace, kernel: str) -> float:
    """Device seconds of the operations named ``kernel`` in a reduction
    (``bench/trace.py``)."""
    own = re.compile(rf"%?{re.escape(kernel)}(\.|$)")
    return sum(s for name, s in trace.op_s.items()
               if own.match(name.split(" = ", 1)[0]))


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


def share(run: dict, kernel: str, counter: str) -> float | None:
    """The roofline share of ``kernel`` in %, from the window's delta of
    the ``counter`` words; None without a trace or where the program has
    no such counter."""
    t = run["trace"]
    if t is None:
        return None
    a, b = run["stats_after"], run["stats_before"]
    if counter not in a or counter not in b:
        return None
    s = kernel_s(t, kernel)
    if s <= 0:
        if t.busy_s > 0:
            raise ValueError(
                f"device time in the trace, but no operation is named "
                f"{kernel!r}; top ops: {sorted(t.op_s)[:10]}")
        return None
    peak = peaks.lookup(device_kind())["hbm_bytes_per_s"]
    return 100 * least_bytes(a[counter] - b[counter]) / (s * peak)
