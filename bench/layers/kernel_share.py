"""Device time in the MBE Pallas kernels over device busy time, in %, from
the trace (``bench/trace.py``): operations whose name or stats name one
of the kernels, or a TPU custom call (the Pallas kernels are the only
custom calls on the MBE path; their ``pallas_call`` sites set no stable
name yet).  A trace with device time but none in a kernel is an error:
the names no longer match, and a share of 0 would hide it."""
KERNELS = ("resident_pool", "resident_kernel", "resident_step",
           "fused_select", "fused_check", "pallas_call", "tpu_custom_call",
           "custom-call", "custom_call")


def read(run):
    t = run["trace"]
    if t is None or t.busy_s <= 0:
        return None
    k = sum(s for name, s in t.op_s.items()
            if any(key in f"{name} {t.op_info.get(name, '')}"
                   for key in KERNELS))
    if k <= 0:
        raise ValueError("device time in the trace, but no operation names "
                         f"an MBE kernel; top ops: {sorted(t.op_s)[:10]}")
    return 100 * k / t.busy_s
