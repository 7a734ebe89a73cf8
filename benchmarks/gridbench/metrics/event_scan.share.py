"""Kernels (kernels/ops.py): device time of the Pallas ``event_scan``
kernel (its custom calls carry the kernel's name in the trace) as a
share of device busy time.  The rank-fed XLA scan of the speculative
steps carries no name in the trace and is not counted."""
from benchmarks.gridbench import trace_reduce


def read(ctx):
    red = ctx["red"]
    busy = trace_reduce.mean_busy_ns(red)
    t, n = trace_reduce.kernel_time_ns(red, "event_scan")
    if busy <= 0 or n == 0:
        return None
    return 100.0 * t / busy
