"""Kernels (kernels/ops.py): the Pallas ``event_scan`` kernel's share of
its roofline: the least time its calls could take on the chip (bytes
from the call shape over peak HBM bandwidth; the kernel does no matrix
work) over the time the trace measured for them."""
from benchmarks.gridbench import roofline, trace_reduce


def read(ctx):
    if ctx["peaks"] is None:
        return None
    t, n = trace_reduce.kernel_time_ns(ctx["red"], "event_scan")
    if n == 0 or t <= 0:
        return None
    least = n * roofline.event_scan_bytes(ctx["cfg"]) / \
        ctx["peaks"]["hbm_bytes_per_s"] * 1e9
    return 100.0 * least / t
