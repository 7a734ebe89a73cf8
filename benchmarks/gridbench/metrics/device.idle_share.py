"""Device: share of the traced window in which no op ran on the chip
(averaged over the cell's chips)."""
from benchmarks.gridbench import trace_reduce


def read(ctx):
    red = ctx["red"]
    if red.window_ns <= 0 or not red.busy_ns:
        return None
    return 100.0 * (1.0 - trace_reduce.mean_busy_ns(red) / red.window_ns)
