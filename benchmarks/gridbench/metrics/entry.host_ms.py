"""Entry points (core/simulation.py): mean time per call in which the
device is idle inside the call's span -- parameter building, dispatch
and the result fetch on the host."""
import statistics

from benchmarks.gridbench import trace_reduce


def read(ctx):
    idle = trace_reduce.idle_in_calls_ns(ctx["red"])
    return statistics.fmean(idle) / 1e6 if idle else None
