"""Engine loop (core/engine.py): device busy time per while-loop
iteration, per chip: committed supersteps of a single run, or the lane
loop's iterations (its longest lane) on each chip's slice of lanes."""
from benchmarks.gridbench import trace_reduce


def read(ctx):
    iters = ctx["iterations"]
    per_chip = sum(sum(c) / len(c) for c in iters)
    if per_chip <= 0 or not ctx["red"].busy_ns:
        return None
    return trace_reduce.mean_busy_ns(ctx["red"]) / per_chip / 1e3
