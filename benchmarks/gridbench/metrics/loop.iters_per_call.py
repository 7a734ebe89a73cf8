"""Engine loop (core/engine.py): mean while-loop iterations per call
(the slowest chip's, which sets the call); a deterministic count."""


def read(ctx):
    iters = ctx["iterations"]
    return sum(max(c) for c in iters) / len(iters) if iters else None
