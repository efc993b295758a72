"""Milliseconds per ``hetero.pareto_codesign`` call in the window: the
Pareto scoring and the energy-aware slack pass of one chip-family batch."""


def read(ctx):
    lo, hi = ctx.window
    got = [b - a for n, a, b in ctx.recorder.in_window(lo, hi)
           if n == "pareto"]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
