"""JAX backend compiles (or persistent-cache loads) inside the measured
window of the open-loop queries: 0 when set-up warmed every program."""


def read(ctx):
    lo, hi = ctx.window
    return float(sum(1 for t, _ in ctx.recorder.compiles if lo <= t <= hi))
