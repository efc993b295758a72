"""Share of the traced window in which the devices ran no operation, in
the closed loop of sharded sweeps: 1 - mean busy / window over the
cell's devices, from the trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
