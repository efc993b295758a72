"""Share of the traced window in which the device ran no operation, in
the closed loop of co-design jobs: 1 - busy / window, from the trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
