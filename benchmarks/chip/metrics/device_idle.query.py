"""Share of the traced window in which the device ran no operation, while
open-loop queries are served: 1 - busy / window, from the trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
