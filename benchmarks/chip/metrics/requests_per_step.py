"""Queries answered per served step in the window (the service's
``stats["completed"]`` over the steps): how far it coalesces."""


def read(ctx):
    r = ctx.result
    if not r.get("steps"):
        return None
    return r["completed"] / r["steps"]
