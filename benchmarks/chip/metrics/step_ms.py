"""Host milliseconds per served ``DSEService.step`` in the window: one
coalesced batch of queued queries of one family, answered."""


def read(ctx):
    r = ctx.result
    if not r.get("steps"):
        return None
    return 1000.0 * r["step_s"] / r["steps"]
