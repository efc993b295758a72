"""Least busy device's busy time over the busiest's, in the traced window
of a cell over several chips (1: every device as busy as the busiest)."""


def read(ctx):
    busy = (ctx.trace or {}).get("busy_per_device_s")
    if not busy or max(busy) <= 0:
        return None
    return min(busy) / max(busy)
