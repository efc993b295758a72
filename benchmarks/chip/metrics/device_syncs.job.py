"""Blocking device-to-host copies per job (``dse.syncs``: one per
``obs.fetch``, each a point where the host waits for the device)."""

from program_spans import counted


def read(ctx):
    n = counted(ctx, "dse.syncs")
    if n is None or not ctx.result["jobs"]:
        return None
    return n / ctx.result["jobs"]
