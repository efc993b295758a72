"""Seconds per job in ``dse.stream.dispatch``: enqueueing the stream's
device work.  Per chunk, the grid kernel and the per-layer fold, with
their host-to-device copies."""

from program_spans import per_job


def read(ctx):
    return per_job(ctx, "dse.stream.dispatch")
