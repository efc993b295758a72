"""Seconds per job in ``dse.stream.wait``: the stream's host blocked on the
device.  Per chunk, the copy of the fold's aggregates and boundary mask;
once, the final fold state."""

from program_spans import per_job


def read(ctx):
    return per_job(ctx, "dse.stream.wait")
