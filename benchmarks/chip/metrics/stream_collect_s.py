"""Seconds per job in ``dse.stream.collect``: host work on what the stream
copied back.  Per chunk, the NaN guard and the boundary-candidate
collection; once, the final prune and sort of the boundary sets."""

from program_spans import per_job


def read(ctx):
    return per_job(ctx, "dse.stream.collect")
