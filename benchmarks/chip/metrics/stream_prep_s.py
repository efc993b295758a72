"""Seconds per job in ``dse.stream.prep``: the stream's host preparation.
Once, the stacked layer struct and the input hash; per chunk, the slice
and padding of the grid columns, then the two-level dedup and the bucket
padding (``_prepare_fields``)."""

from program_spans import per_job


def read(ctx):
    return per_job(ctx, "dse.stream.prep")
