"""Seconds per job in ``dse.stream.merge``: the host merge of the devices'
fold states at the end of a sharded stream (after their copy to the
host, which ``dse.stream.wait`` times)."""

from program_spans import per_job


def read(ctx):
    return per_job(ctx, "dse.stream.merge")
