"""Seconds per job in ``dse.pool.eval``: the per-layer engine call on the
co-design pool's six grid points, through its copy to the host."""

from program_spans import per_job


def read(ctx):
    return per_job(ctx, "dse.pool.eval")
