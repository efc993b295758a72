"""Self seconds per job of ``hetero.codesign_problems_streaming``: the
candidate pool built from the stream's boundary sets and top-k, the
per-layer engine call on the pool and the problem tensors, less the
stream it runs first."""

from spans import per_job


def read(ctx):
    return per_job(ctx, "pool", minus=("stream",))
