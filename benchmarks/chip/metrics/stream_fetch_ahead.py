"""Per-chunk fetches per job made with the next chunk's grid kernel and
fold already enqueued behind the fetched one (``dse.stream.fetch_ahead``:
one-device stream; a five-chunk job reads 4)."""

from program_spans import counted


def read(ctx):
    n = counted(ctx, "dse.stream.fetch_ahead")
    if n is None or not ctx.result["jobs"]:
        return None
    return n / ctx.result["jobs"]
