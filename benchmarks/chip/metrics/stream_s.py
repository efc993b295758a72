"""Seconds per job in ``energymodel.stream_layer_topk``: the engine kernel
and the per-layer fold over every chunk of the grid, up to the host's
copy of the fold's state (so the span ends when the device does)."""

from spans import per_job


def read(ctx):
    return per_job(ctx, "stream")
