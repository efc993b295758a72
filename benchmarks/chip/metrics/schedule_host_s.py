"""Self seconds per job of ``dse.schedule`` (``batch_schedule_hetero``):
its host stages (padding, prefix sums, closed-form rows, the kk == 2
search) and dispatches, less the waits on the device inside it."""

from program_spans import per_job


def read(ctx):
    return per_job(ctx, "dse.schedule", self_time=True)
