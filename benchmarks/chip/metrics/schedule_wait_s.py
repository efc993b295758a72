"""Seconds per job in ``dse.schedule.wait``: the schedule solver's host
blocked on the device, for stage 1's outputs and the bisection's starts."""

from program_spans import per_job


def read(ctx):
    return per_job(ctx, "dse.schedule.wait")
