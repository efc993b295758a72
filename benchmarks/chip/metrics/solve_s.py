"""Seconds per job in the solvers: ``partition.batch_schedule_hetero``
(every chip x network schedule) and ``hetero.score_codesign``."""

from spans import per_job


def read(ctx):
    return per_job(ctx, "solve.schedule", "solve.score")
