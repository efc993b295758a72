"""Share of the grid kernel's config rows that are useful in the stream:
count-unique rows over the rows after bucket padding, summed over the
window's chunks (``dse.stream.rows_unique`` / ``dse.stream.rows_padded``)."""

from program_spans import counted


def read(ctx):
    unique = counted(ctx, "dse.stream.rows_unique")
    padded = counted(ctx, "dse.stream.rows_padded")
    if unique is None or not padded:
        return None
    return unique / padded
