"""Batcher coalescing: candidate rows answered in the window (the generators'
count) per batch the server dispatched in it."""


def read(ctx):
    rows, batches = ctx["gen"].get("rows_answered"), ctx["batcher"].get("batches")
    return rows / batches if rows and batches else None
