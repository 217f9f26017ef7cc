"""Batcher coalescing: share of the window the collector thread spent in
`wait.queue_empty`, with nothing queued to take."""
from _timeline import share_of_window_pct


def read(ctx):
    return share_of_window_pct(ctx, "wait.queue_empty")
