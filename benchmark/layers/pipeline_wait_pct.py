"""D2H and completer: share of the window the collector thread (`wait.pipeline`:
the free ride on a full pipeline and the back-pressure behind the dispatch
thread) and the dispatch thread (`wait.window`: the in-flight window) spent
waiting for a batch in flight to complete. Two threads, so it can pass 100."""
from _timeline import share_of_window_pct


def read(ctx):
    return share_of_window_pct(ctx, "wait.pipeline", "wait.window")
