"""Device: share of the traced window in which no operation ran on it."""


def read(ctx):
    busy, window = ctx["trace"].get("busy_s"), ctx["trace"].get("window_s")
    return 100.0 * (1.0 - busy / window) if busy and window else None
