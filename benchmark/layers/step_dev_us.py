"""Jitted step: device busy time per batch dispatched during the capture."""


def read(ctx):
    batches = ctx["trace"].get("batches")
    return ctx["trace"]["busy_s"] * 1e6 / batches if batches else None
