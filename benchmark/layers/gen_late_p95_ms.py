"""Load generator: 95th percentile of (send time - due time). A starved
generator shows here, and must not be read as a slow server."""


def read(ctx):
    return ctx["gen"].get("late_p95_ms")
