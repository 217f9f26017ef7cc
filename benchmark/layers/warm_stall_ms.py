"""Start-up: the longest time in which no answer arrived while the generators'
unmeasured warm-up was on. A server stalls once, for a second or two, in its
first few hundred requests (PERF.md); the warm-up exists to keep that out of
the window, and this is where it still shows."""


def read(ctx):
    return ctx["gen"].get("warm_stall_ms")
