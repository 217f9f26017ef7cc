"""gRPC transport: CPU of the listeners' `_serve` poller threads over the
window, in percent of ONE core (k pollers may pass 100): accepting RPCs,
reading and parsing requests, handing them to the pool, completions."""
from _cpu import role_pct_of_core


def read(ctx):
    return role_pct_of_core(ctx, "cpu.", ("poller",))
