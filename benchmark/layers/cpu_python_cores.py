"""Service to batcher: cores of CPU taken by the threads Python knows
(`cpu.<role>` over the seven Python roles: a listener's poller, the `rpc`
handlers, the collector, the dispatch thread, the completer, the REST
gateway's loop and every other) over the window. These threads share ONE
interpreter lock: a sum near 1.0 with `runq_wait_pct` small is that lock's
ceiling. A Python thread inside native code that released the lock (the
native assembly, a protobuf parse) still counts here."""
from _cpu import PYTHON_ROLES, role_pct_of_core


def read(ctx):
    pct = role_pct_of_core(ctx, "cpu.", PYTHON_ROLES)
    return None if pct is None else pct / 100.0
