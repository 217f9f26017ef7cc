"""gRPC transport: 100 x the busiest listener's share of the RPCs the window
answered. The server binds k `grpc.server` objects to its one port, each with
its own poller thread, and the kernel spreads CONNECTIONS over them; each
listener's RPCs are counted under its own phase, `rpc.listener<i>`
(`serving/server.py`). 100 / k is an even spread; 100 is one listener doing
all the work, which is also what a program with one listener and no such
phase (the commit before ISSUE 34) reads for a window that answered a
Predict. A window that answered none reads nothing."""
from _lib import phase_count

PHASE = "rpc.listener"


def read(ctx):
    counts = [p["count"] for name, p in ctx["phases"].items() if name.startswith(PHASE)]
    total = sum(counts)
    if not total:
        return 100.0 if phase_count(ctx, "predict.execute") else None
    return 100.0 * max(counts) / total
