"""gRPC transport: mean `rpc.pool_wait` of a Predict, in ms: from the
listener's poller thread handing the RPC to the handler pool (`submit`) to a
pool thread's first line on it (`serving/server.py`, `_StampedPool`). All 16
`rpc` threads busy, or a free one waiting for the interpreter lock."""
from _lib import phase_mean_us


def read(ctx):
    mean = phase_mean_us(ctx, "rpc.pool_wait")
    return None if mean is None else mean / 1e3
