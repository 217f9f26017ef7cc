"""gRPC transport: mean `rpc.request_wait` of a Predict, in ms: from the pool
thread taking the RPC to the handler's first line. In between the pool thread
asks the call for the request's message and sleeps; the listener's poller
thread reads it, parses it (`rpc.parse`, inside this phase) and wakes the pool
thread, which has to take the interpreter lock again."""
from _lib import phase_mean_us


def read(ctx):
    mean = phase_mean_us(ctx, "rpc.request_wait")
    return None if mean is None else mean / 1e3
