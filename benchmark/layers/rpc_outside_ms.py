"""gRPC transport: the generators' mean latency from the instant a request was
SENT to its answer, less mean `rpc.server` (the RPC as the server process sees
it, from the pool's `submit` to the call's termination): what is left outside
the process's own stamps: the client's grpc, the socket, and the listener's
poller picking the new call up. Means on both sides over the same requests."""
from _lib import phase_mean_us


def read(ctx):
    mean, server = ctx["gen"].get("mean_from_send_ms"), phase_mean_us(ctx, "rpc.server")
    return None if mean is None or server is None else mean - server / 1e3
