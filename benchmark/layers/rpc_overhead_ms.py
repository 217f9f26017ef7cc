"""gRPC transport: the generators' mean latency from the instant a request was
SENT (not due) to its answer, less the handler's mean spans (decode, execute,
encode). Means on both sides over the same requests, so the difference is the
mean time a request spends outside the handler: in grpc, on the socket and in
the generator's own callback."""
from _lib import phase_mean_us


def read(ctx):
    mean = ctx["gen"].get("mean_from_send_ms")
    parts = [phase_mean_us(ctx, p) for p in ("predict.execute", "predict.decode", "predict.encode")]
    if mean is None or any(p is None for p in parts):
        return None
    return mean - sum(parts) / 1e3
