"""gRPC transport: mean `rpc.reply` of a Predict, in ms: from the handler's
return to the call's termination callback on the listener's poller thread:
the response's serialization (`rpc.serialize`, inside this phase), the send
batch, and the poller's pick-up of its completion."""
from _lib import phase_mean_us


def read(ctx):
    mean = phase_mean_us(ctx, "rpc.reply")
    return None if mean is None else mean / 1e3
