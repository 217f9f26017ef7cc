"""Codec and service: mean CPU (`time.thread_time`) of the pool thread over
one Predict, from the pool's callable taking the RPC to the handler's
return, in us: `cpu.rpc_handler`, stamped only while a capture is open. The
wall time between the same stamps is mean `rpc.request_wait` + the
handlers' mean under `rpc.listener*`; the rest of it the thread slept."""
from _lib import phase_mean_us


def read(ctx):
    return phase_mean_us(ctx, "cpu.rpc_handler")
