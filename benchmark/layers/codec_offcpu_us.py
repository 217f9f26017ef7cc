"""Codec: the part of `codec_us` its thread was NOT on a core: mean
`offcpu.predict.decode` + mean `offcpu.predict.encode` (wall less
`time.thread_time` of spans that wait for nothing by design: interpreter
lock taken by another thread, or preemption), in us; capture only. The
program sums wall less CPU uncut, so where the CPU clock ticks coarsely (10 ms
under gVisor) a tick that falls into a short span can take a small sample's
sum below zero: that reads 0."""
from _lib import phase_mean_us


def read(ctx):
    decode = phase_mean_us(ctx, "offcpu.predict.decode")
    encode = phase_mean_us(ctx, "offcpu.predict.encode")
    return None if decode is None or encode is None else max(decode + encode, 0.0)
