"""gRPC transport: protobuf's own share of a Predict, in us: mean `rpc.parse`
(`PredictRequest.FromString`, on the listener's poller thread) plus mean
`rpc.serialize` (`PredictResponse.SerializeToString`, on the handler's pool
thread). Outside the handler, so neither `codec_us` nor `predict.*` held it."""
from _lib import phase_mean_us


def read(ctx):
    parse, serialize = phase_mean_us(ctx, "rpc.parse"), phase_mean_us(ctx, "rpc.serialize")
    return None if parse is None or serialize is None else parse + serialize
