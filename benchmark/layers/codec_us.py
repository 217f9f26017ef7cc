"""Codec (codec.py): mean predict.decode + mean predict.encode per request."""
from _lib import phase_mean_us


def read(ctx):
    decode, encode = phase_mean_us(ctx, "predict.decode"), phase_mean_us(ctx, "predict.encode")
    return None if decode is None or encode is None else decode + encode
