"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` JAX reports. A kind that is not here is an error, not a default.

TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bfloat16 and
819 GB/s of HBM bandwidth a chip.
"""

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {device_kind!r}")
    return PEAKS[device_kind]


def least_seconds(flops: float, moved_bytes: float, device_kind: str) -> tuple[float, str]:
    """The least time the chip could take for that work, and which peak
    bounds it."""
    peak = peaks_for(device_kind)
    compute = flops / peak["flops_per_s"]
    memory = moved_bytes / peak["bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
