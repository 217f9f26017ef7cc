"""Kernels: the least time the chip could take for the rows answered during
the capture (operations and bytes from the configuration's shapes, against the
published peaks), over the device's busy time. Useful rows, not padded ones:
padding is the batcher's waste and lowers this share. Operations of the whole
capture against its bytes: never above the per-batch bound."""


def read(ctx):
    rows, batches = ctx["trace"].get("rows"), ctx["trace"].get("batches")
    if not rows or not batches:
        return None
    flops, moved = ctx["cost"](ctx["model"], rows, batches)
    least, ctx["notes"]["step_roofline_bound"] = ctx["least_seconds"](flops, moved, ctx["device_kind"])
    return 100.0 * least / ctx["trace"]["busy_s"]
