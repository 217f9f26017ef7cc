"""Kernels (the step's weight products): of the operations the servables'
entries make in products of an activation in PIECES against a weight (2 M k n
a piece: `models/sequence.py::product`), the share whose pieces meet in ONE
product's own float32 accumulation, so that the float32 result is written
once and never read back to be added to. The program notes every such product
while it traces a served entry and states the two sums, every rung added up,
in the servable's `startup.products` stamp; nothing is counted a batch. A
program without the stamp (a commit before ISSUE 57; a CTR servable, whose
step makes no product in pieces) reads nothing."""


def read(ctx):
    stamps = (ctx["runtime"].get("startup") or {}).get("products") or {}
    ops = sum(stamp.get("ops", 0) for stamp in stamps.values())
    if not ops:
        return None
    return 100.0 * sum(stamp.get("fused_ops", 0) for stamp in stamps.values()) / ops
