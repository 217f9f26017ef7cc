"""Batcher assembly: host time per batch in batch.pad (the generic path's pad
and concat) and batch.dispatch (input digest, pack, upload, jit call; the
batch.cache, batch.fusedpack and batch.jitcall spans nest inside it, so they
are not added again)."""
from _lib import phase_count, phase_total_ms


def read(ctx):
    batches = phase_count(ctx, "batch.dispatch")
    if not batches:
        return None
    return (phase_total_ms(ctx, "batch.pad") + phase_total_ms(ctx, "batch.dispatch")) * 1e3 / batches
