"""Batcher coalescing: share of the executed rows that were padding, from the
server's `mean_occupancy` (candidates over padded candidates). /monitoring has
it over the server's lifetime only, to three digits."""


def read(ctx):
    occupancy = ctx["batcher"].get("mean_occupancy")
    return 100.0 * (1.0 - occupancy) if occupancy else None
