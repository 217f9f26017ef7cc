"""D2H + completer: share of the readback window (issue to fetch done) that a
completer thread spent blocked in the fetch, from the server's
`readback_overlap_fraction`. /monitoring has it over the server's lifetime
only, to three digits."""


def read(ctx):
    overlap = ctx["batcher"].get("readback_overlap_fraction")
    return None if overlap is None else 100.0 * (1.0 - overlap)
