"""Batcher assembly: CPU of the `batch-dispatch` thread over the window, in
percent of ONE core: digest, native assembly (lock released), upload, the
jit call; a batch that crossed direct (ISSUE 42) is not here but under the
handlers."""
from _cpu import role_pct_of_core


def read(ctx):
    return role_pct_of_core(ctx, "cpu.", ("dispatch",))
