"""gRPC transport and codec: CPU of the pool's `rpc` handler threads over
the window, in percent of ONE core (16 threads under one interpreter lock):
decode, submit, encode, serialize; and, since ISSUE 42, the whole dispatch
stage of every batch that crossed direct on its handler's own thread."""
from _cpu import role_pct_of_core


def read(ctx):
    return role_pct_of_core(ctx, "cpu.", ("handler",))
