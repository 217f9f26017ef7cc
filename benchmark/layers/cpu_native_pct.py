"""h2d upload and the jitted step's host side: CPU of the threads Python
does not know (the TPU runtime's, XLA's, grpc core's event engine and
timers: every `cpu.native.<comm>`) over the window, in percent of ONE core.
`ctx["notes"]` gets each name's own share."""
from _cpu import wall_ms


def read(ctx):
    wall = wall_ms(ctx)
    if wall is None:
        return None
    by_name = {name[len("cpu.native."):]: 100.0 * block["total_ms"] / wall
               for name, block in ctx["phases"].items() if name.startswith("cpu.native.")}
    ctx["notes"]["cpu_native_pct_by_comm"] = {k: round(v, 2) for k, v in sorted(by_name.items())}
    return sum(by_name.values())
