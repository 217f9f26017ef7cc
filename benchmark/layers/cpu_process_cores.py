"""Whole process: cores of CPU the server's process burned over the window:
the delta of `cpu.process` (`time.process_time()`: every thread, Python's and
the runtimes' own, user and system) over the delta of `cpu.wall`. About 1.0
of Python is the interpreter lock's ceiling; what is above it is native.
`ctx["notes"]` gets what the window's scrapes of these counters took
(`cpu.scrape`: wall ms of the passes over /proc/self/task, and how many)."""
from _cpu import wall_ms
from _lib import phase_count, phase_total_ms


def read(ctx):
    wall = wall_ms(ctx)
    if wall is None or "cpu.process" not in ctx["phases"]:
        return None
    ctx["notes"]["cpu_scrape_ms"] = [phase_total_ms(ctx, "cpu.scrape"), phase_count(ctx, "cpu.scrape")]
    return phase_total_ms(ctx, "cpu.process") / wall
