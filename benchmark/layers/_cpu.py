"""What the CPU readers share (ISSUE 56). The program's own threads, by role
(`utils/tracing.py`, `ThreadSampler`): the phases `cpu.<role>` and
`sched.<role>` hold CUMULATIVE milliseconds (on a core; runnable and waiting
for one), so a window's delta is the role's CPU (run-queue wait) in the
window, and the delta of `cpu.wall` is the exact length of that window on the
server's own clock: the distance of the two scrapes, not the generators'
seconds. A cumulative phase's count is its live threads, whose delta is 0: a
program without the phases is told by the missing KEY."""
from _lib import phase_total_ms

PYTHON_ROLES = ("poller", "handler", "collector", "dispatch", "completer", "rest", "python_other")
REQUEST_PATH_ROLES = PYTHON_ROLES[:5]


def wall_ms(ctx):
    """The window's length between the two scrapes, None without the phase."""
    return ctx["phases"].get("cpu.wall", {}).get("total_ms") or None


def role_pct_of_core(ctx, prefix, roles):
    """100 x the window's `<prefix><role>` summed over `roles`, over the
    window's length: of ONE core, so k threads may pass 100. A role whose
    threads never ran is absent and adds nothing. None without `cpu.wall`."""
    wall = wall_ms(ctx)
    if wall is None:
        return None
    return 100.0 * sum(phase_total_ms(ctx, prefix + role) for role in roles) / wall
