"""Arithmetic the readers of the request timeline (`req.*`), the thread-state
spans (`wait.*`) and the start-up stamps share (ISSUE 24). Like `_lib.py`,
everything comes from `ctx`; a program that lacks the span or the stamp, as the
commit before ISSUE 24 does, gives None and the metric is left out."""
from _lib import phase_count, phase_mean_us, phase_total_ms


def phase_mean_ms(ctx, name):
    mean = phase_mean_us(ctx, name)
    return None if mean is None else mean / 1e3


def share_of_window_pct(ctx, *names):
    """Total time in the phases `names` over the window's length, which is
    one entry of the generators' `answered_by_second` a second. None when
    none of the phases ran.

    Reads HIGH, by about 0.3 s plus the two phase snapshots' own time over
    the window's length: `ctx["phases"]` is the delta between snapshots taken
    just outside the window, and `ctx` carries no length for that delta, so
    the whole seconds of the window stand in for it. About 1% at 51 s; it
    grows as the window shrinks (a 5 s rehearsal can read over 100%)."""
    seconds = len(ctx["gen"].get("answered_by_second") or ())
    if not seconds or not any(phase_count(ctx, name) for name in names):
        return None
    return 100.0 * sum(phase_total_ms(ctx, name) for name in names) / (seconds * 1e3)


def startup_s(ctx, name):
    return (ctx["runtime"].get("startup") or {}).get(name)
