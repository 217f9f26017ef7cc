"""Arithmetic the per-layer readers share. A reader is a file `<metric>.py`
(or, for a metric named `<base>.<suffix>`, `<base>.py` when no file has the
full name) with one function, `read(ctx)`, that returns the metric's value or
None when its source is empty. Everything in `ctx` comes from the program's
public surface (`/monitoring`), the generators or the profiler's trace:

  ctx["phases"]   {phase: {"count", "total_ms"}}, window deltas of
                  /monitoring?section=phases
  ctx["batcher"]  /monitoring?section=metrics, block `batcher`: `batches` and
                  `requests` as window deltas; `mean_occupancy` and
                  `readback_overlap_fraction` as the server reports them at
                  the window's end, which is over its lifetime (the ladder's
                  warm-up is not counted; the generators' warm-up, of the
                  mix's own sizes, is)
  ctx["runtime"]  /monitoring?section=runtime at the window's end
  ctx["gen"]      what the generators saw: p50_ms, p95_ms, late_p95_ms,
                  mean_from_send_ms, rows_answered, warm_stall_ms, ...
  ctx["trace"]    the reduced trace, with `batches` (the server's count) and
                  `rows` (the generators' answered rows) of the capture
  ctx["model"]    the configuration's [model] section; ctx["cost"] its
                  step_cost; ctx["device_kind"]; ctx["notes"] to leave a remark
"""


def phase_total_ms(ctx, name):
    return ctx["phases"].get(name, {}).get("total_ms", 0.0)


def phase_count(ctx, name):
    return ctx["phases"].get(name, {}).get("count", 0)


def phase_mean_us(ctx, name):
    """Mean duration of `name` over the window, None if it never ran."""
    count = phase_count(ctx, name)
    return phase_total_ms(ctx, name) * 1e3 / count if count else None
