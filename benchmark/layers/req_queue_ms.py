"""Batcher coalescing: mean `req.queue`, from submit's enqueue stamp to the
collector closing the request's group: queue wait and the `max_wait_us`
hold."""
from _timeline import phase_mean_ms


def read(ctx):
    return phase_mean_ms(ctx, "req.queue")
