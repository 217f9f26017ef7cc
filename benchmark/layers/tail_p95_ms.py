"""Service to batcher: 95th percentile of the request latency the generators
saw over the whole window, open loop, from the instant a request was due. The
tail is made in the queue before the batcher, and reads too unsteadily from
run to run to carry a bound (PERF.md section 2), so it stands here beside
`p50_ms` and not end to end."""


def read(ctx):
    return ctx["gen"].get("p95_ms")
