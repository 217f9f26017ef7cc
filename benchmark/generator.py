"""A load-generator child: jax-free, the program's wire format over a real
localhost socket.

Started by run.py, several to a run. Each waits for grpc.health.v1 SERVING,
sends an unmeasured warm-up, reports ready, waits for the start time that
run.py hands every generator, sends its share of the mix for the window, and
writes what it saw to `gen<index>.json`. Generator 0 then sends the
correctness sample and writes the scores to `sample_scores.npz`.

Times are `time.monotonic()`, which on Linux is one clock for every process
of the machine. An open-loop latency runs from the instant the request was
due to the arrival of its response; a request's payload is built before it is
due. A failed request has no latency and counts in `failed`.
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import traffic  # noqa: E402
from benchmark.common import read_json, write_json  # noqa: E402

RPC_TIMEOUT_S = 60.0
SERVING_TIMEOUT_S = 1500.0
DRAIN_GRACE_S = 20.0
OUTPUT_KEY = "prediction_node"
TRACED_LOAD_CAP_S = 240.0  # a traced run's load never outlives this


class Wire:
    """The program's client-side wire code, and the channels of this process."""

    def __init__(self, port: int, model_name: str, channels: int):
        import grpc

        from distributed_tf_serving_tpu import codec
        from distributed_tf_serving_tpu.client.client import build_predict_request
        from distributed_tf_serving_tpu.proto import PredictionServiceStub, health
        from distributed_tf_serving_tpu.proto.service_grpc import (
            LARGE_MESSAGE_CHANNEL_OPTIONS,
        )

        self._grpc = grpc
        self._codec = codec
        self._build = build_predict_request
        self._health = health
        self.model_name = model_name
        self.target = f"127.0.0.1:{port}"
        options = LARGE_MESSAGE_CHANNEL_OPTIONS + (("grpc.use_local_subchannel_pool", 1),)
        self.channels = [
            grpc.insecure_channel(self.target, options=options) for _ in range(channels)
        ]
        self.stubs = [PredictionServiceStub(c) for c in self.channels]

    def close(self) -> None:
        for channel in self.channels:
            channel.close()

    def serving(self) -> bool:
        health = self._health
        try:
            status = health.HealthStub(self.channels[0]).Check(
                health.HealthCheckRequest(""), timeout=5
            ).status
        except self._grpc.RpcError:
            return False
        return status == health.SERVING

    def encode(self, arrays: dict) -> bytes:
        return self._build(arrays, self.model_name).SerializeToString()

    def send(self, lane: int, request: bytes):
        return self.stubs[lane % len(self.stubs)].PredictRaw.future(
            request, timeout=RPC_TIMEOUT_S
        )

    def scores(self, response) -> np.ndarray:
        return np.asarray(self._codec.to_ndarray(response.outputs[OUTPUT_KEY]))


def check_scores(scores: np.ndarray, rows: int) -> str | None:
    """None for a well-formed answer, else what is wrong with it. Sigmoid
    scores lie in [0, 1]: float32 saturates to the ends for a large logit."""
    if scores.shape != (rows,):
        return f"score shape {scores.shape}, want ({rows},)"
    if not np.all(np.isfinite(scores)):
        return "non-finite scores"
    if scores.min() < 0.0 or scores.max() > 1.0:
        return f"scores outside [0, 1]: {scores.min()} .. {scores.max()}"
    return None


class Log:
    """What one generator saw. Appended to from grpc's callback threads."""

    def __init__(self):
        self.lock = threading.Lock()
        self.done: list[tuple] = []  # (measured, due, sent, finished, rows, fault)
        self.outstanding = 0
        self.idle = threading.Condition(self.lock)

    def begin(self) -> None:
        with self.lock:
            self.outstanding += 1

    def finish(self, record: tuple) -> None:
        with self.idle:
            self.done.append(record)
            self.outstanding -= 1
            self.idle.notify_all()

    def wait_idle(self, timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        with self.idle:
            while self.outstanding:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self.idle.wait(left)
        return True


def fire(wire: Wire, log: Log, lane: int, request: bytes, rows: int,
         measured: bool, due: float, on_done=None) -> None:
    """Send one request; its record is written when the response arrives."""
    log.begin()
    sent = time.monotonic()
    future = wire.send(lane, request)

    def done(fut) -> None:
        finished = time.monotonic()
        try:
            fault = check_scores(wire.scores(fut.result()), rows)
        except Exception as exc:  # noqa: BLE001 - every failure is a count
            fault = f"{type(exc).__name__}: {str(exc)[:200]}"
        log.finish((measured, due, sent, finished, rows, fault))
        if on_done is not None:
            on_done()

    future.add_done_callback(done)


def sleep_until(t: float) -> None:
    """Sleep to 1.5 ms before `t`, then spin: a sleeping core wakes about a
    millisecond late (gen_late p95 1.0 ms at 50 req/s, my chip run, PR 23)."""
    left = t - time.monotonic() - 0.0015
    if left > 0:
        time.sleep(left)
    while time.monotonic() < t:
        pass


def run_open(wire, log, mix, payloads, args, t_start, stop) -> None:
    """This process's share of the schedule: request k of every segment goes
    to generator k % of. Segment 0 is the measured window; a traced run goes
    on with further segments until run.py says the trace is secured."""
    segment = 0
    while not stop.is_set():
        due, sizes = traffic.open_schedule(mix, args.seed + segment, args.seconds)
        base = t_start + segment * args.seconds
        for k in range(args.index, due.size, args.of):
            if stop.is_set():
                return
            rows = int(sizes[k])
            request = wire.encode(
                payloads.make(traffic.STREAM_MEASURED, segment * (1 << 24) + k, rows)
            )
            sleep_until(base + due[k])
            fire(wire, log, k, request, rows, segment == 0, base + due[k])
        segment += 1
        if not args.traced:
            return
        if base + args.seconds - t_start > TRACED_LOAD_CAP_S:
            return


def run_closed(wire, log, mix, payloads, args, t_start, stop) -> None:
    """The callers c with c % of == index, one thread each. A caller builds
    its next request while the last one is in flight. A request is measured
    if its answer arrived inside the window."""
    callers = [c for c in range(int(mix["callers"])) if c % args.of == args.index]
    sizes = traffic.closed_sizes(mix, args.seed)
    t_end = t_start + args.seconds

    def caller(c: int) -> None:
        answered = threading.Semaphore(0)
        j = 0

        def build(j: int) -> tuple[bytes, int]:
            k = j * int(mix["callers"]) + c
            rows = int(sizes[k % sizes.size])
            return wire.encode(payloads.make(traffic.STREAM_MEASURED, k, rows)), rows

        request, rows = build(j)
        sleep_until(t_start)
        while not stop.is_set():
            now = time.monotonic()
            if now >= t_end and not args.traced:
                return
            if now - t_start > TRACED_LOAD_CAP_S:
                return
            fire(wire, log, c, request, rows, True, now, on_done=answered.release)
            j += 1
            request, rows = build(j)
            answered.acquire()

    threads = [threading.Thread(target=caller, args=(c,), daemon=True) for c in callers]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def warm_up(wire, log, mix, payloads, args, concurrency: int) -> None:
    """Unmeasured: a few requests at the smallest, median and largest size,
    one after another; then this generator's share of the mix's
    `warmup_requests`, at the mix's own sizes, `concurrency` in flight, which
    makes the batcher coalesce and lets whatever the server does once in its
    first few hundred requests happen before the window."""
    index = args.index * (1 << 20)
    for rows in traffic.size_range(mix["rows"]):
        for _ in range(3):
            fire(wire, log, index, wire.encode(
                payloads.make(traffic.STREAM_WARMUP, index, rows)), rows, False, 0.0)
            index += 1
            log.wait_idle(RPC_TIMEOUT_S)
    share = -(-int(mix.get("warmup_requests", 0)) // args.of)
    sizes = traffic.closed_sizes(mix, args.seed)
    slots = threading.Semaphore(concurrency)
    for i in range(share):
        rows = int(sizes[(args.index + i * args.of) % sizes.size])
        request = wire.encode(payloads.make(traffic.STREAM_WARMUP, index, rows))
        slots.acquire()
        fire(wire, log, index, request, rows, False, 0.0, on_done=slots.release)
        index += 1
    log.wait_idle(RPC_TIMEOUT_S)


def send_sample(wire, mix, shape, seed, out_dir) -> None:
    scores = {}
    for name, arrays in traffic.sample_requests(mix, shape, seed).items():
        response = wire.send(0, wire.encode(arrays)).result()
        scores[name] = wire.scores(response)
    tmp = os.path.join(out_dir, "sample_scores.tmp.npz")
    np.savez(tmp, **scores)
    os.replace(tmp, os.path.join(out_dir, "sample_scores.npz"))


def wait_for(path: str, stop: threading.Event, timeout: float):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and not stop.is_set():
        if os.path.exists(path):
            return read_json(path)
        time.sleep(0.02)
    return None


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--mix", required=True)
    parser.add_argument("--config", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--port", type=int, required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--index", type=int, required=True)
    parser.add_argument("--of", type=int, required=True)
    parser.add_argument("--traced", type=int, default=0)
    parser.add_argument("--sweep", default="")
    args = parser.parse_args()

    mix = traffic.load_mix(args.mix)
    config = read_json(args.config)
    shape = config["toml"]["model"]
    parent = os.getppid()
    stop = threading.Event()  # run.py says the load may end

    def watch() -> None:
        while True:
            if os.getppid() != parent or os.path.exists(os.path.join(args.out, "abort")):
                os._exit(3)  # run.py is gone or gave up: leave nothing behind
            if os.path.exists(os.path.join(args.out, "stop")):
                stop.set()
            time.sleep(0.05)

    threading.Thread(target=watch, daemon=True).start()

    closed = mix["loop"] == "closed"
    share = (
        len([c for c in range(int(mix["callers"])) if c % args.of == args.index])
        if closed else 4
    )
    wire = Wire(args.port, config["toml"]["server"].get("model_name", "DCN"), max(share, 1))
    log = Log()
    payloads = traffic.Payloads(mix, shape, args.seed)

    deadline = time.monotonic() + SERVING_TIMEOUT_S
    while not wire.serving():
        if time.monotonic() > deadline:
            print("server never reached SERVING", flush=True)
            return 2
        time.sleep(0.5)
    warm_up(wire, log, mix, payloads, args, concurrency=max(share, 2))
    warm_faults = [r[5] for r in log.done if r[5]]
    if warm_faults:
        print(f"warm-up failed: {warm_faults[:3]}", flush=True)
        return 2
    # When each warm-up answer arrived, on the machine's one monotonic clock:
    # run.py looks for the longest silence over all generators.
    warm_finished = [r[3] for r in log.done]
    log.done.clear()
    write_json(os.path.join(args.out, f"gen{args.index}.ready"),
               {"t": time.monotonic(), "warm_finished": warm_finished})

    if args.sweep:
        return sweep(wire, log, mix, payloads, args, stop)

    go = wait_for(os.path.join(args.out, "go.json"), stop, SERVING_TIMEOUT_S)
    if go is None:
        return 3
    t_start = float(go["t_start"])
    (run_closed if closed else run_open)(wire, log, mix, payloads, args, t_start, stop)
    drained = log.wait_idle(DRAIN_GRACE_S)

    t_end = t_start + args.seconds
    with log.lock:
        records = list(log.done)
        lost = log.outstanding
    # A closed loop's request is measured if its answer arrived inside the
    # window; an open loop's if it was due inside it.
    in_window = [(t_start <= r[3] <= t_end) if closed else r[0] for r in records]
    measured = [r for r, m in zip(records, in_window) if m]
    faults = [r[5] for r in measured if r[5] is not None]
    good = [(r, m) for r, m in zip(records, in_window) if r[5] is None]
    write_json(os.path.join(args.out, f"gen{args.index}.json"), {
        "attempted": len(measured) + (0 if closed else lost),
        "failed": len(faults) + (0 if closed else lost),
        "faults": faults[:5],
        "drained": drained,
        # Every well-formed answer of this generator, measured or not (a
        # traced run's load goes on after the window): times in seconds from
        # the window's start.
        "measured": [m for _, m in good],
        "due": [r[1] - t_start for r, _ in good],
        "sent": [r[2] - t_start for r, _ in good],
        "finished": [r[3] - t_start for r, _ in good],
        "rows": [r[4] for r, _ in good],
    })
    if args.index == 0:
        send_sample(wire, mix, shape, args.seed, args.out)
    wire.close()
    return 0


def sweep(wire, log, mix, payloads, args, stop) -> int:
    """Knee-finding mode (run.py --sweep; the driver never calls it): the
    rates of `--sweep`, one after another inside one server lifetime, each
    for `--seconds`. All generators start a step together, on a wall-clock
    grid of step boundaries that run.py hands them."""
    go = wait_for(os.path.join(args.out, "go.json"), stop, SERVING_TIMEOUT_S)
    if go is None:
        return 3
    rates = [float(r) for r in args.sweep.split(",")]
    rows = []
    for step, rate in enumerate(rates):
        t_start = float(go["t_start"]) + step * (args.seconds + float(go["gap_s"]))
        step_mix = dict(mix, rate_per_s=rate)
        log.done.clear()
        one = argparse.Namespace(**{**vars(args), "traced": 0})
        run_open(wire, log, step_mix, payloads, one, t_start, stop)
        drained = log.wait_idle(float(go["gap_s"]) * 0.8)
        with log.lock:
            records = [r for r in log.done if r[0]]
            lost = log.outstanding
        good = [r for r in records if r[5] is None]
        rows.append({
            "rate": rate, "offered": len(records) + lost,
            "failed": len(records) - len(good) + lost, "drained": drained,
            "latency_ms": [(r[3] - r[1]) * 1e3 for r in good],
            "due": [r[1] - t_start for r in good],
            "late_ms": [(r[2] - r[1]) * 1e3 for r in good],
        })
        log.wait_idle(RPC_TIMEOUT_S)
    write_json(os.path.join(args.out, f"gen{args.index}.json"), {"sweep": rows})
    wire.close()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
