#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This parent never imports jax. It starts the chip child (chip_child.py: the
program's CLI server, unchanged, in the one process that holds the chip) and
the generator children (generator.py), each in a session of its own with its
output in a file under the run's directory `bench_out/<cell>/...`, and in a
`finally` on every path kills their process groups and reaps them. Progress
goes to earlier lines; the result object is the very last thing printed, by
`result.emit`, after every child is reaped. A run that cannot produce a valid
object leaves through `result.fail`: a reason, a non-zero code, no object.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is found by the name BENCHMARK.json gives it: the
configuration's `file` with its reference.py and cost.py beside it,
`traffic/<mix>.json` and `layers/<metric>.py` under the benchmark's directory
(`paths`). A new cell needs new files and entries, no edit here.

Other modes, which the driver never calls: `--sweep r1,r2,...` offers an
open-loop mix at each rate in turn inside one server lifetime and prints the
table a knee is read from; `--rehearse 1` lets the flow run on the CPU backend
(platform `cpu` reported, `correct` false); `--benchmark <file>` reads another
BENCHMARK.json and finds configurations and mixes beside it (rehearse.py
derives a tiny one from the real one).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import time
import traceback

T_PROCESS_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(HERE, "layers"))

import numpy as np  # noqa: E402

from benchmark import peaks, result, traffic  # noqa: E402
from benchmark.common import load_module, monitoring, read_json, toml_text, write_json  # noqa: E402
from benchmark.result import say  # noqa: E402

READY_TIMEOUT_S = 1100.0  # a first run compiles the whole ladder
COLLECT_TIMEOUT_S = 120.0
CHILD_EXIT_TIMEOUT_S = 180.0
TRACE_SECONDS = 3.0
SWEEP_GAP_S = 4.0
BEND = 1.6


class RunFailed(RuntimeError):
    """The run cannot produce a result; the message is the reason."""


# ---------------------------------------------------------------- children


class Children:
    """Every process this run starts, each in its own session, so that its
    whole group can be killed."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.procs: dict[str, subprocess.Popen] = {}

    def start(self, name: str, argv: list[str]) -> subprocess.Popen:
        log = open(os.path.join(self.out_dir, f"{name}.log"), "w")
        try:
            proc = subprocess.Popen(
                argv, cwd=ROOT, stdin=subprocess.DEVNULL, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
            )
        finally:
            log.close()
        self.procs[name] = proc
        return proc

    def log_tail(self, name: str, size: int = 3000) -> str:
        try:
            with open(os.path.join(self.out_dir, f"{name}.log"), errors="replace") as f:
                return f.read()[-size:]
        except OSError:
            return ""

    def check_alive(self) -> None:
        for name, proc in self.procs.items():
            code = proc.poll()
            if code is not None and code != 0:
                why = " (NO_ACCELERATOR)" if name == "chip_child" and code == 3 else ""
                raise RunFailed(
                    f"child {name} exited with code {code}{why}:\n{self.log_tail(name)}"
                )

    def kill_all(self) -> None:
        """SIGKILL every child's process group and reap it. Idempotent."""
        for proc in self.procs.values():
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        for proc in self.procs.values():
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass


def free_port() -> int:
    """A port nothing listens on, from BELOW the range the kernel hands to
    outgoing connections. The server binds half a minute after this probe,
    and meanwhile the generators poll the port: a client socket that draws
    the port it is connecting to connects to itself and then holds it."""
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            top = int(f.read().split()[0])
    except (OSError, ValueError):
        top = 32768
    for _ in range(200):
        port = random.SystemRandom().randrange(min(10000, top - 1000), top)
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
            return port
    raise RunFailed("found no free port")


def wait_files(paths: list[str], children: Children, timeout: float, what: str) -> None:
    deadline = time.monotonic() + timeout
    while not all(os.path.exists(p) for p in paths):
        children.check_alive()
        if time.monotonic() > deadline:
            missing = [os.path.basename(p) for p in paths if not os.path.exists(p)]
            raise RunFailed(f"timed out after {timeout:.0f}s waiting for {what}: {missing}")
        time.sleep(0.05)


# ----------------------------------------------------- the program's surface


def snapshot(rest_port: int) -> dict:
    return {
        "phases": monitoring(rest_port, "phases"),
        "runtime": monitoring(rest_port, "runtime"),
        "batcher": monitoring(rest_port, "metrics")["batcher"],
    }


class ControlChannel:
    """run.py's end of chip_child.Control: numbered command files."""

    def __init__(self, out_dir: str, children: Children):
        self.ctl = os.path.join(out_dir, "ctl")
        self.children = children
        self.n = 0

    def send(self, cmd: dict) -> int:
        write_json(os.path.join(self.ctl, f"cmd-{self.n}.json"), cmd)
        self.n += 1
        return self.n - 1

    def answer(self, n: int, timeout: float) -> dict:
        path = os.path.join(self.ctl, f"ack-{n}.json")
        wait_files([path], self.children, timeout, f"the chip child's answer {n}")
        answer = read_json(path)
        if "error" in answer:
            raise RunFailed(f"the chip child could not answer command {n}:\n{answer['error']}")
        return answer


# ------------------------------------------------------------------ metrics


def delta_phases(before: dict, after: dict) -> dict:
    out = {}
    for name, block in after.items():
        base = before.get(name, {"count": 0, "total_ms": 0.0})
        out[name] = {
            "count": block["count"] - base["count"],
            "total_ms": block["total_ms"] - base["total_ms"],
        }
    return out


def batcher_block(before: dict, after: dict) -> dict:
    """The server's batcher counters for the readers: counts as window
    deltas, the two ratios as /monitoring has them at the window's end."""
    return {
        "batches": after["batches"] - before["batches"],
        "requests": after["requests"] - before["requests"],
        "mean_occupancy": after["mean_occupancy"],
        "readback_overlap_fraction": after["readback_overlap_fraction"],
    }


def load_reader(metric: str):
    """`layers/<metric>.py`, else `layers/<metric up to its first dot>.py`."""
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(HERE, "layers", stem + ".py")
        if os.path.exists(path):
            return load_module(path, "bench_layer_" + stem.replace(".", "_")).read
    return None


def merge_generators(out_dir: str, count: int) -> dict:
    """What all generators saw. `answers` is every well-formed answer as
    (measured, due, sent, finished, rows), times in seconds from the window's
    start; the lists below it are of the measured ones."""
    parts = [read_json(os.path.join(out_dir, f"gen{i}.json")) for i in range(count)]
    answers = np.array(
        [row for p in parts
         for row in zip(p["measured"], p["due"], p["sent"], p["finished"], p["rows"])],
        dtype=np.float64).reshape(-1, 5)
    window = answers[answers[:, 0] > 0]
    return {
        "attempted": sum(p["attempted"] for p in parts),
        "failed": sum(p["failed"] for p in parts),
        "faults": [f for p in parts for f in p["faults"]][:5],
        "drained": all(p["drained"] for p in parts),
        "answers": answers,
        "latency_ms": (window[:, 3] - window[:, 1]) * 1e3,
        "from_send_ms": (window[:, 3] - window[:, 2]) * 1e3,
        "late_ms": (window[:, 2] - window[:, 1]) * 1e3,
        "finished": window[:, 3],
        "rows": window[:, 4],
    }


def warm_stall_ms(out_dir: str, count: int) -> float | None:
    """The longest silence between two answers while the generators' warm-up
    was on, over all generators (one monotonic clock for the machine)."""
    times = sorted(
        t for i in range(count)
        for t in read_json(os.path.join(out_dir, f"gen{i}.ready"))["warm_finished"])
    return float(np.max(np.diff(times))) * 1e3 if len(times) > 1 else None


def thirds(gen: dict, seconds: float) -> list:
    """Median latency of the answers that arrived in each third of the
    window: a window that is not steady (a stall, a queue that grows) shows."""
    latency, finished = gen["latency_ms"], gen["finished"]
    out = []
    for i in range(3):
        part = latency[(finished >= i * seconds / 3.0) & (finished < (i + 1) * seconds / 3.0)]
        out.append(traffic.percentile(part, 50) if part.size else None)
    return out


def sample_error(out_dir: str) -> float:
    """Largest |served - reference| over the correctness sample."""
    worst = 0.0
    with np.load(os.path.join(out_dir, "sample_scores.npz")) as served, \
            np.load(os.path.join(out_dir, "sample_expected.npz")) as expected:
        for name in expected.files:
            if served[name].shape != expected[name].shape:
                raise RunFailed(f"sample {name}: served {served[name].shape}, reference {expected[name].shape}")
            worst = max(worst, float(np.max(np.abs(served[name].astype(np.float64) - expected[name]))))
    return worst


# ---------------------------------------------------------------------- run


def start_children(args, cell, config_path, mix_path, mix, out_dir, children) -> tuple[int, int]:
    port, rest_port = free_port(), free_port()
    toml_path = os.path.join(out_dir, "server.toml")
    with open(toml_path, "w") as f:
        f.write(toml_text(read_json(config_path)))
    children.start("chip_child", [
        sys.executable, os.path.join(HERE, "chip_child.py"),
        "--config", config_path, "--toml", toml_path, "--mix", mix_path,
        "--out", out_dir, "--port", str(port), "--rest-port", str(rest_port),
        "--seed", str(args.seed), "--chips", str(cell["chips"]),
        "--rehearse", str(args.rehearse),
    ])
    for i in range(int(mix["generators"])):
        argv = [
            sys.executable, os.path.join(HERE, "generator.py"),
            "--mix", mix_path, "--config", config_path, "--out", out_dir,
            "--port", str(port), "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--index", str(i),
            "--of", str(mix["generators"]), "--traced", str(args.trace),
        ]
        if args.sweep:
            argv += ["--sweep", args.sweep]
        children.start(f"gen{i}", argv)
    return port, rest_port


def trace_span(seconds: float) -> tuple[float, float]:
    """(offset into the window, length) of the capture: a few seconds in the
    middle of the window, not the whole of it."""
    span = min(TRACE_SECONDS, seconds / 3.0)
    return (seconds - span) / 2.0, span


def measure(args, benchmark, cell, children, out_dir) -> dict:
    entry = next(c for c in benchmark["configs"] if c["name"] == cell["config"])
    base = os.path.dirname(os.path.abspath(args.benchmark))
    config_path = os.path.join(base, entry["file"])
    config = read_json(config_path)
    mix_path = os.path.join(base, benchmark["paths"][0], "traffic", cell["traffic"] + ".json")
    mix = traffic.load_mix(mix_path)
    traced = bool(args.trace)
    generators = int(mix["generators"])

    port, rest_port = start_children(args, cell, config_path, mix_path, mix, out_dir, children)
    say(f"cell {cell['name']}: config {entry['name']}, mix {cell['traffic']} "
        f"({mix['loop']} loop, {generators} generators), seed {args.seed}, "
        f"{args.seconds}s, trace {args.trace}; output in {os.path.relpath(out_dir, ROOT)}")
    ready = [os.path.join(out_dir, f"gen{i}.ready") for i in range(generators)]
    wait_files(ready, children, READY_TIMEOUT_S, "SERVING and the generators' warm-up")
    control = ControlChannel(out_dir, children)

    if args.sweep:
        return run_sweep(args, children, out_dir, generators)

    stall = warm_stall_ms(out_dir, generators)
    before = snapshot(rest_port)
    t_start = time.monotonic() + 0.3
    write_json(os.path.join(out_dir, "go.json"), {"t_start": t_start})
    setup_s = t_start - T_PROCESS_START
    t_end = t_start + args.seconds
    say(f"window starts; setup_s {setup_s:.3f} (warmup_s {before['runtime'].get('warmup_s')}, "
        f"compile cache {before['runtime'].get('compile_cache')})")

    trace_cmd = None
    if traced:
        offset, span = trace_span(args.seconds)
        time.sleep(max(t_start + offset - time.monotonic(), 0.0))
        trace_cmd = control.send({"op": "trace", "seconds": span})
    while time.monotonic() < t_end:
        children.check_alive()
        time.sleep(min(0.1, max(t_end - time.monotonic(), 0.0)))
    after = snapshot(rest_port)
    if traced:
        control.answer(trace_cmd, 240)
    with open(os.path.join(out_dir, "stop"), "w"):
        pass

    outputs = [os.path.join(out_dir, f"gen{i}.json") for i in range(generators)]
    outputs.append(os.path.join(out_dir, "sample_scores.npz"))
    wait_files(outputs, children, COLLECT_TIMEOUT_S, "the generators' results")
    for i in range(generators):
        children.procs[f"gen{i}"].wait(timeout=30)
    gen = merge_generators(out_dir, generators)

    chip = children.procs["chip_child"]
    chip.send_signal(signal.SIGTERM)
    try:
        code = chip.wait(timeout=CHILD_EXIT_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        raise RunFailed("the chip child did not exit after SIGTERM") from None
    if code != 0 or "shutdown complete" not in children.log_tail("chip_child", 200000):
        raise RunFailed(f"the server's exit: code {code}:\n{children.log_tail('chip_child')}")
    child = read_json(os.path.join(out_dir, "child_result.json"))

    # --- arithmetic -------------------------------------------------------
    if not gen["latency_ms"].size:
        raise RunFailed(f"no request was answered: {gen['faults']}")
    values = {"setup_s": setup_s}
    if mix["loop"] == "open":
        values["p50_ms"] = traffic.percentile(gen["latency_ms"], 50)
        values["p95_ms"] = traffic.percentile(gen["latency_ms"], 95)
    else:
        values["cand_per_s"] = float(gen["rows"].sum()) / args.seconds
    gen_summary = {
        "p50_ms": traffic.percentile(gen["latency_ms"], 50),
        "p95_ms": traffic.percentile(gen["latency_ms"], 95),
        "late_p95_ms": traffic.percentile(gen["late_ms"], 95),
        "mean_from_send_ms": float(gen["from_send_ms"].mean()),
        "answered": int(gen["latency_ms"].size),
        "p50_ms_by_third": thirds(gen, args.seconds),
        "answered_by_second": np.bincount(
            np.clip(gen["finished"], 0, args.seconds - 1e-9).astype(int),
            minlength=int(np.ceil(args.seconds))).tolist(),
        "rows_answered": int(gen["rows"].sum()),
        "warm_stall_ms": stall,
    }
    device = dict(child["device"])
    device.pop("bytes_in_use", None)
    notes: dict = {}
    breakdown = None
    if traced:
        trace = child.get("trace")
        if not trace:
            raise RunFailed(
                f"no device operation in {len(child.get('trace_tries', []))} capture(s)")
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        breakdown = trace["breakdown"]
        # Rows the generators had answered while the capture was open: their
        # clock and the chip child's are the machine's one monotonic clock.
        finished = gen["answers"][:, 3] + t_start
        captured = (finished >= trace["t0"]) & (finished <= trace["t1"])
        trace["rows"] = int(gen["answers"][captured, 4].sum())
        ctx = {
            "phases": delta_phases(before["phases"], after["phases"]),
            "batcher": batcher_block(before["batcher"], after["batcher"]),
            "runtime": after["runtime"], "gen": gen_summary, "trace": trace,
            "model": config["toml"]["model"], "notes": notes,
            # A rehearsal on the CPU backend exercises the roofline arithmetic
            # against the v5e's peaks; its result is never a chip number.
            "device_kind": "TPU v5 lite" if args.rehearse and device["platform"] == "cpu" else device["kind"],
            "cost": load_step_cost(config_path), "least_seconds": peaks.least_seconds,
        }
        for name in result.cell_metrics(benchmark, cell["name"], True):
            reader = load_reader(name)
            values[name] = reader(ctx) if reader is not None else None
            if values[name] is None:
                say(f"per-layer metric {name}: its source was empty in this run")

    misses0 = (before["runtime"].get("compile_cache") or {}).get("misses")
    misses1 = (after["runtime"].get("compile_cache") or {}).get("misses")
    worst = sample_error(out_dir)
    checks = {
        "platform_is_tpu": device["platform"] == "tpu",
        "every_answer_well_formed": gen["failed"] == 0,
        "nothing_compiled_in_window": misses0 is not None and misses1 == misses0,
        "sample_within_tolerance": worst <= float(config["tolerance"]),
    }
    obj = result.build(
        benchmark, cell["name"], traced, correct=all(checks.values()),
        attempted=gen["attempted"], failed=gen["failed"], values=values,
        device=device, breakdown=breakdown,
    )
    detail = {
        "checks": checks, "sample_max_abs_error": worst, "tolerance": config["tolerance"],
        "generators": gen_summary, "faults": gen["faults"], "drained": gen["drained"],
        "compile_misses": [misses0, misses1],
        "compile_requests": [(snap["runtime"].get("compile_cache") or {}).get("requests")
                             for snap in (before, after)],
        "notes": notes, "values": values,
        "bytes_in_use_after_serve": child["device"].get("bytes_in_use"),
        "result": obj,
    }
    write_json(os.path.join(out_dir, "result.json"), detail)
    say("detail: " + json.dumps({k: v for k, v in detail.items() if k != "result"}))
    return obj


def load_step_cost(config_path: str):
    """The configuration's `step_cost`, from the cost.py beside its file."""
    return load_module(
        os.path.join(os.path.dirname(config_path), "cost.py"), "bench_cost").step_cost


def run_sweep(args, children, out_dir, generators) -> dict:
    rates = [float(r) for r in args.sweep.split(",")]
    t_start = time.monotonic() + 0.5
    write_json(os.path.join(out_dir, "go.json"), {"t_start": t_start, "gap_s": SWEEP_GAP_S})
    outputs = [os.path.join(out_dir, f"gen{i}.json") for i in range(generators)]
    wait_files(outputs, children, len(rates) * (args.seconds + SWEEP_GAP_S) + 180, "the sweep")
    parts = [read_json(p)["sweep"] for p in outputs]
    table = []
    for step, rate in enumerate(rates):
        rows = [p[step] for p in parts]
        latency = np.array([x for r in rows for x in r["latency_ms"]])
        due = np.array([x for r in rows for x in r["due"]])
        late = np.array([x for r in rows for x in r["late_ms"]])
        offered = sum(r["offered"] for r in rows)
        failed = sum(r["failed"] for r in rows)
        first = latency[due < args.seconds / 3.0]
        last = latency[due >= 2.0 * args.seconds / 3.0]
        line = {
            "rate": rate, "offered": offered, "answered": int(latency.size), "failed": failed,
            "p50_ms": traffic.percentile(latency, 50) if latency.size else None,
            "p95_ms": traffic.percentile(latency, 95) if latency.size else None,
            "p50_first_third_ms": traffic.percentile(first, 50) if first.size else None,
            "p50_last_third_ms": traffic.percentile(last, 50) if last.size else None,
            "gen_late_p95_ms": traffic.percentile(late, 95) if late.size else None,
        }
        line["sustained"] = bool(
            latency.size >= 0.98 * offered and failed == 0 and first.size and last.size
            and line["p50_last_third_ms"] <= 1.5 * line["p50_first_third_ms"]
        )
        table.append(line)
    # Past the bend the queue does the timing and the tail swings from run to
    # run, so the knee a cell's rate is taken from is the highest sustained
    # rate whose p50 is still within BEND times the p50 of the lowest rate.
    floor = min((l["p50_ms"] for l in table if l["sustained"]), default=None)
    for line in table:
        line["below_bend"] = bool(
            line["sustained"] and floor is not None and line["p50_ms"] <= BEND * floor)
        say("sweep " + json.dumps(line))
    write_json(os.path.join(out_dir, "sweep.json"), table)
    children.procs["chip_child"].send_signal(signal.SIGTERM)
    children.procs["chip_child"].wait(timeout=CHILD_EXIT_TIMEOUT_S)
    return {"sweep": table}


def one_run(args, benchmark, cell):
    """(result object, failure). Every child is killed and reaped before
    this returns, whatever happened."""
    obj, failure, children, out_dir = None, None, None, None
    try:
        out_dir = os.path.join(
            ROOT, "bench_out", cell["name"], f"trace{args.trace}-seed{args.seed}")
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(os.path.join(out_dir, "ctl"))
        children = Children(out_dir)
        obj = measure(args, benchmark, cell, children, out_dir)
    except BaseException as exc:  # noqa: BLE001 - every path ends in emit or fail
        failure = exc if isinstance(exc, RunFailed) else traceback.format_exc()
    finally:
        if children is not None:
            if failure is not None:
                open(os.path.join(out_dir, "abort"), "w").close()
            children.kill_all()
    return obj, failure


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"))
    parser.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    parser.add_argument("--sweep", default="")
    args = parser.parse_args()

    try:
        benchmark = read_json(args.benchmark)
        cells = {c["name"]: c for c in benchmark["workloads"]}
        if args.workload not in cells:
            raise RunFailed(f"BENCHMARK.json has no workload {args.workload!r}")
        cell = cells[args.workload]
    except BaseException as exc:  # noqa: BLE001 - every path ends in emit or fail
        result.fail(str(exc) if isinstance(exc, RunFailed) else traceback.format_exc())
    obj, failure = one_run(args, benchmark, cell)
    if failure is not None:
        result.fail(str(failure))
    if args.sweep:
        sys.stdout.flush()
        os._exit(0)
    if not args.rehearse and obj["device"]["platform"] != "tpu":
        result.fail("no accelerator: the run was not on a tpu")
    result.emit(obj, benchmark, cell["name"], bool(args.trace))


if __name__ == "__main__":
    main()
