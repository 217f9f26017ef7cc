"""The chip child: the one process of a run that touches jax.

In its main thread it calls the program's CLI entry unchanged,
`distributed_tf_serving_tpu.serving.server.serve(argv)`, with the run's TOML
and no checkpoint, so the server makes its parameters on the device itself
(`load_demo_servable`, seed 0). Because it is the process that holds the
chip, it is also the one that

  * scores the correctness sample with the configuration's plain float32
    reference, BEFORE the server starts: it makes the same parameters with the
    program's `init` at seed 0, keeps the dense weights and the embedding rows
    the sample touches on the host, and frees the table again, so that two
    copies of a table of GiB never sit on the chip together;
  * traces, from a side thread run.py talks to through files under `ctl/`:
    `jax.profiler.start_trace`, a `bench_window` annotation held open for the
    capture, `stop_trace` — all synchronous, so the `.xplane.pb` is closed
    before the answer is written;
  * after `serve()` has returned from its SIGTERM drain, reads the peak device
    memory, reduces the trace and writes `child_result.json`.

Its stdout and stderr are a log file; run.py reads only the files it writes.
"""

from __future__ import annotations

import argparse
import gc
import os
import resource
import shutil
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce, traffic  # noqa: E402
from benchmark.common import load_module, monitoring, read_json, write_json  # noqa: E402

TRACE_ATTEMPTS = 3
NO_ACCELERATOR = 3


def reference_scores(config_path: str, toml_path: str, mix: dict, seed: int) -> dict:
    """The float32 reference's scores for the correctness sample, from the
    parameters the server is about to make for itself."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tf_serving_tpu.models import build_model
    from distributed_tf_serving_tpu.utils.config import load_config

    t0 = time.monotonic()
    cfgs = load_config(toml_path)
    model = build_model(cfgs["server"].model_kind, cfgs["model"])
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(0)))
    print(f"[chip_child] reference: imports and init {time.monotonic() - t0:.1f}s", flush=True)
    shape = read_json(config_path)["toml"]["model"]
    samples = traffic.sample_requests(mix, shape, seed)
    vocab = int(shape["vocab_size"])
    folded = {name: arrays["feat_ids"] % vocab for name, arrays in samples.items()}
    touched, inverse = np.unique(
        np.concatenate([f.ravel() for f in folded.values()]), return_inverse=True
    )
    small = {k: v for k, v in params.items() if k != "embedding"}
    small = jax.tree.map(np.asarray, small)
    small["embedding"] = np.asarray(
        jnp.take(params["embedding"], jnp.asarray(touched.astype(np.int32)), axis=0)
    )
    del params
    gc.collect()
    print(f"[chip_child] reference: rows gathered at {time.monotonic() - t0:.1f}s", flush=True)

    reference = load_module(
        os.path.join(os.path.dirname(config_path), "reference.py"), "bench_reference")
    forward = jax.jit(reference.forward)
    out, at = {}, 0
    # The sample is a few thousand rows: the host's CPU backend scores it in
    # true float32 in well under a second, where the chip's compiler took 27 s
    # over the same graph at "highest" precision (my chip run, PR 23). The
    # chip's own float32 is the fallback where jax has no CPU backend.
    try:
        where = jax.devices("cpu")[0]
    except RuntimeError:
        where = jax.devices()[0]
    with jax.default_device(where), jax.default_matmul_precision("highest"):
        for name, arrays in samples.items():
            n = folded[name].size
            batch = dict(arrays, feat_ids=inverse[at:at + n].reshape(folded[name].shape).astype(np.int32))
            at += n
            out[name] = np.asarray(forward(small, batch))
    return out


class Control(threading.Thread):
    """Answers run.py's commands: `ctl/cmd-<n>.json` in, `ctl/ack-<n>.json`
    out, one at a time and in order."""

    def __init__(self, out_dir: str, rest_port: int):
        super().__init__(name="bench-control", daemon=True)
        self.ctl = os.path.join(out_dir, "ctl")
        self.out_dir = out_dir
        self.rest_port = rest_port
        self.traces: list[dict] = []

    def run(self) -> None:
        n = 0
        while True:
            path = os.path.join(self.ctl, f"cmd-{n}.json")
            if not os.path.exists(path):
                time.sleep(0.02)
                continue
            cmd = read_json(path)
            try:
                answer = getattr(self, "do_" + cmd["op"])(cmd)
            except Exception:  # noqa: BLE001 - reported to run.py, which decides
                answer = {"error": traceback.format_exc()[-2000:]}
            write_json(os.path.join(self.ctl, f"ack-{n}.json"), answer)
            n += 1

    def batches(self) -> int:
        """Batches the server has dispatched, as its /monitoring counts them."""
        return int(monitoring(self.rest_port, "metrics")["batcher"]["batches"])

    def do_trace(self, cmd: dict) -> dict:
        """Capture `seconds` of the load, again if a capture holds no device
        operation. Returns once the trace file is closed."""
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # a frame event per Python call would slow the server
        options.host_tracer_level = 2
        tries = []
        for attempt in range(TRACE_ATTEMPTS):
            trace_dir = os.path.join(self.out_dir, f"trace{attempt}")
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                before = self.batches()
                t0 = time.monotonic()
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_EVENT):
                    time.sleep(float(cmd["seconds"]))
                t1 = time.monotonic()
                after = self.batches()
            finally:
                jax.profiler.stop_trace()
            path = trace_reduce.find_xplane(trace_dir)
            has_ops = bool(path) and _has_device_ops(path)
            tries.append({"dir": trace_dir, "xplane": path, "has_ops": has_ops,
                          "t0": t0, "t1": t1, "batches": after - before})
            if has_ops:
                break
        self.traces = tries
        return {"t": time.monotonic(), "tries": tries}


def _has_device_ops(path: str) -> bool:
    """Whether the capture holds any device operation: looks at the lines,
    not at every event, so it is cheap enough to run beside the load."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    device_planes = [p for p in planes if p.name.startswith(trace_reduce.DEVICE_PLANE)]
    for plane in device_planes:
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE and next(iter(line.events), None) is not None:
                return True
    if device_planes:
        return False
    for plane in planes:  # the CPU backend of a rehearsal: operations sit on the host plane
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for event in line.events:
                    if any(k == "hlo_op" for k, _ in event.stats):
                        return True
    return False


def log_long_collections(threshold_s: float = 0.05) -> None:
    """Print every garbage collection of this process that holds the
    interpreter longer than `threshold_s`: a stall the generators see as a
    burst of slow answers shows here with its cause."""
    started = {}

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            started["t"] = time.monotonic()
        elif "t" in started and time.monotonic() - started["t"] >= threshold_s:
            print(f"[chip_child] gc generation {info['generation']} held the interpreter "
                  f"{time.monotonic() - started['t']:.3f}s at {time.monotonic():.3f} "
                  f"(collected {info['collected']})", flush=True)

    gc.callbacks.append(on_gc)


def device_block(rehearse: bool) -> dict:
    import jax

    devices = jax.devices()
    peaks, in_use = [], []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
            in_use.append(int(stats.get("bytes_in_use", 0)))
    block = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks) if peaks else None,
        "bytes_in_use": max(in_use) if in_use else None,
    }
    if block["memory_peak_bytes"] is None and rehearse and block["platform"] == "cpu":
        # The CPU backend keeps no memory statistics; on it the device's
        # memory is this process's. A rehearsal only, never a chip number.
        block["memory_peak_bytes"] = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    return block


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--toml", required=True)
    parser.add_argument("--mix", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--port", type=int, required=True)
    parser.add_argument("--rest-port", type=int, required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--chips", type=int, required=True)
    parser.add_argument("--rehearse", type=int, default=0)
    args = parser.parse_args()
    result_path = os.path.join(args.out, "child_result.json")

    import jax

    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu" or len(devices) < args.chips):
        write_json(result_path, {"error": (
            f"the cell needs {args.chips} tpu chip(s); jax reports "
            f"{len(devices)} device(s) of platform {devices[0].platform!r}")})
        return NO_ACCELERATOR

    mix = traffic.load_mix(args.mix)

    from distributed_tf_serving_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()
    t0 = time.monotonic()
    import numpy as np

    expected = reference_scores(args.config, args.toml, mix, args.seed)
    np.savez(os.path.join(args.out, "sample_expected.npz"), **expected)
    print(f"[chip_child] reference scored in {time.monotonic() - t0:.1f}s; "
          f"device after it: {device_block(bool(args.rehearse))}", flush=True)

    control = Control(args.out, args.rest_port)
    control.start()
    log_long_collections()

    from distributed_tf_serving_tpu.serving.server import serve

    print(f"[chip_child] serve() starts at {time.monotonic():.3f}", flush=True)
    serve([
        "--config", args.toml, "--host", "127.0.0.1",
        "--port", str(args.port), "--rest-port", str(args.rest_port),
    ])

    # serve() has drained and returned; this process still holds the chip.
    block = device_block(bool(args.rehearse))
    result = {"device": block, "trace": None}
    good = [t for t in control.traces if t["has_ops"]]
    if control.traces:
        result["trace_tries"] = control.traces
    if good:
        last = good[-1]
        reduced = trace_reduce.reduce(trace_reduce.extract(last["xplane"]))
        if reduced is not None:
            reduced["batches"] = last["batches"]
            reduced["t0"], reduced["t1"] = last["t0"], last["t1"]
        result["trace"] = reduced
    for t in control.traces:
        shutil.rmtree(t["dir"], ignore_errors=True)
    write_json(result_path, result)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:  # noqa: BLE001 - the log is all this process can tell
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
