#!/usr/bin/env python3
"""Chip smoke: does the CLI server start on the accelerator and answer right?

Drives the main path once, through the entry points a user would call:

  1. a JAX_PLATFORMS=cpu child writes a `save_servable` checkpoint of the
     config's model from a fixed seed;
  2. `python -m distributed_tf_serving_tpu.serving.server --config CFG
     --checkpoint CKPT` starts as a child on whatever device jax gives it,
     and must reach grpc.health.v1 SERVING;
  3. the jax-free ShardedPredictClient sends reference-shaped Predict
     requests over a localhost socket: a few sequential 1000 x F requests
     (the first one twice), one concurrent burst, one request that fills the
     top bucket;
  4. SIGTERM; the server must exit 0 after "shutdown complete";
  5. a second JAX_PLATFORMS=cpu child scores the same payloads with a plain
     float32 evaluation of the same checkpoint.

It fails (non-zero, no result line) when the server's platform is not "tpu",
a request fails, a score is non-finite or outside (0, 1), the repeated
payload scores differently, scores leave SCORE_TOLERANCE of the float32
reference, or the server exits non-zero. There is no "allow CPU" switch:
under JAX_PLATFORMS=cpu the same flow runs and the platform check fails it,
which is the debugging mode.

This parent process never imports jax — a process that has touched jax holds
the chip — and its children run one after another. Last stdout line on
success: {"ok": true, "device": {"platform", "kind", "count"}}.

Not a measurement: it reports set-up time (warmup_s) and counts, no rate.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
DEFAULT_CONFIG = os.path.join(REPO, "configs", "throughput.toml")
SEED = 0
# |served - float32 reference| bound on the sigmoid score. The server
# computes in bf16 (8 significant bits: weights ride the wire as bf16, the
# embedding product and three cross layers run in bf16 with f32
# accumulation). Observed on these seeded payloads: 1.05e-3 on the v5e,
# 1.08e-3 on the CPU backend (PR 21). The bound is about 5x that — a path
# that computed in fewer bits than the config states would fail it.
SCORE_TOLERANCE = 5e-3
CANDIDATES = 1000  # the reference client's request shape
SEQUENTIAL = 3
BURST = 24
SERVING_TIMEOUT_S = 900
RPC_TIMEOUT_S = 120


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ----------------------------------------------------------------- children
# Both run under JAX_PLATFORMS=cpu and are the only code here that imports
# jax.


def _child_save(config_path: str, out_dir: str) -> None:
    from distributed_tf_serving_tpu.models import ServableRegistry
    from distributed_tf_serving_tpu.serving.server import load_demo_servable
    from distributed_tf_serving_tpu.train.checkpoint import save_servable
    from distributed_tf_serving_tpu.utils.config import load_config

    cfgs = load_config(config_path)
    server = cfgs["server"]
    # The servable the CLI server would build for this config without a
    # checkpoint, pinned to SEED and written out.
    servable = load_demo_servable(
        ServableRegistry(), kind=server.model_kind, name=server.model_name,
        seed=SEED, config=cfgs.get("model"), num_fields=server.num_fields,
    )
    save_servable(out_dir, servable, kind=server.model_kind)


def _child_reference(checkpoint: str, payloads_npz: str, out_npz: str) -> None:
    import numpy as np

    from distributed_tf_serving_tpu.models import build_model
    from distributed_tf_serving_tpu.serving.batcher import prepare_inputs
    from distributed_tf_serving_tpu.train.checkpoint import load_servable

    servable = load_servable(checkpoint)
    served = servable.model
    f32 = build_model(served.kind, served.config, compute_dtype="float32")
    out = {}
    with np.load(payloads_npz) as data:
        for name in sorted({k.split("/")[0] for k in data.files}):
            arrays = prepare_inputs(f32, {
                "feat_ids": data[f"{name}/feat_ids"],
                "feat_wts": data[f"{name}/feat_wts"],
            })
            out[name] = np.asarray(
                f32.apply(servable.params, arrays)[f32.score_output]
            )
    np.savez(out_npz, **out)


def run_cpu_child(role: str, *args: str) -> None:
    """One of the two helpers above, in its own process, held to the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.perf_counter()
    subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", role, *args],
        env=env, cwd=REPO, check=True, timeout=600,
    )
    say(f"cpu child {role!r} done in {time.perf_counter() - t0:.1f}s")


# ------------------------------------------------------------------- parent


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def monitoring(rest_port: int, section: str):
    url = f"http://127.0.0.1:{rest_port}/monitoring?section={section}"
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.load(r)[section]


def wait_serving(proc: subprocess.Popen, port: int, rest_port: int) -> dict:
    """Returns once health answers SERVING and the REST gateway answers too,
    with its /monitoring `runtime` block. serve() answers SERVING from the
    end of the warm-up and starts the gateway after that (its first import
    of aiohttp among the rest): for that moment the REST port refuses."""
    import grpc

    from distributed_tf_serving_tpu.proto import health

    deadline = time.monotonic() + SERVING_TIMEOUT_S
    with grpc.insecure_channel(f"127.0.0.1:{port}") as channel:
        stub = health.HealthStub(channel)
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"server exited with code {proc.returncode} before SERVING"
                )
            try:
                status = stub.Check(health.HealthCheckRequest(""), timeout=5).status
            except grpc.RpcError:
                status = None  # not listening yet: still loading/compiling
            if status == health.SERVING:
                try:
                    return monitoring(rest_port, "runtime")
                except urllib.error.URLError:
                    pass  # SERVING, the gateway not listening yet
            time.sleep(1.0)
    raise RuntimeError(f"server not SERVING after {SERVING_TIMEOUT_S}s")


def make_payloads(num_fields: int, top_bucket: int) -> dict:
    from distributed_tf_serving_tpu.client import make_payload

    payloads = {
        f"seq{i}": make_payload(CANDIDATES, num_fields, seed=100 + i)
        for i in range(SEQUENTIAL)
    }
    for i in range(BURST):
        payloads[f"burst{i:02d}"] = make_payload(
            CANDIDATES, num_fields, seed=200 + i
        )
    payloads["top"] = make_payload(top_bucket, num_fields, seed=300)
    return payloads


async def drive(port: int, model_name: str, payloads: dict) -> tuple[dict, dict]:
    """Send every payload; returns (scores by name, counts). A failed RPC
    raises out of here — nothing below turns a failure into a pass."""
    import numpy as np

    from distributed_tf_serving_tpu.client import ShardedPredictClient

    scores: dict = {}
    counts = {"sent": 0, "answered": 0, "failed": 0}

    async def one(client, name: str, store: bool = True):
        counts["sent"] += 1
        try:
            got = np.asarray(await client.predict(payloads[name]))
        except Exception:
            counts["failed"] += 1
            raise
        counts["answered"] += 1
        if store:
            scores[name] = got
        return got

    async with ShardedPredictClient(
        [f"127.0.0.1:{port}"], model_name, timeout_s=RPC_TIMEOUT_S,
        channels_per_host=4,
    ) as client:
        for i in range(SEQUENTIAL):
            await one(client, f"seq{i}")
        again = await one(client, "seq0", store=False)
        if not np.array_equal(again, scores["seq0"]):
            raise RuntimeError(
                "the same payload sent twice scored differently (max |d| "
                f"{np.max(np.abs(again - scores['seq0']))})"
            )
        await asyncio.gather(
            *(one(client, f"burst{i:02d}") for i in range(BURST))
        )
        await one(client, "top")
    return scores, counts


def check_scores(scores: dict, payloads: dict) -> None:
    import numpy as np

    for name, got in scores.items():
        want_rows = payloads[name]["feat_ids"].shape[0]
        if got.shape != (want_rows,):
            raise RuntimeError(f"{name}: score shape {got.shape}, want ({want_rows},)")
        if not np.all(np.isfinite(got)):
            raise RuntimeError(f"{name}: non-finite scores")
        if not (np.all(got > 0.0) and np.all(got < 1.0)):
            raise RuntimeError(
                f"{name}: scores outside (0, 1): min {got.min()} max {got.max()}"
            )


def kill_server(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()


def stop_server(proc: subprocess.Popen) -> int:
    proc.send_signal(signal.SIGTERM)
    try:
        return proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        kill_server(proc)
        raise RuntimeError("server did not exit within 120s of SIGTERM")


def read_log(path: str) -> str:
    with open(path, errors="replace") as f:
        return f.read()


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--config", default=DEFAULT_CONFIG,
                        help="server TOML (default: the shipped throughput config)")
    parser.add_argument("--child", nargs="+", help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child:
        role, *rest = args.child
        {"save": _child_save, "reference": _child_reference}[role](*rest)
        return

    import tomllib

    import numpy as np

    from distributed_tf_serving_tpu.utils.config import ServerConfig

    # The few values the driver needs, read straight from the TOML:
    # load_config would build the [model] section's ModelConfig, whose
    # module imports jax.
    config_path = os.path.abspath(args.config)
    with open(config_path, "rb") as f:
        raw = tomllib.load(f)
    server, defaults = raw.get("server", {}), ServerConfig()
    model_name = server.get("model_name", defaults.model_name)
    buckets = server.get("buckets", defaults.buckets)
    num_fields = raw.get("model", {}).get(
        "num_fields", server.get("num_fields", defaults.num_fields)
    )
    say(f"ambient JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} "
        f"JAX_COMPILATION_CACHE_DIR={os.environ.get('JAX_COMPILATION_CACHE_DIR')!r}")
    say(f"config {config_path}: model {model_name} x {num_fields} fields, "
        f"buckets {list(buckets)}")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        checkpoint = os.path.join(work, "ckpt")
        run_cpu_child("save", config_path, checkpoint)

        port, rest_port = free_port(), free_port()
        log_path = os.path.join(work, "server.log")
        with open(log_path, "w") as log_file:
            proc = subprocess.Popen(
                [sys.executable, "-m", "distributed_tf_serving_tpu.serving.server",
                 "--config", config_path, "--checkpoint", checkpoint,
                 "--host", "127.0.0.1", "--port", str(port),
                 "--rest-port", str(rest_port)],
                cwd=REPO, stdout=log_file, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        try:
            t0 = time.perf_counter()
            at_start = wait_serving(proc, port, rest_port)
            say(f"SERVING after {time.perf_counter() - t0:.1f}s")
            say(f"runtime: {json.dumps(at_start)}")

            payloads = make_payloads(num_fields, max(buckets))
            scores, counts = asyncio.run(
                drive(port, model_name, payloads)
            )
            say(f"requests: {json.dumps(counts)}")
            check_scores(scores, payloads)
            mesh_block = monitoring(rest_port, "mesh")
            if mesh_block is not None:
                say(f"mesh: {json.dumps(mesh_block)}")
            say("batcher: " + json.dumps(
                monitoring(rest_port, "metrics").get("batcher")))
            runtime = monitoring(rest_port, "runtime")
            if at_start["compile_cache"] is not None:
                say("compile requests while serving (after warm-up): "
                    f"{runtime['compile_cache']['requests'] - at_start['compile_cache']['requests']}")
            rc = stop_server(proc)
        except BaseException:
            kill_server(proc)
            sys.stderr.write(read_log(log_path)[-8000:])
            raise
        server_log = read_log(log_path)
        if rc != 0 or "shutdown complete" not in server_log:
            sys.stderr.write(server_log[-8000:])
            raise RuntimeError(
                f"server exit code {rc}, 'shutdown complete' "
                f"{'seen' if 'shutdown complete' in server_log else 'missing'}"
            )
        say("server exit code 0 after SIGTERM (shutdown complete)")
        # What the installed jax deprecates (or any other warning) on the
        # path just run shows up here, once per distinct line.
        for line in sorted({
            line.strip() for line in server_log.splitlines() if "Warning" in line
        }):
            say(f"server warned: {line[:300]}")

        payloads_npz = os.path.join(work, "payloads.npz")
        np.savez(payloads_npz, **{
            f"{name}/{key}": value
            for name, arrays in payloads.items() for key, value in arrays.items()
        })
        reference_npz = os.path.join(work, "reference.npz")
        run_cpu_child("reference", checkpoint, payloads_npz, reference_npz)
        with np.load(reference_npz) as reference:
            worst = max(
                float(np.max(np.abs(scores[name] - reference[name])))
                for name in scores
            )
        say(f"max |served - float32 cpu reference| = {worst:.6f} "
            f"(tolerance {SCORE_TOLERANCE})")
        if not worst <= SCORE_TOLERANCE:
            raise RuntimeError(
                f"scores off the float32 reference by {worst} > {SCORE_TOLERANCE}"
            )
        # Kept for side-by-side runs (one-chip vs mesh servers score the
        # same seeded payloads): chiprun_out/ is what a chip call brings back.
        keep = os.path.join(REPO, "chiprun_out", "chip_smoke")
        os.makedirs(keep, exist_ok=True)
        stem = os.path.splitext(os.path.basename(config_path))[0]
        np.savez(os.path.join(keep, f"scores_{stem}.npz"), **scores)

    if "jax" in sys.modules:
        raise RuntimeError("the smoke's parent imported jax")
    device = {
        "platform": runtime["platform"],
        "kind": runtime["device_kind"],
        "count": runtime["device_count"],
    }
    if device["platform"] != "tpu":
        raise SystemExit(
            f"[chip_smoke] FAIL: server ran on platform "
            f"{device['platform']!r} ({device['kind']}), not on a tpu"
        )
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
