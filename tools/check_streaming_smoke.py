#!/usr/bin/env python
"""Streaming/transport tier-1 smoke (ISSUE 9): a CPU-safe, self-contained
gate asserting the PR's correctness contract end to end over REAL gRPC —

- streamed (PredictStream, chunked sub-batches) and unary Predict return
  BIT-IDENTICAL scores, over TCP loopback AND a Unix-domain socket, with
  the fault injector delaying readbacks so chunks genuinely complete out
  of order;
- the client's incremental merge survives the out-of-order arrival and
  records first-scores latency;
- the k-deep pipeline (depth 4, in-flight window 4) serves
  the same scores as the defaults would;
- a mid-stream deadline aborts DEADLINE_EXCEEDED instead of hanging.

Prints one JSON line; exit 0 = gate passed. Run in tier-1 by
tests/test_tool_smokes.py.
"""

import asyncio
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from distributed_tf_serving_tpu import faults  # noqa: E402
from distributed_tf_serving_tpu.client import (  # noqa: E402
    ShardedPredictClient,
    make_payload,
)
from distributed_tf_serving_tpu.models import ServableRegistry  # noqa: E402
from distributed_tf_serving_tpu.serving.batcher import DynamicBatcher  # noqa: E402
from distributed_tf_serving_tpu.serving.server import (  # noqa: E402
    create_server,
    load_demo_servable,
)
from distributed_tf_serving_tpu.serving.service import (  # noqa: E402
    PredictionServiceImpl,
    ServiceError,
)

CANDIDATES = int(os.environ.get("SMOKE_CANDIDATES", "200"))
CHUNK = int(os.environ.get("SMOKE_CHUNK", "48"))
NUM_FIELDS = 16


def build_stack():
    registry = ServableRegistry()
    batcher = DynamicBatcher(
        buckets=(32, 64, 128, 256),
        max_wait_us=200,
        pipeline_depth=4,
        inflight_window=4,
    ).start()
    servable = load_demo_servable(
        registry, kind="dcn_v2", name="DCN",
        num_fields=NUM_FIELDS, vocab_size=1 << 12, embed_dim=4,
        mlp_dims=(16,), num_cross_layers=1, compute_dtype="float32",
    )
    batcher.warmup(servable)
    impl = PredictionServiceImpl(registry, batcher)
    impl.response_arena = True
    return registry, batcher, impl


async def main() -> dict:
    _registry, batcher, impl = build_stack()
    uds = os.path.join(tempfile.gettempdir(), f"dts_smoke_{os.getpid()}.sock")
    server, port = create_server(impl, "127.0.0.1:0", uds_path=uds)
    server.start()
    out = {
        "bit_identical": {},
        "out_of_order_seen": False,
        "first_scores_p50_ms": None,
        "stream_chunks": 0,
        "deadline_aborted": False,
        "pipeline": None,
        "errors": [],
    }
    payloads = [
        make_payload(candidates=CANDIDATES, num_fields=NUM_FIELDS, seed=s)
        for s in (1, 2, 3)
    ]
    try:
        # Out-of-order pressure: every few readbacks stall 60 ms, so chunk
        # completion order decouples from offset order deterministically
        # enough to observe across the run.
        faults.get().add("readback", "delay", rate=0.34, delay_s=0.06)
        for target in (f"127.0.0.1:{port}", f"unix:{uds}"):
            async with ShardedPredictClient(
                [target], "DCN", stream_chunk_candidates=CHUNK,
            ) as client:
                identical = True
                for p in payloads:
                    unary = await client.predict(p, sort_scores=True)
                    streamed = await client.predict_streamed(
                        p, sort_scores=True
                    )
                    if not np.array_equal(unary, streamed):
                        identical = False
                        out["errors"].append(
                            f"{target}: streamed != unary (max delta "
                            f"{float(np.max(np.abs(unary - streamed)))})"
                        )
                out["bit_identical"][target] = identical
                stats = client.stream_stats()
                out["stream_chunks"] += stats["stream_chunks"]
                if stats["first_score_p50_ms"] is not None:
                    out["first_scores_p50_ms"] = stats["first_score_p50_ms"]
        faults.reset()

        # Direct generator probe for out-of-order arrival: delay exactly
        # the first sub-batch's readback; its chunk must flush last.
        from distributed_tf_serving_tpu.client import build_predict_request

        faults.get().add("readback", "delay", delay_s=0.3, count=1)
        req = build_predict_request(
            payloads[0], "DCN", output_filter=("prediction_node",)
        )
        offsets = [c.offset for c in impl.predict_stream(req, chunk=CHUNK)]
        faults.reset()
        out["out_of_order_seen"] = offsets != sorted(offsets)
        if not out["out_of_order_seen"]:
            out["errors"].append(
                f"chunks arrived in offset order {offsets} despite a "
                "delayed first readback"
            )

        # Deadline mid-stream: every dispatch stalls past the budget.
        faults.get().add("batcher.dispatch", "delay", delay_s=1.0)
        t0 = time.perf_counter()
        try:
            for _c in impl.predict_stream(req, deadline_s=0.25, chunk=CHUNK):
                pass
            out["errors"].append("mid-stream deadline did not abort")
        except ServiceError as e:
            out["deadline_aborted"] = e.code == "DEADLINE_EXCEEDED"
            if not out["deadline_aborted"]:
                out["errors"].append(f"aborted with {e.code}, not DEADLINE_EXCEEDED")
        if time.perf_counter() - t0 > 5.0:
            out["errors"].append("deadline abort took > 5s")
        faults.reset()

        out["pipeline"] = impl.pipeline_stats()
        if out["pipeline"]["inflight_peak"] < 2:
            out["errors"].append(
                "inflight_peak < 2: sub-batches never overlapped "
                f"({out['pipeline']})"
            )
        if not all(out["bit_identical"].values()) or len(out["bit_identical"]) != 2:
            out["errors"].append("bit-identity did not hold on both transports")
    finally:
        faults.reset()
        server.stop(0).wait()
        batcher.stop()
        try:
            os.unlink(uds)
        except OSError:
            pass
    out["ok"] = not out["errors"] and out["deadline_aborted"]
    return out


if __name__ == "__main__":
    result = asyncio.run(main())
    print(json.dumps(result))
    sys.exit(0 if result["ok"] else 1)
