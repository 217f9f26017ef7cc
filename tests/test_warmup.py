"""Model-warmup replay (serving/warmup.py): TFRecord framing + CRC32C
against known vectors AND TensorFlow's own writer, PredictionLog replay
through the real impl/batcher, failure classification, watcher integration."""

import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from distributed_tf_serving_tpu.models import (
    ModelConfig,
    Servable,
    build_model,
    ctr_signatures,
)
from distributed_tf_serving_tpu.serving import DynamicBatcher
from distributed_tf_serving_tpu.serving.warmup import (
    WarmupError,
    crc32c,
    make_warmup_record,
    masked_crc32c,
    read_tfrecords,
    replay_warmup_file,
    write_tfrecords,
)

F = 6
CFG = ModelConfig(
    name="DCN", num_fields=F, vocab_size=1 << 12, embed_dim=8,
    mlp_dims=(16,), num_cross_layers=1, compute_dtype="float32",
)


def _servable(version=1):
    model = build_model("dcn_v2", CFG)
    return Servable(
        name="DCN", version=version, model=model,
        params=model.init(jax.random.PRNGKey(0)),
        signatures=ctr_signatures(F),
    )


def _arrays(n=4, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, F)).astype(np.int64),
        "feat_wts": rng.rand(n, F).astype(np.float32),
    }


def test_crc32c_known_vectors():
    # RFC 3720 / Castagnoli check value.
    assert crc32c(b"") == 0
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(b"\x00" * 32) == 0x8A9136AA  # iSCSI test vector


def test_tfrecord_roundtrip_and_corruption(tmp_path):
    p = tmp_path / "records"
    payloads = [b"alpha", b"", b"x" * 1000]
    write_tfrecords(p, payloads)
    assert list(read_tfrecords(p)) == payloads

    raw = bytearray(p.read_bytes())
    raw[14] ^= 0xFF  # flip a payload byte of record 0
    (tmp_path / "bad").write_bytes(bytes(raw))
    with pytest.raises(WarmupError, match="checksum mismatch at record 0"):
        list(read_tfrecords(tmp_path / "bad"))

    (tmp_path / "trunc").write_bytes(p.read_bytes()[:-2])
    with pytest.raises(WarmupError, match="truncated"):
        list(read_tfrecords(tmp_path / "trunc"))


def test_tfrecord_matches_tensorflows_writer(tmp_path):
    """Cross-implementation: TF's tf.io.TFRecordWriter produces the file,
    our reader validates framing + checksums byte-for-byte. (Separate
    process: TF and our protos cannot share a descriptor pool.)"""
    p = tmp_path / "tf_written"
    r = subprocess.run(
        [sys.executable, "-c", f"""
import tensorflow as tf
with tf.io.TFRecordWriter({str(p)!r}) as w:
    w.write(b"from-tensorflow")
    w.write(b"\\x00\\x01\\x02" * 100)
"""],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "CUDA_VISIBLE_DEVICES": ""},
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert list(read_tfrecords(p)) == [b"from-tensorflow", b"\x00\x01\x02" * 100]
    # And the reverse: TF reads OUR framing.
    q = tmp_path / "ours"
    write_tfrecords(q, [b"from-dts-tpu"])
    r = subprocess.run(
        [sys.executable, "-c", f"""
import tensorflow as tf
got = [bytes(x.numpy()) for x in tf.data.TFRecordDataset({str(q)!r})]
assert got == [b"from-dts-tpu"], got
print("ok")
"""],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "CUDA_VISIBLE_DEVICES": ""},
    )
    assert r.returncode == 0 and "ok" in r.stdout, r.stderr[-2000:]


def test_replay_warms_and_counts(tmp_path):
    from distributed_tf_serving_tpu.proto import serving_apis_pb2 as apis
    from distributed_tf_serving_tpu.serving.example_codec import make_example

    sv = _servable()
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    try:
        # Two predict records (one under a WRONG model name — upstream
        # ignores the recorded spec and targets the loading version) and
        # one classify record.
        classify = apis.PredictionLog()
        req = classify.classify_log.request
        req.model_spec.name = "whatever"
        arrays = _arrays(3, seed=2)
        for i in range(3):
            req.input.example_list.examples.append(
                make_example(arrays["feat_ids"][i], arrays["feat_wts"][i])
            )
        p = tmp_path / "tf_serving_warmup_requests"
        write_tfrecords(p, [
            make_warmup_record(_arrays(4, seed=0), "DCN"),
            make_warmup_record(_arrays(2, seed=1), "SOME_OTHER_NAME"),
            classify.SerializeToString(),
        ])
        before = batcher.stats.batches
        assert replay_warmup_file(p, sv, batcher) == 3
        assert batcher.stats.batches - before == 3  # every record executed

        # MultiInference records replay too (specs live per TASK there).
        mi = apis.PredictionLog()
        mreq = mi.multi_inference_log.request
        for method in ("classify", "regress"):
            task = mreq.tasks.add()
            task.model_spec.name = "recorded-name"
            task.method_name = f"tensorflow/serving/{method}"
        arrays = _arrays(2, seed=3)
        for i in range(2):
            mreq.input.example_list.examples.append(
                make_example(arrays["feat_ids"][i], arrays["feat_wts"][i])
            )
        write_tfrecords(p, [mi.SerializeToString()])
        assert replay_warmup_file(p, sv, batcher) == 1
    finally:
        batcher.stop()


def test_replay_failure_names_record(tmp_path):
    from distributed_tf_serving_tpu.proto import serving_apis_pb2 as apis

    sv = _servable()
    batcher = DynamicBatcher(buckets=(32,), max_wait_us=0).start()
    try:
        bad = apis.PredictionLog()
        bad.predict_log.request.model_spec.name = "DCN"
        # Unknown input key -> INVALID_ARGUMENT -> WarmupError at index 1.
        from distributed_tf_serving_tpu import codec

        codec.from_ndarray(
            np.zeros((2, F), np.int64), out=bad.predict_log.request.inputs["nope"]
        )
        p = tmp_path / "w"
        write_tfrecords(p, [make_warmup_record(_arrays(), "DCN"),
                            bad.SerializeToString()])
        with pytest.raises(WarmupError, match="record 1 .*failed"):
            replay_warmup_file(p, sv, batcher)

        empty = apis.PredictionLog()
        write_tfrecords(p, [empty.SerializeToString()])
        with pytest.raises(WarmupError, match="no log_type"):
            replay_warmup_file(p, sv, batcher)
    finally:
        batcher.stop()


def test_watcher_corrupt_warmup_fails_load_bounded(tmp_path):
    """A corrupt warmup file fails the version load (upstream posture) —
    the version never flips into the registry, retries are bounded, and
    the failure is the named WarmupError, not a silent skip."""
    from distributed_tf_serving_tpu.models import ServableRegistry
    from distributed_tf_serving_tpu.serving import VersionWatcher, VersionWatcherConfig
    from distributed_tf_serving_tpu.serving.warmup import WARMUP_DIRNAME, WARMUP_FILENAME
    from distributed_tf_serving_tpu.train.checkpoint import save_servable

    sv = _servable(version=1)
    save_servable(tmp_path / "1", sv, kind="dcn_v2")
    extra = tmp_path / "1" / WARMUP_DIRNAME
    extra.mkdir()
    (extra / WARMUP_FILENAME).write_bytes(b"not a tfrecord at all")

    calls = []

    def failing_replay(servable, wf):
        calls.append(wf)
        from distributed_tf_serving_tpu.serving.warmup import replay_warmup_file

        return replay_warmup_file(wf, servable, None)  # raises before batcher use

    registry = ServableRegistry()
    w = VersionWatcher(
        tmp_path, registry,
        VersionWatcherConfig(
            poll_interval_s=3600, model_name="DCN", max_load_attempts=2
        ),
        warmup_replay=failing_replay,
    )
    for _ in range(4):
        w.poll_once()
    assert registry.models() == {}  # never flipped
    assert len(calls) == 2  # bounded by max_load_attempts, then blacklisted


def test_watcher_replays_warmup_file(tmp_path):
    from distributed_tf_serving_tpu.models import ServableRegistry
    from distributed_tf_serving_tpu.serving import VersionWatcher, VersionWatcherConfig
    from distributed_tf_serving_tpu.serving.warmup import WARMUP_DIRNAME, WARMUP_FILENAME
    from distributed_tf_serving_tpu.train.checkpoint import save_servable

    sv = _servable(version=1)
    save_servable(tmp_path / "1", sv, kind="dcn_v2")
    extra = tmp_path / "1" / WARMUP_DIRNAME
    extra.mkdir()
    write_tfrecords(extra / WARMUP_FILENAME, [make_warmup_record(_arrays(), "DCN")])

    replayed = []
    registry = ServableRegistry()
    w = VersionWatcher(
        tmp_path, registry,
        VersionWatcherConfig(poll_interval_s=3600, model_name="DCN"),
        warmup_replay=lambda servable, wf: replayed.append((servable.version, wf)) or 1,
    )
    w.poll_once()
    assert registry.models() == {"DCN": [1]}
    assert replayed == [(1, extra / WARMUP_FILENAME)]
