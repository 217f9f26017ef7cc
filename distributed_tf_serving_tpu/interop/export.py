"""Export a native servable as a TensorFlow SavedModel — the reverse
interop leg.

The importer (interop/savedmodel.py) brings TF-Serving artifacts IN; this
module takes trained in-tree models OUT to consumers still running
`tensorflow_model_server`: the zoo forward is converted with jax2tf
(StableHLO carried in an `XlaCallModule` op, which TF's runtime executes
natively — jax 0.9 emits this for every conversion mode), wrapped in the
reference serving contract (`feat_ids` DT_INT64 + `feat_wts` DT_FLOAT
[n,F] -> `prediction_node`, DCNClient.java:98-108), with the vocab fold
expressed in TF ops (`floormod` == the host fold's exact mod) so int64
ids beyond 2^31 survive exactly as they do in-tree. Weights land as
ordinary tf.Variables, so the artifact has the standard `variables/`
TensorBundle layout and version-directory lifecycle tools work unchanged.

This is the BASELINE.json north star's direction ("a jax2tf-exported
SavedModel") implemented as the exit path; round-trip intake of such an
artifact by OUR graph executor is out of scope by design — XlaCallModule
embeds StableHLO, not TF ops, and the native side serves its own
checkpoints (train/checkpoint.py) without any TF detour.

MUST run in a process that has NOT imported the vendored protos: our
tensorflow.* descriptors collide with TensorFlow's in the process-wide
descriptor pool. `python -m distributed_tf_serving_tpu.interop.export`
imports tensorflow first and only proto-free subpackages after (models/
train keep their proto imports lazy for exactly this reason —
models/registry.py note).
"""

from __future__ import annotations

import argparse
import json
import sys


def publish_version(
    base_dir: str,
    write_fn,
    at_least: int = 1,
    max_attempts: int = 10,
) -> tuple[int, str]:
    """Land one artifact in a TF-Serving versioned base dir ATOMICALLY,
    allocating the next monotonic version number: `<base>/<N>` where N =
    max(existing numeric dirs, at_least - 1) + 1.

    `write_fn(tmp_dir)` writes the complete artifact into a sibling temp
    directory (dot-prefixed and non-numeric, so the version watcher's
    scan never lists it); the commit is a single os.rename into the
    numbered slot. The watcher's `_version_ready` probe therefore can
    never observe a half-written version dir — the probe only fires on
    directories that exist, and a published directory exists only fully
    written. Concurrent publishers can race the SAME number: the loser's
    rename fails (the winner's landed dir is non-empty, so rename raises
    ENOTEMPTY/EEXIST rather than silently merging), the allocator
    re-scans and retries the rename under the next number — the written
    artifact is reused, never re-generated, and the directory number is
    authoritative over anything the artifact recorded (the watcher's own
    loader contract). Returns (version, path).

    TF-free; the lifecycle plane's publisher, soaks, and tests call this
    with whatever writer fits (train/checkpoint.py save_servable,
    export_servable, a test fixture). The number allocation reuses the
    watcher's OWN scanner (lazy import), so publisher and watcher can
    never disagree about what counts as a version directory."""
    import os
    import shutil

    from ..serving.version_watcher import scan_versions

    base = os.path.abspath(str(base_dir))
    os.makedirs(base, exist_ok=True)

    def _numeric_versions() -> list[int]:
        return list(scan_versions(base))

    tmp = os.path.join(base, f".tmp-publish-{os.getpid()}-{id(write_fn):x}")
    shutil.rmtree(tmp, ignore_errors=True)
    try:
        write_fn(tmp)
        if not os.path.isdir(tmp):
            raise RuntimeError(
                f"publish writer did not create the artifact dir {tmp}"
            )
        last_exc: OSError | None = None
        for _ in range(max_attempts):
            version = max(_numeric_versions() + [int(at_least) - 1]) + 1
            dst = os.path.join(base, str(version))
            try:
                os.rename(tmp, dst)
            except OSError as exc:
                # A racing publisher landed this number first: the rename
                # onto its non-empty dir raises (ENOTEMPTY/EEXIST) instead
                # of silently merging. Only a now-existing destination is
                # a collision; anything else — EXDEV, EACCES — is a real
                # failure and must surface, not spin.
                if not os.path.isdir(dst):
                    raise
                last_exc = exc
                continue
            return version, dst
        raise RuntimeError(
            f"could not allocate a version under {base} after "
            f"{max_attempts} collisions"
        ) from last_exc
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def publish_export(
    base_dir: str, checkpoint_dir: str, validate: bool = True,
    at_least: int = 1,
) -> dict:
    """export_servable -> the next numeric version slot under `base_dir`
    (the SavedModel flavor of the lifecycle publish path; requires
    TensorFlow in-process like export_servable itself). The export's own
    validate-then-commit runs inside the publish temp dir, so the rename
    into the numbered slot stays the single commit point."""
    summary: dict = {}

    def write(tmp_dir: str) -> None:
        summary.update(export_servable(checkpoint_dir, tmp_dir, validate=validate))

    version, path = publish_version(base_dir, write, at_least=at_least)
    summary.update({"version": version, "path": path})
    return summary


def export_servable(checkpoint_dir: str, out_dir: str, validate: bool = True) -> dict:
    """Convert the checkpointed servable to a SavedModel at `out_dir`.

    Returns a summary dict (model kind, num params, validation result).
    Supports the standard 2-input CTR contract and the 3-input
    dense_features (DLRM) contract; anything else raises."""
    import os

    import tensorflow as tf  # noqa: F401 — must precede any proto import
    import jax
    import numpy as np
    from jax.experimental import jax2tf

    from ..models.embeddings import unpack_params
    from ..train.checkpoint import load_servable

    servable = load_servable(checkpoint_dir)
    model = servable.model
    config = model.config
    sig = servable.signature("")
    input_names = sorted(s.name for s in sig.inputs)
    dense_dim = None
    if input_names == ["dense_features", "feat_ids", "feat_wts"]:
        dense_spec = sig.input_specs["dense_features"]
        dense_dim = dense_spec.shape[1] if dense_spec.shape else None
        if not dense_dim:
            # A declared-but-unknown dense width must FAIL, not silently
            # ship a 2-input artifact: DLRM substitutes zeros for a missing
            # dense input, so validation alone could never catch the
            # dropped contract (review finding).
            raise NotImplementedError(
                "dense_features with unknown width cannot be exported "
                f"(signature shape {dense_spec.shape}); re-save the "
                "servable with a concrete num_dense_features"
            )
    elif input_names != ["feat_ids", "feat_wts"]:
        raise NotImplementedError(
            f"export supports the CTR contracts (2-input, or 3-input with "
            f"dense_features); servable declares {input_names}"
        )
    if not model.folds_ids_on_host:
        raise NotImplementedError(
            "export requires a zoo servable with the host id fold contract"
        )
    F = config.num_fields
    vocab = config.vocab_size
    # The artifact holds the logical [V, D] table, as import_savedmodel's
    # templates expect it; load_servable hands it over in the serving shape.
    params = jax.tree.map(np.asarray, unpack_params(servable.params, config.embed_dim))

    def forward(p, ids32, wts, dense=None):
        batch = {"feat_ids": ids32, "feat_wts": wts}
        if dense is not None:
            batch["dense_features"] = dense
        return model.apply(p, batch)["prediction_node"]

    poly = [None, f"(b, {F})", f"(b, {F})"]
    if dense_dim is not None:
        poly.append(f"(b, {dense_dim})")
    tf_fn = jax2tf.convert(forward, polymorphic_shapes=poly, with_gradient=False)

    class ExportedCTR(tf.Module):
        pass

    module = ExportedCTR()
    # tf.Variables per leaf: standard variables/ layout in the artifact.
    module.params = tf.nest.map_structure(tf.Variable, params)

    specs = [
        tf.TensorSpec([None, F], tf.int64, name="feat_ids"),
        tf.TensorSpec([None, F], tf.float32, name="feat_wts"),
    ]
    if dense_dim is not None:
        specs.append(
            tf.TensorSpec([None, dense_dim], tf.float32, name="dense_features")
        )

    @tf.function(input_signature=specs)
    def serve(feat_ids, feat_wts, dense_features=None):
        # TF-side exact fold (floormod == mathematical mod): int64 wire ids
        # stay faithful past 2^31, and the converted fn sees the folded
        # int32 ids the in-tree serving path feeds the model.
        ids32 = tf.cast(tf.math.floormod(feat_ids, tf.constant(vocab, tf.int64)), tf.int32)
        args = (ids32, feat_wts) if dense_features is None else (
            ids32, feat_wts, dense_features
        )
        return {"prediction_node": tf_fn(module.params, *args)}

    module.serve = serve
    # Validate-then-commit: the artifact is written to a sibling temp dir,
    # validated THROUGH TF from there, and only renamed into place when it
    # passes — a version watcher pointed at the output base path must
    # never see a complete-looking directory holding a diverged model
    # (same protocol as train/checkpoint.py save_servable).
    import shutil

    tmp_dir = out_dir.rstrip("/") + f".tmp-export-{os.getpid()}"
    shutil.rmtree(tmp_dir, ignore_errors=True)
    try:
        tf.saved_model.save(module, tmp_dir, signatures={"serving_default": serve})
        _write_warmup_assets(tmp_dir, servable.name, F, dense_dim)
        summary = {
            "out": out_dir,
            "model": servable.name,
            "version": servable.version,
            "num_fields": F,
            "vocab_size": vocab,
            "param_leaves": len(jax.tree.leaves(params)),
        }
        if validate:
            # Reload the artifact THROUGH TF and compare against the
            # in-tree forward on ids past 2^31 (the fold-fidelity
            # regression the importer tests pin in the other direction).
            # Scores are sigmoid outputs in (0,1): a single absolute gate
            # is the right metric, and it is the SAME bound the export
            # tests assert — one threshold, no flaky gap between them.
            max_abs_err_bound = 1e-5
            rng = np.random.RandomState(7)
            ids = rng.randint(0, 1 << 40, size=(16, F)).astype(np.int64)
            wts = rng.rand(16, F).astype(np.float32)
            feeds = {"feat_ids": tf.constant(ids), "feat_wts": tf.constant(wts)}
            extra = ()
            if dense_dim is not None:
                dense = rng.rand(16, dense_dim).astype(np.float32)
                feeds["dense_features"] = tf.constant(dense)
                extra = (dense,)
            reloaded = tf.saved_model.load(tmp_dir).signatures["serving_default"]
            got = reloaded(**feeds)["prediction_node"].numpy()
            from .. import native

            want = np.asarray(
                forward(servable.params, native.fold_ids(ids, vocab), wts, *extra)
            )
            err = float(np.max(np.abs(got - want)))
            if err >= max_abs_err_bound:
                raise RuntimeError(
                    f"exported SavedModel diverges from the native forward "
                    f"(max abs err {err:.3e} >= {max_abs_err_bound})"
                )
            summary["validated"] = True
            summary["max_abs_err"] = err
        shutil.rmtree(out_dir, ignore_errors=True)
        os.replace(tmp_dir, out_dir)
    except BaseException:
        shutil.rmtree(tmp_dir, ignore_errors=True)
        raise
    return summary


def _write_warmup_assets(artifact_dir: str, model_name: str, num_fields: int,
                         dense_dim: int | None) -> None:
    """Give the artifact TF-Serving's warmup convention: a representative
    predict request in assets.extra/tf_serving_warmup_requests, so
    tensorflow_model_server (and our own version watcher) compile/warm the
    serving signature at load instead of on the first real request.

    Written by a TF-FREE subprocess: the PredictionLog record needs our
    vendored tensorflow.serving bindings, which cannot share this
    process's descriptor pool with TensorFlow (module docstring).
    """
    import os
    import subprocess

    import numpy as np

    rng = np.random.RandomState(11)
    warm = {
        "feat_ids": rng.randint(0, 1 << 40, size=(16, num_fields)).astype(np.int64),
        "feat_wts": rng.rand(16, num_fields).astype(np.float32),
    }
    if dense_dim is not None:
        warm["dense_features"] = rng.rand(16, dense_dim).astype(np.float32)
    extra_dir = os.path.join(artifact_dir, "assets.extra")
    os.makedirs(extra_dir, exist_ok=True)
    npz = os.path.join(extra_dir, "_warm_inputs.npz")
    np.savez(npz, **warm)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # never let the child touch a device
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (
            os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
            env.get("PYTHONPATH"),
        ) if p
    )
    try:
        subprocess.run(
            [sys.executable, "-c", (
                "import sys, numpy as np\n"
                "from distributed_tf_serving_tpu.serving.warmup import (\n"
                "    make_warmup_record, write_tfrecords)\n"
                "arrays = dict(np.load(sys.argv[1]))\n"
                "write_tfrecords(sys.argv[2], [make_warmup_record(arrays, sys.argv[3])])\n"
            ), npz, os.path.join(extra_dir, "tf_serving_warmup_requests"),
             model_name],
            check=True, capture_output=True, text=True, timeout=300, env=env,
        )
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"warmup-asset writer failed: {e.stderr[-1000:]}"
        ) from e
    finally:
        os.remove(npz)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        description="Export a native servable checkpoint as a TF SavedModel"
    )
    parser.add_argument("--checkpoint", required=True,
                        help="servable checkpoint dir (train.save_servable)")
    parser.add_argument("--out", required=True, help="SavedModel output dir")
    parser.add_argument("--no-validate", action="store_true")
    args = parser.parse_args(argv)
    summary = export_servable(
        args.checkpoint, args.out, validate=not args.no_validate
    )
    print(json.dumps(summary))


if __name__ == "__main__":
    sys.exit(main())
