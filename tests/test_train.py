"""Training tests: loss decreases, AUC beats random, sharded step works on
the 8-device mesh, checkpoints round-trip into servables."""

import dataclasses

import jax
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import ModelConfig, build_model
from distributed_tf_serving_tpu.parallel import make_mesh
from distributed_tf_serving_tpu.train import Trainer, auc, load_servable, save_servable
from distributed_tf_serving_tpu.train.data import SyntheticCTRStream

CFG = ModelConfig(
    num_fields=8, vocab_size=4096, embed_dim=8, mlp_dims=(32, 16),
    bottom_mlp_dims=(16, 8), num_cross_layers=2, compute_dtype="float32",
)


def test_auc_metric():
    labels = np.array([0, 0, 1, 1])
    assert auc(labels, np.array([0.1, 0.2, 0.8, 0.9])) == 1.0
    assert auc(labels, np.array([0.9, 0.8, 0.2, 0.1])) == 0.0
    assert auc(labels, np.array([0.5, 0.5, 0.5, 0.5])) == 0.5


def test_synthetic_stream_deterministic():
    s1, s2 = SyntheticCTRStream(), SyntheticCTRStream()
    b1, b2 = s1.batch(16, 3), s2.batch(16, 3)
    np.testing.assert_array_equal(b1["feat_ids"], b2["feat_ids"])
    np.testing.assert_array_equal(b1["labels"], b2["labels"])
    assert 0.05 < b1["labels"].mean() < 0.95  # both classes present


def test_training_learns():
    trainer = Trainer(build_model("dcn_v2", CFG), seed=1, learning_rate=1e-2)
    before = trainer.eval_auc(batches=2, batch_size=512)
    first = trainer.fit(steps=80, batch_size=512)
    after_auc = trainer.eval_auc(batches=2, batch_size=512)
    # Synthetic task's Bayes AUC is ~0.93; 80 steps reaches ~0.7 — the test
    # asserts real generalization, not the ceiling.
    assert after_auc > max(before + 0.05, 0.62), (before, after_auc)
    assert int(trainer.state.step) == 80
    assert np.isfinite(first["loss"])


def test_snapshot_params_survives_donation():
    """The train step donates its state, so state.params leaves die on the
    next fit(); snapshot_params must return copies that stay live (the
    serve-while-training contract — a Servable built from the snapshot
    keeps scoring after training continues)."""
    trainer = Trainer(build_model("dcn_v2", CFG), seed=3)
    trainer.fit(steps=1, batch_size=64)
    snap = trainer.snapshot_params()
    live_ref = trainer.state.params
    trainer.fit(steps=1, batch_size=64)
    # The old live state is donated-dead...
    with pytest.raises(Exception):
        np.asarray(jax.tree_util.tree_leaves(live_ref)[0])
    # ...but the snapshot still scores.
    model = trainer.model
    batch = {
        "feat_ids": np.zeros((4, CFG.num_fields), np.int64),
        "feat_wts": np.ones((4, CFG.num_fields), np.float32),
    }
    out = np.asarray(model.apply(snap, batch)["prediction_node"])
    assert out.shape == (4,) and np.all(np.isfinite(out))


def test_snapshot_params_preserves_mesh_sharding():
    mesh = make_mesh(8, model_parallel=2)
    trainer = Trainer(build_model("dcn_v2", CFG), mesh=mesh, seed=3, tensor_parallel=True)
    trainer.fit(steps=1, batch_size=64)
    snap = trainer.snapshot_params()
    for live, copy in zip(
        jax.tree_util.tree_leaves(trainer.state.params),
        jax.tree_util.tree_leaves(snap),
    ):
        assert live.sharding == copy.sharding


@pytest.mark.parametrize("model_parallel", [1, 2])
def test_sharded_training_matches_semantics(model_parallel):
    """Same seed, same data: mesh-sharded training must track the
    single-placement run (dp grad psum + EP collectives are exact)."""
    t_plain = Trainer(build_model("dcn_v2", CFG), seed=2)
    t_mesh = Trainer(
        build_model("dcn_v2", CFG), mesh=make_mesh(8, model_parallel=model_parallel), seed=2
    )
    m_plain = t_plain.fit(steps=5, batch_size=128)
    m_mesh = t_mesh.fit(steps=5, batch_size=128)
    assert m_mesh["loss"] == pytest.approx(m_plain["loss"], rel=2e-4)


def test_checkpoint_roundtrip(tmp_path):
    from distributed_tf_serving_tpu.models import Servable, ctr_signatures

    model = build_model("dcn_v2", CFG)
    sv = Servable(
        name="DCN", version=7, model=model,
        params=model.init(jax.random.PRNGKey(3)),
        signatures=ctr_signatures(CFG.num_fields),
    )
    save_servable(tmp_path / "ckpt", sv, kind="dcn_v2")
    loaded = load_servable(tmp_path / "ckpt")
    assert loaded.name == "DCN" and loaded.version == 7
    # Saved logical, loaded in the serving shape (lane-packed), same scores;
    # saved again from the packed servable, the file is the logical one.
    vocab, dim = CFG.vocab_size, CFG.embed_dim
    assert sv.embedding_pack == 1 and loaded.embedding_pack == 128 // dim
    assert loaded.params["embedding"].shape == (vocab * dim // 128, 128)
    save_servable(tmp_path / "again", loaded, kind="dcn_v2")
    host = load_servable(tmp_path / "again", host=True)
    assert isinstance(host.params["embedding"], np.ndarray) and host.embedding_pack == 128 // dim
    np.testing.assert_array_equal(
        host.params["embedding"].reshape(vocab, dim), np.asarray(sv.params["embedding"])
    )
    # Compare to the built model's config (build_model("dcn_v2") flips
    # cross_full_matrix on), not the pre-build CFG.
    assert loaded.model.config == sv.model.config
    rng = np.random.RandomState(0)
    batch = {
        "feat_ids": rng.randint(0, CFG.vocab_size, size=(6, 8)).astype(np.int32),
        "feat_wts": rng.rand(6, 8).astype(np.float32),
    }
    np.testing.assert_array_equal(
        np.asarray(sv.model.apply(sv.params, batch)["prediction_node"]),
        np.asarray(loaded.model.apply(loaded.params, batch)["prediction_node"]),
    )


def test_checkpoint_restores_onto_mesh(tmp_path):
    from distributed_tf_serving_tpu.models import Servable, ctr_signatures
    from distributed_tf_serving_tpu.parallel import MODEL_AXIS

    model = build_model("dcn_v2", CFG)
    sv = Servable(
        name="DCN", version=1, model=model,
        params=model.init(jax.random.PRNGKey(4)),
        signatures=ctr_signatures(CFG.num_fields),
    )
    save_servable(tmp_path / "ckpt", sv, kind="dcn_v2")
    mesh = make_mesh(8, model_parallel=4)
    loaded = load_servable(tmp_path / "ckpt", mesh=mesh)
    emb = loaded.params["embedding"]
    assert emb.sharding.spec == jax.sharding.PartitionSpec(MODEL_AXIS, None)
    # Lane-packed and split over the model axis: each shard a contiguous
    # range of logical rows.
    assert emb.shape == (CFG.vocab_size * CFG.embed_dim // 128, 128)
    assert emb.addressable_shards[0].data.shape == (emb.shape[0] // 4, 128)
    np.testing.assert_array_equal(
        np.asarray(emb).reshape(CFG.vocab_size, CFG.embed_dim),
        np.asarray(sv.params["embedding"]),
    )


def test_trainer_cli_writes_servable_checkpoint(tmp_path):
    """The train -> checkpoint -> serve workflow's first leg: the CLI must
    produce a checkpoint load_servable can serve."""
    from distributed_tf_serving_tpu.train.checkpoint import load_servable
    from distributed_tf_serving_tpu.train.trainer import main

    out = tmp_path / "ckpt"
    main([
        "--out", str(out), "--steps", "3", "--batch-size", "32",
        "--num-fields", "6", "--vocab-size", "512", "--embed-dim", "4",
        "--name", "CLI", "--version", "5",
    ])
    sv = load_servable(out)
    assert sv.name == "CLI" and sv.version == 5
    batch = {
        "feat_ids": np.zeros((3, 6), np.int32),
        "feat_wts": np.ones((3, 6), np.float32),
    }
    assert sv(batch)["prediction_node"].shape == (3,)
