"""_build_entry's variant cache (serving/batcher.py): one jit closure a
distinct (layout, out_keys, topk, prune), or (out_keys, topk, prune) off the
combined buffer; a second call with the same key builds nothing; the
executable is named for the model and the variant; and what it returns is
`model.apply`'s."""

import jax
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import ModelConfig, Servable, build_model, ctr_signatures
from distributed_tf_serving_tpu.ops.transfer import (
    combined_layout,
    pack_host,
    pack_host_combined,
    topk_restore_host,
)
from distributed_tf_serving_tpu.serving import batcher as batcher_mod
from distributed_tf_serving_tpu.serving.batcher import DynamicBatcher, prepare_inputs

CFG = ModelConfig(num_fields=6, vocab_size=1 << 12, embed_dim=8, mlp_dims=(16,), num_cross_layers=2,
                  cross_full_matrix=True, compute_dtype="float32")
ROWS, VALID, K = 8, 5, 3
# variant -> (out_keys, topk, prune)
VARIANTS = {
    "score": (None, 0, False),
    "filtered": (("prediction_node",), 0, False),
    "topk": (None, K, False),
    "prune": (None, K, True),
}


@pytest.fixture(scope="module")
def servable():
    model = build_model("dcn_v2", CFG)
    return Servable(name="M", version=1, model=model, params=model.init(jax.random.PRNGKey(0)),
                    signatures=ctr_signatures(CFG.num_fields))


@pytest.mark.parametrize("variant", list(VARIANTS))
@pytest.mark.parametrize("combined", [True, False], ids=["combined", "per_key"])
def test_a_variant_is_one_closure_named_for_it_and_scores_as_apply(servable, combined, variant, monkeypatch):
    out_keys, topk, prune = VARIANTS[variant]
    built = []
    real = batcher_mod.step_jit

    def step_jit(model, run):
        built.append(run.__name__)
        return real(model, run)

    monkeypatch.setattr(batcher_mod, "step_jit", step_jit)
    rng = np.random.RandomState(3)
    arrays = prepare_inputs(servable.model, {
        "feat_ids": rng.randint(0, 1 << 40, size=(ROWS, CFG.num_fields)).astype(np.int64),
        "feat_wts": rng.rand(ROWS, CFG.num_fields).astype(np.float32),
    })
    want = np.asarray(servable.model.apply(servable.params, arrays)["prediction_node"])
    batcher = DynamicBatcher(buckets=(ROWS,), max_wait_us=0)  # never started: the entry alone
    fn, spec, is_combined = batcher._build_entry(servable, combined)
    assert is_combined is combined and spec == {"feat_ids": "u24"}
    variants = fn.__defaults__[-1]
    assert variants == {}
    if combined:
        layout = combined_layout(arrays, spec)
        args, key = (pack_host_combined(arrays, spec), layout), (layout, out_keys, topk, prune)
    else:
        args, key = (pack_host(arrays, spec),), (out_keys, topk, prune)
    n_valid = np.int32(VALID) if topk else None

    def call(**kw):
        return fn(servable.params, *args, **{"out_keys": out_keys, "topk": topk, "n_valid": n_valid,
                                             "prune": prune, **kw})

    out = call()
    assert list(variants) == [key] and built == [f"M_{variant if variant != 'filtered' else 'score'}"]
    again = call()
    assert list(variants) == [key] and len(built) == 1, "the second call built a closure"
    jitted = variants[key]
    operands = (servable.params, args[0]) + ((n_valid,) if topk else ())
    assert f"module @jit_{built[0]} " in jitted.lower(*operands).as_text()[:200]
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if topk:
        best = np.argsort(-want[:VALID])[:K]
        pairs = ("survivor_scores", "survivor_indices") if prune else ("topk_scores", "topk_indices")
        assert set(out) == set(pairs) | ({"stage1_scores"} if prune else set())
        np.testing.assert_array_equal(np.asarray(out[pairs[1]]), best)
        restored = topk_restore_host(out[pairs[0]], out[pairs[1]], ROWS, "prediction_node")["prediction_node"]
        np.testing.assert_allclose(restored[best], want[best], rtol=1e-6)
        assert not restored[np.setdiff1d(np.arange(ROWS), best)].any()
        if prune:
            np.testing.assert_allclose(np.asarray(out["stage1_scores"]), want, rtol=1e-6)
    else:
        assert set(out) == ({"prediction_node"} if out_keys else {"prediction_node", "logits"})
        np.testing.assert_allclose(np.asarray(out["prediction_node"]), want, rtol=1e-6)
    # Another key is another closure beside the first; the first is not rebuilt.
    call(out_keys=("logits",) if not topk else None, topk=topk and K - 1)
    assert len(variants) == 2 and len(built) == 2 and variants[key] is jitted
