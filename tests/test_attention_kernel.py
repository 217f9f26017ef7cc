"""The attention at all positions as a Pallas kernel (ops/attention_kernel.py),
run here in interpret mode: the XLA path that stands beside it to float32
rounding in the four callers' forms, the planted precision told apart, who
takes it, and what the batcher stamps and counts. Times come from the chip
(PERF.md section 6, PR 48); its compile for a v5e is in test_tpu_compile.py."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import build_model, exaone_moe, phi4flash, sequence
from distributed_tf_serving_tpu.models.registry import Servable, ctr_signatures
from distributed_tf_serving_tpu.ops import attention_kernel
from distributed_tf_serving_tpu.serving import batcher as batcher_mod
from distributed_tf_serving_tpu.serving.batcher import DynamicBatcher
from distributed_tf_serving_tpu.utils.config import load_config

CD = jnp.bfloat16
CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")
SMALL = {"exaone_moe": "exaone_moe_small", "pangu_moe": "pangu_moe_small", "phi4flash": "phi4flash_small",
         "olmo_hybrid": "olmo_hybrid_small"}
interpreted = functools.partial(sequence.serving_attention, interpret=True)


def _normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape), jnp.float32)


def _dense(qs, ks, v, window, scale):
    """The float32 result: every product at `highest`, the heads repeated."""
    heads = qs[0].shape[2]
    einsum = functools.partial(jnp.einsum, precision="highest")
    wide = lambda x: jnp.repeat(x, heads // x.shape[2], axis=2)  # noqa: E731
    scores = sum(einsum("nqhd,nkhd->nhqk", q, wide(k)) for q, k in zip(qs, ks)) * scale
    probs = sequence.causal_softmax(scores, v.shape[1] - qs[0].shape[1], window)
    return einsum("nhqk,nkhd->nqhd", probs, wide(v))


# The four callers' forms, small: (queries' parts, keys' parts, values, window,
# scale), position-major as `sequence.attention` takes them, and the XLA path
# of that caller on the same operands.


def _grouped_full(count):
    q, k, v = _normal(1, 1, 256, 4, 64), _normal(2, 1, 256, 2, 64), _normal(3, 1, 256, 2, 64)

    def xla():
        return sequence.blocked_attention(q.reshape(1, 256, 2, 2, 64), k, v, None, CD, count).reshape(q.shape)

    return (q,), (k,), v, None, 64 ** -0.5, xla


def _window_off_the_block(count):
    # 300 positions: two whole tiles of 128 and 44 of a third.
    q, k, v = _normal(4, 1, 300, 2, 64), _normal(5, 1, 300, 1, 64), _normal(6, 1, 300, 1, 64)

    def xla():
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(exaone_moe, "OPERAND_PIECES", count)
            return exaone_moe.band_attention(q.reshape(1, 300, 1, 2, 64), k, v, 128, CD).reshape(q.shape)

    return (q,), (k,), v, 128, 64 ** -0.5, xla


def _nope_and_shared_rope(count):
    q_nope, q_rope = _normal(7, 1, 256, 2, 64), _normal(8, 1, 256, 2, 32)
    k_nope, k_rope, v = _normal(9, 1, 256, 2, 64), _normal(10, 1, 256, 32), _normal(11, 1, 256, 2, 64)
    scale = 96 ** -0.5

    def xla():  # pangu_moe.latent_attention's blocks
        out = []
        for start, stop, first, last in sequence.query_blocks(256, 256):
            scores = (
                sequence.product("nqhd,nkhd->nhqk", q_nope[:, start:stop], k_nope[:, first:last], CD, count)
                + sequence.product("nqhd,nkd->nhqk", q_rope[:, start:stop], k_rope[:, first:last], CD, count)
            ) * scale
            probs = sequence.causal_softmax(scores, start - first)
            out.append(sequence.product("nhqk,nkhd->nqhd", probs, v[:, first:last], CD, count))
        return jnp.concatenate(out, axis=1)

    return (q_nope, q_rope), (k_nope, k_rope[:, :, None]), v, None, scale, xla


def _head_pairs_window_512(count):
    # 1,024 tokens and a window of 512: the second block's first key is key 1.
    s = {"kv": 2, "heads": 4, "head": 64}
    q, k, v = _normal(12, 1, 1024, 4 * 64), _normal(13, 1, 1024, 1, 2, 64), _normal(14, 1, 1024, 1, 128)

    def xla():  # phi4flash._attend's blocks, before the difference
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(phi4flash, "OPERAND_PIECES", count)
            patch.setattr(phi4flash, "_difference", lambda p, out, layer: out)
            out = phi4flash._attend({}, q, k, v, 3, 512, s, CD)  # [n, L, G, J, 2, 2d]
        return jnp.swapaxes(out, 3, 4).reshape(1, 1024, 4, 128)

    halves = jnp.swapaxes(q.reshape(1, 1024, 1, 2, 2, 64), 3, 4).reshape(1, 1024, 4, 64)
    return (halves,), (k.reshape(1, 1024, 2, 64),), v, 512, 64 ** -0.5, xla


def _wide_heads_eight_a_group(count):
    # qwen3_next's full layer, short: heads 256 wide both ways, 8 query heads a key-value head, two of them a step.
    q, k, v = _normal(15, 1, 256, 8, 256), _normal(16, 1, 256, 1, 256), _normal(17, 1, 256, 1, 256)

    def xla():
        return sequence.blocked_attention(q.reshape(1, 256, 1, 8, 256), k, v, None, CD, count).reshape(q.shape)

    return (q,), (k,), v, None, 256 ** -0.5, xla


FORMS = [_grouped_full, _window_off_the_block, _nope_and_shared_rope, _head_pairs_window_512, _wide_heads_eight_a_group]
# What the kernel holds of a key-value head: the keys' pieces a PAIR and the head's float32 block in VMEM, or COMPACT,
# the pieces once each and the float32 rows from HBM a chunk at a time (`attention_kernel.columns`); the shapes choose
# (`held_compact`), here every form runs in both.
HELD = pytest.mark.parametrize("compact", [False, True], ids=["pairs", "compact"])


def _kernel(qs, ks, v, window, scale, count, compact=None):
    if compact is None:  # as a caller reaches it: the shapes choose
        with interpreted([]):
            return sequence.attention(qs, ks, v, window, CD, count, scale)
    heads_first = functools.partial(jnp.transpose, axes=(0, 2, 1, 3))
    return heads_first(attention_kernel.attention(
        tuple(map(heads_first, qs)), tuple(map(heads_first, ks)), heads_first(v), scale=float(scale), window=window,
        cd=jnp.dtype(CD), count=count, interpret=True, compact=compact))


@HELD
@pytest.mark.parametrize("count", [2, 3], ids=["two_pieces", "three_pieces"])
@pytest.mark.parametrize("form", FORMS, ids=lambda f: f.__name__.strip("_"))
def test_kernel_is_the_xla_path_to_float32_rounding(form, count, compact):
    """Against the float32 result the kernel is where the XLA path is, to
    1e-6: three pieces hold a float32 value whole, so there the two agree to
    1e-6 themselves; two drop what is below 2 ** -17 of an operand, each path
    of ITS operands (the kernel cuts the exponentials before the sum divides
    them), so there it is their errors that agree. Either way of holding a
    head: the same pieces in the same pairs, their products added in another
    order."""
    qs, ks, v, window, scale, xla = form(count)
    want = _dense(qs, ks, v, window, scale)
    got, stands = _kernel(qs, ks, v, window, scale, count, compact), xla()
    assert got.shape == stands.shape == want.shape
    error = lambda x: float(jnp.max(jnp.abs(x - want)))  # noqa: E731
    assert error(got) <= error(stands) + 1e-6
    if count == 3:
        assert float(jnp.max(jnp.abs(got - stands))) <= 1e-6 * max(1.0, float(jnp.max(jnp.abs(want))))
    else:
        assert error(got) < 1e-4  # 2 ** -17 of operands of order one, not 2 ** -9


@HELD
@pytest.mark.parametrize("form", FORMS, ids=lambda f: f.__name__.strip("_"))
def test_one_piece_is_told_apart(form, compact):
    """The precision below the stated one, planted through the caller's piece
    count: bfloat16 operands alone are a thousand times further out."""
    qs, ks, v, window, scale, _ = form(3)
    want = _dense(qs, ks, v, window, scale)
    error = lambda count: float(jnp.max(jnp.abs(_kernel(qs, ks, v, window, scale, count, compact) - want)))  # noqa: E731
    assert error(1) > 1e-3 > 1e-5 > error(3)


def test_the_shapes_choose_how_a_head_is_held_and_a_caller_gets_that_form():
    """`sequence.attention` passes no form: at a shape that fits in pairs the
    kernel it reaches is the pairs' one, bit for bit, and `columns` lays the
    same pairs out either way (six column chunks of a part, or three pieces
    met by three products 3, 2 and 1 chunks deep)."""
    qs, ks, v, window, scale, _ = _grouped_full(3)
    np.testing.assert_array_equal(_kernel(qs, ks, v, window, scale, 3), _kernel(qs, ks, v, window, scale, 3, False))
    q_width, k_width, q_places, k_places, products = attention_kernel.columns((64,), 3, False)
    assert (q_width, k_width, products) == (384, 384, [(0, 0, 384)])
    assert [(i, j) for (_, i, _), (_, j, _) in zip(q_places, k_places)] == [(0, 0), (0, 1), (0, 2), (1, 0), (1, 1), (2, 0)]
    q_width, k_width, q_places, k_places, products = attention_kernel.columns((64,), 3, True)
    assert (q_width, k_width, products) == (512, 256, [(0, 0, 256), (256, 0, 128), (384, 0, 128)])
    assert k_places == [(0, 0, 0), (0, 1, 64), (0, 2, 128)]
    assert q_places == [(0, 0, 0), (0, 0, 64), (0, 0, 128), (0, 1, 256), (0, 1, 320), (0, 2, 384)]
    # two parts stand side by side inside a piece, and the last product's zeros fill its lanes
    assert attention_kernel.columns((128, 64), 2, True)[2:] == (
        [(0, 0, 0), (1, 0, 128), (0, 0, 192), (1, 0, 320), (0, 1, 384), (1, 1, 512)],
        [(0, 0, 0), (1, 0, 128), (0, 1, 192), (1, 1, 320)], [(0, 0, 384), (384, 0, 256)])
    assert attention_kernel._gaps(attention_kernel.columns((128, 64), 2, True)[2], (128, 64), 640) == [(576, 640)]


def test_the_kernels_tiles_skip_what_the_masks_throw_away():
    """`tile_pairs` counts the tiles `key_blocks` walks: a causal row of 2,048
    in tiles of 512 computes ten of sixteen, a window of 128 two tiles of 128
    a block (one for the first), a window of 512 over 1,024 three tiles."""
    assert attention_kernel.tile(2048, None) == 512 and attention_kernel.tile(2048, 128) == 128
    assert attention_kernel.tile(80, 16) == 128 and attention_kernel.tile(1024, 512) == 512
    assert attention_kernel.tile_pairs(2048, 2048) == 10 * 512 * 512
    assert attention_kernel.tile_pairs(2048, 2048, 128) == 31 * 128 * 128
    assert attention_kernel.tile_pairs(1024, 1024, 512) == 3 * 512 * 512
    assert attention_kernel.tile_pairs(300, 300, 128) == 5 * 128 * 128
    assert attention_kernel.key_blocks(512, 512, 1024, 512) == (0, 2)


# ------------------------------------------------------------ who takes it


def _family(kind):
    config = load_config(os.path.join(CONFIGS, SMALL[kind] + ".toml"))["model"]
    model = build_model(kind, config)
    rng = np.random.default_rng(1)
    batch = {
        "feat_ids": jnp.asarray(rng.integers(0, config.vocab_size, (2, config.num_fields)), jnp.int32),
        "feat_wts": jnp.asarray(rng.uniform(0.5, 1.5, (2, config.num_fields)), jnp.float32),
    }
    return model, model.init(jax.random.PRNGKey(0)), batch


def _refused(*args, **kwargs):
    raise AssertionError("the Pallas attention outside a one-chip served entry")


def _served_on_the_cpu(model, params, batch):
    with sequence.serving_attention([]) as notes:
        jax.jit(model.apply)(params, batch)
    return notes


def _one_query(model, params, batch):
    with interpreted([]) as notes:
        q, kv = _normal(1, 2, 1, 2, 2, 64), _normal(2, 2, 40, 2, 64)
        sequence.blocked_attention(q, kv, kv, None, CD, 2)
    return notes


def _outside_the_entry(model, params, batch):
    jax.jit(model.apply)(params, batch)
    return []


def _gspmd_executor(model, params, batch):
    from distributed_tf_serving_tpu.parallel import ShardedExecutor, make_mesh

    sv = Servable(name="m", version=1, model=model, params=params,
                  signatures=ctr_signatures(model.config.num_fields))
    out = ShardedExecutor(make_mesh(4, model_parallel=2))(sv, jax.tree.map(np.asarray, batch))
    assert np.isfinite(np.asarray(out["prediction_node"])).all()
    return []


def _gradient(model, params, batch):
    loss = lambda p: jnp.sum(model.apply(p, batch)["logits"])  # noqa: E731
    grads = jax.jit(jax.grad(loss))(params)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in jax.tree.leaves(grads))
    return []


@pytest.mark.parametrize(
    "caller, kind, noted",
    [(_served_on_the_cpu, "exaone_moe", True), (_one_query, "exaone_moe", True),
     (_outside_the_entry, "exaone_moe", False), (_gspmd_executor, "exaone_moe", False),
     (_gradient, "phi4flash", False)],  # a routed family's expert loops have no gradient themselves
    ids=lambda x: x.__name__.strip("_") if callable(x) else None)
def test_everything_but_the_served_entry_on_a_tpu_keeps_xla(caller, kind, noted, monkeypatch):
    """A CPU run, the last layer's one query, a trace outside the batcher's
    entry, a GSPMD executor and a gradient: none reaches the kernel (made to
    raise here), whatever the backend says outside the entry."""
    if caller is not _served_on_the_cpu:
        monkeypatch.setattr(sequence.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention_kernel, "attention", _refused)
    notes = caller(*_family(kind))
    assert bool(notes) == noted and all(n["kernel"] == "xla" and n["block"] == 0 for n in notes)


@pytest.mark.parametrize("kind", sorted(SMALL))
def test_a_served_entry_on_a_tpu_takes_the_kernel(kind, monkeypatch):
    """Inside the entry, on a backend that answers `tpu`, every family's
    attention at all positions reaches for the kernel (and the last layer's
    one query does not); interpreted, the step's logits are the XLA step's
    and the counters count the kernel's tiles."""
    model, params, batch = _family(kind)
    run = model.apply_stats or model.apply
    want = jax.jit(run)(params, batch)
    monkeypatch.setattr(sequence.jax, "default_backend", lambda: "tpu")
    with sequence.serving_attention([]) as notes, pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention_kernel, "attention", _refused)
        with pytest.raises(AssertionError, match="outside a one-chip"):
            jax.eval_shape(lambda p, b: run(p, b), params, batch)  # a trace of its own: none is cached for it
    assert notes[0]["kernel"] == "pallas" and notes[0]["block"] % 128 == 0

    def served(p, b):
        with interpreted([]):
            return run(p, b)

    got = jax.jit(served)(params, batch)
    logits = lambda out: (out[0] if model.step_stats else out)["logits"]  # noqa: E731
    # olmo_hybrid's small model turns the ORDER of float32 sums alone into 3e-5 on a logit, and 2e-5 held at this seed
    # by the draw: over sixteen seeds of weights and ids the two paths stand a median 3.0e-5 apart (6.7e-5 at the most,
    # eleven seeds over 2e-5) at PR 56's tree and 3.2e-5 (6.8e-5, twelve) at PR 57's, which reorders the sums of every
    # weight product on both paths; this seed read 1.6e-5 before and reads 2.7e-5. Each path's distance from the same
    # step with its products in float64 did not move (medians 9.0e-5 -> 1.0e-4 XLA's, 8.6e-5 -> 6.9e-5 the kernels':
    # the two pieces' rounding). PERF.md section 6, PR 57 has the readings; the other families read 6e-6 at the most.
    np.testing.assert_allclose(logits(got), logits(want), atol=5e-5 if kind == "olmo_hybrid" else 2e-5)
    if "attn.scores_computed" in model.step_stats:
        at = model.step_stats.index("attn.scores_computed")
        assert int(got[1][at]) > int(want[1][at]) and int(got[1][at + 1]) == int(want[1][at + 1])


# ------------------------------------------------- what the batcher stamps


def _stack():
    import dataclasses

    from distributed_tf_serving_tpu.serving.server import build_stack

    cfgs = load_config(os.path.join(CONFIGS, "pangu_moe_small.toml"))
    config = dataclasses.replace(cfgs["model"], name="M")
    cfg = dataclasses.replace(cfgs["server"], model_name="M", warmup=False)
    _registry, batcher, impl, servable, _mesh, _watcher = build_stack(cfg, model_config=config)
    return batcher, impl, servable


def _serve(payloads):
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    batcher, impl, servable = _stack()
    try:
        before = request_trace.snapshot().get("batch.attention_kernel", {}).get("count", 0)
        scores = [batcher.submit(servable, p).result(timeout=600)["prediction_node"] for p in payloads]
        counted = request_trace.snapshot().get("batch.attention_kernel", {}).get("count", 0) - before
        return np.concatenate(scores), batcher.stats, counted, impl.runtime_stats()["startup"]["attention"]
    finally:
        batcher.stop()


def test_batcher_stamps_the_attention_and_counts_its_batches(monkeypatch):
    """`startup.attention` per servable on the runtime block and the batches
    that ran the kernel, beside `batches`; the scores are the XLA entry's to
    float32 rounding."""
    fields = load_config(os.path.join(CONFIGS, "pangu_moe_small.toml"))["model"].num_fields
    rng = np.random.RandomState(3)
    payloads = [{
        "feat_ids": rng.randint(0, 1 << 40, size=(n, fields)).astype(np.int64),
        "feat_wts": rng.rand(n, fields).astype(np.float32),
    } for n in (1, 2)]
    want, stats, counted, stamp = _serve(payloads)
    assert stats.batches == 2 and stats.attention_kernel_batches == 0 and counted == 0
    assert stamp == {"M:1": {"kernel": "xla", "block": 0, "pieces": 3}}
    monkeypatch.setattr(batcher_mod, "serving_attention", interpreted)
    got, stats, counted, stamp = _serve(payloads)
    assert stats.batches == 2 and stats.attention_kernel_batches == 2 and counted == 2
    assert stamp == {"M:1": {"kernel": "pallas", "block": 128, "pieces": 3}}
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_a_ctr_servable_has_no_attention_stamp():
    from distributed_tf_serving_tpu.models import ModelConfig

    model = build_model("dcn_v2", ModelConfig(num_fields=8, vocab_size=256, embed_dim=16, mlp_dims=(16,)))
    sv = Servable(name="dcn", version=1, model=model, params=model.init(jax.random.PRNGKey(0)),
                  signatures=ctr_signatures(8))
    batcher = DynamicBatcher(buckets=(4,), max_wait_us=0).start()
    try:
        batcher.submit(sv, {"feat_ids": np.arange(16).reshape(2, 8), "feat_wts": np.ones((2, 8), np.float32)}
                       ).result(timeout=120)
        assert batcher.attentions() == {} and batcher.stats.attention_kernel_batches == 0
    finally:
        batcher.stop()
