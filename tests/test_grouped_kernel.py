"""The held experts' part of a routed layer as one pass of Pallas kernels
(ops/grouped_kernel.py), run here in interpret mode: `routed.held_experts`'
loops on the same operands to float32 rounding and the counters exactly, under
every routing the buffers are sized for; the three families' small steps
through the served entry; the planted faults told apart through the kernels;
who takes them, and what the batcher stamps and counts. Times come from the
chip (PERF.md section 6, PR 51); the compile for a v5e is in
test_tpu_compile.py."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import build_model, pangu_moe, routed, sequence
from distributed_tf_serving_tpu.ops import grouped_kernel
from distributed_tf_serving_tpu.serving import batcher as batcher_mod
from distributed_tf_serving_tpu.utils.config import load_config

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")
SMALL = {"exaone_moe": "exaone_moe_small", "pangu_moe": "pangu_moe_small", "mimo_v2": "mimo_v2_small"}
TILE = grouped_kernel.TILE
interpreted = functools.partial(sequence.serving_attention, interpret=True)

# name -> (tokens, held, first, k, the experts routed over, routing, live rows left out, compute dtype, pieces
#          [, the experts' form where not the gated one [, the width of a row where not 256
#          [, the width of an expert where not 128]]])
ROUTINGS = {
    "uniform": (300, 3, 0, 2, 12, "uniform", False, jnp.bfloat16, 3),
    "an expert no token chose": (300, 3, 0, 2, 12, "one_empty", False, jnp.bfloat16, 3),
    "no token here": (64, 3, 0, 2, 12, "none_here", False, jnp.bfloat16, 3),
    "every token on one expert": (300, 3, 0, 2, 12, "all_to_one", False, jnp.bfloat16, 3),
    "every token on all min(k, held) held experts": (260, 3, 0, 3, 12, "all_held", False, jnp.bfloat16, 3),
    "k under held, every choice held": (140, 4, 0, 2, 12, "all_held", False, jnp.bfloat16, 3),
    "a token count that is no whole tile": (37, 3, 0, 2, 12, "uniform", False, jnp.bfloat16, 3),
    "fewer tokens than a tile holds": (5, 3, 0, 2, 12, "uniform", False, jnp.bfloat16, 3),
    "live rows left out": (300, 3, 0, 2, 12, "uniform", True, jnp.bfloat16, 3),
    "first != 0": (300, 3, 5, 2, 12, "uniform", False, jnp.bfloat16, 3),
    "one piece": (200, 3, 5, 2, 12, "uniform", False, jnp.bfloat16, 1),
    "two pieces": (200, 3, 5, 2, 12, "uniform", False, jnp.bfloat16, 2),
    "float32 compute dtype": (200, 3, 5, 2, 12, "uniform", True, jnp.float32, 3),
    # PR 60 (nemotron_h): experts of two matrices and a squared relu, the first kernel against ONE weight; rows of a
    # latent's width (1,024: eight lane chunks, two steps of the contraction), not the residual's; k over twice 8
    "ungated": (300, 3, 0, 2, 12, "uniform", False, jnp.bfloat16, 3, "relu2"),
    "ungated, an expert no token chose, live rows left out": (300, 3, 0, 2, 12, "one_empty", True, jnp.bfloat16, 3, "relu2"),
    "ungated, every token on all min(k, held) held experts": (260, 3, 0, 3, 12, "all_held", False, jnp.bfloat16, 3, "relu2"),
    "ungated, float32 compute dtype": (200, 3, 5, 2, 12, "uniform", True, jnp.float32, 3, "relu2"),
    "ungated, 1,024-wide rows, 16 held at top-22 of 128": (90, 16, 0, 22, 128, "uniform", False, jnp.bfloat16, 3, "relu2", 1024),
    "gated, 1,024-wide rows": (90, 4, 4, 3, 16, "uniform", False, jnp.bfloat16, 3, "gated_silu", 1024),
    # PR 64 (sdar_moe): the layer held WHOLE (held == experts: every one of a token's 8 choices is here) and experts
    # three lane tiles wide: `_block(384, N_BLOCK)` is 384, a block that is no power of two (the cell's 768 is six)
    "held == experts at top-8, experts 384 wide": (150, 16, 0, 8, 16, "all_held", False, jnp.bfloat16, 3, "gated_silu", 256, 384),
}


def _operands(tokens, held, first, k, experts, routing, dead, cd, form="gated_silu", hidden=256, width=128, seed=0):
    rng = np.random.default_rng(seed)
    p = {"gate": rng.standard_normal((held, hidden, width)) * 0.1, "up": rng.standard_normal((held, hidden, width)) * 0.1,
         "down": rng.standard_normal((held, width, hidden)) * 0.1}
    p = {name: jnp.asarray(w, cd) for name, w in p.items() if form == "gated_silu" or name != "gate"}
    x = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    if routing == "uniform":
        chosen = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])
    elif routing == "one_empty":  # never the held expert first + 1
        others = [e for e in range(experts) if e != first + 1]
        chosen = np.stack([rng.permutation(others)[:k] for _ in range(tokens)])
    elif routing == "none_here":
        chosen = np.tile(np.arange(first + held, first + held + k), (tokens, 1))
    elif routing == "all_to_one":
        chosen = np.tile([first + 1] + list(range(first + held, first + held + k - 1)), (tokens, 1))
    else:  # every choice a held expert
        chosen = np.stack([first + rng.permutation(held)[:k] for _ in range(tokens)])
    gates = jnp.asarray(rng.random((tokens, k)) + 0.1, jnp.float32)
    live = jnp.asarray(rng.random(tokens) > 0.3) if dead else None
    return p, x, jnp.asarray(chosen.astype(np.int32)), gates, live


@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_the_pass_is_the_loops_on_the_same_operands(case):
    """Values to float32 rounding (the same pieces against the same weights,
    added in another order), the tokens each held expert took exactly, and the
    rows computed: the tiles that hold a token, padding and all."""
    tokens, held, first, k, experts, routing, dead, cd, count, *form = ROUTINGS[case]
    p, x, chosen, gates, live = _operands(tokens, held, first, k, experts, routing, dead, cd, *form)
    assert routed.expert_form(p) == (form[0] if form else "gated_silu")
    run = lambda: routed.held_experts(p, x, chosen, gates, first, cd, live=live, count=count)  # noqa: E731
    want, took, _ = jax.jit(run)()

    def served():
        with interpreted([]):
            return run()

    got, took_here, computed = jax.jit(served)()
    assert took_here.tolist() == took.tolist()
    mask = (np.asarray(chosen)[:, :, None] == first + np.arange(held)).any(1) & (True if live is None else np.asarray(live)[:, None])
    assert took.tolist() == mask.sum(0).tolist()
    assert int(computed) == max(sum(-(-int(n) // TILE) * TILE for n in took), TILE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6, atol=2e-6 * float(jnp.max(jnp.abs(want)) + 1))
    if routing == "all_held":  # the worst case the buffers are sized for: nothing is dropped
        assert int(took.sum()) == tokens * min(k, held)
    if len(form) == 3:
        assert grouped_kernel._block(form[2], grouped_kernel.N_BLOCK) == form[2] == 3 * grouped_kernel.LANES
    if live is not None:  # a row left out is zero, to the bit
        assert not np.asarray(got)[~np.asarray(live)].any()


def test_the_planted_precision_is_told_apart_through_the_kernels():
    """One piece where three are stated moves the result by bfloat16's
    rounding, a thousand times what separates the kernels from the loops."""
    p, x, chosen, gates, _ = _operands(200, 3, 0, 2, 12, "uniform", False, jnp.bfloat16)

    def served(count):
        with interpreted([]):
            return routed.held_experts(p, x, chosen, gates, 0, jnp.bfloat16, count=count)[0]

    three, one = jax.jit(served, static_argnums=0)(3), jax.jit(served, static_argnums=0)(1)
    loops = jax.jit(lambda: routed.held_experts(p, x, chosen, gates, 0, jnp.bfloat16, count=3)[0])()
    assert float(jnp.max(jnp.abs(three - loops))) * 300 < float(jnp.max(jnp.abs(one - loops)))


def test_the_layout_walks_the_tiles_that_hold_a_token_in_the_experts_order():
    """One sort lays the pairs out: loads 130, 0, 5, 256 of four held experts
    (first = 2) are five tiles of 128, each expert's tokens in row order."""
    loads, first, tokens = [130, 0, 5, 256], 2, 300
    chosen = np.full((tokens, 4), 9, np.int32)  # expert 9 is not held
    for e, n in enumerate(loads):
        chosen[np.random.default_rng(e).permutation(tokens)[:n], e] = first + e
    orders, expert, rows, live = routed.lay_out(jnp.asarray(chosen), first, 4, 128)
    assert orders.shape == (routed.layout_tiles(tokens, 4, 4, 128), 128)
    assert [int(rows[np.asarray(expert) == e].sum()) for e in range(4)] == loads
    assert int(live) == 5 and expert.tolist()[:5] == [0, 0, 2, 3, 3]
    assert rows.tolist() == [128, 2, 5, 128, 128] + [0] * (orders.shape[0] - 5)
    for i, e in enumerate(expert.tolist()[:5]):
        took = orders[i, :int(rows[i])].tolist()
        assert took == sorted(took) and all(first + e in chosen[t] for t in took)
        assert (np.asarray(orders[i, int(rows[i]):]) == tokens).all()
    nobody = routed.lay_out(jnp.full((64, 2), 9, jnp.int32), first, 4, 128)
    assert int(nobody[3]) == 0 and not nobody[2].any() and (np.asarray(nobody[0]) == 64).all()


# ------------------------------------------------ the three families' steps


def _family(kind):
    config = load_config(os.path.join(CONFIGS, SMALL[kind] + ".toml"))["model"]
    model = build_model(kind, config)
    rng = np.random.default_rng(1)
    batch = {
        "feat_ids": jnp.asarray(rng.integers(0, config.vocab_size, (3, config.num_fields)), jnp.int32),
        "feat_wts": jnp.asarray(rng.uniform(0.5, 1.5, (3, config.num_fields)), jnp.float32).at[2].set(0.0),
    }
    # The matrices scaled so that the router's logits spread as they do at the
    # published widths (every family's own tests do the same): as drawn, the
    # small configurations send nearly every token to the same few experts.
    gain = (6144 / config.embed_dim) ** 0.5

    def scale(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        return leaf if name == "embedding" or leaf.ndim < 2 else leaf * gain

    return model, jax.tree_util.tree_map_with_path(scale, model.init(jax.random.PRNGKey(0))), batch


def _served_step(model):
    def served(p, b):
        with interpreted([], grouped=(notes := [])):
            out = model.apply_stats(p, b)
        # a note a token count: the layers at all positions, the last layer's one position a row
        assert notes and all(
            dict(n, held=0, rows=0, width=0) == {"kernel": "pallas", "tile": TILE, "pieces": 3, "held": 0, "rows": 0,
                                                 "form": "gated_silu", "width": 0}
            and 0 < n["held"] <= model.config.experts_held and n["rows"] % TILE == 0 for n in notes)  # a planted fault drops one
        return out

    return jax.jit(served)


@pytest.mark.parametrize("kind", sorted(SMALL))
def test_a_familys_step_through_the_served_entry_is_its_xla_step(kind):
    """The small configuration's whole step (a padded row among its rows)
    inside the entry, the kernels interpreted: the logits to float32 rounding,
    and every routing counter the XLA step's exactly but the rows computed,
    which follow the tile where they followed the block."""
    model, params, batch = _family(kind)
    want, stats = jax.jit(model.apply_stats)(params, batch)
    got, stats_here = _served_step(model)(params, batch)
    np.testing.assert_allclose(np.asarray(got["logits"]), np.asarray(want["logits"]), atol=2e-5)
    named, named_here = dict(zip(model.step_stats, stats.tolist())), dict(zip(model.step_stats, stats_here.tolist()))
    for name in ("moe.tokens", "moe.assignments_here", "moe.busiest_expert_tokens"):
        assert named_here[name] == named[name] > 0
    assert named["moe.assignments_here"] <= named_here["moe.rows_computed"] <= named["moe.rows_computed"]
    assert named_here["moe.rows_computed"] % TILE == 0 and named["moe.rows_computed"] % routed.EXPERT_BLOCK == 0
    assert float(got["logits"][2]) == 0.0  # the padded row, through the kernels too


def _an_expert_dropped(kind, monkeypatch):
    """The last held expert's part left out of the routed sum, planted under
    the name the family's own tests and precision readings replace."""
    module = pangu_moe if kind == "pangu_moe" else routed
    whole = module.held_experts
    monkeypatch.setattr(module, "held_experts", lambda p, *a, **kw: whole({n: w[:-1] for n, w in p.items()}, *a, **kw))


def _one_choice_fewer(kind, monkeypatch):
    """Top k - 1 where the configuration states k."""
    module = pangu_moe if kind == "pangu_moe" else routed
    route = module.route
    monkeypatch.setattr(module, "route", lambda router, x, k, scaling: route(router, x, k - 1, scaling))


@pytest.mark.parametrize("fault", [_an_expert_dropped, _one_choice_fewer], ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("kind", sorted(SMALL))
def test_a_planted_fault_runs_through_the_kernels_and_still_shows(kind, fault, monkeypatch):
    """The fault is planted where the XLA step's tests plant it, the step is
    traced inside the entry, and the kernels run what was planted: the logits
    move, and by what they move on the XLA path."""
    model, params, batch = _family(kind)
    sound = _served_step(model)(params, batch)[0]["logits"]
    fault(kind, monkeypatch)
    faulty_xla = jax.jit(model.apply_stats)(params, batch)[0]["logits"]
    faulty = _served_step(model)(params, batch)[0]["logits"]
    moved = float(jnp.max(jnp.abs(faulty - sound)))
    assert moved > 1e-3
    np.testing.assert_allclose(np.asarray(faulty), np.asarray(faulty_xla), atol=2e-5)


# ------------------------------------------------------------ who takes it


def _refused(*args, **kwargs):
    raise AssertionError("the grouped kernels outside a one-chip served entry")


def _served_on_the_cpu(model, params, batch):
    with sequence.serving_attention([], grouped=(notes := [])):
        jax.jit(model.apply)(params, batch)
    return notes


def _outside_the_entry(model, params, batch):
    jax.jit(model.apply)(params, batch)
    return []


def _gspmd_executor(model, params, batch):
    from distributed_tf_serving_tpu.models.registry import Servable, ctr_signatures
    from distributed_tf_serving_tpu.parallel import ShardedExecutor, make_mesh

    sv = Servable(name="m", version=1, model=model, params=params,
                  signatures=ctr_signatures(model.config.num_fields))
    batch = {k: np.asarray(v)[:2] for k, v in batch.items()}
    out = ShardedExecutor(make_mesh(4, model_parallel=2))(sv, batch)
    assert np.isfinite(np.asarray(out["prediction_node"])).all()
    return []


@pytest.mark.parametrize(
    "caller, noted", [(_served_on_the_cpu, True), (_outside_the_entry, False), (_gspmd_executor, False)],
    ids=lambda x: x.__name__.strip("_") if callable(x) else None)
def test_everything_but_the_served_entry_on_a_tpu_keeps_the_loops(caller, noted, monkeypatch):
    """A CPU run, a trace outside the batcher's entry and a GSPMD executor:
    none reaches the kernels (made to raise here), whatever the backend says
    outside the entry."""
    if caller is not _served_on_the_cpu:
        monkeypatch.setattr(sequence.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(grouped_kernel, "grouped_experts", _refused)
    notes = caller(*_family("mimo_v2"))
    assert bool(notes) == noted and all(
        {k: n[k] for k in ("kernel", "tile", "pieces")} == {"kernel": "xla", "tile": routed.EXPERT_BLOCK, "pieces": 3}
        for n in notes)


def test_a_served_entry_on_a_tpu_takes_the_kernels(monkeypatch):
    """Inside the entry, on a backend that answers `tpu`, a routed layer
    reaches for the kernels, whatever its token count (the rule takes none:
    the last layer's few tokens run them too), and notes its choice once."""
    monkeypatch.setattr(sequence.jax, "default_backend", lambda: "tpu")
    with sequence.serving_attention([], grouped=(notes := [])):
        assert routed.takes_kernel(3) and routed.takes_kernel(3)
        assert routed.takes_kernel(3, 16384, 10, 128, "gated_silu", 2048)
        assert routed.takes_kernel(3, 16384, 22, 64, "relu2", 1024)
    assert notes == [{"kernel": "pallas", "tile": TILE, "pieces": 3},
                     # 128 of 512 experts at top-10 over 16,384 tokens: 1,408 tiles where `[held, T]` is 16,384
                     {"kernel": "pallas", "tile": TILE, "pieces": 3, "held": 128, "rows": 1408 * TILE,
                      "form": "gated_silu", "width": 2048},
                     # 64 of 512 at top-22 (PR 60): 2,880 tiles, of which the even share fills an eighth
                     {"kernel": "pallas", "tile": TILE, "pieces": 3, "held": 64, "rows": 2880 * TILE,
                      "form": "relu2", "width": 1024}]
    assert not routed.takes_kernel(3)  # outside it


# ------------------------------------------------- what the batcher stamps


def _serve(payloads):
    import dataclasses

    from distributed_tf_serving_tpu.serving.server import build_stack
    from distributed_tf_serving_tpu.utils.tracing import request_trace

    cfgs = load_config(os.path.join(CONFIGS, "mimo_v2_small.toml"))
    config = dataclasses.replace(cfgs["model"], name="M")
    cfg = dataclasses.replace(cfgs["server"], model_name="M", warmup=False)
    _registry, batcher, impl, servable, _mesh, _watcher = build_stack(cfg, model_config=config)
    try:
        count = lambda: request_trace.snapshot().get("batch.grouped_kernel", {}).get("count", 0)  # noqa: E731
        before = count()
        scores = [batcher.submit(servable, p).result(timeout=600)["prediction_node"] for p in payloads]
        return np.concatenate(scores), batcher.stats, count() - before, impl.runtime_stats()["startup"]["grouped"]
    finally:
        batcher.stop()


def test_batcher_stamps_the_grouped_product_and_counts_its_batches(monkeypatch):
    """`startup.grouped` per servable on the runtime block and the batches
    that ran the kernels, beside `batches`; the scores are the XLA entry's to
    float32 rounding."""
    fields = load_config(os.path.join(CONFIGS, "mimo_v2_small.toml"))["model"].num_fields
    rng = np.random.RandomState(3)
    payloads = [{
        "feat_ids": rng.randint(0, 1 << 40, size=(n, fields)).astype(np.int64),
        "feat_wts": rng.rand(n, fields).astype(np.float32),
    } for n in (1, 2)]
    want, stats, counted, stamp = _serve(payloads)
    assert stats.batches == 2 and stats.grouped_kernel_batches == 0 and counted == 0
    held = load_config(os.path.join(CONFIGS, "mimo_v2_small.toml"))["model"].experts_held
    rows = stamp["M:1"].pop("rows")  # the widest layout traced: the top rung's layers at all positions
    width = load_config(os.path.join(CONFIGS, "mimo_v2_small.toml"))["model"].embed_dim
    assert stamp == {"M:1": {"kernel": "xla", "tile": routed.EXPERT_BLOCK, "pieces": 3, "held": held,
                             "form": "gated_silu", "width": width}}
    assert rows >= held * routed.EXPERT_BLOCK and rows % routed.EXPERT_BLOCK == 0
    monkeypatch.setattr(batcher_mod, "serving_attention", interpreted)
    got, stats, counted, stamp = _serve(payloads)
    assert stats.batches == 2 and stats.grouped_kernel_batches == 2 and counted == 2
    assert stamp["M:1"].pop("rows") % TILE == 0
    assert stamp == {"M:1": {"kernel": "pallas", "tile": TILE, "pieces": 3, "held": held,
                             "form": "gated_silu", "width": width}}
    np.testing.assert_allclose(got, want, atol=1e-6)
