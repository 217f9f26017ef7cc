"""Model runtime core: functional CTR models + builder registry.

The reference delegates model execution to an external SavedModel inside
tensorflow_model_server (SURVEY.md §0); here models are in-tree pure-JAX
functions. Every model follows the serving contract the reference's client
expects (DCNClient.java:33-35,98-108,162):

  inputs : feat_ids  int64  [n, num_fields]   hashed categorical ids
           feat_wts  float  [n, num_fields]   per-feature weights
  output : prediction_node  float [n]         CTR score in [0, 1]

Models are (init, apply) pairs over pytrees — no framework classes — so they
compose directly with jit/pjit/shard_map/grad. TPU-first numerics: parameters
live in float32, matmul compute runs in a configurable dtype (bfloat16 by
default for MXU throughput) with float32 accumulation via
preferred_element_type; `compute_dtype="float32"` is the AUC-parity mode
(BASELINE.md: parity to 1e-6 vs the f32 GPU baseline).
"""

from __future__ import annotations

import dataclasses
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp

Params = Any  # pytree of jax.Arrays
Batch = dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Knob set shared by the CTR model zoo and the nine sequence families
    (phi4flash, pangu_moe, exaone_moe, olmo_hybrid, mimo_v2, falcon_h1, qwen3_next, nemotron_h, sdar_moe), whose keys
    carry the names of their published config.json and whose defaults build a
    small valid model.

    Matches the reference workload point where applicable: num_fields=43
    (FIELD_NUM, DCNClient.java:25).
    """

    name: str = "DCN"
    num_fields: int = 43
    vocab_size: int = 1 << 20
    embed_dim: int = 16
    mlp_dims: tuple[int, ...] = (256, 128, 64)
    # DCN / DCN-v2
    num_cross_layers: int = 3
    cross_full_matrix: bool = False  # False => DCN-v1 rank-1 cross, True => DCN-v2
    # dlrm_dcnv2: rank r of the low-rank cross layers (the [d, d] matrix is
    # the product V_l W_l, V_l [d, r], W_l [r, d]); 0 => a full matrix.
    cross_low_rank: int = 0
    # Embedding bags (dlrm_dcnv2): ids per bag, laid end to end across the
    # num_fields wire columns in field order (columns 0..h_0-1 are bag 0,
    # ...), so sum(multi_hot_sizes) == num_fields. Empty => one id a field.
    multi_hot_sizes: tuple[int, ...] = ()
    # two-tower
    num_user_fields: int = 8
    # DLRM
    num_dense_features: int = 13
    bottom_mlp_dims: tuple[int, ...] = (64, 32, 16)
    # phi4flash (models/phi4flash.py): a candidate row is a sequence of
    # num_fields token ids; embed_dim is the hidden size and mlp_dims[0] the
    # width of every layer's gated MLP. The other keys carry the names of the
    # published config.json; the ssm_* sizes are the Mamba layer's (d_state,
    # d_conv, expand; its dt_rank is ceil(embed_dim / 16), the published rule).
    num_hidden_layers: int = 8
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    # pangu_moe (models/pangu_moe.py): a row is num_fields token ids as above;
    # embed_dim the hidden size, intermediate_size the leading dense layers'
    # gated MLP width (mlp_dims is not read), layer_norm_eps the RMSNorms'
    # epsilon. The keys carry the published config.json's names, but for the
    # two that say what THIS chip holds of a layer other chips share:
    # num_attention_heads is the heads held (of num_attention_heads_published,
    # which the start-up stamp alone reads), and the routed layer routes over
    # all n_routed_experts and computes experts_held of them, from
    # first_expert_held on.
    first_k_dense_replace: int = 1
    intermediate_size: int = 128
    q_lora_rank: int = 64
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 10000.0
    num_attention_heads_published: int = 0  # 0 => the heads held are all there are
    moe_intermediate_size: int = 64
    n_routed_experts: int = 16
    num_experts_per_tok: int = 2
    routed_scaling_factor: float = 1.0
    experts_held: int = 0  # 0 => all n_routed_experts
    first_expert_held: int = 0
    # exaone_moe (models/exaone_moe.py): a row is num_fields token ids as
    # above, and the keys shared with pangu_moe mean what they mean there
    # (embed_dim, intermediate_size, first_k_dense_replace, moe_intermediate_size,
    # num_experts_per_tok, routed_scaling_factor, experts_held, first_expert_held,
    # rope_theta, layer_norm_eps; sliding_window and num_key_value_heads as
    # phi4flash's). Under the published config.json's names: the kind of
    # every layer's attention ("sliding_attention" or "full_attention", one
    # for each of num_hidden_layers; empty => the published period, three
    # sliding layers then a full one, repeated), the width of a head (0 =>
    # embed_dim / num_attention_heads, which the published 128 is not), and
    # the ROUTER's width, of which experts_held are computed here.
    layer_types: tuple[str, ...] = ()
    head_dim: int = 0
    num_experts: int = 16
    # olmo_hybrid (models/olmo_hybrid.py): a row is num_fields token ids as
    # above; embed_dim the hidden size, intermediate_size every layer's gated
    # MLP width (mlp_dims is not read), layer_norm_eps the RMSNorms' epsilon,
    # num_attention_heads / num_key_value_heads / head_dim the full layers'.
    # layer_types takes "linear_attention" beside "full_attention" (empty =>
    # the published period, three linear layers then a full one, repeated).
    # Under the published config.json's names, the linear layers' gated delta
    # rule: its key and value heads (one key head a value head), their widths
    # (the state is [key dim, value dim] a head), the taps of the causal
    # convolution before it, and whether b reaches (0, 2) and not (0, 1).
    linear_num_key_heads: int = 2
    linear_num_value_heads: int = 2
    linear_key_head_dim: int = 8
    linear_value_head_dim: int = 16
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    # mimo_v2 (models/mimo_v2.py): a row is num_fields token ids as above, and
    # the keys shared with pangu_moe and exaone_moe mean what they mean there
    # (embed_dim, intermediate_size, moe_intermediate_size, n_routed_experts the
    # ROUTER's width, num_experts_per_tok, routed_scaling_factor, experts_held,
    # first_expert_held, layer_norm_eps, sliding_window, num_attention_heads;
    # head_dim the keys' and queries' width, v_head_dim the values';
    # num_key_value_heads and rope_theta are the FULL layers'). Under the
    # published config.json's names: every layer's attention kind (0 full, 1
    # window; empty => the published pattern, layer 0 and every sixth from
    # layer 5 on full), every layer's FFN (0 dense, 1 routed; empty => layer 0
    # dense), the WINDOW layers' key-value heads (0 => num_key_value_heads)
    # and rotary base, the share of a head's dims the rotary turns (the first
    # int(head_dim * factor)), what the values are multiplied by, and which
    # kind of layer's softmax holds a learned sink logit a head.
    hybrid_layer_pattern: tuple[int, ...] = ()
    moe_layer_freq: tuple[int, ...] = ()
    swa_num_key_value_heads: int = 0
    swa_rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    attention_value_scale: float = 1.0
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    # falcon_h1 (models/falcon_h1.py): a row is num_fields token ids as above;
    # embed_dim the hidden size, intermediate_size every layer's gated MLP
    # width (mlp_dims is not read), layer_norm_eps the RMSNorms' epsilon,
    # num_attention_heads / num_key_value_heads / head_dim / rope_theta the
    # attention's (rotary on all of a head's dims). EVERY layer holds a
    # Mamba-2 mixer beside its attention, both on one normed input. Under the
    # published config.json's names, the mixer's sizes: its inner width
    # (mamba_n_heads heads of mamba_d_head), the state's width a head (the
    # state is [mamba_d_head, mamba_d_state] a head), the groups that share B
    # and C, the taps of the causal convolution and the positions a chunk of
    # the SSD's matrix form; and the model's multipliers, constants on the
    # path of every product: the embedding's, the attention's input, output
    # and keys, the mixer's input and output, one a slice of the mixer's
    # input projection (z, x, B, C, dt in that order) and the MLP's gate and
    # output.
    mamba_d_ssm: int = 64
    mamba_n_heads: int = 4
    mamba_d_head: int = 16
    mamba_d_state: int = 32
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: tuple[float, ...] = (1.0, 1.0)
    # qwen3_next (models/qwen3_next.py): a row is num_fields token ids as
    # above, and the shared keys mean what they mean elsewhere: embed_dim the
    # hidden size, num_experts the ROUTER's width (as exaone_moe reads it),
    # num_experts_per_tok, moe_intermediate_size, experts_held,
    # first_expert_held, the linear_* keys the gated delta rule's (here with
    # fewer key heads than value heads, whole groups of value heads a key
    # head, and linear_allow_neg_eigval false), partial_rotary_factor as
    # mimo_v2 reads it, head_dim, rope_theta, num_attention_heads /
    # num_key_value_heads the full layers', layer_norm_eps. Every layer holds
    # the routed block; the router is a softmax over all num_experts (the
    # family's own: the published config has no key for it). Under the
    # published config.json's names: every how-manieth layer is a full
    # (gated) attention layer, the others gated-delta-rule layers
    # (layer_types, where given, says it layer by layer), the width of the
    # one shared expert, whose output a gate a token scales, and whether the
    # chosen experts' probabilities are normalised to sum 1.
    full_attention_interval: int = 4
    shared_expert_intermediate_size: int = 64
    norm_topk_prob: bool = True
    # nemotron_h (models/nemotron_h.py): a row is num_fields token ids as
    # above, and the shared keys mean what they mean elsewhere: embed_dim the
    # hidden size, num_attention_heads / num_key_value_heads / head_dim the
    # attention layers' (no rotary turn: rope_theta is not read), the mamba_*
    # keys the Mamba-2 layers' as falcon_h1 reads them, n_routed_experts the
    # ROUTER's width (as pangu_moe reads it), num_experts_per_tok,
    # routed_scaling_factor, norm_topk_prob, experts_held, first_expert_held,
    # moe_intermediate_size an expert's width, layer_norm_eps. Every layer is
    # ONE mixer. Under the published config.json's names: the kind of every
    # layer, a letter each (`M` Mamba-2, `*` attention, `E` the routed block;
    # the layers run are the first num_hidden_layers), the width of the latent
    # the routed experts live in, between two projections every token meets,
    # and the width of the one shared expert, which reads the full width.
    # Both kinds of expert are ungated, relu(x U)^2 D: the family's
    # (mlp_hidden_act relu2 as published).
    hybrid_override_pattern: str = "MEMEMEM*EMEM"  # the published pattern's first twelve
    moe_latent_size: int = 32
    moe_shared_expert_intermediate_size: int = 64
    # sdar_moe (models/sdar_moe.py): a row is num_fields token ids as above,
    # and the shared keys mean what they mean elsewhere: embed_dim the hidden
    # size, num_attention_heads / num_key_value_heads / head_dim / rope_theta
    # the attention's (rotary on all of a head's dims, a learned RMS weight a
    # query and a key head), num_experts the ROUTER's width (as qwen3_next
    # reads it; a softmax, the family's own), num_experts_per_tok,
    # norm_topk_prob, moe_intermediate_size, experts_held, first_expert_held,
    # layer_norm_eps. Every layer is routed and there is no shared expert.
    # The one key of its own: the positions of a block of the mask. A
    # position sees its whole block and every block before it (block
    # diffusion's one denoising pass). It divides num_fields, and nothing
    # else chooses a mask. 1, the default (these defaults build a valid
    # model at any num_fields), is the causal mask; the published family's
    # is 4, and every configuration of it says so.
    block_length: int = 1
    # numerics
    compute_dtype: str = "bfloat16"  # "float32" for AUC-parity mode
    param_dtype: str = "float32"

    @property
    def cdtype(self) -> jnp.dtype:
        return jnp.dtype(self.compute_dtype)

    @property
    def pdtype(self) -> jnp.dtype:
        return jnp.dtype(self.param_dtype)


@dataclasses.dataclass(frozen=True)
class Model:
    """A functional model: params = init(rng); outputs = apply(params, batch).

    wts_in_compute_dtype: True when the model consumes feat_wts exclusively
    after casting to compute_dtype (via embeddings.field_embed) — the
    precondition for the batcher's lossless bf16 weight-transfer compression.
    Models with a float32 sparse-linear term over the raw weights
    (wide_deep, deepfm) must leave it False.

    score_output: the name of the per-candidate score vector in the apply()
    output dict — the one tensor the serving path ultimately ranks on.
    The batcher's output-compaction pipeline keys on it: wire-dtype
    downcast applies to every f32 output, but top-k compaction (retrieval-
    style servables, e.g. two_tower scoring a large candidate set) returns
    only this vector's top-k (score, index) pairs over the D2H link.
    """

    config: ModelConfig
    init: Callable[[jax.Array], Params]
    apply: Callable[[Params, Batch], dict[str, jax.Array]]
    wts_in_compute_dtype: bool = True
    score_output: str = "prediction_node"
    # False for graph-executor models (interop/graph_exec.py): the imported
    # graph consumes RAW int64 ids (its own hashing/mod/lookup semantics),
    # so the batcher must not vocab-fold them on host.
    folds_ids_on_host: bool = True
    # True when the model's graph carries int64/float64 tensors that JAX's
    # default 32-bit canonicalization would silently corrupt; the batcher
    # traces AND calls such models inside jax.enable_x64().
    needs_x64: bool = False
    # Zoo family this model was built as (build_model stamps it); "" for
    # directly-constructed models (imported graphs, tests). The mesh
    # serving mode keys its named partition rules on it
    # (parallel/embedding_sharding.MODEL_PARTITION_RULES) — unknown kinds
    # fall back to the generic path-name layout.
    kind: str = ""
    # True when the signature carries `dense_features` [n, num_dense_features]
    # beside the id/weight pair (the DLRM families).
    takes_dense: bool = False
    # The kind of every layer of a sequence family (phi4flash, pangu_moe, exaone_moe,
    # olmo_hybrid, mimo_v2, falcon_h1, qwen3_next, nemotron_h, sdar_moe), whose rows are num_fields TOKENS;
    # empty for the CTR families.
    layer_plan: tuple[str, ...] = ()
    # What a family with a routed layer holds of it, as (name, number) pairs:
    # published, held, first, top_k, heads_published, heads_held,
    # chips_sharing_layer (pangu_moe, exaone_moe, mimo_v2, qwen3_next, nemotron_h, sdar_moe); empty
    # for every other family.
    expert_plan: tuple[tuple[str, int], ...] = ()
    # For a family whose mixer differs by layer, as (name, value) pairs a
    # layer: an attention layer's kind, window, block of queries and keys a
    # block (exaone_moe, olmo_hybrid, mimo_v2, which adds what else differs by
    # kind: kv_heads, rotary_dims, theta, sink); a linear layer's kind, chunk,
    # state hand-overs a row and bytes of a row's state (olmo_hybrid; qwen3_next,
    # which adds the rule's key and value heads, and to a full layer its
    # kv_heads, rotary_dims, theta and output gate);
    # falcon_h1's layers, which hold both, state the attention's entry and
    # beside it `ssd`, the SSM's (kind, chunk, hand-overs and state bytes a
    # row); nemotron_h's layers, one mixer each, state a Mamba-2 layer's entry
    # as falcon_h1's `ssd`, an attention layer's as a full one's (rotary_dims
    # 0) and a routed layer's kind, latent width and experts' form; sdar_moe's
    # layers, all alike, state the block mask's `span` beside a full layer's
    # entries; empty for every other family.
    attention_plan: tuple[tuple[tuple[str, object], ...], ...] = ()
    # For a family whose step counts what it did on the device (pangu_moe's,
    # exaone_moe's, mimo_v2's, qwen3_next's, nemotron_h's and sdar_moe's routing, exaone_moe's, olmo_hybrid's,
    # mimo_v2's, qwen3_next's and sdar_moe's score tiles (sdar_moe's also the pairs its mask keeps AHEAD of the query), olmo_hybrid's, qwen3_next's and falcon_h1's state hand-overs,
    # falcon_h1's and nemotron_h's score tiles and state hand-overs, mimo_v2's sinks): `apply_stats(params, batch) -> (apply's outputs, int32
    # [len(step_stats)])`, the counters named by `step_stats` in order. The
    # batcher decides on it when it BUILDS the servable's entry: the counters
    # then ride back beside the scores and are recorded as phases by count.
    # None, as for every other family, and nothing of that exists.
    apply_stats: Callable[[Params, Batch], tuple[dict[str, jax.Array], jax.Array]] | None = None
    step_stats: tuple[str, ...] = ()
    # XLA options the family's served step compiles with where the backend is
    # a TPU, as (name, value) pairs (`step_jit`); empty for every family but
    # olmo_hybrid, whose executables are then the ones `jax.jit` alone makes.
    tpu_compiler_options: tuple[tuple[str, object], ...] = ()


def step_jit(model: Model, run: Callable, platform: str | None = None) -> Callable:
    """`jax.jit(run)` for a served step of `model`: with the family's
    `tpu_compiler_options` where the backend (`platform`, else the process's
    default) is a TPU. No other backend knows those options by name, and a
    family without any compiles as `jax.jit(run)` does, to the same cache key."""
    if model.tpu_compiler_options and (platform or jax.default_backend()) == "tpu":
        return jax.jit(run, compiler_options=dict(model.tpu_compiler_options))
    return jax.jit(run)


# ---------------------------------------------------------------------------
# Shared building blocks
# ---------------------------------------------------------------------------


def dense_init(rng: jax.Array, in_dim: int, out_dim: int, dtype) -> dict[str, jax.Array]:
    """He-style init for a dense layer."""
    wkey, _ = jax.random.split(rng)
    scale = jnp.sqrt(2.0 / in_dim).astype(dtype)
    return {
        "w": jax.random.normal(wkey, (in_dim, out_dim), dtype) * scale,
        "b": jnp.zeros((out_dim,), dtype),
    }


def dense_apply(p: dict[str, jax.Array], x: jax.Array, compute_dtype) -> jax.Array:
    """x @ w + b in compute_dtype with f32 accumulation on the MXU."""
    y = jax.lax.dot_general(
        x.astype(compute_dtype),
        p["w"].astype(compute_dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return y + p["b"].astype(jnp.float32)


def mlp_init(rng: jax.Array, in_dim: int, dims: tuple[int, ...], dtype) -> list:
    layers = []
    for out_dim in dims:
        rng, sub = jax.random.split(rng)
        layers.append(dense_init(sub, in_dim, out_dim, dtype))
        in_dim = out_dim
    return layers


def mlp_apply(layers: list, x: jax.Array, compute_dtype, final_relu: bool = True) -> jax.Array:
    with jax.named_scope("mlp"):
        for i, p in enumerate(layers):
            x = dense_apply(p, x, compute_dtype)
            if final_relu or i + 1 < len(layers):
                x = jax.nn.relu(x)
    return x


# ---------------------------------------------------------------------------
# Builder registry
# ---------------------------------------------------------------------------

_BUILDERS: dict[str, Callable[[ModelConfig], Model]] = {}


def register_model(kind: str):
    def deco(fn: Callable[[ModelConfig], Model]):
        _BUILDERS[kind] = fn
        return fn

    return deco


def build_model(kind: str, config: ModelConfig | None = None, **overrides) -> Model:
    """Instantiate a model family by kind: dcn, dcn_v2, wide_deep, deepfm,
    two_tower, dlrm, dlrm_dcnv2, phi4flash, pangu_moe, exaone_moe, olmo_hybrid, mimo_v2,
    falcon_h1, qwen3_next, nemotron_h, sdar_moe."""
    if kind not in _BUILDERS:
        raise KeyError(f"unknown model kind {kind!r}; have {sorted(_BUILDERS)}")
    if config is None:
        config = ModelConfig(**overrides)
    elif overrides:
        config = dataclasses.replace(config, **overrides)
    model = _BUILDERS[kind](config)
    if not model.kind:
        # Stamp the family so downstream layout policy (mesh partition
        # rules) can key on it without re-plumbing the kind string.
        model = dataclasses.replace(model, kind=kind)
    return model


def model_kinds() -> list[str]:
    return sorted(_BUILDERS)
