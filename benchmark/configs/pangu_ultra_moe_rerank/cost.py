"""What one step of this configuration needs at the least, from its shapes
alone: plain arithmetic, no jax, kept with the benchmark.

The configuration is ONE chip's share of a layer (32 of the 128 heads, 8 of
the 256 routed experts; the dense layer's MLP whole), and the counts are of
that share. The served step computes every layer but the last at all L
positions, the last layer's keys and values at all positions, and its
queries, attention output and FFN at the LAST position only (the score reads
the last position; exact, see the configuration's file): `step_cost` counts
that. A product counts ONCE, 2 operations a weight and position, however many
passes of the MXU the stated precision takes, as `phi4_mini_flash_rerank`'s
does and for its reason. The held experts' work depends on the routing; the
step's count takes the EVEN share (each token's top_k choices fall on a held
expert with probability held / routed: 0.25 expert-passes a token here),
which is what seeded random weights and uniform ids give within a percent
(`held_assignments_per_token.bulk` reads what it was). `expert_cost` and
`attention_cost` are the two new blocks' own counts; no metric reads them yet
(a device time by named scope is not in the trace's breakdown)."""


def _sizes(config):
    heads = config["num_attention_heads"]
    nope, rope, v = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    hidden, q_rank, kv_rank = config["embed_dim"], config["q_lora_rank"], config["kv_lora_rank"]
    return {
        "H": hidden, "I": config["intermediate_size"], "L": config["num_fields"],
        "N": config["num_hidden_layers"], "dense": config["first_k_dense_replace"],
        "F": config["moe_intermediate_size"], "E": config["n_routed_experts"],
        "held": config["experts_held"] or config["n_routed_experts"], "k": config["num_experts_per_tok"],
        # weights of the latent attention held here: the two key/value matrices, and all five
        "kv": hidden * (kv_rank + rope) + kv_rank * heads * (nope + v),
        "attn": (hidden * q_rank + q_rank * heads * (nope + rope) + hidden * (kv_rank + rope)
                 + kv_rank * heads * (nope + v) + heads * v * hidden),
        # operations a (query, visible key) pair: q k' over nope + rope, p v over v
        "pair": 2 * heads * (nope + rope + v),
    }


def attention_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE layer's latent
    attention over `rows` rows at all positions: the five products, and the
    causal scores and their product with the values. Bytes: the weights at 2
    bytes, the normed input in and the output out in float32."""
    s = _sizes(config)
    length = s["L"]
    flops = rows * (length * 2 * s["attn"] + length * (length + 1) // 2 * s["pair"])
    return flops, 2 * s["attn"] + rows * length * 2 * 4 * s["H"]


def expert_cost(config, assignments):
    """(floating-point operations, bytes moved) of the grouped product of ONE
    routed layer over `assignments` (token, held expert) pairs: three
    products of the expert's width a pair. Bytes: every held expert's
    weights once at 2 bytes, a row gathered in and a row added back out in
    float32 a pair."""
    s = _sizes(config)
    weights = 3 * s["H"] * s["F"]
    return assignments * 2 * weights, 2 * s["held"] * weights + assignments * 2 * 4 * s["H"]


def step_cost(config, rows, batches):
    """(floating-point operations, bytes moved) that scoring `rows` rows in
    `batches` batches needs, at the even share of the routing. Bytes: every
    weight held once a batch at 2 bytes, a token's embedding row (2 bytes a
    value), its id (3 bytes) and weight (4), a score out (4)."""
    s = _sizes(config)
    H, I, L, N, F = (s[k] for k in ("H", "I", "L", "N", "F"))
    dense_ffn = 3 * H * I
    routed_ffn = H * s["E"] + 3 * H * F + s["k"] * s["held"] / s["E"] * 3 * H * F  # router, shared, held share
    ffn = [dense_ffn if i < s["dense"] else routed_ffn for i in range(N)]
    weights = N * s["attn"] + sum(
        dense_ffn if i < s["dense"] else H * s["E"] + (1 + s["held"]) * 3 * H * F for i in range(N))
    every_position = 2 * ((N - 1) * s["attn"] + sum(ffn[:-1]) + s["kv"])
    last_position = 2 * (s["attn"] - s["kv"] + ffn[-1]) + L * s["pair"] + 2 * H
    flops_row = L * every_position + (N - 1) * (L * (L + 1) // 2) * s["pair"] + last_position
    bytes_row = L * (2 * H + 3 + 4) + 4
    return rows * flops_row, rows * bytes_row + batches * 2 * weights
