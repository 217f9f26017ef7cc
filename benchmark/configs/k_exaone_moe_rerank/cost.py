"""What one step of this configuration needs at the least, from its shapes
alone: plain arithmetic, no jax, kept with the benchmark.

The configuration is ONE chip's share of a layer (8 of the 128 routed experts;
the attention, the shared expert and the dense layer's MLP whole), and the
counts are of that share. The served step computes every layer but the last
at all L positions, and of the last layer its keys and values at the positions
the last query sees (the last `sliding_window` on a sliding layer, else all)
and its queries, attention output and FFN at the LAST position only (the
score reads the last position; exact, see the configuration's file):
`step_cost` counts that. A product counts ONCE, 2 operations a weight and
position, however many passes of the MXU the stated precision takes, as the
other sequence configurations' do. A (query, key) pair counts where the mask
keeps it, whatever tiles the program computes (`attn_masked_score_pct.bulk`
reads what it computed beside what it kept). The held experts' work depends on
the routing; the step's count takes the EVEN share (each token's top_k
choices fall on a held expert with probability held / routed: 0.5
expert-passes a token here), which is what seeded random weights and uniform
ids give within a few percent (`held_assignments_per_token.bulk` reads what it
was). `window_attention_cost`, `full_attention_cost` and `expert_cost` are the
blocks' own counts; no metric reads them yet (a device time by named scope is
not in the trace's breakdown)."""

SLIDING = "sliding_attention"


def _sizes(config):
    heads, kv, head = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    hidden = config["embed_dim"]
    return {
        "H": hidden, "I": config["intermediate_size"], "L": config["num_fields"],
        "kinds": list(config["layer_types"]), "dense": config["first_k_dense_replace"],
        "window": config["sliding_window"], "F": config["moe_intermediate_size"], "E": config["num_experts"],
        "held": config["experts_held"] or config["num_experts"], "k": config["num_experts_per_tok"],
        # weights of one layer's attention: the two key/value matrices, and all four
        "kv": 2 * hidden * kv * head, "attn": 2 * hidden * heads * head + 2 * hidden * kv * head,
        # operations a (query, visible key) pair: q k' and p v over the head's width, every query head
        "pair": 2 * heads * 2 * head,
    }


def seen_pairs(kind, length, window):
    """(query, key) pairs a row that one layer's mask keeps at all positions."""
    if kind != SLIDING:
        return length * (length + 1) // 2
    return sum(min(t + 1, window) for t in range(length))


def _attention_cost(config, rows, kind):
    s = _sizes(config)
    flops = rows * (s["L"] * 2 * s["attn"] + seen_pairs(kind, s["L"], s["window"]) * s["pair"])
    return flops, 2 * s["attn"] + rows * s["L"] * 2 * 4 * s["H"]


def window_attention_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE sliding layer's
    attention over `rows` rows at all positions: the four products, and the
    scores inside the window and their product with the values. Bytes: the
    weights at 2 bytes, the input in and the output out in float32."""
    return _attention_cost(config, rows, SLIDING)


def full_attention_cost(config, rows):
    """As `window_attention_cost`, of ONE full layer: every causal pair."""
    return _attention_cost(config, rows, "full_attention")


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
    H, I, L, F, kinds = (s[k] for k in ("H", "I", "L", "F", "kinds"))
    N = len(kinds)
    dense_ffn = 3 * H * I
    routed_ffn = H * s["E"] + 3 * H * F + s["k"] * s["held"] / s["E"] * 3 * H * F  # router, shared, held share
    ffn = [dense_ffn if i < s["dense"] else routed_ffn for i in range(N)]
    weights = N * s["attn"] + sum(
        dense_ffn if i < s["dense"] else H * s["E"] + (1 + s["held"]) * 3 * H * F for i in range(N))
    every_position = 2 * ((N - 1) * s["attn"] + sum(ffn[:-1]))
    pairs = sum(seen_pairs(kind, L, s["window"]) for kind in kinds[:-1])
    reach = min(L, s["window"]) if kinds[-1] == SLIDING else L  # positions the last query sees
    last_layer = reach * 2 * s["kv"] + 2 * (s["attn"] - s["kv"] + ffn[-1]) + reach * s["pair"] + 2 * H
    flops_row = L * every_position + pairs * s["pair"] + last_layer
    bytes_row = L * (2 * H + 3 + 4) + 4
    return rows * flops_row, rows * bytes_row + batches * 2 * weights
