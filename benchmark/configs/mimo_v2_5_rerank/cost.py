"""What one step of this configuration needs at the least, from its shapes
alone: plain arithmetic, no jax, kept with the benchmark.

The configuration is ONE chip's share of a layer (8 of the 256 routed experts;
the attention and the dense layer's MLP whole; no shared expert), and the
counts are of that share. The served step computes every layer but the last
at all L positions, and of the last layer its keys and values at the positions
the last query sees (the last `sliding_window` on a window layer, else all)
and its queries, attention output and FFN at the LAST position only (the
score reads the last position; exact, see the configuration's file):
`step_cost` counts that. A product counts ONCE, 2 operations a weight and
position, however many passes of the MXU the stated precision takes, as the
other sequence configurations' do. A (query, key) pair counts where the mask
keeps it, whatever tiles the program computes (`attn_masked_score_pct.bulk`
reads what it computed beside what it kept): `q k'` over the keys' 192 dims
and `p v` over the values' 128. The sink is one exponential a (query, head)
and is not counted; nor is the rotary turn or the value scale. The shapes of
a layer's key and value projections follow its KIND (4 key-value heads on a
full layer, 8 on a window layer). The held experts' work depends on the
routing; the step's count takes the EVEN share (each token's top_k choices
fall on a held expert with probability held / routed: 0.25 expert-passes a
token here), which is what seeded random weights and uniform ids give within
a few percent (`held_assignments_per_token.bulk` reads what it was).
`window_attention_cost`, `full_attention_cost` and `expert_cost` are the
blocks' own counts; no metric reads them yet (a device time by named scope is
not in the trace's breakdown)."""

FULL, WINDOW = 0, 1  # hybrid_layer_pattern's


def _sizes(config):
    heads, head, v_head = config["num_attention_heads"], config["head_dim"], config["v_head_dim"]
    hidden = config["embed_dim"]
    kv = {FULL: config["num_key_value_heads"], WINDOW: config["swa_num_key_value_heads"]}
    return {
        "H": hidden, "I": config["intermediate_size"], "L": config["num_fields"],
        "kinds": list(config["hybrid_layer_pattern"]), "moe": list(config["moe_layer_freq"]),
        "window": config["sliding_window"], "F": config["moe_intermediate_size"], "E": config["n_routed_experts"],
        "held": config["experts_held"] or config["n_routed_experts"], "k": config["num_experts_per_tok"],
        # weights of one layer's key and value matrices, and of all four, by the layer's kind
        "kv": {kind: hidden * n * (head + v_head) for kind, n in kv.items()},
        "attn": {kind: hidden * heads * (head + v_head) + hidden * n * (head + v_head) for kind, n in kv.items()},
        # operations a (query, visible key) pair: q k' over the keys' width, p v over the values', every query head
        "pair": 2 * heads * (head + v_head),
    }


def seen_pairs(kind, length, window):
    """(query, key) pairs a row that one layer's mask keeps at all positions."""
    if kind == FULL:
        return length * (length + 1) // 2
    return sum(min(t + 1, window) for t in range(length))


def _attention_cost(config, rows, kind):
    s = _sizes(config)
    flops = rows * (s["L"] * 2 * s["attn"][kind] + seen_pairs(kind, s["L"], s["window"]) * s["pair"])
    return flops, 2 * s["attn"][kind] + rows * s["L"] * 2 * 4 * s["H"]


def window_attention_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE window layer's
    attention over `rows` rows at all positions: the four products (8
    key-value heads), and the scores inside the window and their product with
    the values. Bytes: the weights at 2 bytes, the input in and the output
    out in float32."""
    return _attention_cost(config, rows, WINDOW)


def full_attention_cost(config, rows):
    """As `window_attention_cost`, of ONE full layer: 4 key-value heads, every causal pair."""
    return _attention_cost(config, rows, FULL)


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
    routed_ffn = H * s["E"] + s["k"] * s["held"] / s["E"] * 3 * H * F  # router, the held share; no shared expert
    ffn = [routed_ffn if moe else dense_ffn for moe in s["moe"]]
    attn = [s["attn"][kind] for kind in kinds]
    weights = sum(attn) + sum(H * s["E"] + s["held"] * 3 * H * F if moe else dense_ffn for moe in s["moe"])
    every_position = 2 * (sum(attn[:-1]) + sum(ffn[:-1]))
    pairs = sum(seen_pairs(kind, L, s["window"]) for kind in kinds[:-1])
    reach = min(L, s["window"]) if kinds[-1] == WINDOW else L  # positions the last query sees
    kv_last = s["kv"][kinds[-1]]
    last_layer = reach * 2 * kv_last + 2 * (attn[-1] - kv_last + ffn[-1]) + reach * s["pair"] + 2 * H
    flops_row = L * every_position + pairs * s["pair"] + last_layer
    bytes_row = L * (2 * H + 3 + 4) + 4
    return rows * flops_row, rows * bytes_row + batches * 2 * weights
