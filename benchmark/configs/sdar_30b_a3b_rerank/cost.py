"""What one step of this configuration needs at the least, from its shapes
alone: plain arithmetic, no jax, kept with the benchmark.

The configuration holds every layer WHOLE (all `num_experts` routed experts,
the attention, the router, the norms), and the counts are of that. The served
step computes every layer but the last at all L positions; of the last layer
the keys and values at all of them and the rest at the LAST position only:
its queries, scores, output and the whole routed block (the score reads the
last position, which is the last of its block and sees every key; exact, see
the configuration's file). `step_cost` counts that. A product counts ONCE, 2
operations a weight and position, however many passes of the MXU the stated
precision takes, as the other sequence configurations' do. A (query, key) pair
counts where the BLOCK mask keeps it (`u // B <= t // B`: the causal pairs and
`L (B - 1) / 2` ahead of their query a row and layer), whatever tiles the
program computes (`attn_masked_score_pct.bulk` reads what it computed beside
what it kept, `attn_ahead_score_pct.bulk` the share kept ahead). The held
experts' work depends on the routing only through WHICH experts a token meets:
with every expert held a token meets `num_experts_per_tok` of them whatever
the router says (`held_assignments_per_token.bulk` reads 8.0), so the count is
exact and not an even share; `passes` is written as the other routed
configurations write theirs (top_k x held / routed) so that a share of the
layer counts its own. The experts' weights count once a step: every expert
has work at a mean of 1,024 tokens an expert."""


def _sizes(config):
    hidden, heads, kv, head = (config[k] for k in ("embed_dim", "num_attention_heads", "num_key_value_heads", "head_dim"))
    experts = config["num_experts"]
    held = config.get("experts_held") or experts
    return {
        "H": hidden, "L": config["num_fields"], "B": config["block_length"], "layers": config["num_hidden_layers"],
        # the attention's weights: the key and value matrices; the queries' and the output's
        "kv": 2 * hidden * kv * head, "q_o": 2 * hidden * heads * head,
        # operations a (query, visible key) pair: q k' and p v over the head's width, every query head
        "pair": 2 * heads * 2 * head,
        # the routed block: the router every token meets, an expert's three matrices, the held ones
        "router": hidden * experts, "expert": 3 * hidden * config["moe_intermediate_size"], "held": held,
        "passes": config["num_experts_per_tok"] * held / experts,
        "norms": 2 * hidden + 2 * head,
    }


def seen_pairs(length, block):
    """(query, key) pairs the block mask keeps over a row at all positions:
    the causal ones and, ahead of its query, the rest of a query's block."""
    return length * (length + 1) // 2 + length * (block - 1) // 2


def step_cost(config, rows, batches):
    """(floating-point operations, bytes moved) that scoring `rows` rows in
    `batches` batches needs. Bytes: every weight held once a batch at 2
    bytes, a token's embedding row (2 bytes a value), its id (3 bytes) and
    weight (4), a score out (4)."""
    s = _sizes(config)
    L = s["L"]
    routed = 2 * (s["router"] + s["passes"] * s["expert"])  # operations a token of a routed block
    flops_row = 0
    for i in range(s["layers"]):
        last = i == s["layers"] - 1
        after = 1 if last else L  # positions of what follows the keys and values
        pairs = L if last else seen_pairs(L, s["B"])
        flops_row += L * 2 * s["kv"] + after * 2 * s["q_o"] + pairs * s["pair"] + after * routed
    weights = s["layers"] * (s["kv"] + s["q_o"] + s["router"] + s["held"] * s["expert"] + s["norms"]) + 2 * s["H"]
    flops_row += 2 * s["H"]
    bytes_row = L * (2 * s["H"] + 3 + 4) + 4
    return rows * flops_row, rows * bytes_row + batches * 2 * weights
