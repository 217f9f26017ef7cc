"""What one step of this configuration needs at the least, from its shapes
alone: plain arithmetic, no jax, kept with the benchmark.

The configuration is ONE chip's share of a layer (128 of the 512 routed
experts; the mixers, the router, the gated shared expert and the norms whole),
and the counts are of that share. The served step computes every layer but
the last at all L positions; of the last layer what mixes along the positions
at all of them (a linear layer's q, k, v, b and a projections, convolutions
and rule; a full layer's keys and values) and the rest at the LAST position
only: a linear layer's output gate and projection, a full layer's queries,
gate, scores and output, the whole routed block (the score reads the last
position; exact, see the configuration's file). `step_cost` counts that. A
product counts ONCE, 2 operations a weight and position, however many passes
of the MXU the stated precision takes, as the other sequence configurations'
do. A (query, key) pair counts where the mask keeps it, whatever tiles the
program computes (`attn_masked_score_pct.bulk` reads what it computed beside
what it kept). The gated delta rule counts as its RECURRENCE's operations,
whatever form computes it: a position and VALUE head reads the state with the
key (`S' k`), makes the rank-one update and reads it with the query (`S' q`),
2 operations an entry of the `[dk, dv]` state each; its bytes the state in and
out once a CHUNK of positions (`delta_handovers_per_row.bulk`). The held
experts' work depends on the routing; the step's count takes the EVEN share
(each of a token's top_k choices falls on a held expert with probability
held / routed: 2.5 expert-passes a token here), which is what seeded random
weights and uniform ids give within a few percent
(`held_assignments_per_token.bulk` reads what it was). `expert_cost`,
`delta_rule_cost`, `full_attention_cost` and `conv_cost` are the blocks' own
counts; no metric reads them yet (a device time by named scope is not in the
trace's breakdown)."""

CHUNK = 64  # positions a state hand-over (models/olmo_hybrid.py DELTA_CHUNK)


def layer_kinds(config):
    """`full` or `linear` of every layer run: `layer_types` where the
    configuration gives them, else by `full_attention_interval`."""
    kinds = config.get("layer_types")
    if kinds:
        return ["full" if kind == "full_attention" else "linear" for kind in kinds]
    interval = config["full_attention_interval"]
    return ["full" if (i + 1) % interval == 0 else "linear" for i in range(config["num_hidden_layers"])]


def _sizes(config):
    hidden, heads, kv, head = (config[k] for k in ("embed_dim", "num_attention_heads", "num_key_value_heads", "head_dim"))
    keys, values, dk, dv = (config[k] for k in (
        "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim"))
    expert, held = 3 * hidden * config["moe_intermediate_size"], config["experts_held"] or config["num_experts"]
    outside = hidden * config["num_experts"] + 3 * hidden * config["shared_expert_intermediate_size"] + hidden
    return {
        "H": hidden, "L": config["num_fields"], "kinds": layer_kinds(config),
        # a full layer's weights: the key and value matrices; the queries with their gates, and the output
        "kv": 2 * hidden * kv * head, "q_o": hidden * heads * 2 * head + heads * head * hidden,
        # operations a (query, visible key) pair: q k' and p v over the head's width, every query head
        "pair": 2 * heads * 2 * head,
        # a linear layer's weights read at every position (q, k, v, b, a) and those after the rule (z, out)
        "lin_in": hidden * (2 * keys * dk + values * dv + 2 * values), "lin_out": 2 * hidden * values * dv,
        "channels": 2 * keys * dk + values * dv, "taps": config["linear_conv_kernel_dim"],
        "rule": values * 6 * dk * dv, "state_bytes": values * dk * dv * 4,
        "keys": keys, "values": values, "dk": dk, "dv": dv,
        # the routed block: what every token meets (router, shared expert, its gate), an expert, the held ones
        "outside": outside, "expert": expert, "held": held,
        "passes": config["num_experts_per_tok"] * held / config["num_experts"],
    }


def handovers(length):
    """State hand-overs a row and linear layer: chunks of CHUNK positions."""
    return -(-length // CHUNK)


def expert_cost(config, assignments):
    """(floating-point operations, bytes moved) of the grouped product of ONE
    routed layer over `assignments` (token, held expert) pairs: three
    products of the expert's width a pair. Bytes: every held expert's
    weights once at 2 bytes, a row gathered in and a row added back out in
    float32 a pair."""
    s = _sizes(config)
    return assignments * 2 * s["expert"], 2 * s["held"] * s["expert"] + assignments * 2 * 4 * s["H"]


def delta_rule_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE linear layer's gated
    delta rule over `rows` rows at all positions: the recurrence's three
    products a position and value head. Bytes: q, k of the key heads, v in
    and o out of the value heads in float32, the two gates, and the state in
    and out once a chunk."""
    s = _sizes(config)
    moved = (s["L"] * 4 * (2 * s["keys"] * s["dk"] + 2 * s["values"] * s["dv"] + 2 * s["values"])
             + handovers(s["L"]) * 2 * s["state_bytes"])
    return rows * s["L"] * s["rule"], rows * moved


def full_attention_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE full layer's gated
    attention over `rows` rows at all positions: the four products (the
    queries' with the gates' columns), every causal pair's score and its
    product with the values. Bytes: the weights at 2 bytes, the input in and
    the output out in float32."""
    s = _sizes(config)
    flops = rows * (s["L"] * 2 * (s["kv"] + s["q_o"]) + s["L"] * (s["L"] + 1) // 2 * s["pair"])
    return flops, 2 * (s["kv"] + s["q_o"]) + rows * s["L"] * 2 * 4 * s["H"]


def conv_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE linear layer's causal
    depthwise convolutions over `rows` rows: a multiply and an add a tap,
    channel and position. Bytes: the channels in and out in float32."""
    s = _sizes(config)
    return rows * s["L"] * 2 * s["taps"] * s["channels"], rows * s["L"] * 2 * 4 * s["channels"]


def step_cost(config, rows, batches):
    """(floating-point operations, bytes moved) that scoring `rows` rows in
    `batches` batches needs, at the even share of the routing. Bytes: every
    weight held once a batch at 2 bytes, a token's embedding row (2 bytes a
    value), its id (3 bytes) and weight (4), the rule's state once a chunk, a
    score out (4)."""
    s = _sizes(config)
    L, kinds = s["L"], s["kinds"]
    conv = 2 * s["taps"] * s["channels"]
    routed = 2 * (s["outside"] + s["passes"] * s["expert"])  # operations a token of a routed block
    flops_row = bytes_row = weights = 0
    for i, kind in enumerate(kinds):
        last = i == len(kinds) - 1
        after = 1 if last else L  # positions of what follows the mixing along the row
        if kind == "linear":
            flops_row += L * (2 * s["lin_in"] + conv + s["rule"]) + after * 2 * s["lin_out"]
            bytes_row += handovers(L) * 2 * s["state_bytes"]
            weights += s["lin_in"] + s["lin_out"] + s["taps"] * s["channels"]
        else:
            pairs = L if last else L * (L + 1) // 2
            flops_row += L * 2 * s["kv"] + after * 2 * s["q_o"] + pairs * s["pair"]
            weights += s["kv"] + s["q_o"]
        flops_row += after * routed
        weights += s["outside"] + s["held"] * s["expert"]
    flops_row += 2 * s["H"]
    bytes_row += L * (2 * s["H"] + 3 + 4) + 4
    return rows * flops_row, rows * bytes_row + batches * 2 * weights
