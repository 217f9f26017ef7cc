"""What one step of this configuration needs at the least, from its shapes
alone: plain arithmetic, no jax, kept with the benchmark.

The served step computes every layer but the last at all L positions; of the
last layer what mixes along the positions at all of them (a full layer's keys
and values, a linear layer's projections, convolutions and rule) and the rest
at the LAST position only: a full layer's query, scores and output, a linear
layer's output gate and projection, the MLP (the score reads the last
position; exact, see the configuration's file). `step_cost` counts that. A
product counts ONCE, 2 operations a weight and position, however many passes
of the MXU the stated precision takes, as the other sequence configurations'
do. A (query, key) pair counts where the mask keeps it, whatever tiles the
program computes (`attn_masked_score_pct.bulk` reads what it computed beside
what it kept). The gated delta rule counts as its RECURRENCE's operations,
whatever form computes it: a position and head reads the state with the key
(`S' k`), makes the rank-one update and reads it with the query (`S' q`), 2
operations an entry of the `[dk, dv]` state each; the chunked form's triangular
solve and its products inside a chunk are how, not what. Its bytes: the state
in and out once a CHUNK of positions (the hand-over
`delta_handovers_per_row.bulk` counts), float32. `delta_rule_cost`,
`full_attention_cost` and `conv_cost` are the blocks' own counts; no metric
reads them yet (a device time by named scope is not in the trace's breakdown)."""

LINEAR = "linear_attention"
CHUNK = 64  # positions a state hand-over (models/olmo_hybrid.py DELTA_CHUNK)


def _sizes(config):
    hidden, heads, kv, head = (config[k] for k in ("embed_dim", "num_attention_heads", "num_key_value_heads", "head_dim"))
    lin, dk, dv = (config[k] for k in ("linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim"))
    return {
        "H": hidden, "L": config["num_fields"], "kinds": list(config["layer_types"]),
        "mlp": 3 * hidden * config["intermediate_size"],
        # a full layer's weights: the key and value matrices, and all four
        "kv": 2 * hidden * kv * head, "attn": 2 * hidden * heads * head + 2 * hidden * kv * head,
        # operations a (query, visible key) pair: q k' and p v over the head's width, every query head
        "pair": 2 * heads * 2 * head,
        # a linear layer's weights read at every position (q, k, v, the two gates' vectors) and those after the rule
        "lin_in": hidden * (2 * lin * dk + lin * dv + 2 * lin), "lin_out": 2 * hidden * lin * dv,
        "channels": lin * (2 * dk + dv), "taps": config["linear_conv_kernel_dim"],
        "rule": lin * 6 * dk * dv, "state_bytes": lin * dk * dv * 4, "lin": lin, "dk": dk, "dv": dv,
    }


def handovers(length):
    """State hand-overs a row and linear layer: chunks of CHUNK positions."""
    return -(-length // CHUNK)


def delta_rule_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE linear layer's gated
    delta rule over `rows` rows at all positions: the recurrence's three
    products a position and head. Bytes: q, k, v in and o out in float32, the
    two gates, and the state in and out once a chunk."""
    s = _sizes(config)
    moved = s["L"] * 4 * (s["lin"] * (2 * s["dk"] + 2 * s["dv"]) + 2 * s["lin"]) + handovers(s["L"]) * 2 * s["state_bytes"]
    return rows * s["L"] * s["rule"], rows * moved


def full_attention_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE full layer's attention
    over `rows` rows at all positions: the four products, and every causal
    pair's score and its product with the values. Bytes: the weights at 2
    bytes, the input in and the output out in float32."""
    s = _sizes(config)
    flops = rows * (s["L"] * 2 * s["attn"] + s["L"] * (s["L"] + 1) // 2 * s["pair"])
    return flops, 2 * s["attn"] + rows * s["L"] * 2 * 4 * s["H"]


def conv_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE linear layer's three
    causal depthwise convolutions over `rows` rows: a multiply and an add a
    tap, channel and position. Bytes: the channels in and out in float32."""
    s = _sizes(config)
    return rows * s["L"] * 2 * s["taps"] * s["channels"], rows * s["L"] * 2 * 4 * s["channels"]


def step_cost(config, rows, batches):
    """(floating-point operations, bytes moved) that scoring `rows` rows in
    `batches` batches needs. Bytes: every weight once a batch at 2 bytes, a
    token's embedding row (2 bytes a value), its id (3 bytes) and weight (4),
    the rule's state once a chunk, a score out (4)."""
    s = _sizes(config)
    L, kinds = s["L"], s["kinds"]
    conv = 2 * s["taps"] * s["channels"]
    flops_row = bytes_row = weights = 0
    for i, kind in enumerate(kinds):
        last = i == len(kinds) - 1
        after = 1 if last else L  # positions of what follows the mixing along the row
        if kind == LINEAR:
            flops_row += L * (2 * s["lin_in"] + conv + s["rule"]) + after * 2 * s["lin_out"]
            bytes_row += handovers(L) * 2 * s["state_bytes"]
            weights += s["lin_in"] + s["lin_out"] + s["taps"] * s["channels"]
        else:
            pairs = L if last else L * (L + 1) // 2
            flops_row += L * 2 * s["kv"] + after * 2 * (s["attn"] - s["kv"]) + pairs * s["pair"]
            weights += s["attn"]
        flops_row += after * 2 * s["mlp"]
        weights += s["mlp"]
    flops_row += 2 * s["H"]
    bytes_row += L * (2 * s["H"] + 3 + 4) + 4
    return rows * flops_row, rows * bytes_row + batches * 2 * weights
