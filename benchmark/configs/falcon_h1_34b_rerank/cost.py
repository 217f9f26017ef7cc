"""What one step of this configuration needs at the least, from its shapes
alone: plain arithmetic, no jax, kept with the benchmark.

Every layer holds a Mamba-2 mixer beside its attention. The served step
computes every layer but the last at all L positions; of the last layer what
mixes along the positions at all of them (the keys and values, the SSM's input
projection, its convolution and its state's walk) and the rest at the LAST
position only: the query, the scores and the attention's output, the state's
read with C, the gate, the gated norm, both output products and the MLP (the
score reads the last position; exact, see the configuration's file).
`step_cost` counts that. A product counts ONCE, 2 operations a weight and
position, however many passes of the MXU the stated precision takes, as the
other sequence configurations' do. A (query, key) pair counts where the mask
keeps it, whatever tiles the program computes (`attn_masked_score_pct.bulk`
reads what it computed beside what it kept). The SSD counts as its
RECURRENCE's operations, whatever form computes it: a position and head
updates the state (`exp(dt A) S + dt x (x) B`) and reads it with C (`S C`), 2
operations an entry of the `[P, N]` state each; the chunked form's products
inside a chunk are how, not what. Its bytes: the state in and out once a CHUNK
of positions (the hand-over `ssd_handovers_per_row.bulk` counts), float32.
`ssd_cost`, `full_attention_cost` and `conv_cost` are the blocks' own counts;
no metric reads them yet (a device time by named scope is not in the trace's
breakdown)."""


def _sizes(config):
    hidden, heads, kv, head = (config[k] for k in ("embed_dim", "num_attention_heads", "num_key_value_heads", "head_dim"))
    d_ssm, ssm_heads, width, state, groups = (
        config[k] for k in ("mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups"))
    channels = d_ssm + 2 * groups * state
    return {
        "H": hidden, "L": config["num_fields"], "layers": config["num_hidden_layers"],
        "mlp": 3 * hidden * config["intermediate_size"],
        # the attention's weights: the key and value matrices, and all four
        "kv": 2 * hidden * kv * head, "attn": 2 * hidden * heads * head + 2 * hidden * kv * head,
        # operations a (query, visible key) pair: q k' and p v over the head's width, every query head
        "pair": 2 * heads * 2 * head,
        # the SSM's weights read at every position (the input projection: z, x, B, C, dt) and that after the state
        "ssm_in": hidden * (d_ssm + channels + ssm_heads), "ssm_out": d_ssm * hidden,
        "channels": channels, "taps": config["mamba_d_conv"],
        # the convolution's weights and bias, dt_bias, A_log and D a head, the gated norm's weight
        "ssm_small": channels * (config["mamba_d_conv"] + 1) + 3 * ssm_heads + d_ssm,
        # a position's update of the state and its read with C: 2 operations an entry each
        "update": 2 * ssm_heads * width * state, "read": 2 * ssm_heads * width * state,
        "state_bytes": ssm_heads * width * state * 4, "d_ssm": d_ssm,
    }


def handovers(config):
    """State hand-overs a row and layer: chunks of `mamba_chunk_size` positions."""
    return -(-config["num_fields"] // min(config["mamba_chunk_size"], config["num_fields"]))


def ssd_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE layer's SSD over `rows`
    rows at all positions: the recurrence's update and read a position and
    head. Bytes: x in and y out (d_ssm wide), B and C and dt in, float32, and
    the state in and out once a chunk."""
    s = _sizes(config)
    moved = s["L"] * 4 * (s["d_ssm"] + s["channels"] + config["mamba_n_heads"])
    moved += handovers(config) * 2 * s["state_bytes"]
    return rows * s["L"] * (s["update"] + s["read"]), rows * moved


def full_attention_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE layer's attention over
    `rows` rows at all positions: the four products, and every causal pair's
    score and its product with the values. Bytes: the weights at 2 bytes, the
    input in and the output out in float32."""
    s = _sizes(config)
    flops = rows * (s["L"] * 2 * s["attn"] + s["L"] * (s["L"] + 1) // 2 * s["pair"])
    return flops, 2 * s["attn"] + rows * s["L"] * 2 * 4 * s["H"]


def conv_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE layer's causal
    depthwise convolution over `rows` rows: a multiply and an add a tap,
    channel and position, and the bias. Bytes: the channels in and out in
    float32."""
    s = _sizes(config)
    return rows * s["L"] * (2 * s["taps"] + 1) * s["channels"], rows * s["L"] * 2 * 4 * s["channels"]


def step_cost(config, rows, batches):
    """(floating-point operations, bytes moved) that scoring `rows` rows in
    `batches` batches needs. Bytes: every weight once a batch at 2 bytes, a
    token's embedding row (2 bytes a value), its id (3 bytes) and weight (4),
    the SSD's state in and out once a chunk, a score out (4)."""
    s = _sizes(config)
    L, layers = s["L"], s["layers"]
    conv = (2 * s["taps"] + 1) * s["channels"]
    flops_row = bytes_row = 0
    for i in range(layers):
        last = i == layers - 1
        after = 1 if last else L  # positions of what follows the mixing along the row
        pairs = L if last else L * (L + 1) // 2
        flops_row += L * 2 * s["kv"] + after * 2 * (s["attn"] - s["kv"]) + pairs * s["pair"]
        flops_row += L * (2 * s["ssm_in"] + conv + s["update"]) + after * (s["read"] + 2 * s["ssm_out"])
        flops_row += after * 2 * s["mlp"]
        bytes_row += handovers(config) * 2 * s["state_bytes"]
    weights = layers * (s["attn"] + s["ssm_in"] + s["ssm_out"] + s["ssm_small"] + s["mlp"])
    flops_row += 2 * s["H"]
    bytes_row += L * (2 * s["H"] + 3 + 4) + 4
    return rows * flops_row, rows * bytes_row + batches * 2 * weights
