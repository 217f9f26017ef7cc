"""What one step of this configuration needs at the least, from its shapes
alone: plain arithmetic, no jax, kept with the benchmark.

The served step computes layers 0 .. N/2 at all L positions, the keys and
values of layer N/2 + 1 at all positions, and everything after that at the
LAST position only (the score reads the last position; exact, see the
configuration's file): `step_cost` counts that, not the plain forward pass.
A product counts ONCE, 2 operations a weight and position, however many
passes of the MXU the stated precision takes (two against a weight, three
between activations): the passes are the program's way to the precision, not
work the model asks for, so a step bound by its products reads under 50% of
the roofline. `scan_cost` is the selective scan's own count; no metric reads
it yet (a device time by named scope is not in the trace's breakdown)."""

SCAN_FLOPS = 7  # a (channel, state) pair a position: delta*A, exp, decay*S, (delta u)*B, +, S*C, +


def _sizes(config):
    hidden = config["embed_dim"]
    return {
        "H": hidden, "I": config["mlp_dims"][0], "L": config["num_fields"],
        "N": config["num_hidden_layers"], "Di": config["ssm_expand"] * hidden,
        "S": config["ssm_state"], "K": config["ssm_conv"],
        "R": -(-hidden // 16),
        "KV": config["num_key_value_heads"] * hidden // config["num_attention_heads"],
        "W": config["sliding_window"],
    }


def scan_layers(config):
    """Mamba layers, each with one scan: layers 0, 2, ..., N/2."""
    return config["num_hidden_layers"] // 4 + 1


def scan_cost(config, rows):
    """(floating-point operations, bytes moved) of the selective scans of
    `rows` rows: SCAN_FLOPS a (channel, state) pair a position; delta, u in
    and y out (float32, a channel) and B, C in (float32, a state) a position."""
    s = _sizes(config)
    positions = rows * s["L"] * scan_layers(config)
    return positions * SCAN_FLOPS * s["Di"] * s["S"], positions * 4 * (3 * s["Di"] + 2 * s["S"])


def step_cost(config, rows, batches):
    """(floating-point operations, bytes moved) that scoring `rows` rows in
    `batches` batches needs. Matrix products at 2 operations a weight and
    position; attention at 6 * H a (query, visible key) pair (q k' for both
    maps, and both maps times the double-width value); the scans as
    `scan_cost`. Bytes: every weight once a batch at 2 bytes, a token's
    embedding row (2 bytes a value), its id (3 bytes) and weight (4), a
    score out (4)."""
    s = _sizes(config)
    H, I, L, N, Di, S, K, R, KV, W = (s[k] for k in ("H", "I", "L", "N", "Di", "S", "K", "R", "KV", "W"))
    mlp = 3 * H * I
    mamba = H * 2 * Di + Di * (R + 2 * S) + R * Di + Di * H  # + the convolution and small vectors
    attn = H * (H + 2 * KV) + H * H
    gmu, cross = 2 * H * Di, 2 * H * H
    n_mamba, n_window, n_tail = N // 4 + 1, N // 4, N // 4 - 1  # tail: gmu and cross layers, each
    weights = (
        n_mamba * (mamba + mlp) + (n_window + 1) * (attn + mlp) + n_tail * (gmu + cross + 2 * mlp)
    )
    visible = W * (W + 1) // 2 + (L - W) * W if L >= W else L * (L + 1) // 2
    every_position = (
        2 * (n_mamba * (mamba + mlp + K * Di) + n_window * (attn + mlp) + H * 2 * KV) + H
    )
    last_position = (
        2 * (2 * H * H + mlp + n_tail * (gmu + cross + 2 * mlp))  # the full layer's q and o, the tail
        + (1 + n_tail) * 6 * H * L  # one query against L keys: the full and the cross layers
        + 2 * H
    )
    flops_row = L * every_position + n_window * 6 * H * visible + last_position + scan_cost(config, 1)[0]
    bytes_row = L * (2 * H + 3 + 4) + 4
    return rows * flops_row, rows * bytes_row + batches * 2 * weights
