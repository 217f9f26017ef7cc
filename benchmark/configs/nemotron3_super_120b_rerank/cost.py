"""What one step of this configuration needs at the least, from its shapes
alone: plain arithmetic, no jax, kept with the benchmark.

The configuration is ONE chip's share of a layer (64 of the 512 routed
experts; the mixers, the router, the latent projections, the shared expert
and the norms whole), and the counts are of that share. Every layer is ONE
mixer (`layer_kinds`: a Mamba-2 mixer, an attention or the latent routed
block). The served step computes the trailing layers that do not mix along
the row (`E`) at the LAST position only; of the last layer that does, what
mixes along the positions at all of them (a Mamba-2 layer's input projection,
convolution and state walk; an attention's keys and values) and the rest at
the last position (the state's read with C, the gate, the gated norm and the
output product; the queries, scores and output); every layer before it at all
L positions (the score reads the last position; exact, see the
configuration's file). `step_cost` counts that. A product counts ONCE, 2
operations a weight and position, however many passes of the MXU the stated
precision takes, as the other sequence configurations' do. A (query, key) pair
counts where the mask keeps it, whatever tiles the program computes. The SSD
counts as its RECURRENCE's operations, whatever form computes it: a position
and head updates the state (`exp(dt A) S + dt x (x) B`) and reads it with C
(`S C`), 2 operations an entry of the `[P, N]` state each; its bytes the state
in and out once a CHUNK of positions (`ssd_handovers_per_row.bulk`). The held
experts' work depends on the routing; the step's count takes the EVEN share
(each of a token's top_k choices falls on a held expert with probability
held / routed: 2.75 expert-passes a token here), which is what seeded random
weights and uniform ids give within a few percent
(`held_assignments_per_token.bulk` reads what it was). `ssd_cost`,
`expert_cost`, `latent_cost`, `attention_cost` and `conv_cost` are the blocks'
own counts, which PERF.md's shares of a kernel's roofline are worked out from
by hand (a device time by named scope is not in the trace's breakdown)."""

KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


def layer_kinds(config):
    """`mamba`, `attention` or `moe` of every layer run: the first
    `num_hidden_layers` letters of the pattern."""
    return [KINDS[letter] for letter in config["hybrid_override_pattern"][:config["num_hidden_layers"]]]


def positions(kinds):
    """`all`, `cut` (the last layer that mixes along the row) or `last` (what
    follows it) of every layer, as the program's `positions_plan`."""
    mixing = [i for i, kind in enumerate(kinds) if kind != "moe"]
    cut = mixing[-1] if mixing else -1
    return ["all" if i < cut else "cut" if i == cut else "last" for i in range(len(kinds))]


def _sizes(config):
    hidden, heads, kv, head = (config[k] for k in ("embed_dim", "num_attention_heads", "num_key_value_heads", "head_dim"))
    d_ssm, ssm_heads, width, state, groups = (
        config[k] for k in ("mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups"))
    channels = d_ssm + 2 * groups * state
    latent, held = config["moe_latent_size"], config["experts_held"] or config["n_routed_experts"]
    return {
        "H": hidden, "L": config["num_fields"], "kinds": layer_kinds(config),
        # the attention's weights: the key and value matrices; the queries' and the output's
        "kv": 2 * hidden * kv * head, "q_o": 2 * hidden * heads * head,
        # operations a (query, visible key) pair: q k' and p v over the head's width, every query head
        "pair": 2 * heads * 2 * head,
        # the Mamba-2 mixer's weights read at every position (the input projection: z, x, B, C, dt) and that after the state
        "ssm_in": hidden * (d_ssm + channels + ssm_heads), "ssm_out": d_ssm * hidden,
        "channels": channels, "taps": config["mamba_d_conv"],
        # the convolution's weights and bias, dt_bias, A_log and D a head, the gated norm's weight
        "ssm_small": channels * (config["mamba_d_conv"] + 1) + 3 * ssm_heads + d_ssm,
        # a position's update of the state and its read with C: 2 operations an entry each
        "update": 2 * ssm_heads * width * state, "read": 2 * ssm_heads * width * state,
        "state_bytes": ssm_heads * width * state * 4, "d_ssm": d_ssm,
        # the routed block: what every token meets (router, shared expert) and the two latent projections, an
        # expert (two matrices at the latent's width), the held ones
        "router": hidden * config["n_routed_experts"], "shared": 2 * hidden * config["moe_shared_expert_intermediate_size"],
        "latent_io": 2 * hidden * latent, "latent": latent, "expert": 2 * latent * config["moe_intermediate_size"],
        "held": held, "passes": config["num_experts_per_tok"] * held / config["n_routed_experts"],
    }


def handovers(config):
    """State hand-overs a row and Mamba-2 layer: chunks of `mamba_chunk_size` positions."""
    return -(-config["num_fields"] // min(config["mamba_chunk_size"], config["num_fields"]))


def ssd_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE Mamba-2 layer's SSD
    over `rows` rows at all positions: the recurrence's update and read a
    position and head. Bytes: x in and y out (d_ssm wide), B and C and dt in,
    float32, and the state in and out once a chunk."""
    s = _sizes(config)
    moved = s["L"] * 4 * (s["d_ssm"] + s["channels"] + config["mamba_n_heads"])
    moved += handovers(config) * 2 * s["state_bytes"]
    return rows * s["L"] * (s["update"] + s["read"]), rows * moved


def expert_cost(config, assignments):
    """(floating-point operations, bytes moved) of the grouped product of ONE
    routed layer over `assignments` (token, held expert) pairs: two products
    of the expert's width a pair, at the latent's width. Bytes: every held
    expert's weights once at 2 bytes, a latent-wide row gathered in and a row
    added back out in float32 a pair."""
    s = _sizes(config)
    return assignments * 2 * s["expert"], 2 * s["held"] * s["expert"] + assignments * 2 * 4 * s["latent"]


def latent_cost(config, tokens):
    """(floating-point operations, bytes moved) of ONE routed layer's two
    latent projections over `tokens` tokens. Bytes: the two weights at 2
    bytes, a hidden-wide row and a latent-wide row in and out of each in
    float32."""
    s = _sizes(config)
    return tokens * 2 * s["latent_io"], 2 * s["latent_io"] + tokens * 2 * 4 * (s["H"] + s["latent"])


def attention_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE attention layer over
    `rows` rows at all positions: the four products, and every causal pair's
    score and its product with the values. Bytes: the weights at 2 bytes, the
    input in and the output out in float32."""
    s = _sizes(config)
    flops = rows * (s["L"] * 2 * (s["kv"] + s["q_o"]) + s["L"] * (s["L"] + 1) // 2 * s["pair"])
    return flops, 2 * (s["kv"] + s["q_o"]) + rows * s["L"] * 2 * 4 * s["H"]


def conv_cost(config, rows):
    """(floating-point operations, bytes moved) of ONE Mamba-2 layer's causal
    depthwise convolution over `rows` rows: a multiply and an add a tap,
    channel and position, and the bias. Bytes: the channels in and out in
    float32."""
    s = _sizes(config)
    return rows * s["L"] * (2 * s["taps"] + 1) * s["channels"], rows * s["L"] * 2 * 4 * s["channels"]


def step_cost(config, rows, batches):
    """(floating-point operations, bytes moved) that scoring `rows` rows in
    `batches` batches needs, at the even share of the routing. Bytes: every
    weight held once a batch at 2 bytes, a token's embedding row (2 bytes a
    value), its id (3 bytes) and weight (4), the SSD's state in and out once a
    chunk, a score out (4)."""
    s = _sizes(config)
    L, kinds = s["L"], s["kinds"]
    conv = (2 * s["taps"] + 1) * s["channels"]
    routed = 2 * (s["router"] + s["shared"] + s["latent_io"] + s["passes"] * s["expert"])  # operations a token of a block
    flops_row = bytes_row = weights = 0
    for kind, at in zip(kinds, positions(kinds)):
        after = L if at == "all" else 1  # positions of what follows the mixing along the row
        if kind == "mamba":
            flops_row += L * (2 * s["ssm_in"] + conv + s["update"]) + after * (s["read"] + 2 * s["ssm_out"])
            bytes_row += handovers(config) * 2 * s["state_bytes"]
            weights += s["ssm_in"] + s["ssm_out"] + s["ssm_small"]
        elif kind == "attention":
            pairs = L * (L + 1) // 2 if at == "all" else L
            flops_row += L * 2 * s["kv"] + after * 2 * s["q_o"] + pairs * s["pair"]
            weights += s["kv"] + s["q_o"]
        else:
            flops_row += after * routed
            weights += s["router"] + s["shared"] + s["latent_io"] + s["held"] * s["expert"]
    flops_row += 2 * s["H"]
    bytes_row += L * (2 * s["H"] + 3 + 4) + 4
    return rows * flops_row, rows * bytes_row + batches * 2 * weights
