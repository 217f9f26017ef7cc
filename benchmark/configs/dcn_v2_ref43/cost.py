"""What one step of this configuration needs at the least, from its shapes
alone: plain arithmetic, no jax, kept with the benchmark."""


def step_cost(config, rows, batches):
    """(floating-point operations, bytes moved) that scoring `rows`
    rows in `batches` batches needs: every row's matmuls and its embedding
    rows, the ids and weights in and the score out, and one read of the dense
    weights per batch."""
    f, d_e = config["num_fields"], config["embed_dim"]
    d = f * d_e
    dims = [d] + list(config["mlp_dims"])
    mlp = sum(a * b for a, b in zip(dims, dims[1:]))
    cross = config["num_cross_layers"] * d * d
    head = d + dims[-1]
    flops_row = 2 * (cross + mlp + head) + d  # + the weight multiply
    weight_bytes = 4 * (cross + mlp + head)  # float32 parameters
    bytes_row = f * d_e * 4 + f * 4 + f * 2 + 4  # table rows, int32 ids, bf16 weights, score
    return rows * flops_row, rows * bytes_row + batches * weight_bytes
