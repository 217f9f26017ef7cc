"""What one step of this configuration needs at the least, from its shapes
alone: plain arithmetic, no jax, kept with the benchmark."""


def step_cost(config, rows, batches):
    """(floating-point operations, bytes moved) that scoring `rows`
    rows in `batches` batches needs. A row reads its `num_fields` embedding
    rows once (float32, as the table holds them) and its inputs as the
    program uploads them (ids in 3 bytes while the table has at most 1 << 24
    rows, else 4; bfloat16 weights; float32 dense features); the dense weights
    are read once a batch."""
    lookups, d = config["num_fields"], config["embed_dim"]
    width = (len(config["multi_hot_sizes"]) + 1) * d
    bottom = [config["num_dense_features"]] + list(config["bottom_mlp_dims"])
    top = [width] + list(config["mlp_dims"]) + [1]
    weights = (
        sum(a * b for a, b in zip(bottom, bottom[1:]))
        + config["num_cross_layers"] * 2 * width * config["cross_low_rank"]
        + sum(a * b for a, b in zip(top, top[1:]))
    )
    flops_row = 2 * weights + 2 * lookups * d  # the matmuls; a multiply and an add a pooled value
    id_bytes = 3 if config["vocab_size"] <= 1 << 24 else 4
    bytes_row = lookups * (d * 4 + id_bytes + 2) + config["num_dense_features"] * 4 + 4
    return rows * flops_row, rows * bytes_row + batches * 4 * weights
