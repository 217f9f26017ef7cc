"""What one step of this configuration needs at the least, from its shapes
alone: plain arithmetic, no jax, kept with the benchmark."""


def step_cost(config, rows, batches):
    """(floating-point operations, bytes moved) that scoring `rows`
    rows in `batches` batches needs. The interaction counts the (F + 1) F / 2
    pairs the algorithm needs, not the full Z Z^T a program may compute."""
    f, d = config["num_fields"], config["embed_dim"]
    pairs = (f + 1) * f // 2
    bottom = [config["num_dense_features"]] + list(config["bottom_mlp_dims"])
    top = [d + pairs] + list(config["mlp_dims"]) + [1]
    weights = sum(a * b for a, b in zip(bottom, bottom[1:])) + sum(
        a * b for a, b in zip(top, top[1:])
    )
    flops_row = 2 * (weights + pairs * d) + f * d
    bytes_row = f * d * 4 + f * 4 + f * 2 + config["num_dense_features"] * 4 + 4
    return rows * flops_row, rows * bytes_row + batches * 4 * weights
