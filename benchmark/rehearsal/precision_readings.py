#!/usr/bin/env python3
"""The readings `phi4_mini_flash_rerank`'s `tolerance` was set from, on the chip:

  chiprun -- python3 benchmark/rehearsal/precision_readings.py [--seeds 48] [--tiny 1]

Over `--seeds` correctness samples (the harness's own: 2 + 2 rows a seed) it
scores each row with the program's family at float32 and `highest` matmul
precision (within 1e-6 of the CPU reference: tests/test_phi4flash.py; a
stand-in for it that takes a second a seed where the host takes half a minute)
and prints the distance of the score from it, a row and a sample (the largest
of its four rows, what a run's `sample_max_abs_error` is), for

  served            the family as configured: bfloat16 weights, activations as
                    two bfloat16 pieces into every product, float32 elsewhere
  one piece         the nearest precision below: every activation rounded to
                    bfloat16 where it enters a product (OPERAND_PIECES 1)
  bf16 scan state   served, with the scan's state rounded to bfloat16 a position
  no lambda         served, with the differential attention's second map dropped
  reference in bf16 the configuration's plain reference computed wholly in bfloat16

The two faults are planted here, not in the program. `--tiny 1` shrinks the
widths so that the flow runs on the CPU; its numbers mean nothing. One
process, which holds the chip.
"""

import argparse
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seeds", type=int, default=48)
    parser.add_argument("--tiny", type=int, default=0)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import traffic
    from benchmark.common import load_module, read_json
    from distributed_tf_serving_tpu.models import ModelConfig, build_model, phi4flash

    here = os.path.join(ROOT, "benchmark", "configs", "phi4_mini_flash_rerank")
    shape = read_json(os.path.join(here, "config.json"))["toml"]["model"]
    if args.tiny:
        shape.update(num_fields=96, vocab_size=5000, embed_dim=64, mlp_dims=[128],
                     num_attention_heads=4, num_key_value_heads=2)
    config = ModelConfig(**{**shape, "mlp_dims": tuple(shape["mlp_dims"])})
    model = build_model("phi4flash", config)
    exact = build_model("phi4flash", dataclasses.replace(config, compute_dtype="float32"))
    reference = load_module(os.path.join(here, "reference.py"), "bench_reference")
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(0)))
    print(f"device {jax.devices()[0].device_kind}, {config.num_hidden_layers} layers, "
          f"{sum(x.size for x in jax.tree.leaves(params)) / 1e9:.2f} B parameters", flush=True)

    @jax.jit
    def highest(p, b):
        with jax.default_matmul_precision("highest"):
            return exact.apply(p, b)["prediction_node"]

    def patched(name, planted):
        """The served step with one name of the family replaced."""
        def run(p, b):
            keep = getattr(phi4flash, name)
            setattr(phi4flash, name, planted(keep))
            try:
                return model.apply(p, b)["prediction_node"]
            finally:
                setattr(phi4flash, name, keep)
        return jax.jit(run)

    def bfloat16_state(_scan):
        def scan(u, delta, a, b, c):
            def step(state, xs):
                d, du, b_t, c_t = xs
                state = jnp.exp(d[:, None, :] * a.T[None]) * state.astype(jnp.float32)
                state = (state + du[:, None, :] * b_t[:, :, None]).astype(jnp.bfloat16)
                return state, jnp.sum(state.astype(jnp.float32) * c_t[:, :, None], axis=1)

            state0 = jnp.zeros((u.shape[0], a.shape[1], u.shape[2]), jnp.bfloat16)
            xs = tuple(jnp.moveaxis(x, 1, 0) for x in (delta, delta * u, b, c))
            return jnp.moveaxis(jax.lax.scan(step, state0, xs)[1], 0, 1)
        return scan

    def no_second_map(product):
        def run(spec, x, y, cd):
            out = product(spec, x, y, cd)
            return out.at[..., 1, :].set(0.0) if spec.endswith("->nqgjce") else out
        return run

    def in_bfloat16(p, b):
        keep = reference._f32
        reference._f32 = lambda x: jnp.asarray(x).astype(jnp.bfloat16)
        try:
            return reference.forward(p, b)
        finally:
            reference._f32 = keep

    variants = {
        "served": jax.jit(lambda p, b: model.apply(p, b)["prediction_node"]),
        "one piece": patched("OPERAND_PIECES", lambda _two: 1),
        "bf16 scan state": patched("selective_scan", bfloat16_state),
        "no lambda": patched("_product", no_second_map),
        "reference in bf16": jax.jit(in_bfloat16),
    }
    mix = {"rows": {"kind": "fixed", "value": 2}}
    errors = {name: [] for name in variants}
    for i in range(args.seeds):
        sample = traffic.sample_requests(mix, shape, 2_950_000_000 + 7919 * i)
        ids = np.concatenate([s["feat_ids"] for s in sample.values()]) % config.vocab_size
        wts = np.concatenate([s["feat_wts"] for s in sample.values()])
        batch = {"feat_ids": jnp.asarray(ids.astype(np.int32)), "feat_wts": jnp.asarray(wts)}
        want = np.asarray(highest(params, batch), np.float64)
        for name, run in variants.items():
            got = np.asarray(run(params, batch))
            errors[name].append(np.abs(got.astype(np.float64) - want))
    for name, rows in errors.items():
        rows = np.stack(rows)
        worst = np.sort(rows.max(axis=1))
        print(f"{name}: a row rms {np.sqrt((rows ** 2).mean()):.2e} p99 {np.percentile(rows, 99):.2e}; "
              f"a sample of 4 rows p50 {np.percentile(worst, 50):.2e} p90 {np.percentile(worst, 90):.2e} "
              f"least {worst[0]:.2e} largest {worst[-1]:.2e}", flush=True)


if __name__ == "__main__":
    main()
