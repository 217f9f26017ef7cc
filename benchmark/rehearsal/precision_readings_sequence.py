#!/usr/bin/env python3
"""The readings a sequence configuration's `tolerance` is set from, on the
chip, for a configuration given as an ARGUMENT (the three forks beside this
file, one a family, stay as they are; a `benchmark` PR, which may edit them,
should fold them into this one's table):

  chiprun -- python3 benchmark/rehearsal/precision_readings_sequence.py --config olmo_hybrid_rerank
      [--seeds 24] [--fault-seeds 8] [--reference 6] [--only served,"one piece"] [--tiny 1]

Two comparisons over the harness's own correctness samples (the cell's traffic
file: two requests of 2 rows a seed).

AGAINST THE CONFIGURATION'S `reference.py`, as a run of the benchmark decides
`correct`: for the first `--reference` seeds the plain float32 reference scores
each request on the host's CPU backend, from the program's own init and the
touched embedding rows, as `chip_child.reference_scores` does (in a thread
beside the chip's work); each variant's scores and the reference's go to
`sample_scores.npz` and `sample_expected.npz` and `run.py::sample_error` reads
them, as it stands. A variant is REFUSED on a seed where that reading is over
the file's `tolerance`.

AGAINST THE FAMILY AT FLOAT32 and `highest` matmul precision on the chip (a
stand-in for the reference that takes seconds a seed: more seeds, for the
tails).

Every family is read at
  served             the family as configured
  a piece more       OPERAND_PIECES + 1: what the next piece would buy
  one piece          the nearest precision below: every activation rounded to
                     the compute dtype where it enters a product
  reference in bf16  the configuration's plain reference computed wholly in bfloat16
and, over `--fault-seeds` seeds, at the served step with one fault of FAMILIES'
table each: a row is (the fault's name, the module and the name in it that is
replaced, what takes its place given what was there). The faults are planted
here, not in the program. The NEXT family adds its rows to FAMILIES; no new
script. `--tiny 1` shrinks the widths so that the flow runs on the CPU; its
numbers mean nothing. One process, which holds the chip.
"""

import argparse
import dataclasses
import importlib
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
LIMITS = (3e-5, 6e-5, 1e-4, 1.5e-4, 2e-4, 3e-4, 1e-3)
MODELS = "distributed_tf_serving_tpu.models."


def olmo_hybrid_faults(config):
    """Rows of (name, module, attribute, planted(kept)) for `olmo_hybrid`."""
    import jax
    import jax.numpy as jnp

    from distributed_tf_serving_tpu.models import routed

    head = config.head_dim or config.embed_dim // config.num_attention_heads

    def per_head(_whole):
        def planted(p, q, k, eps):
            norm = lambda w, x: routed.rms_norm(  # noqa: E731
                w.reshape(-1, head), x.reshape(x.shape[:-1] + (-1, head)), eps).reshape(x.shape)
            return norm(p["q_norm"], q), norm(p["k_norm"], k)
        return planted

    def turned(blocked):
        def planted(q, k, v, window, cd, count):
            cos, sin = routed.rope_table(k.shape[1], q.shape[-1], 10000.0)
            first = k.shape[1] - q.shape[1]
            q = routed.rotate(q, cos[first:, None, None, :], sin[first:, None, None, :])
            return blocked(q, routed.rotate(k, cos[:, None, :], sin[:, None, :]), v, window, cd, count)
        return planted

    return [
        ("a bfloat16 state", "olmo_hybrid", "STATE_DTYPE", lambda _f32: jnp.bfloat16),
        ("b not doubled", "olmo_hybrid", "_sizes", lambda sizes: lambda c: dict(sizes(c), neg=False)),
        ("no decay", "olmo_hybrid", "gated_delta_rule",
         lambda rule: lambda q, k, v, g, b, *rest, **kw: rule(q, k, v, jnp.zeros_like(g), b, *rest, **kw)),
        ("no l2 norm", "olmo_hybrid", "l2_norm", lambda _norm: lambda x: x),
        ("no convolution", "sequence", "causal_conv", lambda _conv: lambda x, w, b=None: jax.nn.silu(x)),
        ("no output gate", "olmo_hybrid", "out_gate",
         lambda _gate: lambda p, o, x, cd, eps: routed.rms_norm(p["o_norm"], o, eps)),
        ("query norm per head", "olmo_hybrid", "qk_norm", per_head),
        ("rotary on", "sequence", "blocked_attention", turned),
    ]


# model_kind -> (the module that holds OPERAND_PIECES, the reference's keyword
# arguments from the config, the planted faults' rows, what --tiny 1 shrinks)
FAMILIES = {
    "olmo_hybrid": (
        "olmo_hybrid",
        lambda c: {"layer_types": c.layer_types, "head": c.head_dim, "eps": c.layer_norm_eps,
                   "neg_eigval": c.linear_allow_neg_eigval},
        olmo_hybrid_faults,
        {"num_fields": 200, "vocab_size": 5000, "embed_dim": 64, "intermediate_size": 96, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "linear_num_key_heads": 3, "linear_num_value_heads": 3,
         "linear_key_head_dim": 8, "linear_value_head_dim": 12}),
}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--config", required=True, help="a directory of benchmark/configs")
    parser.add_argument("--seeds", type=int, default=24)
    parser.add_argument("--fault-seeds", type=int, default=8)
    parser.add_argument("--reference", type=int, default=6)
    parser.add_argument("--tiny", type=int, default=0)
    parser.add_argument("--only", default="", help="variants to read, by name and comma-separated; all where empty")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import traffic
    from benchmark.common import load_module, read_json
    from distributed_tf_serving_tpu.models import ModelConfig, build_model

    here = os.path.join(ROOT, "benchmark", "configs", args.config)
    config_file = read_json(os.path.join(here, "config.json"))
    shape, tolerance = config_file["toml"]["model"], float(config_file["tolerance"])
    kind = config_file["toml"]["server"]["model_kind"]
    if kind not in FAMILIES:
        sys.exit(f"no row for the family {kind!r} in FAMILIES: add one (have {sorted(FAMILIES)})")
    pieces_in, reference_sizes, fault_rows, tiny = FAMILIES[kind]
    if args.tiny:
        shape.update(tiny)
    config = ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in shape.items()})
    family = importlib.import_module(MODELS + pieces_in)
    model = build_model(kind, config)
    exact = build_model(kind, dataclasses.replace(config, compute_dtype="float32"))
    reference = load_module(os.path.join(here, "reference.py"), "bench_reference")
    sizes = reference_sizes(config)
    sample_error = load_module(os.path.join(ROOT, "benchmark", "run.py"), "bench_run").sample_error
    cell = next(c for c in read_json(os.path.join(ROOT, "BENCHMARK.json"))["workloads"] if c["config"] == args.config)
    mix = read_json(os.path.join(ROOT, "benchmark", "traffic", cell["traffic"] + ".json"))
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(0)))
    print(f"device {jax.devices()[0].device_kind}, {kind} {model.layer_plan}, "
          f"{sum(x.size for x in jax.tree.leaves(params)) / 1e9:.3f} B parameters, tolerance {tolerance}, "
          f"OPERAND_PIECES {family.OPERAND_PIECES}", flush=True)

    seeds = [2_960_000_000 + 7919 * i for i in range(max(args.seeds, args.fault_seeds, args.reference))]
    samples = [traffic.sample_requests(mix, shape, seed) for seed in seeds]
    folded = [{name: s["feat_ids"] % config.vocab_size for name, s in sample.items()} for sample in samples]

    # ---- the configuration's reference on the host, as chip_child.reference_scores scores it
    expected: list[dict] = []

    def score_on_the_host() -> None:
        t0 = time.monotonic()
        touched, inverse = np.unique(
            np.concatenate([f.ravel() for sample in folded[:args.reference] for f in sample.values()]),
            return_inverse=True)
        small = jax.tree.map(np.asarray, {k: v for k, v in params.items() if k != "embedding"})
        small["embedding"] = np.asarray(jnp.take(params["embedding"], jnp.asarray(touched.astype(np.int32)), axis=0))
        forward, at = jax.jit(lambda p, b: reference.forward(p, b, **sizes)), 0
        with jax.default_device(jax.devices("cpu")[0]), jax.default_matmul_precision("highest"):
            for sample, ids in zip(samples[:args.reference], folded):
                out = {}
                for name, arrays in sample.items():
                    n = ids[name].size
                    rows = inverse[at:at + n].reshape(ids[name].shape).astype(np.int32)
                    at += n
                    out[name] = np.asarray(forward(small, dict(arrays, feat_ids=rows)))
                expected.append(out)
                print(f"reference.py scored seed {len(expected)} of {args.reference} on the host at "
                      f"{time.monotonic() - t0:.0f}s", flush=True)

    host = threading.Thread(target=score_on_the_host)
    if args.reference:
        host.start()

    # ---- the variants, on the chip
    def patched(served, module, name, planted):
        """`served`'s step with one name of `module` replaced while it is traced."""
        module = importlib.import_module(MODELS + module)

        def run(p, b):
            keep = getattr(module, name)
            setattr(module, name, planted(keep))
            try:
                return served.apply(p, b)["prediction_node"]
            finally:
                setattr(module, name, keep)
        return jax.jit(run)

    def in_bfloat16(p, b):
        keep = reference._f32
        reference._f32 = lambda x: jnp.asarray(x).astype(jnp.bfloat16)
        try:
            return reference.forward(p, b, **sizes)
        finally:
            reference._f32 = keep

    highest_inner = patched(exact, pieces_in, "OPERAND_PIECES", lambda kept: kept)

    @jax.jit
    def highest(p, b):
        with jax.default_matmul_precision("highest"):
            return highest_inner(p, b)

    precisions = {
        "served": patched(model, pieces_in, "OPERAND_PIECES", lambda kept: kept),
        "a piece more": patched(model, pieces_in, "OPERAND_PIECES", lambda kept: kept + 1),
        "one piece": patched(model, pieces_in, "OPERAND_PIECES", lambda _kept: 1),
        "reference in bf16": jax.jit(in_bfloat16),
    }
    faults = {name: patched(model, module, attr, planted) for name, module, attr, planted in fault_rows(config)}
    variants = {**precisions, **faults}
    if args.only:
        variants = {name: variants[name] for name in args.only.split(",")}
    scores = {name: [] for name in variants}  # a [rows] array a seed
    errors = {name: [] for name in variants}  # against the family at float32
    t0 = time.monotonic()
    for i, (sample, ids) in enumerate(zip(samples, folded)):
        batch = {"feat_ids": jnp.asarray(np.concatenate(list(ids.values())).astype(np.int32)),
                 "feat_wts": jnp.asarray(np.concatenate([s["feat_wts"] for s in sample.values()]))}
        on = {name: run for name, run in variants.items()
              if i < (args.seeds if name in precisions else args.fault_seeds) or i < args.reference}
        if not on:
            break
        want = np.asarray(highest(params, batch), np.float64)
        for name, run in on.items():
            got = np.asarray(run(params, batch))
            scores[name].append(got)
            errors[name].append(np.abs(got.astype(np.float64) - want))
        if i == 0 or (i + 1) % 8 == 0:
            print(f"seed {i + 1} scored by {len(on)} variants at {time.monotonic() - t0:.0f}s", flush=True)

    def line(name, worst, limit=None):
        worst = np.sort(np.where(np.isnan(worst), np.inf, worst))  # a score that is no number is refused
        refused = "" if limit is None else f"refused on {int((worst > limit).sum())} of {len(worst)}; "
        return (f"{name}: {refused}{len(worst)} samples; p50 {np.percentile(worst, 50):.2e}; its three least "
                f"{' '.join(f'{w:.2e}' for w in worst[:3])}, its three largest {' '.join(f'{w:.2e}' for w in worst[-3:])}")

    print("\nAGAINST THE FAMILY AT FLOAT32 ON THE CHIP (the stand-in); the largest of a sample's rows")
    for name, rows in errors.items():
        if rows:
            worst = np.stack(rows).max(axis=1)
            print(line(name, worst) + "; samples over " + " / ".join(f"{limit:g}" for limit in LIMITS) + ": "
                  + " / ".join(str(int((np.nan_to_num(worst, nan=np.inf) > limit).sum())) for limit in LIMITS), flush=True)

    if not args.reference:
        return
    host.join()
    print(f"\nAGAINST reference.py ON THE HOST, THROUGH run.py::sample_error, OVER {args.reference} SEEDS; tolerance {tolerance}")
    with tempfile.TemporaryDirectory() as out_dir:
        for name in variants:
            read = []
            for want, got in zip(expected, scores[name]):
                cuts = np.cumsum([len(v) for v in want.values()])[:-1]
                np.savez(os.path.join(out_dir, "sample_expected.npz"), **want)
                np.savez(os.path.join(out_dir, "sample_scores.npz"), **dict(zip(want, np.split(got, cuts))))
                # sample_error's max() passes a NaN by; read it for what it is
                read.append(sample_error(out_dir) if np.isfinite(got).all() else np.nan)
            print(line(name, np.asarray(read), tolerance), flush=True)


if __name__ == "__main__":
    main()
