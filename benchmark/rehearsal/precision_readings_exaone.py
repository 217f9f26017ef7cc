#!/usr/bin/env python3
"""The readings `k_exaone_moe_rerank`'s `tolerance` was set from, on the chip
(precision_readings_pangu.py's flow, for the family `exaone_moe`; a `benchmark`
PR, which may edit those files, should merge the three behind a family
argument):

  chiprun -- python3 benchmark/rehearsal/precision_readings_exaone.py [--seeds 48] [--fault-seeds 24] [--reference 6] [--tiny 1]

Two comparisons over the harness's own correctness samples (the cell's traffic
file: two requests of 2 rows, 4 rows of 2,048 tokens a seed).

AGAINST THE CONFIGURATION'S `reference.py`, as a run of the benchmark decides
`correct`: for the first `--reference` seeds the plain float32 reference scores
each request on the host's CPU backend, from the program's own init and the
touched embedding rows, as `chip_child.reference_scores` does (over two
minutes a seed on the chip machine's cores, in a thread beside the chip's
work); each variant's scores and the reference's go to `sample_scores.npz` and
`sample_expected.npz` and `run.py::sample_error` reads them, as it stands. A
variant is REFUSED on a seed where that reading is over the file's `tolerance`.

AGAINST THE FAMILY AT FLOAT32 and `highest` matmul precision on the chip (a
stand-in for the reference that takes seconds a seed: more seeds, for the
tails), with the count of (token, routed layer) pairs that chose another set
of experts than it did.

  served             the family as configured: bfloat16 weights, activations as
                     THREE bfloat16 pieces into every product, float32 elsewhere,
                     the router float32 at `highest`
  two pieces         one piece fewer (OPERAND_PIECES 2): what the third piece buys
  one piece          the nearest precision below: every activation rounded to
                     bfloat16 where it enters a product (OPERAND_PIECES 1)
  reference in bf16  the configuration's plain reference computed wholly in bfloat16
and over `--fault-seeds` seeds, each the served step with one fault
  every layer full         no window on the sliding layers
  rotary on the full layer the full layer's queries and keys turned like a sliding layer's
  no head norms            the RMSNorm of the query and key heads left out
  top-7                    one choice fewer a token
  an expert dropped        the last held expert's part left out of every routed layer
  no post norms            the norm after each sub-layer left out

The faults are planted here, not in the program. `--tiny 1` shrinks the widths
so that the flow runs on the CPU; its numbers mean nothing. One process, which
holds the chip.
"""

import argparse
import dataclasses
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
LIMITS = (3e-5, 6e-5, 1e-4, 1.5e-4, 2e-4, 3e-4, 1e-3)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seeds", type=int, default=48)
    parser.add_argument("--fault-seeds", type=int, default=24)
    parser.add_argument("--reference", type=int, default=6)
    parser.add_argument("--tiny", type=int, default=0)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import traffic
    from benchmark.common import load_module, read_json
    from distributed_tf_serving_tpu.models import ModelConfig, build_model, exaone_moe, routed

    here = os.path.join(ROOT, "benchmark", "configs", "k_exaone_moe_rerank")
    config_file = read_json(os.path.join(here, "config.json"))
    shape, tolerance = config_file["toml"]["model"], float(config_file["tolerance"])
    if args.tiny:
        shape.update(num_fields=96, vocab_size=5000, embed_dim=64, intermediate_size=96, num_attention_heads=8,
                     num_key_value_heads=2, head_dim=16, sliding_window=8, moe_intermediate_size=32)
    config = ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in shape.items()})
    layers = config.num_hidden_layers
    model = build_model("exaone_moe", config)
    exact = build_model("exaone_moe", dataclasses.replace(config, compute_dtype="float32"))
    reference = load_module(os.path.join(here, "reference.py"), "bench_reference")
    sizes = {"layer_types": config.layer_types, "window": config.sliding_window, "head": config.head_dim}
    sample_error = load_module(os.path.join(ROOT, "benchmark", "run.py"), "bench_run").sample_error
    mix = read_json(os.path.join(ROOT, "benchmark", "traffic", "rerank_pairs_closed.json"))
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(0)))
    print(f"device {jax.devices()[0].device_kind}, plan {model.layer_plan}, "
          f"{sum(x.size for x in jax.tree.leaves(params)) / 1e9:.3f} B parameters, tolerance {tolerance}", flush=True)

    seeds = [2_950_000_000 + 7919 * i for i in range(max(args.seeds, args.fault_seeds, args.reference))]
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
    def patched(family, module, name, planted):
        """`family`'s step with one name of `module` replaced, returning
        (scores, the experts every routed layer chose)."""
        def run(p, b):
            taken = []
            keep = getattr(module, name)
            setattr(module, name, planted(keep))
            route = routed.route  # the planted one, where `route` is what is planted

            def recording(router, x, k, scaling):
                out = route(router, x, k, scaling)
                taken.append(jnp.sort(out[0], axis=-1))
                return out

            routed.route = recording
            try:
                return family.apply(p, b)["prediction_node"], taken
            finally:
                routed.route = route
                setattr(module, name, keep)
        return jax.jit(run)

    def without_the_last(whole):
        return lambda p, *a, **k: whole({name: w[:-1] for name, w in p.items()}, *a, **k)

    def but(which):
        """Of a layer's four norms in the order the step calls them (query
        heads, key heads, post attention, post FFN): not those in `which`.
        The final norm, the call after the last layer's, stays."""
        def plant(norm):
            calls = []

            def planted(w, x, eps):
                calls.append(None)
                return x if (len(calls) - 1) % 4 in which and len(calls) <= 4 * layers else norm(w, x, eps)
            return planted
        return plant

    def all_full(attention):
        return lambda p, x, s, kind, *rest: attention(p, x, s, "full", *rest)

    def full_turned(attention):
        """The full layer as a sliding one whose window is the whole row (the
        same mask, and the rotary turn it should not have), still in the full
        layer's blocks: one band block of the whole row would not fit the chip."""
        def planted(p, x, s, kind, *rest):
            if kind == "window":
                return attention(p, x, s, kind, *rest)
            band = exaone_moe.band_attention
            exaone_moe.band_attention = lambda q, k, v, window, cd: exaone_moe.blocked_attention(q, k, v, None, cd)
            try:
                return attention(p, x, dict(s, window=x.shape[1]), "window", *rest)
            finally:
                exaone_moe.band_attention = band
        return planted

    def in_bfloat16(p, b):
        keep = reference._f32
        reference._f32 = lambda x: jnp.asarray(x).astype(jnp.bfloat16)
        try:
            return reference.forward(p, b, **sizes), []
        finally:
            reference._f32 = keep

    as_it_is = lambda kept: kept  # noqa: E731
    highest_inner = patched(exact, exaone_moe, "OPERAND_PIECES", as_it_is)

    @jax.jit
    def highest(p, b):
        with jax.default_matmul_precision("highest"):
            return highest_inner(p, b)

    precisions = {
        "served": patched(model, exaone_moe, "OPERAND_PIECES", as_it_is),
        "two pieces": patched(model, exaone_moe, "OPERAND_PIECES", lambda _three: 2),
        "one piece": patched(model, exaone_moe, "OPERAND_PIECES", lambda _three: 1),
        "reference in bf16": jax.jit(in_bfloat16),
    }
    faults = {
        "every layer full": patched(model, exaone_moe, "attention", all_full),
        "rotary on the full layer": patched(model, exaone_moe, "attention", full_turned),
        "no head norms": patched(model, exaone_moe, "rms_norm", but((0, 1))),
        "top-7": patched(model, routed, "route", lambda route: lambda r, x, k, s: route(r, x, k - 1, s)),
        "an expert dropped": patched(model, routed, "held_experts", without_the_last),
        "no post norms": patched(model, exaone_moe, "rms_norm", but((2, 3))),
    }
    variants = {**precisions, **faults}
    scores = {name: [] for name in variants}  # a [4] array a seed
    errors = {name: [] for name in variants}  # against the family at float32
    flipped = {name: 0 for name in ("served", "two pieces", "one piece")}
    pairs, t0 = 0, time.monotonic()
    for i, (sample, ids) in enumerate(zip(samples, folded)):
        batch = {"feat_ids": jnp.asarray(np.concatenate(list(ids.values())).astype(np.int32)),
                 "feat_wts": jnp.asarray(np.concatenate([s["feat_wts"] for s in sample.values()]))}
        on = {name: run for name, run in variants.items()
              if i < (args.seeds if name in precisions else args.fault_seeds) or i < args.reference}
        if not on:
            break
        want, chosen = highest(params, batch)
        want = np.asarray(want, np.float64)
        pairs += sum(int(c.shape[0]) for c in chosen) if i < args.seeds else 0
        for name, run in on.items():
            got, taken = run(params, batch)
            scores[name].append(np.asarray(got))
            errors[name].append(np.abs(np.asarray(got).astype(np.float64) - want))
            if name in flipped and i < args.seeds:
                flipped[name] += sum(int(jnp.sum(jnp.any(a != b, axis=-1))) for a, b in zip(taken, chosen))
        if i == 0 or (i + 1) % 8 == 0:
            print(f"seed {i + 1} scored by {len(on)} variants at {time.monotonic() - t0:.0f}s", flush=True)

    print("\nAGAINST THE FAMILY AT FLOAT32 ON THE CHIP (the stand-in)")
    for name, rows in errors.items():
        if not rows:
            continue
        rows = np.stack(rows)
        worst = np.sort(rows.max(axis=1))
        print(f"{name}: {len(worst)} samples; a row rms {np.sqrt((rows ** 2).mean()):.2e} p99 {np.percentile(rows, 99):.2e}; "
              f"a sample of 4 rows p50 {np.percentile(worst, 50):.2e} p90 {np.percentile(worst, 90):.2e}; its three least "
              f"{' '.join(f'{w:.2e}' for w in worst[:3])}, its three largest {' '.join(f'{w:.2e}' for w in worst[-3:])}; "
              f"samples over {' / '.join(f'{limit:g}' for limit in LIMITS)}: "
              f"{' / '.join(str(int((worst > limit).sum())) for limit in LIMITS)}", flush=True)
    for name, count in flipped.items():
        print(f"{name}: {count} of {pairs} (token, routed layer) pairs chose another top-k set than float32", flush=True)

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
                read.append(sample_error(out_dir))
            read = np.sort(read)
            print(f"{name}: refused on {int((read > tolerance).sum())} of {len(read)}; p50 {np.percentile(read, 50):.2e}; "
                  f"its three least {' '.join(f'{w:.2e}' for w in read[:3])}, its three largest "
                  f"{' '.join(f'{w:.2e}' for w in read[-3:])}", flush=True)


if __name__ == "__main__":
    main()
