#!/usr/bin/env python3
"""The readings `mimo_v2_5_rerank`'s `tolerance` is set from, on the chip:
`precision_readings_sequence.py`'s flow and arguments (that file may not be
edited by the PR that adds a family, and is not forked again), with the family
`mimo_v2`'s row added to its FAMILIES at run time:

  chiprun -- python3 benchmark/rehearsal/precision_readings_mimo_v2.py [--seeds 24] [--fault-seeds 8]
      [--reference 6] [--only served,"one piece"] [--tiny 1] [--sink-seeds 2] [--xla 1]

Two things beside that flow. The variants are traced inside
`sequence.serving_attention`, as the batcher traces the served entry, so that
on a TPU every layer but the last runs the Pallas kernel the cell runs
(`--xla 1`: outside it, the XLA blocks; two variants need it at the cell's
size, where a full-size tile stands at the edge of the kernel's 16 MiB of
VMEM: `a piece more`, whose four pieces ask for 16.82 MB, and `every layer
full`, a 512-wide tile with a window's mask AND a sink, 17.07 MB: my chip
runs, PR 50). And BEFORE the flow, over
`--sink-seeds` of the harness's samples (0: none), the served step's own
counters `attn.sink_mass_ppm / attn.sink_rows / 1e4` beside the reference's
`sink_mass_pct` over the same rows (float32, `highest`, on the default
device), which is what `attn_sink_mass_pct.bulk` reads over a window.

The planted faults (each the served step with one name replaced while it is
traced; nothing is planted in the program):
  sink left out               the window layers' softmax over their keys alone
  sink on the full layers too a drawn logit a head joins the full layers' softmax as well
  one rotary base             the window layers' base on the full layers too
  rotary on all dims          all 192 dims of a head turned, not the first 64
  values unscaled             attention_value_scale left out
  window kv heads as full     a window layer reads the first 4 of its 8 key-value heads, 16 query heads each
  top-7                       one choice fewer a token
  an expert dropped           the last held expert's part left out of every routed layer
  every layer full            no window on the window layers (sink, base and heads as they are)
"""

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
CONFIG = "mimo_v2_5_rerank"


def mimo_v2_faults(config):
    """Rows of (name, module, attribute, planted(kept)) for `mimo_v2`."""
    import jax

    def resized(change):
        """`mimo_v2.attention` with its layer's tree and sizes changed by
        `change(p, s, kind, x) -> (p, s)`."""
        def plant(attention):
            def planted(p, x, s, kind, *rest):
                p, s = change(p, s, kind, x)
                return attention(p, x, s, kind, *rest)
            return planted
        return plant

    def sink_everywhere(p, s, kind, x):
        drawn = jax.random.normal(jax.random.PRNGKey(50), (s["heads"],)) * 3.0
        return dict(p, sink=p.get("sink", drawn)), dict(s, sink={"full": True, "window": True})

    def fewer_heads(p, s, kind, x):
        if kind != "window":
            return p, s
        n = s["kv"]["full"]
        return (dict(p, k=p["k"][:, :n * s["head"]], v=p["v"][:, :n * s["v_head"]]),
                dict(s, kv=dict(s["kv"], window=n)))

    def without_the_last(whole):
        return lambda p, *a, **k: whole({name: w[:-1] for name, w in p.items()}, *a, **k)

    return [
        ("sink left out", "mimo_v2", "attention",
         resized(lambda p, s, kind, x: (p, dict(s, sink={"full": False, "window": False})))),
        ("sink on the full layers too", "mimo_v2", "attention", resized(sink_everywhere)),
        ("one rotary base", "mimo_v2", "attention",
         resized(lambda p, s, kind, x: (p, dict(s, theta=dict(s["theta"], full=s["theta"]["window"]))))),
        ("rotary on all dims", "mimo_v2", "attention", resized(lambda p, s, kind, x: (p, dict(s, rotary=s["head"])))),
        ("values unscaled", "mimo_v2", "attention", resized(lambda p, s, kind, x: (p, dict(s, value_scale=1.0)))),
        ("window kv heads as full", "mimo_v2", "attention", resized(fewer_heads)),
        ("top-7", "routed", "route", lambda route: lambda r, x, k, scaling: route(r, x, k - 1, scaling)),
        ("an expert dropped", "routed", "held_experts", without_the_last),
        ("every layer full", "mimo_v2", "attention", resized(lambda p, s, kind, x: (p, dict(s, window=x.shape[1])))),
    ]


def reference_sizes(c) -> dict:
    """reference.py's keyword arguments from the served configuration."""
    return {
        "hybrid_layer_pattern": c.hybrid_layer_pattern, "window": c.sliding_window, "head": c.head_dim,
        "v_head": c.v_head_dim, "rotary": int(c.head_dim * c.partial_rotary_factor), "theta_full": c.rope_theta,
        "theta_window": c.swa_rope_theta, "value_scale": c.attention_value_scale, "top_k": c.num_experts_per_tok,
        "scaling": c.routed_scaling_factor, "first": c.first_expert_held, "eps": c.layer_norm_eps,
    }


TINY = {"num_fields": 200, "vocab_size": 5000, "embed_dim": 64, "intermediate_size": 96, "num_attention_heads": 8,
        "num_key_value_heads": 2, "swa_num_key_value_heads": 4, "head_dim": 24, "v_head_dim": 16,
        "sliding_window": 16, "moe_intermediate_size": 32}


def sink_readings(seeds: int, tiny: bool) -> None:
    """The served step's sink counters beside the reference's, over the same rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import traffic
    from benchmark.common import load_module, read_json
    from distributed_tf_serving_tpu.models import ModelConfig, build_model

    here = os.path.join(ROOT, "benchmark", "configs", CONFIG)
    shape = read_json(os.path.join(here, "config.json"))["toml"]["model"]
    if tiny:
        shape.update(TINY)
    config = ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in shape.items()})
    model = build_model("mimo_v2", config)
    reference = load_module(os.path.join(here, "reference.py"), "bench_reference")
    mix = read_json(os.path.join(ROOT, "benchmark", "traffic", "rerank_pairs_closed.json"))
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(0)))
    step = jax.jit(model.apply_stats)
    names = list(model.step_stats)

    @jax.jit
    def expected(p, b):
        with jax.default_matmul_precision("highest"):
            return reference.sink_mass_pct(p, b, **reference_sizes(config))

    for i in range(seeds):
        sample = traffic.sample_requests(mix, shape, 2_970_000_000 + 7919 * i)
        batch = {"feat_ids": jnp.asarray(np.concatenate(
                     [s["feat_ids"] % config.vocab_size for s in sample.values()]).astype(np.int32)),
                 "feat_wts": jnp.asarray(np.concatenate([s["feat_wts"] for s in sample.values()]))}
        stats = dict(zip(names, np.asarray(step(params, batch)[1]).tolist()))
        served = stats["attn.sink_mass_ppm"] / stats["attn.sink_rows"] / 1e4
        want = float(expected(params, batch))
        print(f"sink mass, seed {i + 1}: served step {served:.4f}% over {stats['attn.sink_rows']} (row, layer) pairs, "
              f"reference {want:.4f}%, relative difference {abs(served - want) / want:.2e}; "
              f"held assignments a token {stats['moe.assignments_here'] / stats['moe.tokens']:.4f}", flush=True)


def main() -> None:
    from benchmark.common import load_module
    from distributed_tf_serving_tpu.models import sequence

    flow = load_module(os.path.join(HERE, "precision_readings_sequence.py"), "precision_readings_sequence")
    flow.FAMILIES["mimo_v2"] = ("mimo_v2", reference_sizes, mimo_v2_faults, TINY)

    def taken(flag: str, default: int) -> int:  # this file's own flags, which the flow's parser does not know
        if flag not in sys.argv:
            return default
        at = sys.argv.index(flag)
        value = int(sys.argv[at + 1])
        del sys.argv[at:at + 2]
        return value

    sink_seeds, xla = taken("--sink-seeds", 2), taken("--xla", 0)
    if "--config" not in sys.argv:
        sys.argv += ["--config", CONFIG]
    tiny = "--tiny" in sys.argv and sys.argv[sys.argv.index("--tiny") + 1] != "0"
    notes: list = []
    with contextlib.nullcontext() if xla else sequence.serving_attention(notes):
        if sink_seeds:
            sink_readings(sink_seeds, tiny)
        flow.main()
    print(f"the attention the variants ran: {notes or 'the XLA blocks'}", flush=True)


if __name__ == "__main__":
    main()
