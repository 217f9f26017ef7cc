#!/usr/bin/env python3
"""The readings `sdar_30b_a3b_rerank`'s `tolerance` is set from, on the chip:
`precision_readings_sequence.py`'s flow and arguments (that file may not be
edited by the PR that adds a family, and is not forked again), with the family
`sdar_moe`'s row added to its FAMILIES at run time, as
`precision_readings_nemotron_h.py` adds its own:

  chiprun -- python3 benchmark/rehearsal/precision_readings_sdar_moe.py [--seeds 24] [--fault-seeds 8]
      [--reference 6] [--only served,"one piece"] [--tiny 1] [--xla 1]

The variants are traced inside `sequence.serving_attention` with the lists the
batcher hands it, so that on a TPU the attention at all positions runs the
Pallas kernel under the block mask and the routed layers the grouped kernels
over the layer held whole, as the cell's step does (`--xla 1`: outside it,
XLA's paths).

The planted precisions and faults (each the served step with one name replaced
while it is traced; nothing is planted in the program):
  two pieces                                  an activation enters a product as two bfloat16 pieces
  the router in bfloat16                      the router's operands rounded to bfloat16 before its product
  the causal mask in place of the block mask  block_length read as 1: what the autoregressive parent computes
  a block of 8 in place of 4                  block_length read as 8
  no RMS on the query heads                   q_norm left out (k_norm kept)
  the rotary turn on half the dims            partial_rotary_factor 0.5: pairs (i, i + 32), a table half as wide
  gates not normalised                        norm_topk_prob taken for false
  sigmoid scores in place of the softmax      the router's scoring of four other families
  top-7                                       num_experts_per_tok read as 7
"""

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
CONFIG = "sdar_30b_a3b_rerank"


def sdar_moe_faults(config):
    """Rows of (name, module, attribute, planted(kept)) for `sdar_moe`."""
    import jax.numpy as jnp

    from distributed_tf_serving_tpu.models import routed

    def resized(**keys):
        """`_sizes` with some of its entries replaced."""
        return lambda sizes: lambda c: dict(sizes(c), **keys)

    def rounded(route):
        bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return lambda router, x, *rest, **kw: route(bf16(router), bf16(x), *rest, **kw)

    def sigmoid(_route):
        return lambda router, x, k, scaling, normalise=True: routed.route(router, x, k, scaling, "sigmoid", normalise)

    return [
        ("two pieces", "sdar_moe", "OPERAND_PIECES", lambda _kept: 2),
        ("the router in bfloat16", "sdar_moe", "route", rounded),
        ("the causal mask in place of the block mask", "sdar_moe", "_sizes", resized(span=1)),
        ("a block of 8 in place of 4", "sdar_moe", "_sizes", resized(span=8)),
        ("no RMS on the query heads", "sdar_moe", "qk_norm",
         lambda _norm: lambda p, q, k, eps: (q, routed.rms_norm(p["k_norm"], k, eps))),
        ("the rotary turn on half the dims", "sdar_moe", "rotate",
         lambda rotate: lambda x, cos, sin: rotate(x, cos[..., ::2], sin[..., ::2], x.shape[-1] // 2)),
        ("gates not normalised", "sdar_moe", "_sizes", resized(norm_topk=False)),
        ("sigmoid scores in place of the softmax", "sdar_moe", "route", sigmoid),
        (f"top-{config.num_experts_per_tok - 1}", "sdar_moe", "_sizes", resized(top_k=config.num_experts_per_tok - 1)),
    ]


def reference_sizes(c) -> dict:
    """reference.py's keyword arguments from the served configuration."""
    return {"head": c.head_dim or c.embed_dim // c.num_attention_heads, "theta": c.rope_theta, "eps": c.layer_norm_eps,
            "block": c.block_length, "first": c.first_expert_held, "top_k": c.num_experts_per_tok,
            "norm_topk": c.norm_topk_prob}


TINY = {"num_fields": 200, "vocab_size": 5000, "embed_dim": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 32, "num_experts": 16, "experts_held": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
        "mlp_dims": [32]}


def main() -> None:
    from benchmark.common import load_module
    from distributed_tf_serving_tpu.models import sequence

    flow = load_module(os.path.join(HERE, "precision_readings_sequence.py"), "precision_readings_sequence")
    flow.FAMILIES["sdar_moe"] = ("sdar_moe", reference_sizes, sdar_moe_faults, TINY)
    xla = 0
    if "--xla" in sys.argv:  # this file's own flag, which the flow's parser does not know
        at = sys.argv.index("--xla")
        xla = int(sys.argv[at + 1])
        del sys.argv[at:at + 2]
    if "--config" not in sys.argv:
        sys.argv += ["--config", CONFIG]
    notes, grouped = [], []
    with contextlib.nullcontext() if xla else sequence.serving_attention(notes, grouped=grouped):
        flow.main()
    print(f"the variants ran: attention {notes or 'the XLA blocks'}; grouped {grouped}", flush=True)


if __name__ == "__main__":
    main()
