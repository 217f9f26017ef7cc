#!/usr/bin/env python3
"""The readings `qwen3_next_80b_rerank`'s `tolerance` is set from, on the chip:
`precision_readings_sequence.py`'s flow and arguments (that file may not be
edited by the PR that adds a family, and is not forked again), with the family
`qwen3_next`'s row added to its FAMILIES at run time, as
`precision_readings_falcon_h1.py` adds its own:

  chiprun -- python3 benchmark/rehearsal/precision_readings_qwen3_next.py [--seeds 24] [--fault-seeds 8]
      [--reference 6] [--only served,"one piece"] [--tiny 1] [--xla 1]

The variants are traced inside `sequence.serving_attention` with the lists the
batcher hands it, so that on a TPU the routed layers run the grouped kernels,
the rules the delta kernel and the full layer what `attention_choice` gives it,
as the cell's step does (`--xla 1`: outside it, XLA's paths).

The planted precisions and faults (each the served step with one name replaced
while it is traced; nothing is planted in the program):
  two pieces                     an activation enters a product as two bfloat16 pieces
  a bfloat16 state               the rule's state carried from chunk to chunk in bfloat16
  the router in bfloat16         the router's operands rounded to bfloat16 before its product
  w for 1 + w                    the zero-centred norms read as plain ones
  no attention gate              the full layer's sigmoid(gate_h) left out
  no shared gate                 the shared expert's sigmoid(b . w_sg) left out
  sigmoid for softmax            the router scored an expert alone
  top-10 not normalised          norm_topk_prob taken for false
  b doubled                      linear_allow_neg_eigval taken for true
  key head h % 16 for h // 2     a value head under the wrong key head
  rotary on all 256 dims         partial_rotary_factor taken for 1
"""

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
CONFIG = "qwen3_next_80b_rerank"


def qwen3_next_faults(config):
    """Rows of (name, module, attribute, planted(kept)) for `qwen3_next`."""
    import jax.numpy as jnp

    from distributed_tf_serving_tpu.models import routed

    def resized(**keys):
        """`_sizes` with some of its entries replaced."""
        return lambda sizes: lambda c: dict(sizes(c), **keys)

    def rounded(route):
        bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return lambda router, x, *rest, **kw: route(bf16(router), bf16(x), *rest, **kw)

    def under_the_wrong_key_head(rule):
        def planted(q, k, v, g, b, *rest, **kw):
            r = v.shape[2] // q.shape[2]
            return rule(jnp.tile(q, (1, 1, r, 1)), jnp.tile(k, (1, 1, r, 1)), v, g, b, *rest, **kw)
        return planted

    head = config.head_dim or config.embed_dim // config.num_attention_heads
    return [
        ("two pieces", "qwen3_next", "OPERAND_PIECES", lambda _kept: 2),
        ("a bfloat16 state", "olmo_hybrid", "STATE_DTYPE", lambda _f32: jnp.bfloat16),
        ("the router in bfloat16", "qwen3_next", "route", rounded),
        ("w for 1 + w", "qwen3_next", "rms0", lambda _rms0: routed.rms_norm),
        ("no attention gate", "qwen3_next", "attention_gate", lambda _gate: lambda o, gate: o),
        ("no shared gate", "routed", "routed_ffn",
         lambda ffn: lambda layer, *a, **kw: ffn({k: v for k, v in layer.items() if k != "shared_gate"}, *a, **kw)),
        ("sigmoid for softmax", "qwen3_next", "route",
         lambda _route: lambda router, x, k, scaling, normalise=True: routed.route(
             router, x, k, scaling, "sigmoid", normalise)),
        ("top-10 not normalised", "qwen3_next", "_sizes", resized(norm_topk=False)),
        ("b doubled", "qwen3_next", "_sizes", resized(neg=True)),
        ("key head h % 16 for h // 2", "olmo_hybrid", "gated_delta_rule", under_the_wrong_key_head),
        ("rotary on all 256 dims", "qwen3_next", "_sizes", resized(rotary=head)),
    ]


def reference_sizes(c) -> dict:
    """reference.py's keyword arguments from the served configuration."""
    head = c.head_dim or c.embed_dim // c.num_attention_heads
    return {"first": c.first_expert_held, "top_k": c.num_experts_per_tok, "head": head,
            "rotary": int(head * c.partial_rotary_factor), "theta": c.rope_theta, "key_dim": c.linear_key_head_dim,
            "eps": c.layer_norm_eps, "neg_eigval": c.linear_allow_neg_eigval}


TINY = {"num_fields": 200, "vocab_size": 5000, "embed_dim": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 32, "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 16,
        "linear_value_head_dim": 24, "num_experts": 32, "experts_held": 8, "num_experts_per_tok": 4,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32, "mlp_dims": [32]}


def main() -> None:
    from benchmark.common import load_module
    from distributed_tf_serving_tpu.models import sequence

    flow = load_module(os.path.join(HERE, "precision_readings_sequence.py"), "precision_readings_sequence")
    flow.FAMILIES["qwen3_next"] = ("qwen3_next", reference_sizes, qwen3_next_faults, TINY)
    xla = 0
    if "--xla" in sys.argv:  # this file's own flag, which the flow's parser does not know
        at = sys.argv.index("--xla")
        xla = int(sys.argv[at + 1])
        del sys.argv[at:at + 2]
    if "--config" not in sys.argv:
        sys.argv += ["--config", CONFIG]
    notes, grouped, delta = [], [], []
    with contextlib.nullcontext() if xla else sequence.serving_attention(notes, grouped=grouped, delta=delta):
        flow.main()
    print(f"the variants ran: attention {notes or 'the XLA blocks'}; grouped {grouped}; delta_rule {delta}", flush=True)


if __name__ == "__main__":
    main()
