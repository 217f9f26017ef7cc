#!/usr/bin/env python3
"""The readings `nemotron3_super_120b_rerank`'s `tolerance` is set from, on the
chip: `precision_readings_sequence.py`'s flow and arguments (that file may not
be edited by the PR that adds a family, and is not forked again), with the
family `nemotron_h`'s row added to its FAMILIES at run time, as
`precision_readings_qwen3_next.py` adds its own:

  chiprun -- python3 benchmark/rehearsal/precision_readings_nemotron_h.py [--seeds 24] [--fault-seeds 8]
      [--reference 6] [--only served,"one piece"] [--tiny 1] [--xla 1]

The variants are traced inside `sequence.serving_attention` with the lists the
batcher hands it, so that on a TPU the routed layers run the grouped kernels
(at the ungated form, over the latent's rows), the Mamba-2 layers the SSD
kernel and the attention layer what `attention_choice` gives it, as the
cell's step does (`--xla 1`: outside it, XLA's paths).

The planted precisions and faults (each the served step with one name replaced
while it is traced; nothing is planted in the program):
  two pieces                      an activation enters a product as two bfloat16 pieces
  a bfloat16 state                the SSD's state carried from chunk to chunk in bfloat16
  the router in bfloat16          the router's operands rounded to bfloat16 before its product
  gates from the biased scores    the selection bias weighs as well as chooses
  top-22 not normalised           norm_topk_prob taken for false
  the scaling left out            routed_scaling_factor taken for 1
  silu for relu squared           } the SHARED expert's activation (the held experts' is inside the
  relu not squared                } grouped kernels on this path, which read no name of the program's;
  a gated expert                  } tests/test_nemotron_h.py plants these three in both at a small size)
  head h % G for h // (H / G)     a Mamba-2 head under the wrong group's B and C
  the norm before the gate        the gated norm's order turned round
  a rotary turn on all 128 dims   rope_theta read as if the attention turned its heads
(The shared expert in the latent and the router on the latent input, which the
tests plant at a size where the latent is as wide as the residual, have no
weights of their shape at the published widths, 1,024 against 4,096.)
"""

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
CONFIG = "nemotron3_super_120b_rerank"


def nemotron_h_faults(config):
    """Rows of (name, module, attribute, planted(kept)) for `nemotron_h`."""
    import jax
    import jax.numpy as jnp

    from distributed_tf_serving_tpu.models import routed

    def resized(**keys):
        """`_sizes` with some of its entries replaced."""
        return lambda sizes: lambda c: dict(sizes(c), **keys)

    def rounded(route):
        bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return lambda router, bias, x, s: route(bf16(router), bias, bf16(x), s)

    def weighed_by_the_bias(_route):
        def planted(router, bias, x, s):
            chosen, _, scores = routed.route(router, x, s["top_k"], s["scaling"], bias=bias)
            top = jnp.take_along_axis(scores + bias, chosen, axis=-1)
            return chosen, top / jnp.sum(top, axis=-1, keepdims=True) * s["scaling"], scores
        return planted

    def act(f):
        return lambda _mlp: lambda p, x, cd, count: routed.dot(f(routed.dot(x, p["up"], cd, count)), p["down"], cd, count)

    def under_the_wrong_group(ssd):
        """Head h reads group `h % G`: the heads handed over in the order that
        puts head h in group `h % G`'s run, and `y` put back in theirs."""
        def planted(x, dt, a, b, c, *rest, **kw):
            heads, groups = x.shape[2], b.shape[2]
            order = jnp.arange(heads).reshape(heads // groups, groups).T.reshape(-1)  # position g * per + i: head g + G i
            y, state = ssd(x[:, :, order], dt[:, :, order], a[order], b, c, *rest, **kw)
            back = jnp.argsort(order)
            return y[:, :, back], state[:, back]
        return planted

    def the_norm_first(_gated_norm):
        def planted(p, y, z, s, eps):
            grouped = y.reshape(y.shape[:-1] + (s["groups"], -1))
            return routed.rms_norm(p["norm"].reshape(s["groups"], -1), grouped, eps).reshape(y.shape) * jax.nn.silu(z)
        return planted

    def turned(blocked_attention):
        def planted(q, k, v, *rest, **kw):
            cos, sin = routed.rope_table(k.shape[1], q.shape[-1], 10000.0)
            at = k.shape[1] - q.shape[1]
            q = routed.rotate(q, cos[at:, None, None, :], sin[at:, None, None, :])
            return blocked_attention(q, routed.rotate(k, cos[:, None, :], sin[:, None, :]), v, *rest, **kw)
        return planted

    head = config.head_dim or config.embed_dim // config.num_attention_heads
    return [
        ("two pieces", "nemotron_h", "OPERAND_PIECES", lambda _kept: 2),
        ("a bfloat16 state", "falcon_h1", "STATE_DTYPE", lambda _f32: jnp.bfloat16),
        ("the router in bfloat16", "nemotron_h", "route", rounded),
        ("gates from the biased scores", "nemotron_h", "route", weighed_by_the_bias),
        (f"top-{config.num_experts_per_tok} not normalised", "nemotron_h", "_sizes", resized(norm_topk=False)),
        ("the scaling left out", "nemotron_h", "_sizes", resized(scaling=1.0)),
        ("silu for relu squared", "routed", "relu2_mlp", act(jax.nn.silu)),
        ("relu not squared", "routed", "relu2_mlp", act(jax.nn.relu)),
        ("a gated expert", "routed", "relu2_mlp", act(lambda u: jax.nn.silu(u) * u)),
        ("head h % G for h // (H / G)", "falcon_h1", "ssd", under_the_wrong_group),
        ("the norm before the gate", "falcon_h1", "gated_norm", the_norm_first),
        (f"a rotary turn on all {head} dims", "sequence", "blocked_attention", turned),
    ]


def reference_sizes(c) -> dict:
    """reference.py's keyword arguments from the served configuration."""
    return {"head": c.head_dim or c.embed_dim // c.num_attention_heads, "ssm_head": c.mamba_d_head,
            "groups": c.mamba_n_groups, "first": c.first_expert_held, "top_k": c.num_experts_per_tok,
            "scaling": c.routed_scaling_factor, "norm_topk": c.norm_topk_prob, "eps": c.layer_norm_eps}


TINY = {"num_fields": 200, "vocab_size": 5000, "embed_dim": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 32, "mamba_d_ssm": 128, "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 32,
        "mamba_n_groups": 2, "mamba_chunk_size": 64, "n_routed_experts": 32, "experts_held": 8, "num_experts_per_tok": 6,
        "moe_latent_size": 128, "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48, "mlp_dims": [32]}


def main() -> None:
    from benchmark.common import load_module
    from distributed_tf_serving_tpu.models import sequence

    flow = load_module(os.path.join(HERE, "precision_readings_sequence.py"), "precision_readings_sequence")
    flow.FAMILIES["nemotron_h"] = ("nemotron_h", reference_sizes, nemotron_h_faults, TINY)
    xla = 0
    if "--xla" in sys.argv:  # this file's own flag, which the flow's parser does not know
        at = sys.argv.index("--xla")
        xla = int(sys.argv[at + 1])
        del sys.argv[at:at + 2]
    if "--config" not in sys.argv:
        sys.argv += ["--config", CONFIG]
    notes, grouped, ssd = [], [], []
    with contextlib.nullcontext() if xla else sequence.serving_attention(notes, grouped=grouped, ssd=ssd):
        flow.main()
    print(f"the variants ran: attention {notes or 'the XLA blocks'}; grouped {grouped}; ssd {ssd}", flush=True)


if __name__ == "__main__":
    main()
