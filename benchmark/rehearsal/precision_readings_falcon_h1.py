#!/usr/bin/env python3
"""The readings `falcon_h1_34b_rerank`'s `tolerance` is set from, on the chip:
`precision_readings_sequence.py`'s flow and arguments (that file may not be
edited by the PR that adds a family, and is not forked again), with the family
`falcon_h1`'s row added to its FAMILIES at run time, as
`precision_readings_mimo_v2.py` adds its own:

  chiprun -- python3 benchmark/rehearsal/precision_readings_falcon_h1.py [--seeds 24] [--fault-seeds 8]
      [--reference 6] [--only served,"one piece"] [--tiny 1] [--xla 1]

The variants are traced inside `sequence.serving_attention`, as the batcher
traces the served entry, so that on a TPU every layer but the last runs the
Pallas attention kernel the cell runs (`--xla 1`: outside it, XLA's blocks).

The planted faults (each the served step with one name replaced while it is
traced; nothing is planted in the program):
  a bfloat16 state          the SSD's state carried from chunk to chunk in bfloat16
  <multiplier> = 1          each of the twelve multipliers taken for 1 (attention_in_multiplier, 1 as published, for 2)
  slices in another order   the five ssm_multipliers read dt, C, B, x, z
  no convolution bias       mamba_conv_bias taken for false
  no D x                    the skip past the state left out
  norm before gate          mamba_norm_before_gate taken for true
  one norm group for two    the gated norm's RMS over all 4,096 channels
  rotary off                q and k unturned
  dt_bias off               dt = softplus(dt)
  B and C of the wrong group  a head reads the other group's B and C
"""

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
CONFIG = "falcon_h1_34b_rerank"
MULTIPLIERS = ("embedding_multiplier", "attention_in_multiplier", "attention_out_multiplier", "key_multiplier",
               "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers")
# the name `models/falcon_h1.py::_sizes` gives each multiplier
SIZES_KEY = {"embedding_multiplier": "embed_mult", "attention_in_multiplier": "attn_in",
             "attention_out_multiplier": "attn_out", "key_multiplier": "key_mult", "ssm_in_multiplier": "ssm_in",
             "ssm_out_multiplier": "ssm_out", "ssm_multipliers": "ssm_mults", "mlp_multipliers": "mlp_mults"}


def falcon_h1_faults(config):
    """Rows of (name, module, attribute, planted(kept)) for `falcon_h1`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def resized(key, value):
        """`_sizes` with one multiplier replaced."""
        return lambda sizes: lambda c: dict(sizes(c), **{key: value(sizes(c)[key])})

    def at(index):
        return lambda kept: tuple(1.0 if i == index else m for i, m in enumerate(kept))

    def before_the_gate(_gated):
        def planted(p, y, z, s, eps):
            grouped = y.reshape(y.shape[:-1] + (s["groups"], -1))
            grouped = grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
            return grouped.reshape(y.shape) * p["norm"].astype(jnp.float32) * jax.nn.silu(z)
        return planted

    rows = [("a bfloat16 state", "falcon_h1", "STATE_DTYPE", lambda _f32: jnp.bfloat16)]
    for name in MULTIPLIERS[:6]:
        # attention_in_multiplier is 1 as published: taken for 2, or the fault is the served step
        value = (lambda kept: 2.0) if getattr(config, name) == 1.0 else (lambda kept: 1.0)
        rows.append((f"{name} = {value(None):g}", "falcon_h1", "_sizes", resized(SIZES_KEY[name], value)))
    rows += [(f"ssm_multipliers[{part}] = 1", "falcon_h1", "_sizes", resized("ssm_mults", at(i)))
             for i, part in enumerate(("z", "x", "B", "C", "dt"))]
    rows += [(f"mlp_multipliers[{part}] = 1", "falcon_h1", "_sizes", resized("mlp_mults", at(i)))
             for i, part in enumerate(("gate", "down"))]
    rows += [
        ("slices in another order", "falcon_h1", "slice_multipliers",
         lambda _spread: lambda s: np.repeat(np.asarray(s["ssm_mults"][::-1], np.float32), s["widths"])),
        ("no convolution bias", "sequence", "causal_conv", lambda conv: lambda x, w, b=None: conv(x, w)),
        ("no D x", "falcon_h1", "skip", lambda _skip: lambda p, y, x: y),
        ("norm before gate", "falcon_h1", "gated_norm", before_the_gate),
        ("one norm group for two", "falcon_h1", "gated_norm",
         lambda norm: lambda p, y, z, s, eps: norm(p, y, z, dict(s, groups=1), eps)),
        ("rotary off", "falcon_h1", "rotate", lambda _rotate: lambda x, cos, sin, width=None: x),
        ("dt_bias off", "falcon_h1", "time_steps", lambda _steps: lambda p, dt: jax.nn.softplus(dt)),
        ("B and C of the wrong group", "falcon_h1", "ssd",
         lambda ssd: lambda x, dt, a, b, c, *rest, **kw: ssd(x, dt, a, b[:, :, ::-1], c[:, :, ::-1], *rest, **kw)),
    ]
    return rows


def reference_sizes(c) -> dict:
    """reference.py's keyword arguments from the served configuration."""
    return {"head": c.head_dim, "ssm_head": c.mamba_d_head, "groups": c.mamba_n_groups, "theta": c.rope_theta,
            "eps": c.layer_norm_eps, **{name: getattr(c, name) for name in MULTIPLIERS}}


TINY = {"num_fields": 200, "vocab_size": 5000, "embed_dim": 64, "intermediate_size": 96, "num_attention_heads": 6,
        "num_key_value_heads": 2, "head_dim": 16, "mamba_d_ssm": 64, "mamba_n_heads": 4, "mamba_d_head": 16,
        "mamba_d_state": 32, "mamba_chunk_size": 64}


def main() -> None:
    from benchmark.common import load_module
    from distributed_tf_serving_tpu.models import sequence

    flow = load_module(os.path.join(HERE, "precision_readings_sequence.py"), "precision_readings_sequence")
    flow.FAMILIES["falcon_h1"] = ("falcon_h1", reference_sizes, falcon_h1_faults, TINY)
    xla = 0
    if "--xla" in sys.argv:  # this file's own flag, which the flow's parser does not know
        at = sys.argv.index("--xla")
        xla = int(sys.argv[at + 1])
        del sys.argv[at:at + 2]
    if "--config" not in sys.argv:
        sys.argv += ["--config", CONFIG]
    notes: list = []
    with contextlib.nullcontext() if xla else sequence.serving_attention(notes):
        flow.main()
    print(f"the attention the variants ran: {notes or 'the XLA blocks'}", flush=True)


if __name__ == "__main__":
    main()
