#!/usr/bin/env python3
"""rehearse_sequence.py's flow for `nemotron3_super_120b_rerank-bulk` alone,
here on the CPU. That file's shrunken copy cuts the hidden size, the MLP and
the attention's head counts and keeps every other width: this family would
keep its 128 Mamba-2 heads of 64 with a `[64, 128]` state each, a 512-wide
router and 64 held experts 2,688 wide behind a 1,024-wide latent, and a CPU
does not serve that inside the generators' warm-up deadline. So the family's
own keys are cut here too: the Mamba-2 head COUNT (8 heads: one a group, still
8 groups) and its state, the experts (a 64-wide router, 8 held: still an
eighth) and their widths, and the depth (layers 0-7, whose attention is the
layer cut to the last position). What the reference reads from its own defaults stays
as published (an attention head of 128, a Mamba-2 head of 64, 8 groups,
top-22, scaling 5, experts from 0): the harness calls `reference.forward` with
no sizes.

  python3 benchmark/rehearsal/rehearse_nemotron_h.py [rehearse.py's options]

About seven minutes (a window of 20 s: a CPU takes seconds over a step of
2,048-token rows, and a traced window of 8 answered none; on this sandbox's
CPU the traced run still reads `LEFT OUT: ['handler_cpu_us.bulk']`, because no
request begins and ends inside the capture's 3 s there: the chip's run reports
it, PERF.md section 5). Never imports jax.
(The real size's step and init are
compiled for a described v5e, without the chip, by
`JAX_PLATFORMS=cpu python3 benchmark/rehearsal/compile_only.py
benchmark/configs/nemotron3_super_120b_rerank/config.json`.)"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearse  # noqa: E402

rehearse.TINY_MODEL = {
    # eight layers, `MEMEMEM*`: the attention is then the layer cut to one query, which a CPU can afford
    "vocab_size": 50000, "embed_dim": 64, "mlp_dims": [32], "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "mamba_d_ssm": 512, "mamba_n_heads": 8, "mamba_d_state": 16,
    "n_routed_experts": 64, "experts_held": 8, "moe_latent_size": 128, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 64,
}
rehearse.TINY_BUCKETS = [2, 4]  # a 4-row step fits the trace's 3 s on a CPU; an 8-row one does not
rehearse.TINY_MIX = dict(rehearse.TINY_MIX, closed={
    "callers": 16, "generators": 2, "warmup_requests": 16, "rows": {"kind": "fixed", "value": 2}})


def main() -> int:
    sys.argv[1:] = ["--tiny", "1", "--seconds", "20", "--untraced", "1", "--traced", "1",
                    "--cells", "nemotron3_super_120b_rerank-bulk"] + sys.argv[1:]
    return rehearse.main()


if __name__ == "__main__":
    sys.exit(main())
