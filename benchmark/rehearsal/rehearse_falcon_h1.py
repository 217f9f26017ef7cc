#!/usr/bin/env python3
"""rehearse_sequence.py's flow for `falcon_h1_34b_rerank-bulk` alone, here on
the CPU. That file's shrunken copy cuts the hidden size, the MLP and the
attention's head counts and keeps every other width: for the five families
before this one that leaves a step a CPU serves; this family's Mamba-2 mixer
would keep its 32 heads of 128 over a state of 256 (a 4 MB state a row and
layer), and the generators' warm-up then outlasts its deadline (my run, PR 54).
So the mixer's head COUNT and state are cut here too. The widths the
reference reads from its own defaults stay as published (an attention head and
a Mamba head of 128, two groups): the harness calls `reference.forward` with
no sizes.

  python3 benchmark/rehearsal/rehearse_falcon_h1.py [rehearse.py's options]

About four minutes. Never imports jax."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearse  # noqa: E402

rehearse.TINY_MODEL = {
    "vocab_size": 50000, "embed_dim": 64, "mlp_dims": [128], "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "mamba_d_ssm": 256, "mamba_n_heads": 2, "mamba_d_state": 16,
}
rehearse.TINY_BUCKETS = [2, 4, 8]
rehearse.TINY_MIX = dict(rehearse.TINY_MIX, closed={
    "callers": 16, "generators": 2, "warmup_requests": 16, "rows": {"kind": "fixed", "value": 2}})


def main() -> int:
    sys.argv[1:] = ["--tiny", "1", "--seconds", "8", "--untraced", "1", "--traced", "1",
                    "--cells", "falcon_h1_34b_rerank-bulk"] + sys.argv[1:]
    return rehearse.main()


if __name__ == "__main__":
    sys.exit(main())
