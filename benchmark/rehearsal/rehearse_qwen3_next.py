#!/usr/bin/env python3
"""rehearse_sequence.py's flow for `qwen3_next_80b_rerank-bulk` alone, here on
the CPU. That file's shrunken copy cuts the hidden size, the MLP and the
attention's head counts and keeps every other width: this family would keep
its 16 key and 32 value heads of 128 in every linear layer, a 512-wide router
and 128 held experts 512 wide, and a CPU does not serve that inside the
generators' warm-up deadline. So the family's own keys are cut here too: the
rule's head COUNTS (2 key heads for 4 value heads: still two a key head), the
experts (a 64-wide router, 16 held: still a quarter) and their widths. What
the reference reads from its own defaults stays as published (a full layer's
head of 256 with 64 rotary dims, a linear layer's key head of 128, top-10,
experts from 0): the harness calls `reference.forward` with no sizes.

  python3 benchmark/rehearsal/rehearse_qwen3_next.py [rehearse.py's options]

About four minutes. Never imports jax."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearse  # noqa: E402

rehearse.TINY_MODEL = {
    "vocab_size": 50000, "embed_dim": 64, "mlp_dims": [32], "num_attention_heads": 4, "num_key_value_heads": 2,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_value_head_dim": 32,
    "num_experts": 64, "experts_held": 16, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
}
rehearse.TINY_BUCKETS = [2, 4, 8]
rehearse.TINY_MIX = dict(rehearse.TINY_MIX, closed={
    "callers": 16, "generators": 2, "warmup_requests": 16, "rows": {"kind": "fixed", "value": 2}})


def main() -> int:
    sys.argv[1:] = ["--tiny", "1", "--seconds", "8", "--untraced", "1", "--traced", "1",
                    "--cells", "qwen3_next_80b_rerank-bulk"] + sys.argv[1:]
    return rehearse.main()


if __name__ == "__main__":
    sys.exit(main())
