#!/usr/bin/env python3
"""rehearse_sequence.py's flow for `sdar_30b_a3b_rerank-bulk` alone, here on
the CPU. That file's shrunken copy cuts the hidden size, the MLP and the
attention's head counts and keeps every other width: this family would keep a
128-wide router with all 128 experts held, 768 wide each, and a CPU does not
serve that inside the generators' warm-up deadline. So the family's own keys
are cut here too: the experts (a 16-wide router, all 16 held: still the layer
WHOLE, every one of a token's choices here) and their width, the heads' COUNT
(2 over 1, still 128 wide) and the depth (layers 0-2: two at all positions and
the one cut to the last position). What the
reference reads from its own defaults stays as published (a head of 128, a
rotary base of 1e6, a block of 4, top-8, experts from 0): the harness calls
`reference.forward` with no sizes.

  python3 benchmark/rehearsal/rehearse_sdar_moe.py [rehearse.py's options]

About three minutes (a window of 20 s and a ladder of 2 and 4 rows: a CPU
takes seconds over a step of 2,048-token rows under a head of 128; at the
cell's five layers, four heads and 8-row rung a traced window answered seven
requests and left the trace's metrics out). On this sandbox's CPU the traced
run still reads `LEFT OUT: ['handler_cpu_us.bulk']`, because no request begins
and ends inside the capture's 3 s there, as `rehearse_nemotron_h.py`'s: the
chip's run reports it (PERF.md section 5). Never imports jax.
(The real size's step and init are
compiled for a described v5e, without the chip, by
`JAX_PLATFORMS=cpu python3 benchmark/rehearsal/compile_only.py
benchmark/configs/sdar_30b_a3b_rerank/config.json`, and the served entry with
its kernels by tests/test_tpu_compile.py, so that the ladder's executables are
known to fit before the first timed run.)"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearse  # noqa: E402

rehearse.TINY_MODEL = {
    "vocab_size": 50000, "embed_dim": 64, "mlp_dims": [32], "num_hidden_layers": 3, "num_attention_heads": 2,
    "num_key_value_heads": 1, "num_experts": 16, "experts_held": 16, "moe_intermediate_size": 32,
}
rehearse.TINY_BUCKETS = [2, 4]  # a 4-row step fits the trace's 3 s on a CPU; an 8-row one does not
rehearse.TINY_MIX = dict(rehearse.TINY_MIX, closed={
    "callers": 16, "generators": 2, "warmup_requests": 16, "rows": {"kind": "fixed", "value": 2}})


def main() -> int:
    sys.argv[1:] = ["--tiny", "1", "--seconds", "20", "--untraced", "1", "--traced", "1",
                    "--cells", "sdar_30b_a3b_rerank-bulk"] + sys.argv[1:]
    return rehearse.main()


if __name__ == "__main__":
    sys.exit(main())
