#!/usr/bin/env python3
"""rehearse.py's flow for the cells whose rows are token sequences (a
configuration whose `[model]` has `num_hidden_layers`), here on the CPU: the
same cell, mix, readers, reference and served path at a shrunken copy of the
configuration (the widths cut, the row length, the depth, the window and the
ladder kept), so that a malformed last line or a reader that returns nothing
is found before chip time is spent.

  python3 benchmark/rehearsal/rehearse_sequence.py [rehearse.py's options]

rehearse.py's own `--tiny 1` sizes (a 64k-row table, buckets of 256-4096
rows, requests of 512 rows) are a CTR cell's: at 1,024 tokens a row they would
not fit. Never imports jax.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearse  # noqa: E402

rehearse.TINY_MODEL = {
    "vocab_size": 50000, "embed_dim": 64, "mlp_dims": [128], "num_attention_heads": 4,
    "num_key_value_heads": 2,
}
rehearse.TINY_BUCKETS = [2, 4, 8]
rehearse.TINY_MIX = dict(rehearse.TINY_MIX, closed={
    "callers": 16, "generators": 2, "warmup_requests": 16, "rows": {"kind": "fixed", "value": 2}})


def main() -> int:
    benchmark = rehearse.read_json(os.path.join(rehearse.ROOT, "BENCHMARK.json"))
    sequence = {
        entry["name"] for entry in benchmark["configs"]
        if "num_hidden_layers" in rehearse.read_json(
            os.path.join(rehearse.ROOT, entry["file"]))["toml"]["model"]}
    cells = [c["name"] for c in benchmark["workloads"] if c["config"] in sequence]
    sys.argv[1:] = ["--tiny", "1", "--seconds", "8", "--untraced", "1", "--traced", "1",
                    "--cells", ",".join(cells)] + sys.argv[1:]
    return rehearse.main()


if __name__ == "__main__":
    sys.exit(main())
