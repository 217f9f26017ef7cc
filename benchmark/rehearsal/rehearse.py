#!/usr/bin/env python3
"""Run cells of a BENCHMARK.json back to back, as the driver would, and hold
every printed last line to `result.validate`.

  python3 benchmark/rehearsal/rehearse.py [--tiny 1]
      [--untraced 3] [--traced 2] [--seconds N] [--cells a,b] [--out DIR]

On the chip: BENCHMARK.json as it stands. Here on the CPU: `--tiny 1 --seconds
5`, which derives a tiny benchmark from the real one at run time (the same
cells, metrics, readers, references and code paths; a 64k-row table, a short
ladder and a load the CPU backend sustains), writes it under `bench_out/tiny/`
and runs that with `--rehearse 1`. After each run `ps` must show no child of
the benchmark left. Exit code 0 only if every run exited 0 with a valid last
line that holds every metric of its cell, and left nothing behind. Never
imports jax: a parent that touched it would hold the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import result  # noqa: E402
from benchmark.common import read_json, write_json  # noqa: E402

# What the tiny benchmark puts in place of the real sizes; all else is kept.
TINY_MODEL = {"vocab_size": 1 << 16}
TINY_BUCKETS = [256, 1024, 4096]
TINY_MIX = {
    "open": {"rate_per_s": 20, "generators": 2, "warmup_requests": 40,
             "rows": {"kind": "lognormal", "median": 300, "sigma": 0.5, "min": 100, "max": 900}},
    "closed": {"callers": 3, "generators": 2, "warmup_requests": 40,
               "rows": {"kind": "fixed", "value": 512}},
}


def derive_tiny(benchmark: dict, out: str) -> str:
    """Write the tiny benchmark under `out`, laid out as the real one is
    under the root, and return its BENCHMARK.json's path."""
    shutil.rmtree(out, ignore_errors=True)
    bench_dir = benchmark["paths"][0]
    os.makedirs(os.path.join(out, bench_dir, "traffic"))
    for entry in benchmark["configs"]:
        source = os.path.dirname(os.path.join(ROOT, entry["file"]))
        target = os.path.dirname(os.path.join(out, entry["file"]))
        shutil.copytree(source, target)
        config = read_json(os.path.join(ROOT, entry["file"]))
        config["toml"]["model"].update(TINY_MODEL)
        config["toml"]["server"]["buckets"] = TINY_BUCKETS
        write_json(os.path.join(out, entry["file"]), config)
    for mix in {cell["traffic"] for cell in benchmark["workloads"]}:
        real = read_json(os.path.join(ROOT, bench_dir, "traffic", mix + ".json"))
        write_json(os.path.join(out, bench_dir, "traffic", mix + ".json"),
                   {**real, **TINY_MIX[real["loop"]]})
    write_json(os.path.join(out, "BENCHMARK.json"), benchmark)
    return os.path.join(out, "BENCHMARK.json")


def leftovers() -> list[str]:
    ps = subprocess.run(["ps", "-eo", "pid,args"], capture_output=True, text=True).stdout
    return [l.strip() for l in ps.splitlines()
            if "benchmark/chip_child.py" in l or "benchmark/generator.py" in l]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--tiny", type=int, default=0)
    parser.add_argument("--untraced", type=int, default=3)
    parser.add_argument("--traced", type=int, default=2)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--cells", default="")
    parser.add_argument("--seed", type=int, default=3_100_000_000)
    parser.add_argument("--out", default=os.path.join(ROOT, "bench_out", "rehearsal"))
    args = parser.parse_args()
    benchmark = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    tiny = derive_tiny(benchmark, os.path.join(ROOT, "bench_out", "tiny")) if args.tiny else None
    seconds = args.seconds if args.seconds is not None else benchmark["run_seconds"]
    cells = [c["name"] for c in benchmark["workloads"]]
    if args.cells:
        cells = [c for c in cells if c in args.cells.split(",")]
    os.makedirs(args.out, exist_ok=True)
    bad, seed = 0, args.seed
    for cell in cells:
        for traced in [0] * args.untraced + [1] * args.traced:
            seed += 1
            argv = benchmark["command"] + [
                "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
                "--trace", str(traced)]
            if tiny:
                argv += ["--benchmark", tiny, "--rehearse", "1"]
            t0 = time.monotonic()
            run = subprocess.run(argv, cwd=ROOT, capture_output=True, text=True)
            with open(os.path.join(args.out, f"{cell}-t{traced}-s{seed}.out"), "w") as f:
                f.write(run.stdout + "\n--- stderr ---\n" + run.stderr)
            lines = run.stdout.rstrip("\n").split("\n")
            verdict = "valid"
            try:
                obj = json.loads(lines[-1])
                result.validate(obj, benchmark, cell, bool(traced))
                left_out = set(result.cell_metrics(benchmark, cell, bool(traced))) - set(obj["metrics"])
                if left_out:
                    verdict = f"LEFT OUT: {sorted(left_out)}"
            except (ValueError, result.Malformed) as exc:
                verdict, obj = f"MALFORMED: {exc}", None
            left = leftovers()
            ok = run.returncode == 0 and verdict == "valid" and not left and not run.stderr.strip()
            bad += not ok
            print(f"{'ok ' if ok else 'BAD'} {cell} trace={traced} seed={seed} rc={run.returncode} "
                  f"{time.monotonic() - t0:.0f}s last line {verdict}; left behind: {left}; "
                  f"stderr bytes {len(run.stderr)}", flush=True)
            if obj is not None:
                print("    " + json.dumps({k: v for k, v in obj.items() if k != "breakdown"}), flush=True)
    print(f"rehearsal: {bad} bad run(s)", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
