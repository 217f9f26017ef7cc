"""chip_smoke.py rehearsed on the CPU, and the compile-cache placement it
reports. Both run in subprocesses: the smoke's parent must stay off jax, and
enable_compile_cache changes process-wide jax configuration."""

import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

TINY_TOML = """\
[server]
model_kind = "dcn_v2"
num_fields = 8
buckets = [1024, 4096]

[model]
name = "DCN"
num_fields = 8
vocab_size = 4096
embed_dim = 8
mlp_dims = [32, 16]
num_cross_layers = 2
compute_dtype = "bfloat16"
"""


def test_chip_smoke_runs_whole_flow_on_cpu_and_fails_platform_check(tmp_path):
    """The debugging mode: under JAX_PLATFORMS=cpu the same flow runs —
    checkpoint child, CLI server child, every request answered, float32
    reference child — and only then the platform check fails the run. No
    result line is printed."""
    config = tmp_path / "tiny.toml"
    config.write_text(TINY_TOML)
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache))
    r = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--config", str(config)],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode != 0, r.stdout[-3000:]
    assert "not on a tpu" in r.stderr, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert not lines[-1].startswith("{"), lines[-1]  # no {"ok": ...} result
    runtime = json.loads(
        next(ln for ln in lines if "] runtime: " in ln).split("] runtime: ", 1)[1]
    )
    assert runtime["platform"] == "cpu" and runtime["device_count"] >= 1
    assert runtime["warmup_s"] > 0 and runtime["native_hostops"] is True
    # The cache was placed from outside, and the ladder's compiles used it.
    assert runtime["compile_cache"]["dir"] == str(cache)
    assert runtime["compile_cache"]["misses"] > 0 and any(cache.iterdir())
    counts = json.loads(
        next(ln for ln in lines if "] requests: " in ln).split("] requests: ", 1)[1]
    )
    assert counts["sent"] == counts["answered"] > 0 and counts["failed"] == 0
    assert any("server exit code 0 after SIGTERM" in ln for ln in lines)
    assert any("float32 cpu reference" in ln for ln in lines)


_CACHE_PROBE = """
import json, jax
updates = []
real = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), real(k, v))[1]
from distributed_tf_serving_tpu.utils.runtime import enable_compile_cache
stats = enable_compile_cache()
print(json.dumps({"dir": stats.directory, "updates": updates}))
"""


def _cache_probe(cwd, cache_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=120, check=True,
    )
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_dir_from_env_is_left_alone(tmp_path):
    got = _cache_probe(tmp_path, str(tmp_path / "outside"))
    assert got["dir"] == str(tmp_path / "outside")  # jax read the variable
    assert "jax_compilation_cache_dir" not in got["updates"]


def test_compile_cache_default_is_one_path_in_the_checkout(tmp_path):
    here = _cache_probe(tmp_path, None)
    there = _cache_probe(REPO / "tests", None)
    assert here["dir"] == there["dir"] == str(REPO / ".jax_cache")
