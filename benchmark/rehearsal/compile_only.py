#!/usr/bin/env python3
"""Compile a configuration's parameter init and its largest bucket's step for
one chip of a described v5e:2x2, here, without the chip, and print what the
compiler says each needs (`memory_analysis()`): what does not fit 16 GB is
refused here and costs no chip time.

  JAX_PLATFORMS=cpu python3 benchmark/rehearsal/compile_only.py benchmark/configs/<name>/config.json

Nothing runs, so this says nothing about results or times.
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from distributed_tf_serving_tpu.models import build_model
    from distributed_tf_serving_tpu.utils.config import load_config

    from benchmark.common import read_json, toml_text

    config = read_json(sys.argv[1])
    os.makedirs(os.path.join(ROOT, "bench_out"), exist_ok=True)
    toml_path = os.path.join(ROOT, "bench_out", "compile_only.toml")
    with open(toml_path, "w") as f:
        f.write(toml_text(config))
    cfgs = load_config(toml_path)
    model = build_model(cfgs["server"].model_kind, cfgs["model"])
    server, shape = config["toml"]["server"], config["toml"]["model"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    t0 = time.monotonic()
    init = jax.jit(model.init).lower(on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))).compile()
    print(f"{config['name']} init: compiled in {time.monotonic() - t0:.1f}s: {init.memory_analysis()}")
    rows, fields = max(server["buckets"]), shape["num_fields"]
    batch = {"feat_ids": jax.ShapeDtypeStruct((rows, fields), jnp.int32),
             "feat_wts": jax.ShapeDtypeStruct((rows, fields), jnp.bfloat16)}
    if shape.get("num_dense_features"):
        batch["dense_features"] = jax.ShapeDtypeStruct((rows, shape["num_dense_features"]), jnp.float32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    t0 = time.monotonic()
    step = jax.jit(model.apply).lower(on_chip(params), on_chip(batch)).compile()
    print(f"{config['name']} step at {rows} rows: compiled in {time.monotonic() - t0:.1f}s: {step.memory_analysis()}")


if __name__ == "__main__":
    main()
