"""Servable checkpointing: params + model metadata on disk.

The reference's checkpoint story is the vendored SaverDef schema consumed by
the external SavedModel loader (saver.proto:11-47, meta_graph.proto:75 —
SURVEY.md §5); serving itself is stateless. Here the equivalent is direct:
an Orbax param checkpoint next to a JSON manifest (model kind + ModelConfig
+ name/version), from which load_servable reconstructs a registry-ready
Servable. Sharded param trees save/restore transparently (Orbax records
layouts; restore_args can re-place onto a mesh).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import pathlib

import jax
import orbax.checkpoint as ocp

from ..models.base import ModelConfig, build_model
from ..models.embeddings import pack_params, unpack_params
from ..models.registry import Servable, ctr_signatures

MANIFEST = "servable.json"
PARAMS_DIR = "params"


def save_servable(path, servable: Servable, kind: str) -> None:
    """Write params + manifest. `kind` is the model-zoo family name. The
    file holds the logical [V, D] table whatever shape the servable serves
    (models/embeddings.py pack_table): a packed table is unpacked on the way.

    Write order is a commit protocol: params first, manifest LAST — the
    manifest's existence marks the checkpoint complete, so a concurrent
    reader (serving/version_watcher.py polling a base path) never loads a
    half-written params tree."""
    path = pathlib.Path(path)
    path.mkdir(parents=True, exist_ok=True)
    manifest = {
        "name": servable.name,
        "version": servable.version,
        "kind": kind,
        "config": dataclasses.asdict(servable.model.config),
    }
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(
            (path / PARAMS_DIR).absolute(),
            unpack_params(servable.params, servable.model.config.embed_dim),
            force=True,
        )
    (path / MANIFEST).write_text(json.dumps(manifest, indent=2))


def load_servable(
    path, mesh=None, tensor_parallel: bool = False, host: bool = False
) -> Servable:
    """Reconstruct a Servable, its embedding table in the serving shape
    (pack_table: the table is read to the host, reshaped there at no cost
    and placed once, so it never exists twice on the device; interop/export.py
    unpacks). With a mesh, params restore pre-placed
    (vocab tables over the model axis; dense weights model-axis split too
    when tensor_parallel) instead of replicated — restoring straight into
    the serving layout avoids a second full-tree resharding pass.

    host=True restores plain numpy arrays with NO device placement — the
    mode multi-process serving needs: under jax.distributed, a device
    restore demands explicit cross-process shardings orbax cannot infer
    from a single-process checkpoint, whereas every process can read the
    full tree to host and let the caller place it at a protocol-aligned
    point (parallel/multihost.py MultiHostRunner._place)."""
    import numpy as np

    path = pathlib.Path(path)
    manifest = json.loads((path / MANIFEST).read_text())
    config = ModelConfig(**{
        k: tuple(v) if isinstance(v, list) else v for k, v in manifest["config"].items()
    })
    model = build_model(manifest["kind"], config)

    target = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if host:
        if mesh is not None:
            raise ValueError("host=True restores unplaced arrays; mesh is exclusive")
        # A host restore is a purely LOCAL read, so it must opt out of
        # orbax's cross-process barrier: under jax.distributed the default
        # Checkpointer syncs every process on restore, and the multihost
        # serving protocol restores at different protocol points on leader
        # (before the RELOAD broadcast) vs followers (after) — the barrier
        # would interleave with the runner's own collectives and deadlock
        # the slice (observed: leader in orbax sync_global_processes,
        # follower in the header broadcast).
        local_only = ocp.options.MultiprocessingOptions(
            primary_host=jax.process_index(),
            active_processes={jax.process_index()},
            barrier_sync_key_prefix=f"dts_local_{jax.process_index()}",
        )
        with ocp.Checkpointer(
            ocp.PyTreeCheckpointHandler(), multiprocessing_options=local_only
        ) as ckptr:
            params = ckptr.restore(
                (path / PARAMS_DIR).absolute(),
                restore_args=jax.tree.map(
                    lambda _: ocp.RestoreArgs(restore_type=np.ndarray), target
                ),
            )
        params = pack_params(params, config.embed_dim)
    else:
        # Shapes as served; a table whose packed rows the model axis does
        # not divide stays logical, as the mesh executor would make it.
        served = jax.eval_shape(functools.partial(model.init, packed=True), jax.random.PRNGKey(0))
        if mesh is not None:
            from ..parallel.mesh import MODEL_AXIS
            from ..parallel.sharding import param_shardings

            if served["embedding"].shape[0] % mesh.shape[MODEL_AXIS]:
                served = target
            shardings = param_shardings(served, mesh, tensor_parallel)
        else:
            # Name the placement: without one orbax replays the sharding
            # file, i.e. the SAVING process's devices — a checkpoint written
            # by a CPU process would not restore on the chip.
            here = jax.sharding.SingleDeviceSharding(jax.devices()[0])
            shardings = jax.tree.map(lambda _: here, served)
        restore_args = jax.tree.map(lambda sh: ocp.ArrayRestoreArgs(sharding=sh), shardings)
        restore_args["embedding"] = ocp.RestoreArgs(restore_type=np.ndarray)
        with ocp.Checkpointer(ocp.PyTreeCheckpointHandler()) as ckptr:
            params = ckptr.restore((path / PARAMS_DIR).absolute(), restore_args=restore_args)
        params["embedding"] = jax.device_put(
            params["embedding"].reshape(served["embedding"].shape), shardings["embedding"]
        )

    dense = config.num_dense_features if model.takes_dense else None
    return Servable(
        name=manifest["name"],
        version=manifest["version"],
        model=model,
        params=params,
        signatures=ctr_signatures(config.num_fields, with_dense=dense),
    )
