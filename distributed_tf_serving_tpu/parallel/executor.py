"""Sharded serving executor: candidate scatter / score / ordered gather on a
device mesh.

Drops into DynamicBatcher via its run_fn hook, so the batching logic is
unchanged while execution spans the mesh: the reference's per-host gRPC
scatter (DCNClient.java:146-159) becomes the H2D transfer of a
candidate-sharded batch (each chip receives its contiguous rows over ICI),
and the host-order merge (DCNClient.java:161-164) becomes the ordered
device-to-host gather of the candidate-sharded outputs — contiguous shard
order is preserved by construction, so scores come back in exactly the
reference's concat order.

First-class serving mode (ISSUE 13): the executor is hardened for the
[mesh] production path —

- **Data-axis divisibility is the executor's problem, not the operator's.**
  A bucket the ladder legitimately produces (any size) is padded with zero
  rows to the next multiple of the data-axis size inside __call__ and the
  outputs sliced back before the wire compaction (so e.g. the int8 wire's
  quantization range never sees pad rows). (Historically this raised and
  forced the bucket ladder to be mesh-shaped.) Precision contract: the
  model zoo is row-independent and the pad rows never change WHICH rows
  are served, and the output-FILTERED path (what every production client
  sends — the reference client filters to its output_key) is bit-identical
  to single-chip (tools/check_mesh_smoke.py, in tier-1); an UNFILTERED
  all-outputs request at a padded shape may differ from single-chip by ~1 ULP — the
  padded shape is a different executable and XLA may fuse the
  multi-output graph differently (measured 6e-8 on CPU at one shape) —
  which is float-exact for ranking but not bitwise.
- **Output selection (out_keys) is honored** exactly like the single-chip
  jitted entries: unwanted outputs are DCE'd by XLA and never cross the
  gathered D2H link (supports_out_keys tells the batcher to pass the
  group's union through).
- **Named partition rules**: param placement routes through
  embedding_sharding.MODEL_PARTITION_RULES when the servable's model kind
  has an entry (the match_partition_rules contract), generic path-name
  layout otherwise.
- **Thread-safe entry cache + serving counters** (batches/rows/pad work),
  surfaced as the `mesh` /monitoring block and dts_tpu_mesh_* Prometheus
  series via snapshot().

Also exposes shard_map_score: the explicit shard_map formulation of the same
scatter/score/gather, used to pin the semantics in tests and as the Pallas
hook point.
"""

from __future__ import annotations

import threading
import weakref
from typing import Any

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..models.embeddings import unpack_params
from ..models.registry import Servable
from ..ops.transfer import (
    compact_outputs_device,
    output_wire_dtype as _wire_dtype_of,
    pack_host,
    transfer_spec,
    unpack_device,
)
from .mesh import DATA_AXIS, MODEL_AXIS, candidate_sharding
from .sharding import batch_shardings, place_params


class ShardedExecutor:
    """run_fn for DynamicBatcher executing over a mesh.

    Params are placed once per servable (vocab tables split over the model
    axis per the family's named partition rules, rest replicated); each
    batch is jit-executed with candidate-dim in_shardings so XLA scatters
    rows across the data axis and inserts the collectives the embedding
    sharding implies.

    output_wire_dtype mirrors the batcher's output compaction: f32 outputs
    are downcast on-device before the (gathered) D2H readback; the
    batcher's completer widens them back to f32 transparently.
    """

    # The batcher passes the group's output-selection union through
    # run_fn(servable, arrays, out_keys=...) when this is True, so the
    # mesh path gets the same XLA-DCE output filtering as the single-chip
    # jitted entries (PR-1 wire compaction composing with the mesh).
    supports_out_keys = True

    def __init__(
        self,
        mesh: Mesh,
        compress_transfer: bool = True,
        tensor_parallel: bool = False,
        output_wire_dtype: str = "float32",
    ):
        self.mesh = mesh
        self.compress_transfer = compress_transfer
        self.tensor_parallel = tensor_parallel
        self._wire_dt = _wire_dtype_of(output_wire_dtype)
        # Weak keys: an unloaded servable must not pin its placed params or
        # compiled executables (same rationale as DynamicBatcher._jitted).
        self._placed: weakref.WeakKeyDictionary[Servable, Any] = weakref.WeakKeyDictionary()
        self._jitted: weakref.WeakKeyDictionary[Servable, Any] = weakref.WeakKeyDictionary()
        # _prepare is reached from the dispatch thread, the batcher thread
        # (warmup), and measurement harnesses; one lock keeps the variant
        # build single-shot (the batcher's _jit_lock precedent).
        self._lock = threading.Lock()
        # Serving counters (the `mesh` /monitoring block): fed under the
        # lock from __call__ — one increment set per batch, no clock
        # reads on the hot path.
        self.batches = 0
        self.rows = 0  # batch rows received (the batcher's bucket sizes)
        self.data_pad_rows = 0  # zero rows added for data-axis divisibility
        self.pad_batches = 0  # batches that needed the divisibility pad
        self.rules_used: dict[str, str] = {}  # servable name -> layout source

    # ------------------------------------------------------------ internals

    def _prepare(self, servable: Servable):
        """(variant-dispatching fn, spec, placed params) for `servable`,
        built once and rebuilt when servable.params was swapped (re-serving
        after more training) so this path tracks live params like the
        batcher's default path does."""
        key = servable
        with self._lock:
            placed_for = self._placed.get(key)
            if placed_for is not None and placed_for[0] is not servable.params:
                del self._placed[key]
                self._jitted.pop(key, None)
            entry = self._jitted.get(key)
            if entry is None:
                entry = self._build_entry(servable)
                self._jitted[key] = entry
                model_kind = getattr(servable.model, "kind", "") or ""
                from .embedding_sharding import partition_rules_for

                self.rules_used[servable.name] = (
                    f"rules:{model_kind}"
                    if partition_rules_for(model_kind) is not None
                    else "generic"
                )
                params = servable.params
                table = params.get("embedding") if isinstance(params, dict) else None
                if table is not None and table.shape[0] % self.mesh.shape[MODEL_AXIS]:
                    # A lane-packed table (models/embeddings.py) whose
                    # packed rows the model axis does not divide is placed
                    # logical, where the vocab may still divide.
                    params = unpack_params(params, servable.model.config.embed_dim)
                self._placed[key] = (
                    servable.params,
                    place_params(
                        params, self.mesh, self.tensor_parallel,
                        model_kind=model_kind or None,
                    ),
                )
            return entry, self._placed[key][1]

    def _build_entry(self, servable: Servable):
        """One callable dispatching per-(out_keys, pad) jit variants — the
        mesh analog of DynamicBatcher._build_entry: each distinct output
        selection is a separate jit closure whose dead outputs XLA DCEs
        (they never materialize in HBM or cross the gathered D2H link);
        the inner jax.jit trace cache still keys on the (padded) batch
        shape, giving one executable per (servable, padded bucket,
        out_keys).

        The data-axis divisibility pad's `pad` joins the variant key so
        the slice back to real rows is TRACED BEFORE the wire compaction:
        the int8 wire's per-tensor quantization range must be computed
        over the real rows only — pad-row scores inside the min/max would
        stretch the scale and perturb every real row's dequantized value
        (single-chip would serve differently). `pad` is bounded by the
        data-axis size, so the variant space stays small, and v[:-pad]
        slices correctly for EVERY bucket sharing that pad amount."""
        spec = transfer_spec(servable.model) if self.compress_transfer else {}
        apply = servable.model.apply
        mesh = self.mesh
        wire = self._wire_dt
        variants: dict[tuple, Any] = {}
        vlock = self._lock

        def make(out_keys, pad):
            def run(params, packed):
                batch = unpack_device(packed, spec)
                # Pin candidate-dim layout inside the computation too, so
                # the partitioner cannot re-shard rows and break merge
                # order.
                batch = {
                    k: jax.lax.with_sharding_constraint(
                        v, candidate_sharding(mesh)
                    )
                    for k, v in batch.items()
                }
                n = next(iter(batch.values())).shape[0]
                out = apply(params, batch)
                if out_keys is not None:
                    picked = {k: v for k, v in out.items() if k in out_keys}
                    out = picked or out  # never trace an empty output pytree
                if pad:
                    # Slice the divisibility pad off BEFORE compaction
                    # (candidate-major outputs only): the wire transform
                    # must never see pad rows. The shape[0]==n test is
                    # the stack-wide contract, not a heuristic: the
                    # batcher's completer slices EVERY output
                    # per-request the same way, so serving outputs are
                    # candidate-major by construction on both paths.
                    out = {
                        k: (v[:-pad]
                            if getattr(v, "ndim", 0) >= 1 and v.shape[0] == n
                            else v)
                        for k, v in out.items()
                    }
                # On-device output compaction: the gathered scores cross
                # the D2H link in the wire dtype; the batcher's completer
                # restores f32.
                return compact_outputs_device(out, wire)

            return jax.jit(run)

        def fn(params, packed, out_keys=None, pad=0):
            key = (out_keys, pad)
            jfn = variants.get(key)
            if jfn is None:
                with vlock:
                    jfn = variants.get(key)
                    if jfn is None:
                        jfn = variants[key] = make(out_keys, pad)
            return jfn(params, packed)

        return fn, spec

    # ----------------------------------------------------------------- API

    def __call__(
        self,
        servable: Servable,
        arrays: dict[str, np.ndarray],
        out_keys: tuple[str, ...] | None = None,
    ):
        (fn, spec), params = self._prepare(servable)
        rows = next(iter(arrays.values())).shape[0]
        data = self.mesh.shape[DATA_AXIS]
        pad = (-rows) % data
        if pad:
            # Candidate-dim sharding splits rows contiguously across the
            # data axis; a non-multiple batch cannot be placed. Pad with
            # zero rows to the next multiple HERE (the zoo scores rows
            # independently, so pad rows never perturb real scores) and
            # slice the candidate-major outputs back below — the bucket
            # ladder stays the operator's latency/occupancy decision, not
            # a mesh-geometry constraint (ISSUE 13 divisibility fix).
            padded = {}
            for k, v in arrays.items():
                buf = np.zeros((rows + pad,) + v.shape[1:], v.dtype)
                buf[:rows] = v
                padded[k] = buf
            arrays = padded
        with self._lock:
            self.batches += 1
            self.rows += rows
            if pad:
                self.pad_batches += 1
                self.data_pad_rows += pad
        packed = pack_host(arrays, spec) if spec else arrays
        packed = jax.device_put(packed, batch_shardings(packed, self.mesh))
        # The slice back to `rows` is traced into the entry (before the
        # wire compaction — see _build_entry), so the returned outputs
        # are already real-rows-only; sidecars are minted after it.
        return fn(params, packed, out_keys=out_keys, pad=pad)

    def clear_for_recovery(self) -> None:
        """REINIT hook ([recovery]×[mesh] compose, ISSUE 15): drop the
        placed params and compiled entries — after a device failure they
        reference the dead backend state, exactly like the single-chip
        batcher's _jitted entries the recovery plane already clears. The
        recovery re-warm rebuilds them through the queue before replay
        (the executor recovers as ONE unit; per-chip recovery of an SPMD
        executable is not a thing)."""
        with self._lock:
            self._placed = weakref.WeakKeyDictionary()
            self._jitted = weakref.WeakKeyDictionary()

    def snapshot(self) -> dict:
        """The `mesh` /monitoring block body: mesh geometry + devices +
        serving counters + the layout source per served model. Per-device
        occupancy attribution rides in from the utilization ledger at the
        impl layer (SPMD batches occupy every chip simultaneously)."""
        with self._lock:
            counters = {
                "batches": self.batches,
                "rows": self.rows,
                "pad_batches": self.pad_batches,
                "data_pad_rows": self.data_pad_rows,
                "placed_servables": len(self._placed),
                "layout": dict(self.rules_used),
                # Where every split parameter's shards actually sit — the
                # check that a vocab-sharded table is spread over the
                # model axis and not stacked on device 0.
                "param_shards": {
                    sv.name: _sharded_leaves(placed)
                    for sv, (_, placed) in self._placed.items()
                },
            }
        return {
            "enabled": True,
            "shape": {str(k): int(v) for k, v in self.mesh.shape.items()},
            "devices": [str(d) for d in self.mesh.devices.flat],
            "tensor_parallel": self.tensor_parallel,
            "output_wire_dtype": (
                str(np.dtype(self._wire_dt)) if self._wire_dt is not None
                else "float32"
            ),
            "executor": counters,
        }


def _sharded_leaves(placed) -> dict:
    """{param path: [{device, index}]} for the leaves that are not fully
    replicated; index is each shard's [start, stop) per dimension."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(placed)[0]:
        if leaf.sharding.is_fully_replicated:
            continue
        out[jax.tree_util.keystr(path)] = [
            {
                "device": str(shard.device),
                "index": [
                    [sl.start or 0, dim if sl.stop is None else sl.stop]
                    for sl, dim in zip(shard.index, leaf.shape)
                ],
            }
            for shard in leaf.addressable_shards
        ]
    return out


def shard_map_score(servable: Servable, mesh: Mesh):
    """Explicit scatter/score/gather: each chip scores its contiguous
    candidate block with fully-replicated params; the ordered all-gather is
    implied by the out_spec. Reference-parity formulation (per-host shard ->
    local scoring -> host-order concat)."""
    apply = servable.model.apply

    def local(params, batch):
        return apply(params, batch)["prediction_node"]

    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(DATA_AXIS)),
            out_specs=P(DATA_AXIS),
        )
    )
