"""Servable registry: model name -> versions -> signatures.

Replicates the model-resolution semantics the reference reaches through
ModelSpec (model.proto:9-19): requests name a model, optionally pin a version
via the Int64Value wrapper (absent => latest loaded version,
model.proto:12-14), and select a signature by name (default
"serving_default", matching DCNClient.java:34). GetModelMetadata serves the
stored SignatureDefs (get_model_metadata.proto:15-30).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

# NOTE: the proto bindings are imported LAZILY inside the functions that
# build protobuf messages (to_tensor_info / to_signature_def /
# ctr_signatures): our vendored tensorflow.* descriptors collide with
# TensorFlow's own in the process-wide descriptor pool, and the SavedModel
# EXPORT path (interop/export.py) must import tensorflow + this models
# package in ONE process. Keeping this module proto-free at import time is
# what makes that possible.
from .base import Batch, Model, Params

# TF-Serving method names carried in SignatureDef.method_name.
PREDICT_METHOD = "tensorflow/serving/predict"
CLASSIFY_METHOD = "tensorflow/serving/classify"
REGRESS_METHOD = "tensorflow/serving/regress"

DEFAULT_SIGNATURE = "serving_default"


class ModelNotFoundError(KeyError):
    pass


class VersionNotFoundError(KeyError):
    pass


class SignatureNotFoundError(KeyError):
    pass


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    name: str  # logical tensor alias (the request/response map key)
    dtype: int  # fw.DataType value
    # Per-dim None = unknown/batch dim; whole-shape None = unknown rank
    # (tensor_shape.proto unknown_rank, seen in imported SavedModels).
    shape: tuple[int | None, ...] | None

    def to_tensor_info(self):
        from ..proto import tf_meta_graph_pb2 as mg

        info = mg.TensorInfo(name=f"{self.name}:0", dtype=self.dtype)
        if self.shape is None:
            info.tensor_shape.unknown_rank = True
        else:
            for s in self.shape:
                info.tensor_shape.dim.add(size=-1 if s is None else s)
        return info


@dataclasses.dataclass(frozen=True)
class Signature:
    """One servable signature: typed I/O contract + method name."""

    inputs: tuple[TensorSpec, ...]
    outputs: tuple[TensorSpec, ...]
    method_name: str = PREDICT_METHOD

    # cached_property writes the instance __dict__ directly, which frozen
    # dataclasses permit: rebuilding these per request showed up in the
    # round-3 serving profile.
    @functools.cached_property
    def input_specs(self) -> dict[str, TensorSpec]:
        return {s.name: s for s in self.inputs}

    @functools.cached_property
    def output_names(self) -> list[str]:
        return [s.name for s in self.outputs]

    def to_signature_def(self):
        from ..proto import tf_meta_graph_pb2 as mg

        sd = mg.SignatureDef(method_name=self.method_name)
        for spec in self.inputs:
            sd.inputs[spec.name].CopyFrom(spec.to_tensor_info())
        for spec in self.outputs:
            sd.outputs[spec.name].CopyFrom(spec.to_tensor_info())
        return sd


def ctr_signatures(num_fields: int, with_dense: int | None = None) -> dict[str, Signature]:
    """The standard CTR signature set matching the reference contract
    (feat_ids int64 [n,F] + feat_wts float [n,F] -> prediction_node [n])."""
    # Hardcoded DataType values (types.proto, wire-frozen since TF 1.0:
    # DT_FLOAT=1, DT_STRING=7, DT_INT64=9) rather than the proto enum: the
    # SavedModel EXPORT path calls this from a process where TensorFlow
    # owns the descriptor pool, so this function must not import the
    # vendored bindings even lazily (tests/test_codec.py pins these values
    # against the real enum).
    DT_FLOAT, DT_STRING, DT_INT64 = 1, 7, 9
    inputs = [
        TensorSpec("feat_ids", DT_INT64, (None, num_fields)),
        TensorSpec("feat_wts", DT_FLOAT, (None, num_fields)),
    ]
    if with_dense:
        inputs.append(TensorSpec("dense_features", DT_FLOAT, (None, with_dense)))
    predict = Signature(
        inputs=tuple(inputs),
        outputs=(
            TensorSpec("prediction_node", DT_FLOAT, (None,)),
            TensorSpec("logits", DT_FLOAT, (None,)),
        ),
        method_name=PREDICT_METHOD,
    )
    classify = dataclasses.replace(
        predict,
        outputs=(
            TensorSpec("scores", DT_FLOAT, (None, 2)),
            TensorSpec("classes", DT_STRING, (None, 2)),
        ),
        method_name=CLASSIFY_METHOD,
    )
    regress = dataclasses.replace(
        predict,
        outputs=(TensorSpec("outputs", DT_FLOAT, (None,)),),
        method_name=REGRESS_METHOD,
    )
    return {DEFAULT_SIGNATURE: predict, "classify": classify, "regress": regress}


@dataclasses.dataclass(eq=False)  # identity hash: used as a weak cache key
class Servable:
    """A loaded (model, params) pair plus its signature map."""

    name: str
    version: int
    model: Model
    params: Params
    signatures: dict[str, Signature]

    def signature(self, name: str) -> Signature:
        key = name or DEFAULT_SIGNATURE
        if key not in self.signatures:
            raise SignatureNotFoundError(
                f"signature {key!r} not found in servable {self.name} v{self.version}; "
                f"have {sorted(self.signatures)}"
            )
        return self.signatures[key]

    def __call__(self, batch: Batch) -> dict[str, jnp.ndarray]:
        return self.model.apply(self.params, batch)

    @property
    def embedding_pack(self) -> int | None:
        """Logical rows per row of the embedding table as it is held
        (models/embeddings.py pack_table): 1 for a logical table, None for
        a tree without one (imported graphs)."""
        table = self.params.get("embedding") if isinstance(self.params, dict) else None
        if getattr(table, "ndim", 0) != 2:
            return None
        return table.shape[1] // self.model.config.embed_dim

    @property
    def lookups_per_row(self) -> int | None:
        """Embedding rows one candidate row reads: the wire's id columns
        (None where embedding_pack is)."""
        return None if self.embedding_pack is None else self.model.config.num_fields

    @property
    def bags(self) -> int | None:
        """Vectors those rows are pooled to: the embedding bags of
        multi_hot_sizes, else one a column."""
        config = self.model.config
        if self.embedding_pack is None:
            return None
        return len(config.multi_hot_sizes) or config.num_fields

    @property
    def layer_plan(self) -> dict[str, int] | None:
        """Layers of each kind in a sequence family's stack."""
        return dict(collections.Counter(self.model.layer_plan)) or None

    @property
    def expert_plan(self) -> dict[str, int] | None:
        """What a family with a routed layer holds of it here: experts
        published, held and the first held, experts a token, heads published
        and held, chips sharing a layer. None for every other family."""
        return dict(self.model.expert_plan) or None

    @property
    def attention_plan(self) -> list[dict] | None:
        """Each layer's mixer where a family's differs by layer: an attention
        layer's kind, window, block of queries, keys a block; a linear
        layer's kind, chunk, hand-overs and state bytes a row; under `ssd`
        the same of the Mamba-2 mixer a falcon_h1 layer holds beside its
        attention. None for every other family."""
        return [{name: dict(value) if isinstance(value, tuple) else value for name, value in layer}
                for layer in self.model.attention_plan] or None

    @property
    def params_bytes(self) -> int:
        """Bytes of the parameter tree as it is held."""
        return sum(int(getattr(leaf, "nbytes", 0)) for leaf in jax.tree.leaves(self.params))

    def signature_def_map(self) -> dict:
        return {k: v.to_signature_def() for k, v in self.signatures.items()}


class ServableRegistry:
    """Thread-safe name -> {version -> Servable} store, with version labels.

    Mutation happens on the control plane (load/unload/set_label); the
    serving data plane only reads, so a plain lock around dict ops suffices.

    Version labels replicate tensorflow_model_server's label routing
    (model.proto field 4 upstream; assigned via ModelServerConfig
    version_labels there, via set_label / the server config here): a label
    like "stable"/"canary" names ONE loaded version per model, and requests
    may address it instead of a number — retargeting the label is the
    blue-green flip, no client change.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._servables: dict[str, dict[int, Servable]] = {}
        self._labels: dict[str, dict[str, int]] = {}

    def load(self, servable: Servable) -> None:
        with self._lock:
            self._servables.setdefault(servable.name, {})[servable.version] = servable

    def unload(self, name: str, version: int | None = None) -> None:
        with self._lock:
            if name not in self._servables:
                raise ModelNotFoundError(name)
            if version is None:
                del self._servables[name]
                self._labels.pop(name, None)
            else:
                versions = self._servables[name]
                if version not in versions:
                    raise VersionNotFoundError(f"{name} v{version}")
                del versions[version]
                labels = self._labels.get(name)
                if labels:
                    # A label must never dangle onto an unloaded version
                    # (upstream refuses to assign labels to unavailable
                    # versions for the same reason).
                    for label in [l for l, v in labels.items() if v == version]:
                        del labels[label]
                if not versions:
                    del self._servables[name]
                    self._labels.pop(name, None)

    def set_label(self, name: str, label: str, version: int) -> None:
        """Point `label` at a LOADED version (upstream rule: labels can only
        name available versions, so a typo'd rollout fails at config time,
        not at request time)."""
        if not label:
            raise ValueError("version label must be non-empty")
        with self._lock:
            self._check_labelable(name, label, version)
            self._labels.setdefault(name, {})[label] = version

    def _check_labelable(self, name: str, label: str, version: int) -> None:
        """Lock held by caller."""
        versions = self._servables.get(name)
        if not versions:
            raise ModelNotFoundError(f"model {name!r} not loaded")
        if version not in versions:
            raise VersionNotFoundError(
                f"cannot label {name!r} v{version} as {label!r}: version not "
                f"loaded; have {sorted(versions)}"
            )

    def replace_label_maps(self, maps: dict[str, dict[str, int]]) -> None:
        """REPLACE each named model's whole label map, atomically across all
        models (the reload-config semantics: the supplied map is the
        declarative state, so labels absent from it are unassigned).
        Validation and application happen under ONE lock acquisition — a
        concurrent unload can never leave a reload half-applied."""
        with self._lock:
            for name, mapping in maps.items():
                for label, version in mapping.items():
                    if not label:
                        raise ValueError("version label must be non-empty")
                    self._check_labelable(name, label, version)
            for name, mapping in maps.items():
                self._labels[name] = dict(mapping)

    def resolve(
        self,
        name: str,
        version: int | None = None,
        label: str | None = None,
    ) -> Servable:
        """ModelSpec resolution: absent version wrapper => latest
        (model.proto:12-14); version_label => the labeled version (upstream
        model.proto field 4). version XOR label is enforced by the caller
        (the proto oneof upstream)."""
        with self._lock:
            versions = self._servables.get(name)
            if not versions:
                raise ModelNotFoundError(f"model {name!r} not loaded")
            if label is not None:
                assigned = self._labels.get(name, {})
                if label not in assigned:
                    raise VersionNotFoundError(
                        f"model {name!r} has no version label {label!r}; "
                        f"have {sorted(assigned)}"
                    )
                version = assigned[label]
            if version is None:
                return versions[max(versions)]
            if version not in versions:
                raise VersionNotFoundError(
                    f"model {name!r} has no version {version}; have {sorted(versions)}"
                )
            return versions[version]

    def models(self) -> dict[str, list[int]]:
        with self._lock:
            return {k: sorted(v) for k, v in self._servables.items()}

    def per_servable(self, attr: str) -> dict:
        """"name:version" -> that property of every loaded servable
        (embedding_pack, lookups_per_row, bags, layer_plan, expert_plan,
        attention_plan, params_bytes)."""
        with self._lock:
            loaded = [s for versions in self._servables.values() for s in versions.values()]
        return {f"{s.name}:{s.version}": getattr(s, attr) for s in loaded}

    def labels(self, name: str) -> dict[str, int]:
        with self._lock:
            return dict(self._labels.get(name, {}))
