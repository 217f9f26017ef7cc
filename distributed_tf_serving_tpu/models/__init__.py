"""Pure-JAX CTR model zoo + servable registry.

Model families cover every BASELINE.json config: dcn / dcn_v2 (the
reference's served model, DCNClient.java:33), wide_deep, deepfm, two_tower,
dlrm, dlrm_dcnv2; and nine sequence rankers, phi4flash, pangu_moe, exaone_moe, olmo_hybrid, mimo_v2, falcon_h1,
qwen3_next, nemotron_h and sdar_moe,
whose row is F token ids.
All share the reference serving contract feat_ids/feat_wts [n, F] -> prediction_node [n].
"""

from .base import Batch, Model, ModelConfig, Params, build_model, model_kinds
from .registry import (
    DEFAULT_SIGNATURE,
    ModelNotFoundError,
    Servable,
    ServableRegistry,
    Signature,
    SignatureNotFoundError,
    TensorSpec,
    VersionNotFoundError,
    ctr_signatures,
)

# Import model modules for their registration side effects.
from . import dcn, deepfm, dlrm, exaone_moe, falcon_h1, generic, mimo_v2, nemotron_h, olmo_hybrid, pangu_moe, phi4flash, qwen3_next, sdar_moe, two_tower, wide_deep  # noqa: E402,F401

__all__ = [
    "Batch",
    "Model",
    "ModelConfig",
    "Params",
    "build_model",
    "model_kinds",
    "Servable",
    "ServableRegistry",
    "Signature",
    "TensorSpec",
    "ctr_signatures",
    "DEFAULT_SIGNATURE",
    "ModelNotFoundError",
    "VersionNotFoundError",
    "SignatureNotFoundError",
]
