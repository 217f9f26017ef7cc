"""Serving stack: dynamic batcher + PredictionService semantics + gRPC frontend."""

from .batcher import (
    BatcherStats,
    BatchTooLargeError,
    DeviceWedgedError,
    DynamicBatcher,
    QueueOverloadError,
    bucket_for,
)
from .example_codec import ExampleDecodeError, decode_input, make_example
from .request_log import RequestLogger
from .server import (
    GrpcModelService,
    GrpcPredictionService,
    create_server,
    load_demo_servable,
    load_ssl_credentials,
    serve,
)
from .service import PredictionServiceImpl, ServiceError
from .version_watcher import VersionWatcher, VersionWatcherConfig, scan_versions
from .warmup import (
    WarmupError,
    read_tfrecords,
    replay_warmup_file,
    warmup_file_for,
    write_tfrecords,
)

__all__ = [
    "VersionWatcher",
    "VersionWatcherConfig",
    "scan_versions",
    "DynamicBatcher",
    "BatcherStats",
    "BatchTooLargeError",
    "QueueOverloadError",
    "DeviceWedgedError",
    "bucket_for",
    "decode_input",
    "make_example",
    "ExampleDecodeError",
    "PredictionServiceImpl",
    "ServiceError",
    "GrpcPredictionService",
    "GrpcModelService",
    "create_server",
    "load_demo_servable",
    "load_ssl_credentials",
    "serve",
    "RequestLogger",
    "WarmupError",
    "read_tfrecords",
    "replay_warmup_file",
    "warmup_file_for",
    "write_tfrecords",
]
