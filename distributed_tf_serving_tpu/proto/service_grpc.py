"""Hand-written gRPC wiring for tensorflow.serving.PredictionService.

grpc_tools (the protoc gRPC plugin) is not available in this image, so the
stub and servicer glue that `protoc --grpc_python_out` would emit is written
by hand. Method paths match the reference service definition
(prediction_service.proto:15-31): /tensorflow.serving.PredictionService/<M>.

Works with both `grpc.Channel`/`grpc.Server` and their `grpc.aio` variants —
the channel/server object itself decides sync vs async semantics.
"""

from __future__ import annotations

import grpc

from ..utils.tracing import request_trace
from . import serving_apis_pb2 as apis

SERVICE_NAME = "tensorflow.serving.PredictionService"

# Channel/server tuning for half-MB-per-request traffic, shared by the
# client (client/client.py) and both server factories (serving/server.py).
# A 516 KB message spans 32 default-size (16 KB) HTTP/2 data frames, each
# with its own framing and flow-control bookkeeping; one big frame cuts
# that to a single pass.
LARGE_MESSAGE_CHANNEL_OPTIONS = (
    ("grpc.max_receive_message_length", 64 * 1024 * 1024),
    ("grpc.max_send_message_length", 64 * 1024 * 1024),
    ("grpc.http2.max_frame_size", 1 * 1024 * 1024),
    ("grpc.optimization_target", "throughput"),
    # Re-dial a refused or lost connection about once a second, and give a
    # dial a second (grpc calls that bound min_reconnect_backoff). grpc's
    # defaults back off from 1 s by 1.6 up to 120 s and give a dial 20 s; a
    # channel in back-off fails its RPCs at once, and a channel that no
    # thread is polling notices a finished dial only at grpc's background
    # poll, every 5 s. So a client that began dialing before its replica
    # listened, or whose replica restarts, reached it 7-17 s after it
    # listened; with these, at the next background poll (PERF.md, PR 26: on
    # the chip's machine, 5.5-12 s between the server's SERVING and the
    # generators' first answer). Client-side; a server ignores them.
    ("grpc.initial_reconnect_backoff_ms", 1000),
    ("grpc.min_reconnect_backoff_ms", 1000),
    ("grpc.max_reconnect_backoff_ms", 1000),
)

# Server-side tolerance for client keepalive pings (the client channels run
# grpc.keepalive_time_ms ~10s to detect silently-dead backends fast): grpc's
# server default treats data-free pings more often than 5 minutes as abuse
# and GOAWAYs the connection with ENHANCE_YOUR_CALM/too_many_pings — which
# would turn the resilience feature into a connection-flapping bug. Both
# server factories (serving/server.py) append these.
KEEPALIVE_SERVER_OPTIONS = (
    ("grpc.http2.min_recv_ping_interval_without_data_ms", 5000),
    ("grpc.http2.max_ping_strikes", 0),  # never GOAWAY a keepalive-ing client
    ("grpc.keepalive_permit_without_calls", 1),
)

# method name -> (request class, response class); order matches the reference
# service definition.
_METHODS = {
    "Classify": (apis.ClassificationRequest, apis.ClassificationResponse),
    "Regress": (apis.RegressionRequest, apis.RegressionResponse),
    "Predict": (apis.PredictRequest, apis.PredictResponse),
    "MultiInference": (apis.MultiInferenceRequest, apis.MultiInferenceResponse),
    "GetModelMetadata": (apis.GetModelMetadataRequest, apis.GetModelMetadataResponse),
}


class PredictionServiceStub:
    """Client stub: one unary-unary callable per RPC.

    Each attribute (e.g. ``stub.Predict``) is a grpc multicallable supporting
    ``stub.Predict(request, timeout=...)`` and ``.future(...)`` on sync
    channels, or awaitables on ``grpc.aio`` channels.
    """

    def __init__(self, channel: grpc.Channel):
        for name, (req_cls, resp_cls) in _METHODS.items():
            setattr(
                self,
                name,
                channel.unary_unary(
                    f"/{SERVICE_NAME}/{name}",
                    request_serializer=req_cls.SerializeToString,
                    response_deserializer=resp_cls.FromString,
                ),
            )
        # Raw-bytes variant of the hot RPC: callers that hold an already
        # serialized PredictRequest (client.PreparedRequest) skip the
        # per-call SerializeToString — the wire bytes are identical, grpc
        # passes a bytes request through untouched when the serializer is
        # None.
        self.PredictRaw = channel.unary_unary(
            f"/{SERVICE_NAME}/Predict",
            request_serializer=None,
            response_deserializer=_METHODS["Predict"][1].FromString,
        )
        # Server-streaming Predict (framework extension, ISSUE 9): the
        # request is the ordinary PredictRequest; the response is a stream
        # of PredictStreamChunk sub-batch results, each flushed as its
        # readback completes (possibly out of order — chunks carry
        # offset/count for the client-side incremental merge).
        self.PredictStream = channel.unary_stream(
            f"/{SERVICE_NAME}/PredictStream",
            request_serializer=apis.PredictRequest.SerializeToString,
            response_deserializer=apis.PredictStreamChunk.FromString,
        )
        # Raw-bytes flavor for PreparedRequest callers (same contract as
        # PredictRaw: identical wire bytes, no per-call serialize).
        self.PredictStreamRaw = channel.unary_stream(
            f"/{SERVICE_NAME}/PredictStream",
            request_serializer=None,
            response_deserializer=apis.PredictStreamChunk.FromString,
        )


class PredictionServiceServicer:
    """Service base class; override the RPCs the server implements."""

    def Classify(self, request, context):
        context.abort(grpc.StatusCode.UNIMPLEMENTED, "Classify not implemented")

    def Regress(self, request, context):
        context.abort(grpc.StatusCode.UNIMPLEMENTED, "Regress not implemented")

    def Predict(self, request, context):
        context.abort(grpc.StatusCode.UNIMPLEMENTED, "Predict not implemented")

    def MultiInference(self, request, context):
        context.abort(grpc.StatusCode.UNIMPLEMENTED, "MultiInference not implemented")

    def GetModelMetadata(self, request, context):
        context.abort(grpc.StatusCode.UNIMPLEMENTED, "GetModelMetadata not implemented")

    def PredictStream(self, request, context):
        context.abort(grpc.StatusCode.UNIMPLEMENTED, "PredictStream not implemented")


# `rpc.parse` and `rpc.serialize`: protobuf's own two passes over a Predict,
# which grpc runs OUTSIDE the handler: the parse of the request's bytes on
# the listener's poller thread (sync server: `_server._receive_message`; one
# thread for all of a listener's connections) and the serialization of the
# response on the handler's thread after it returned. Each synchronous on
# one thread, so both also show in an open profiler capture
# (tracing._ON_PROFILER).
def _spanned(phase: str, fn):
    def timed(message):
        with request_trace.span(phase):
            return fn(message)

    return timed


def add_PredictionServiceServicer_to_server(servicer, server) -> None:
    handlers = {
        name: grpc.unary_unary_rpc_method_handler(
            getattr(servicer, name),
            request_deserializer=req_cls.FromString,
            response_serializer=resp_cls.SerializeToString,
        )
        for name, (req_cls, resp_cls) in _METHODS.items()
    }
    handlers["Predict"] = grpc.unary_unary_rpc_method_handler(
        servicer.Predict,
        request_deserializer=_spanned("rpc.parse", apis.PredictRequest.FromString),
        response_serializer=_spanned("rpc.serialize", apis.PredictResponse.SerializeToString),
    )
    # The one non-unary method rides a unary_stream handler; both the
    # threaded server (a plain generator servicer method) and grpc.aio
    # (an async generator) accept this registration shape.
    handlers["PredictStream"] = grpc.unary_stream_rpc_method_handler(
        servicer.PredictStream,
        request_deserializer=apis.PredictRequest.FromString,
        response_serializer=apis.PredictStreamChunk.SerializeToString,
    )
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(SERVICE_NAME, handlers),)
    )


# --- tensorflow.serving.ModelService --------------------------------------
# The model server's second service (model_service.proto upstream): version
# status for readiness probes + runtime config reload (version-label
# retargeting here). Same hand-written pattern as PredictionService.

MODEL_SERVICE_NAME = "tensorflow.serving.ModelService"

_MODEL_METHODS = {
    "GetModelStatus": (apis.GetModelStatusRequest, apis.GetModelStatusResponse),
    "HandleReloadConfigRequest": (apis.ReloadConfigRequest, apis.ReloadConfigResponse),
}


class ModelServiceStub:
    """Client stub for ModelService (unary-unary callables per RPC)."""

    def __init__(self, channel: grpc.Channel):
        for name, (req_cls, resp_cls) in _MODEL_METHODS.items():
            setattr(
                self,
                name,
                channel.unary_unary(
                    f"/{MODEL_SERVICE_NAME}/{name}",
                    request_serializer=req_cls.SerializeToString,
                    response_deserializer=resp_cls.FromString,
                ),
            )


class ModelServiceServicer:
    """Service base class; override the RPCs the server implements."""

    def GetModelStatus(self, request, context):
        context.abort(grpc.StatusCode.UNIMPLEMENTED, "GetModelStatus not implemented")

    def HandleReloadConfigRequest(self, request, context):
        context.abort(
            grpc.StatusCode.UNIMPLEMENTED, "HandleReloadConfigRequest not implemented"
        )


def add_ModelServiceServicer_to_server(servicer, server) -> None:
    handlers = {
        name: grpc.unary_unary_rpc_method_handler(
            getattr(servicer, name),
            request_deserializer=req_cls.FromString,
            response_serializer=resp_cls.SerializeToString,
        )
        for name, (req_cls, resp_cls) in _MODEL_METHODS.items()
    }
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(MODEL_SERVICE_NAME, handlers),)
    )
