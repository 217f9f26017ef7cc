"""Config system: dataclasses + TOML, covering the reference's knob set.

The reference hard-codes every knob as private static finals — changing
hosts or batch size means recompiling (DCNClient.java:25-42, SURVEY.md §5).
This maps that exact knob set (field_num, candidate_num, hosts, port,
concurrency, request_num, model name/signature/output key, async mode) plus
the TPU-side knobs (mesh, buckets, batching) onto TOML-loadable dataclasses.
"""

from __future__ import annotations

import dataclasses
import pathlib
from typing import Any

try:
    import tomllib
except ModuleNotFoundError:  # Python < 3.11: the vendored-API backport
    import tomli as tomllib


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """Serving frontend + batcher + mesh knobs."""

    host: str = "0.0.0.0"
    port: int = 9999  # reference default, DCNClient.java:28
    max_workers: int = 16  # reference thread pool size, DCNClient.java:42
    model_kind: str = "dcn_v2"
    model_name: str = "DCN"  # DCNClient.java:33
    num_fields: int = 43  # FIELD_NUM, DCNClient.java:25
    buckets: tuple[int, ...] = (32, 64, 128, 256, 512, 1024, 2048, 4096)
    max_wait_us: int = 200
    completion_workers: int = 4  # threads finishing readback+delivery
    compress_transfer: bool = True
    # ---- output-transfer pipeline (serving/batcher.py) -------------------
    # Wire dtype for device->host score readback: scores are downcast
    # ON-DEVICE before the D2H transfer and widened back to float32 on the
    # host, so responses stay signature-typed. "float32" = the full-
    # precision fallback (bit-exact); "bfloat16"/"float16" halve the
    # readback bytes at <=1e-2 relative score error.
    output_wire_dtype: str = "float32"
    # >0: retrieval-style compaction — single-request batches return only
    # the top-k (score, index) pairs over the wire; the host rebuilds a
    # full-length score vector with 0.0 off the head (sigmoid scores are
    # strictly positive, so ranking consumers see the same head). 0 = off.
    output_top_k: int = 0
    # Run the device stage (cache/pack/upload/jit-call) on a dedicated
    # dispatch thread so the batching thread's collect+pad of batch k+1
    # overlaps batch k's H2D upload and dispatch. False = the previous
    # single-threaded dispatch.
    pipelined_dispatch: bool = True
    warmup: bool = True
    # Coalescing keeps filling past max_wait while this many batches are in
    # flight (latency-free: the dispatch would queue behind device work
    # anyway — serving/batcher.py pipeline-aware fill; min 1, default 2).
    # The [batching] section's pipeline_depth (when nonzero) wins over
    # this legacy location; the new in-flight window / buffer-ring /
    # streaming knobs live only there.
    pipeline_depth: int = 2
    # Admission bound in queued candidates (None = 16 max-size batches);
    # past it requests shed with RESOURCE_EXHAUSTED instead of queueing
    # beyond any deadline.
    queue_capacity_candidates: int | None = None
    # mesh: 0 = single device; >0 = shard over first n devices
    mesh_devices: int = 0
    model_parallel: int = 1
    # shard dense MLP/cross weights over the model axis (§2.4 TP row;
    # embedding tables are always vocab-sharded when a mesh is used)
    tensor_parallel: bool = False
    # Version-label routing (tensorflow_model_server's version_labels map:
    # "stable"/"canary" -> version number). TOML: version_labels = {stable
    # = 2, canary = 3}; stored as sorted (label, version) pairs so the
    # frozen config stays hashable.
    version_labels: tuple[tuple[str, int], ...] = ()
    # Sampled request logging (upstream LoggingConfig): PredictionLog
    # TFRecords usable directly as warmup files. "" = disabled.
    request_log_file: str = ""
    request_log_sampling: float = 0.01
    # Version-watcher knobs (--model-base-path lifecycle), named for their
    # tensorflow_model_server flags: --file_system_poll_wait_seconds and
    # --max_num_load_retries (upstream semantics: retries AFTER the first
    # attempt; 2 retries = the watcher's historical 3 total attempts).
    file_system_poll_wait_seconds: float = 5.0
    max_num_load_retries: int = 2
    # Multi-model serving (upstream --model_config_file): a text-format
    # ModelServerConfig whose model_config_list entries each get their own
    # version watcher (name, base_path, optional model_platform = zoo
    # family, version_labels). "" = single-model modes.
    model_config_file: str = ""


@dataclasses.dataclass(frozen=True)
class ClientConfig:
    """Fan-out client + closed-loop bench knobs (the DCNClient constants)."""

    hosts: tuple[str, ...] = ("127.0.0.1:9999",)  # DCNClient.java:38
    model_name: str = "DCN"  # DCNClient.java:33
    signature_name: str = "serving_default"  # DCNClient.java:34
    output_key: str = "prediction_node"  # DCNClient.java:35
    num_fields: int = 43  # FIELD_NUM
    candidate_num: int = 1500  # DCNClient.java:29
    request_num: int = 1000  # DCNClient.java:30
    concurrent_num: int = 6  # DCNClient.java:31
    # DCNClient.java:27 — True: concurrent per-shard fan-out; False: shards
    # issued sequentially in host order (ShardedPredictClient.full_async).
    full_async_mode: bool = True
    sort_scores: bool = True  # the ranking sort, DCNClient.java:195
    timeout_s: float = 10.0
    use_tensor_content: bool = True
    # Beyond the reference: reroute a failed shard to the next host(s) on
    # UNAVAILABLE/DEADLINE_EXCEEDED/RESOURCE_EXHAUSTED, up to this many
    # extra attempts (0 = the reference's fail-fast behavior).
    failover_attempts: int = 0
    # Candidate-to-backend placement (ROADMAP 4a seed, ISSUE 13
    # satellite). "contiguous" = the reference's positional split
    # (DCNClient.java:46-55). "affinity" = rows route to backends by a
    # consistent (jump) hash of each row's canonical feature digest
    # (cache/digest.py row identity), so a hot candidate row always lands
    # on the same replica's warm score cache instead of being re-scored
    # everywhere; the scoreboard still steers a group away from its
    # affine backend while that backend is ejected/busy/rebuilding.
    placement: str = "contiguous"
    # Retry budget (ISSUE 11 satellite): cap on TOTAL backend attempts
    # per logical request across every shard's failover hops, hedges,
    # and streamed reroutes — one recovering/quarantined replica must
    # not be able to multiply a request into a fleet-wide retry storm.
    # Each shard's FIRST attempt is always allowed (the request needs
    # it); the budget bounds everything beyond. 0 = unlimited (the
    # historical behavior). Exhaustion counts as
    # `retry_budget_exhausted` in the scoreboard snapshot.
    max_attempts_total: int = 0
    # ---- resilience layer (client/health.py + client.py) -----------------
    # Per-backend scoreboard: EWMA latency + consecutive-failure ejection
    # with half-open probing; steers shard placement and failover rotation
    # away from ejected hosts.
    health_scoreboard: bool = False
    # Consecutive reroutable failures before a backend is ejected, and the
    # first ejection interval (doubles per failed half-open probe).
    ejection_failures: int = 3
    ejection_interval_s: float = 5.0
    # Hedged shard RPCs: fire a second attempt on another healthy host
    # after this delay; first answer wins, the loser is cancelled. 0 = off.
    hedge_delay_ms: int = 0
    # Jittered exponential backoff between failover attempts.
    backoff_initial_ms: int = 50
    backoff_max_ms: int = 2000
    # Exhausted shards degrade the merge (PredictResult.missing_ranges +
    # degraded flag) instead of failing the whole request.
    partial_results: bool = False
    # Half-open backends get a grpc.health.v1 Check before real traffic.
    health_probe: bool = False
    # HTTP/2 keepalive pings on the backend channels: a silently-dead
    # backend is detected in ~time+timeout instead of hanging until the
    # RPC deadline. 0 disables (for stock gRPC backends whose default
    # ping-abuse policy would GOAWAY a 10s pinger; the in-tree servers
    # tolerate it via KEEPALIVE_SERVER_OPTIONS).
    keepalive_time_ms: int = 10000
    keepalive_timeout_ms: int = 5000
    # Route by version label instead of latest ("" = unset; upstream
    # ModelSpec.version_label routing, e.g. "stable"/"canary").
    version_label: str = ""
    # Request criticality lane sent in gRPC metadata (x-dts-criticality):
    # "critical" / "default" / "sheddable". Overloaded servers running the
    # [overload] plane shed sheddable traffic first. "" = unset (servers
    # treat it as "default").
    criticality: str = ""
    # TLS toward an --ssl-config-file server ("" = plaintext). PATHS here
    # (unlike the server's inline-PEM textproto): client configs name the
    # deployed cert files. key+cert both set => mTLS identity.
    tls_root_certs_file: str = ""
    tls_client_key_file: str = ""
    tls_client_cert_file: str = ""
    # Integrity wire checksums (ISSUE 20): stamp x-dts-input-crc CRC32C
    # sidecars on requests and verify the server's x-dts-score-crc
    # response stamps before merging — a mismatch steers (scoreboard
    # kind="corrupt") and fails the shard over to another backend.
    # Advisory both ways: servers without [integrity] ignore/omit the
    # metadata.
    integrity_checksums: bool = False


@dataclasses.dataclass(frozen=True)
class BatchingConfig:
    """Continuous-batching pipeline knobs (serving/batcher.py, ISSUE 9):
    the k-deep dispatch/in-flight window, padded-batch host buffer
    reuse, and the server-side sub-batch split PredictStream uses.
    Every NEW behavior defaults off — pipeline_depth 0 inherits the
    [server] value (historically 2), inflight_window 0 keeps in-flight
    readbacks unbounded, buffer_ring false allocates per batch, and
    stream_chunk_candidates 0 serves PredictStream as a single chunk."""

    # Staged-dispatch depth: how many assembled batches may queue ahead
    # of the device stage (the coalescer's free-ride gate reads it too).
    # 0 = inherit [server] pipeline_depth; >= 1 otherwise (1 serializes
    # assembly against the device stage).
    pipeline_depth: int = 0
    # Max batches simultaneously IN FLIGHT (executing or awaiting D2H
    # readback): the dispatch thread keeps issuing batch k+2 while k
    # awaits readback until the window fills. 0 = unbounded (historical).
    inflight_window: int = 0
    # Reuse padded-batch host buffers across batches (a buffer is reused
    # only after its batch's upload and readback are done).
    buffer_ring: bool = False
    # Default candidates per PredictStream sub-batch (the server-side
    # split; requests may override via x-dts-stream-chunk metadata).
    # 0 = no split: the streaming RPC answers with one chunk.
    stream_chunk_candidates: int = 0

    def __post_init__(self):
        for name in ("pipeline_depth", "inflight_window",
                     "stream_chunk_candidates"):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                raise ValueError(
                    f"[batching] {name} must be a non-negative integer, "
                    f"got {v!r}"
                )
        if self.inflight_window and self.inflight_window > 64:
            raise ValueError(
                "[batching] inflight_window > 64 would pin that many "
                "batches of HBM at once; this is almost certainly a typo"
            )


@dataclasses.dataclass(frozen=True)
class TransportConfig:
    """Transport-floor knobs (ISSUE 9): the Unix-domain-socket listener
    for co-located fan-out clients and the reusable response-encode
    arenas. Both default off (TCP-only, allocate-per-call — the
    historical behavior)."""

    # Also bind the gRPC server to this Unix-domain socket path (next to
    # the TCP port). Co-located clients dial "unix:<path>" as the host
    # string. "" = TCP only.
    uds_path: str = ""
    # Route response encodes through per-thread codec.EncodeArena scratch
    # (and reuse one PredictStreamChunk message per stream) instead of
    # allocating per call.
    response_arena: bool = False

    def __post_init__(self):
        if self.uds_path:
            if not isinstance(self.uds_path, str):
                raise ValueError("[transport] uds_path must be a string")
            # The kernel's sockaddr_un limit is ~107 bytes; failing at
            # config parse beats failing at bind time inside serve().
            if len(self.uds_path.encode()) > 100:
                raise ValueError(
                    "[transport] uds_path exceeds the AF_UNIX path limit "
                    f"(~107 bytes): {self.uds_path!r}"
                )
            if ":" in self.uds_path:
                raise ValueError(
                    "[transport] uds_path is a filesystem path, not a "
                    f"host:port or URI: {self.uds_path!r}"
                )


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Mesh serving mode (ISSUE 13): shard serving over a ("data",
    "model") device mesh — candidate rows split over the data axis,
    embedding vocab over the model axis (parallel/mesh.py axis
    conventions; DLRM-scale CTR models are embedding-dominated, so the
    model axis is what lets a table that does not fit one chip serve at
    all). Off by default: with the section absent serving is single-chip
    and bit-identical to the pre-mesh stack.

    Arming it installs a hardened parallel/executor.ShardedExecutor as
    the batcher's run_fn: same wire protocol, same client semantics, one
    process spanning N chips. Mode conflicts ([recovery], the
    legacy [server] mesh_devices knob, output_top_k) are refused at
    build time — see build_stack."""

    # Master switch: construct the mesh and install the ShardedExecutor.
    enabled: bool = False
    # Devices in the mesh; 0 = every visible device. Must be divisible by
    # model_parallel (the ("data", "model") factorization).
    devices: int = 0
    # Chips sharding the embedding vocab (the EP axis); the rest of the
    # factorization shards candidates. 1 = pure candidate sharding.
    model_parallel: int = 1
    # Also shard dense MLP/cross weights over the model axis (the TP row;
    # embedding tables are vocab-sharded regardless).
    tensor_parallel: bool = False

    def __post_init__(self):
        for name in ("devices", "model_parallel"):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                raise ValueError(
                    f"[mesh] {name} must be a non-negative integer, got {v!r}"
                )
        if self.model_parallel < 1:
            raise ValueError(
                f"[mesh] model_parallel must be >= 1, got {self.model_parallel!r}"
            )
        if self.devices and self.devices % self.model_parallel != 0:
            raise ValueError(
                f"[mesh] devices={self.devices} is not divisible by "
                f"model_parallel={self.model_parallel} (the mesh is the "
                "(devices/model_parallel, model_parallel) factorization)"
            )


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Elastic mesh serving (ISSUE 15, parallel/elastic.py): a ladder of
    ("data", "model") splits over the SAME devices, pre-built and
    pre-warmed at load time, with a pressure-driven controller switching
    the serving split at runtime — hitlessly (in-flight batches on the
    old split drain behind the per-split in-flight barrier; new
    dispatches route to the target immediately; no serving-path
    compiles). Requires [mesh] enabled (the initial split IS the [mesh]
    factorization); off by default — with the section absent, mesh
    serving is exactly the static PR-13 mode."""

    # Master switch: build an ElasticMeshExecutor + ElasticController
    # instead of the static ShardedExecutor.
    enabled: bool = False
    # The split ladder, e.g. ["8x1", "4x2", "2x4"] (DATAxMODEL; every
    # entry must factorize the [mesh] device count). Empty = derived:
    # {n,1}, {n/2,2} (n even), and the [mesh] split. Sorted
    # throughput-first internally; "up" switches move toward the
    # data-parallel end.
    splits: tuple = ()
    # Controller cadence (opportunistic — ticked from dispatches and
    # monitoring scrapes, no thread; the overload plane's precedent).
    tick_interval_s: float = 0.5
    # Minimum time between switches (the anti-flap floor; also the time
    # the FIRST switch waits after arming).
    dwell_s: float = 5.0
    # Consecutive over/under ticks before a one-rung move. Down is
    # deliberately slower: relaxing parallelism is a latency nicety,
    # escalating it is a survival move.
    up_after_ticks: int = 2
    down_after_ticks: int = 6
    # Load-EWMA thresholds (queue fraction / bucket occupancy, max of
    # both): >= up counts an up tick even at NOMINAL pressure; <= down
    # (at NOMINAL) counts a down tick; between is the hysteresis band
    # (streaks reset, split holds).
    load_up_threshold: float = 0.75
    load_down_threshold: float = 0.20
    load_ewma_alpha: float = 0.3
    # Retained switch-history events (the /meshz ring).
    history_events: int = 64

    def __post_init__(self):
        for s in self.splits:
            d, sep, m = str(s).strip().lower().partition("x")
            if not sep or not d.isdigit() or not m.isdigit() \
                    or int(d) < 1 or int(m) < 1:
                raise ValueError(
                    f"[elastic] splits entry {s!r} is not 'DATAxMODEL' "
                    "with positive integer axes (e.g. '4x2')"
                )
        for name in ("tick_interval_s", "dwell_s", "load_ewma_alpha"):
            v = getattr(self, name)
            if not isinstance(v, (int, float)) or isinstance(v, bool) or v <= 0:
                raise ValueError(
                    f"[elastic] {name} must be a positive number, got {v!r}"
                )
        for name in ("up_after_ticks", "down_after_ticks", "history_events"):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                raise ValueError(
                    f"[elastic] {name} must be a positive integer, got {v!r}"
                )
        up, down = self.load_up_threshold, self.load_down_threshold
        for name, v in (("load_up_threshold", up), ("load_down_threshold", down)):
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or not 0.0 <= v <= 1.0:
                raise ValueError(
                    f"[elastic] {name} must be in [0, 1], got {v!r}"
                )
        if down >= up:
            raise ValueError(
                f"[elastic] load_down_threshold ({down}) must be below "
                f"load_up_threshold ({up}) — the gap IS the hysteresis "
                "band; equal thresholds would flap on every load wiggle"
            )
        if self.load_ewma_alpha > 1.0:
            raise ValueError(
                f"[elastic] load_ewma_alpha must be in (0, 1], got "
                f"{self.load_ewma_alpha!r}"
            )


@dataclasses.dataclass(frozen=True)
class ObservabilityConfig:
    """Telemetry-plane knobs (utils/tracing.py + utils/metrics.py): the
    per-request trace recorder behind GET /tracez and the rolling-window
    horizon of /monitoring and the Prometheus endpoint."""

    # Per-request span tracing (W3C traceparent propagation, /tracez,
    # Chrome-trace export). Off by default: the hot path then pays one
    # global bool read per hook.
    tracing: bool = False
    # Retained local-root spans per ring (recent / error) — memory bound.
    trace_buffer: int = 256
    # Tail-sampling rate for unremarkable traces (errors, degraded
    # results, and fault-annotated traces are ALWAYS kept). 0.0 keeps
    # nothing but the tails; 1.0 keeps everything the buffer can hold.
    trace_sample_rate: float = 1.0
    # The slowest-N traces are always retained regardless of sampling.
    trace_slowest_n: int = 32
    # Rolling-window horizon for sliding QPS + windowed p50/p99.
    window_seconds: float = 60.0
    # Fleet trace export (ISSUE 18): when on (and tracing is on), the
    # replica serves its kept span trees incrementally at
    # GET /tracez/export?since= — the pull surface the router-side
    # TraceCollector stitches cross-process traces from. Off by
    # default; costs nothing when off (the route answers
    # {"enabled": false}).
    trace_export: bool = False
    # How often the router's fleet observability plane ticks: scrapes
    # member /monitoring wires, pulls trace exports, advances the SLO
    # monitor.
    trace_export_interval_s: float = 1.0

    def __post_init__(self):
        v = self.trace_export_interval_s
        if not isinstance(v, (int, float)) or isinstance(v, bool) \
                or v <= 0:
            raise ValueError(
                "[observability] trace_export_interval_s must be a "
                f"positive number, got {v!r}"
            )

    def apply(self):
        """Flip the global tracing plane to this config; returns the
        active TraceRecorder (or None when tracing stays off)."""
        from . import tracing as tracing_mod

        if not self.tracing:
            tracing_mod.disable()
            return None
        return tracing_mod.enable(
            buffer_size=self.trace_buffer,
            sample_rate=self.trace_sample_rate,
            slowest_n=self.trace_slowest_n,
        )


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """Cache-plane knobs (cache/score_cache.py + cache/dedup.py): the
    exact-match score cache with single-flight coalescing at
    batcher.submit, and intra-batch duplicate collapse in the batcher.
    Everything defaults OFF and, when off, costs one attribute read on the
    hot path (the tracing/faults precedent)."""

    # Master switch: build a ScoreCache and hand it to the batcher.
    enabled: bool = False
    # LRU capacity in entries and in cached-score bytes (whichever binds
    # first; split across the sharded locks).
    max_entries: int = 8192
    max_bytes: int = 64 << 20
    # Shelf life per entry: CTR scores decay with state not in the request
    # (user history, budget pacing), so exact-match hits are only served
    # this long after the computation that produced them. Version swaps
    # invalidate eagerly regardless (version-watcher hook).
    ttl_s: float = 30.0
    # Single-flight: concurrent IDENTICAL misses ride one computation
    # (one leader executes, every waiter gets its scores).
    coalesce: bool = True
    # Intra-batch duplicate collapse: exact-duplicate rows within a
    # combined batch execute once, scores scattered back per requester.
    dedup: bool = False
    # Row-granular score caching (cache/row_cache.py, ISSUE 14): cache
    # scores PER CANDIDATE ROW so a request with 90% hot rows executes
    # only the cold 10% — the batcher consults the row cache after
    # collect, dispatches only the cold rows (possibly a smaller bucket),
    # and scatters device + cached scores back per request. Master-gated
    # by `enabled` like dedup (enabled=false arms nothing). The
    # whole-request cache stays in front: a full hit never reaches the
    # row path.
    row_granular: bool = False
    # Row-tier LRU capacity (entries are single rows — small values, so
    # the entry bound usually binds first) and shelf life. Row entries
    # ride the same generation invalidation (version swaps drop them
    # eagerly) and the same brownout stale window as request entries.
    row_max_entries: int = 131072
    row_max_bytes: int = 32 << 20
    row_ttl_s: float = 30.0
    # Per-row single-flight: two co-resident batches sharing a cold row
    # execute it once (the second assembles from the first's fill).
    row_coalesce: bool = True

    def build(self):
        """ScoreCache per this config, or None when disabled."""
        if not self.enabled:
            return None
        from ..cache import ScoreCache

        return ScoreCache(
            max_entries=self.max_entries,
            max_bytes=self.max_bytes,
            ttl_s=self.ttl_s,
            coalesce=self.coalesce,
        )

    def build_row(self):
        """RowScoreCache per this config, or None when the plane (or the
        [cache] master switch) is off — enabled=false with
        row_granular=true must arm nothing, the dedup precedent."""
        if not (self.enabled and self.row_granular):
            return None
        from ..cache import RowScoreCache

        return RowScoreCache(
            max_entries=self.row_max_entries,
            max_bytes=self.row_max_bytes,
            ttl_s=self.row_ttl_s,
            coalesce=self.row_coalesce,
        )


@dataclasses.dataclass(frozen=True)
class OverloadConfig:
    """Overload-control knobs (serving/overload.py): the adaptive
    admission controller, criticality lanes, brownout stale-serve, and
    the drain grace the SIGTERM handler honors. Everything defaults OFF;
    when off the batcher keeps its static queue_capacity_candidates bound
    and pays one attribute read per submit."""

    # Master switch: build an AdmissionController and hand it to the
    # batcher (replacing the static queue_capacity_candidates check).
    enabled: bool = False
    # The controlled variable: windowed queue-wait p99 is steered toward
    # this target by growing/shrinking the admission limit.
    target_queue_wait_ms: float = 50.0
    # Sliding window the p99 is computed over, and how often the AIMD
    # controller ticks (opportunistically, from the submit path).
    queue_wait_window_s: float = 10.0
    adjust_interval_s: float = 0.5
    # AIMD step sizes: additive growth while under target, multiplicative
    # shrink while over.
    increase_candidates: int = 1024
    decrease_factor: float = 0.7
    # Limit clamp in candidates. 0 = auto: min one largest bucket (a
    # full-size request always admits on an idle queue), max the static
    # queue capacity the controller replaces.
    min_limit_candidates: int = 0
    max_limit_candidates: int = 0
    # EWMA smoothing for per-candidate service time (deadline pricing).
    service_ewma_alpha: float = 0.2
    # Refuse at enqueue when the backlog's estimated wait already exceeds
    # the request's remaining deadline budget (doomed work).
    deadline_refusal: bool = True
    # Pressure state machine: consecutive over-target ticks before
    # NOMINAL->BROWNOUT and before BROWNOUT->SHED; consecutive under-
    # target ticks before stepping one level back down.
    brownout_after_intervals: int = 4
    shed_after_intervals: int = 12
    recover_after_intervals: int = 6
    # Brownout stale-serve: while pressure is past NOMINAL, score-cache
    # entries up to this far past their TTL still serve (marked degraded,
    # never re-filled). 0 disables stale serving.
    stale_while_overloaded_s: float = 30.0
    # Clamp for the retry-after-ms pushback hint on refusals.
    retry_after_floor_ms: int = 25
    retry_after_cap_ms: int = 2000
    # SIGTERM drain: how long the server waits for queued + in-flight
    # batches to finish before stopping (honored whether or not the
    # adaptive controller is enabled).
    drain_grace_s: float = 5.0

    def build(self):
        """AdmissionController per this config, or None when disabled."""
        if not self.enabled:
            return None
        from ..serving.overload import AdmissionController

        return AdmissionController(self)


@dataclasses.dataclass(frozen=True)
class UtilizationConfig:
    """Utilization-attribution knobs (serving/utilization.py): the
    per-device occupancy ledger + gap waterfall behind GET /utilz, the
    `utilization` block in /monitoring, the dts_tpu_utilization_*
    Prometheus series, and the Perfetto counter track in the Chrome
    export. Off by default; when off every batcher hook is one attribute
    read (the tracing/cache/overload precedent)."""

    # Master switch: build an OccupancyLedger and hand it to the batcher.
    enabled: bool = False
    # Ring bound for retained batch intervals / idle gaps / wait records
    # (the windowed waterfall's memory + lookback bound).
    ring: int = 4096
    # Default waterfall window for /utilz and /monitoring.
    window_seconds: float = 60.0
    # Optional per-bucket pure-device-step table (us) calibrating the
    # live achieved_fraction_of_device_limit estimate: a JSON table of
    # device-step times ({bucket: us} or {bucket: [lo, hi]}). "" = uncalibrated (busy-fraction fallback,
    # labeled as such in the waterfall).
    calibration_file: str = ""
    # Where POST /profilez/start drops capture artifacts (jax profiler
    # trace + host_stacks.json). "" = a tempdir subfolder.
    profile_dir: str = ""

    def build(self):
        """OccupancyLedger per this config (registered as a Chrome
        counter-track source), or None when disabled. Applies
        profile_dir to the process-global capture slot either way —
        /profilez is on-demand and available regardless of the ledger."""
        from ..serving import utilization as util_mod

        if self.profile_dir:
            util_mod.profiler_capture().base_dir = self.profile_dir
        if not self.enabled:
            return None
        calibration = (
            util_mod.load_calibration(self.calibration_file)
            if self.calibration_file else None
        )
        ledger = util_mod.OccupancyLedger(
            ring=self.ring,
            window_s=self.window_seconds,
            calibration=calibration,
        )
        from . import tracing as tracing_mod

        tracing_mod.register_counter_source(ledger)
        return ledger


@dataclasses.dataclass(frozen=True)
class QualityConfig:
    """Model-quality observability knobs (serving/quality.py): the
    per-(model, version) score-distribution sketches, PSI/JS drift vs a
    pinned reference and between live versions, the /labelz label-
    feedback join (windowed AUC + calibration), and drift-linked trace
    exemplars. Off by default; when off the batcher completer pays one
    attribute read per batch (the tracing/cache/overload/utilization
    precedent)."""

    # Master switch: build a QualityMonitor and hand it to the batcher.
    enabled: bool = False
    # Fixed-bin score histogram geometry. CTR scores are sigmoid
    # probabilities, so [0, 1]; out-of-range scores clamp to edge bins.
    bins: int = 50
    range_lo: float = 0.0
    range_hi: float = 1.0
    # Rolling window the drift math and windowed AUC read over, and how
    # many ring slices it is built from (granularity = window/slices).
    window_seconds: float = 300.0
    slices: int = 6
    # Drift alerting: current-window PSI vs the pinned reference (or
    # between live versions) at/above this threshold arms exemplar
    # capture. 0.2 = the standard "moderate shift" PSI band.
    drift_threshold_psi: float = 0.2
    # How often the drift math runs (opportunistically from the observe
    # path — no background thread), and how many of the next traced
    # requests get the force-keep `quality.drift` annotation per check
    # interval while drift stays above threshold.
    drift_check_interval_s: float = 5.0
    exemplar_traces: int = 8
    # Minimum window samples (each side) before a drift number is
    # computed — PSI on a handful of scores is noise, not signal.
    min_drift_count: int = 50
    # Label-feedback join bounds: score-reservoir keys retained (LRU; a
    # label for an evicted key counts as orphaned, never silently
    # dropped), joined (score, label) pairs retained, and the largest
    # request (candidates) that gets per-row digest keys computed.
    reservoir_keys: int = 8192
    label_window: int = 8192
    digest_rows_limit: int = 256
    # Pinned-reference artifact: loaded at build when present, written by
    # POST /qualityz/snapshot. "" disables persistence (pin-only).
    reference_file: str = "artifacts/quality_reference.json"

    def build(self):
        """QualityMonitor per this config, or None when disabled."""
        if not self.enabled:
            return None
        from ..serving.quality import QualityMonitor

        return QualityMonitor(
            bins=self.bins,
            lo=self.range_lo,
            hi=self.range_hi,
            window_s=self.window_seconds,
            slices=self.slices,
            drift_threshold_psi=self.drift_threshold_psi,
            drift_check_interval_s=self.drift_check_interval_s,
            exemplar_traces=self.exemplar_traces,
            min_drift_count=self.min_drift_count,
            reservoir_keys=self.reservoir_keys,
            label_window=self.label_window,
            digest_rows_limit=self.digest_rows_limit,
            reference_file=self.reference_file,
        )


@dataclasses.dataclass(frozen=True)
class LifecycleConfig:
    """Continuous-freshness lifecycle knobs (serving/lifecycle.py): the
    online fine-tune publisher, canary admission ramp, and the drift/AUC
    auto-rollback controller. Off by default; when off the service pays
    one attribute read per version resolution (the tracing/cache/overload
    precedent). Arming it requires --model-base-path (the watched
    versioned dir is both the publish target and the hot-swap mechanism)
    and [quality] enabled (the rollback gate reads the quality plane's
    version-pair drift and per-version label AUC) — build_stack refuses
    a lifecycle with no signal or no actuator rather than arming a
    controller that can only ever promote blind."""

    # Master switch: build a LifecycleController and hand it to the impl.
    enabled: bool = False
    # Control-loop cadence: the background thread's tick interval, also
    # the opportunistic-tick spacing on the routing path.
    tick_interval_s: float = 1.0
    # Canary admission ramp: probe-lane-only warm phase, then a
    # deterministic fraction of default-lane traffic stepping up per
    # dwell until max_fraction.
    canary_probe_only_s: float = 10.0
    canary_initial_fraction: float = 0.05
    canary_ramp_step: float = 0.10
    canary_step_dwell_s: float = 10.0
    canary_max_fraction: float = 0.5
    # Promotion: total healthy CANARY time (past the probe phase) at max
    # fraction, with at least min_canary_scores windowed canary scores,
    # before the routing override drops away and latest serves everyone.
    promote_after_s: float = 60.0
    min_canary_scores: int = 200
    # Rollback: version-pair PSI at/above this (0.5 = well past the
    # "major shift" band — rollback wants stronger evidence than the
    # quality plane's 0.2 alert), or a windowed label-feedback AUC drop
    # of at least rollback_auc_drop with min_auc_pairs joined on each
    # side. The rolled-back state holds rollback_hold_s before the
    # controller re-arms for the next rollout.
    rollback_psi: float = 0.5
    # The rollback PSI is computed over this many MERGED bins, not the
    # quality plane's fine histogram: a fresh canary's window is small,
    # and same-distribution PSI over 50 thin bins at a few hundred
    # samples reads 0.2-0.3 of pure sampling noise (measured) — within
    # reach of the threshold — while ~10 merged bins put the noise floor
    # at ~0.03 with a genuine shift still reading >1.
    rollback_compare_bins: int = 10
    rollback_auc_drop: float = 0.05
    min_auc_pairs: int = 100
    rollback_hold_s: float = 30.0
    # Fine-tune publisher cadence: every interval (while IDLE), continue
    # training the stable servable on fresh rows and publish the result
    # as the next version. 0 = publisher off (canary/rollback still
    # manage externally published versions).
    fine_tune_interval_s: float = 0.0
    fine_tune_steps: int = 200
    fine_tune_batch_size: int = 256
    fine_tune_learning_rate: float = 1e-4
    # Retained transition-event history (/lifecyclez `events`).
    history_events: int = 64


@dataclasses.dataclass(frozen=True)
class RecoveryConfig:
    """Device-failure recovery knobs (serving/recovery.py): the watchdog
    that escalates the batcher's wedge clock into a quarantine, the
    in-process executor reinit, the in-flight/queued replay budget, and
    the poisoned-input bisection thresholds. Off by default; when off
    the batcher pays one attribute read per hook and behavior is
    bit-identical to the pre-plane stack (the tracing/cache/overload
    precedent)."""

    # Master switch: build a RecoveryController and attach it to the
    # batcher + impl.
    enabled: bool = False
    # Watchdog poll cadence (the background thread; failure-triggered
    # cycles wake it early).
    watchdog_interval_s: float = 0.5
    # A dispatched/in-flight batch outstanding this long quarantines the
    # replica — the ESCALATION threshold, far below the circuit
    # breaker's fail-fast bound (default 90s): the breaker protects
    # handler threads, this protects the replica.
    wedge_quarantine_s: float = 15.0
    # Max re-dispatches per work item across the whole recovery history;
    # past it the item fails with the original device error. Sized for
    # bisection: isolating one poison row in a 64-request batch takes
    # ~log2(64)+2 replays of the innocent rows.
    replay_budget: int = 8
    # A SINGLE-request batch that has killed the executor this many
    # times is the poison: it alone fails (INVALID_ARGUMENT).
    poison_kills: int = 2
    # A MULTI-request batch whose members have this many kills is
    # bisected into halves instead of replayed whole.
    bisect_after_kills: int = 2
    # Re-warm every registered servable's bucket ladder through the
    # queue after the executor rebuild (recommended: the first replayed
    # batch must not pay a compile storm under the wedge clock).
    reinit_warmup: bool = True
    rewarm_timeout_s: float = 120.0
    # Also tear down the jax backend client itself (process-global,
    # heavyweight; only for genuinely lost devices — never the default).
    reinit_clear_backend: bool = False
    # How long REPLAY waits for the requeued items to complete before
    # declaring the cycle done (failures re-trigger; this only bounds
    # the state machine's dwell).
    replay_drain_s: float = 30.0
    # Hard bound on reinit+replay rounds inside one cycle (bisection of
    # pathological batches); past it the remaining items fail.
    max_cycle_rounds: int = 20
    # Retained transition-event history (/recoveryz `events`).
    history_events: int = 64
    # Recovery unit. "executor" (the only implemented scope): the whole
    # serving executor quarantines/reinits/replays as ONE unit — over a
    # [mesh] that means the entire mesh (an SPMD executable spans every
    # chip; there is no half-alive mesh to keep serving). "per_chip" is
    # refused at build time when a mesh is armed (documented future
    # work); on a single chip the two scopes are the same thing.
    scope: str = "executor"

    def __post_init__(self):
        if self.scope not in ("executor", "per_chip"):
            raise ValueError(
                f"[recovery] scope must be 'executor' or 'per_chip', "
                f"got {self.scope!r}"
            )
        for name in ("replay_budget", "poison_kills", "bisect_after_kills",
                     "max_cycle_rounds"):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                raise ValueError(
                    f"[recovery] {name} must be a positive integer, got {v!r}"
                )
        for name in ("watchdog_interval_s", "wedge_quarantine_s",
                     "replay_drain_s", "rewarm_timeout_s"):
            v = getattr(self, name)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v <= 0:
                # Refuse up front (the other planes' precedent) instead
                # of silently flooring a 0/negative into hair-trigger
                # quarantines or unbounded dwells downstream.
                raise ValueError(
                    f"[recovery] {name} must be a positive number, got {v!r}"
                )


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Fleet robustness plane knobs (fleet/ package, ISSUE 17): the
    cross-replica health gossip every member runs, and the shared rollout
    state the router (single writer) coordinates. The router process
    itself is `--router` / `python -m distributed_tf_serving_tpu.fleet.router`
    over the SAME file: [server] is its bind address, [client] its
    backend list + steering knobs, [fleet] this section. Off by default;
    a disarmed replica pays one attribute read per hook."""

    # Master switch: start a GossipAgent next to the server and register
    # /fleetz.
    enabled: bool = False
    # Stable member name in gossip records. "" = derive from the gossip
    # listen address (fine for static fleets; set it when replicas sit
    # behind NAT or get respawned on new ports).
    self_id: str = ""
    # Address PEERS use to reach this member's gossip listener
    # ("host:port" or "unix:/path"). "" = the listener's own bind
    # address.
    advertise_addr: str = ""
    # Other members' gossip endpoints ("host:port" or "unix:/path").
    # Every member gossips with every listed peer each interval
    # (push-pull, so one live peer in common converges the fleet).
    peers: tuple[str, ...] = ()
    # Gossip listener bind. Port 0 = ephemeral (tests); production sets
    # a fixed port so peers can list it. gossip_uds switches the
    # listener (and dialing peers given as unix:...) to AF_UNIX.
    gossip_host: str = "127.0.0.1"
    gossip_port: int = 0
    gossip_uds: str = ""
    # Push-pull exchange cadence; fleet-wide convergence is one or two
    # intervals (record rides both the push and the response).
    gossip_interval_s: float = 0.5
    # A member silent this long is dropped from the view (SIGKILL with
    # no goodbye). Must exceed a few intervals or flaky peers flap.
    record_ttl_s: float = 5.0
    # Rollout coordination (fleet/rollout.py). Exactly ONE member — the
    # router — sets rollout_writer=true and owns the state file; every
    # other member follows the rollout state it sees in gossip.
    rollout_writer: bool = False
    # Where the writer persists rollout state (atomic rename). "" on
    # the writer = in-memory only (still distributed via gossip, lost
    # on router restart).
    rollout_state_file: str = ""

    def __post_init__(self):
        for name in ("gossip_interval_s", "record_ttl_s"):
            v = getattr(self, name)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v <= 0:
                raise ValueError(
                    f"[fleet] {name} must be a positive number, got {v!r}"
                )
        if not isinstance(self.gossip_port, int) or \
                isinstance(self.gossip_port, bool) or self.gossip_port < 0:
            raise ValueError(
                f"[fleet] gossip_port must be a non-negative integer, "
                f"got {self.gossip_port!r}"
            )
        if self.record_ttl_s <= self.gossip_interval_s:
            raise ValueError(
                "[fleet] record_ttl_s must exceed gossip_interval_s "
                f"(got ttl={self.record_ttl_s!r} <= "
                f"interval={self.gossip_interval_s!r}) — a member would "
                "expire between its own heartbeats"
            )


@dataclasses.dataclass(frozen=True)
class SloConfig:
    """SLO burn-rate monitor knobs (fleet/observability.py, ISSUE 18):
    the router's multi-window error-budget monitor over aggregated
    fleet telemetry, served at GET /sloz and as dts_tpu_slo_* series.
    Off by default; when on, a breach annotates in-flight router spans
    (`slo.burn`) so the tail sampler force-keeps explaining traces."""

    enabled: bool = False
    # Latency SLO: fraction of requests under latency_target_ms must
    # meet latency_objective.
    latency_target_ms: float = 50.0
    latency_objective: float = 0.99
    # Availability SLO: fraction of non-error requests.
    availability_objective: float = 0.999
    # Multi-window burn rates (Google SRE workbook shape): a page fires
    # only when BOTH the short and long window burn fast — short alone
    # is noise, long alone is stale.
    short_window_s: float = 300.0
    long_window_s: float = 3600.0
    # burn = bad_fraction / error_budget. 14.4x exhausts a 30-day
    # budget in 2 days (page); 6x in 5 days (ticket/warn).
    burn_threshold_fast: float = 14.4
    burn_threshold_slow: float = 6.0

    def __post_init__(self):
        for name in (
            "latency_target_ms", "short_window_s", "long_window_s",
            "burn_threshold_fast", "burn_threshold_slow",
        ):
            v = getattr(self, name)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v <= 0:
                raise ValueError(
                    f"[slo] {name} must be a positive number, got {v!r}"
                )
        for name in ("latency_objective", "availability_objective"):
            v = getattr(self, name)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or not (0.0 < v < 1.0):
                raise ValueError(
                    f"[slo] {name} must be in (0, 1), got {v!r} — an "
                    "objective of 1.0 leaves zero error budget and "
                    "every burn rate divides by zero"
                )
        if self.long_window_s <= self.short_window_s:
            raise ValueError(
                "[slo] long_window_s must exceed short_window_s "
                f"(got long={self.long_window_s!r} <= "
                f"short={self.short_window_s!r}) — multi-window burn "
                "alerting needs distinct horizons"
            )


@dataclasses.dataclass(frozen=True)
class CascadeConfig:
    """Multi-stage ranking cascade knobs (serving/cascade.py, ISSUE 19):
    a cheap first-stage servable prunes the candidate set on-device and
    the full model ranks only the survivors — retrieval->rank in one
    RPC. Off by default (one attribute read per Predict when disabled).
    Refused alongside output_top_k (its wire replaces the score vector
    the cascade's scatter needs) and [mesh]/[elastic] (the sharded
    executor has no prune entry)."""

    enabled: bool = False
    # Registry name the first-stage servable is published/resolved under
    # — a NORMAL model name: the version watcher, lifecycle, and quality
    # planes see it like any other servable.
    stage1_model: str = "stage1"
    # Registered model kind built for the demo stage-1 servable when no
    # stage1_base_path supplies checkpoints (two_tower: the user-tower /
    # item-tower dot product is the classic cheap retrieval scorer).
    stage1_kind: str = "two_tower"
    # Versioned base path for watcher-managed stage-1 rollouts; empty =
    # build the demo stage-1 servable in-process.
    stage1_base_path: str = ""
    # Survivor budget: a fixed top-k when > 0, else ceil of this fraction
    # of the request's candidates.
    survivor_k: int = 0
    survivor_fraction: float = 0.25
    # Optional host-side filter on stage-1 survivor scores: survivors
    # scoring below this are pruned too (0 disables; applied AFTER the
    # top-k selection, so it only ever shrinks the ranked set).
    score_threshold: float = 0.0
    # Requests smaller than this skip the cascade outright — two device
    # round trips cost more than ranking a tiny batch once.
    min_candidates: int = 8

    def __post_init__(self):
        if not self.stage1_model:
            raise ValueError("[cascade] stage1_model must be non-empty")
        if not isinstance(self.survivor_k, int) or isinstance(
            self.survivor_k, bool
        ) or self.survivor_k < 0:
            raise ValueError(
                "[cascade] survivor_k must be a non-negative int, got "
                f"{self.survivor_k!r}"
            )
        if not isinstance(self.survivor_fraction, (int, float)) or isinstance(
            self.survivor_fraction, bool
        ) or not (0.0 < self.survivor_fraction <= 1.0):
            raise ValueError(
                "[cascade] survivor_fraction must be in (0, 1], got "
                f"{self.survivor_fraction!r}"
            )
        if not isinstance(self.min_candidates, int) or isinstance(
            self.min_candidates, bool
        ) or self.min_candidates < 2:
            raise ValueError(
                "[cascade] min_candidates must be an int >= 2, got "
                f"{self.min_candidates!r} — a 1-candidate cascade prunes "
                "nothing and pays two submits"
            )
        if not isinstance(self.score_threshold, (int, float)) or isinstance(
            self.score_threshold, bool
        ) or self.score_threshold < 0.0:
            raise ValueError(
                "[cascade] score_threshold must be >= 0, got "
                f"{self.score_threshold!r}"
            )


@dataclasses.dataclass(frozen=True)
class IntegrityConfig:
    """Data-integrity plane knobs (serving/integrity.py, ISSUE 20): wire
    CRC32C sidecars, the post-D2H readback sanity screen, and sampled
    bit-identity shadow verification — three detection ladders against
    SILENT corruption (flipped D2H bits, decaying host buffers, plausible
    wrong scores) that every other robustness plane is blind to because
    nothing errors. Verdicts escalate into the EXISTING recovery (PR 11)
    and gossip/router (PR 17) machinery instead of new quarantine logic.
    Off by default; when off every hook is one attribute read and served
    bytes are bit-identical to the pre-plane stack."""

    # Master switch: build an IntegrityPlane and attach it to the impl +
    # batcher; arms the server-side wire verify and response stamping.
    enabled: bool = False
    # Layer 1 — wire integrity. Verify x-dts-input-crc request sidecars
    # at decode (the corrupted request alone fails INVALID_ARGUMENT with
    # a corrupt-wire detail) and stamp x-dts-score-crc over the response
    # score tensor for opted-in clients to verify before merge.
    wire_checksums: bool = True
    # Layer 2 — readback sanity screen. Post-D2H NaN/Inf check over the
    # score tensor in the batcher completer; a failing ROW fails its own
    # request while batchmates deliver (the PR-11 per-item machinery).
    screen: bool = True
    # Optional plausible-score interval [screen_min, screen_max] the
    # screen also enforces; (0, 0) disables the range check (NaN/Inf
    # only). CTR scores are probabilities, so (0, 1) is the natural
    # production setting — but the default must not reject imported
    # graphs with logit-scale outputs.
    screen_min: float = 0.0
    screen_max: float = 0.0
    # Screen trips past this count inside screen_window_s escalate to
    # RecoveryController.take_group (output_corrupt): one cosmic-ray row
    # is row-failed and forgotten, a persistently-corrupting executor
    # walks the QUARANTINED->REINIT->REPLAY cycle.
    screen_trips_per_window: int = 3
    screen_window_s: float = 10.0
    # Layer 3 — shadow verification. Fraction of batches re-executed
    # through the SAME jitted entry and compared bit-identically on
    # host; any mismatch is nondeterminism or silent corruption ->
    # recovery escalation + the suspect verdict gossiped fleet-wide.
    # 0.0 = sampled shadowing off (POST /integrityz/audit still works).
    shadow_fraction: float = 0.0
    # Router tier: fraction of forwarded requests additionally fanned to
    # TWO replicas with bit-identical compare; disagreement marks the
    # minority replica suspect in gossip. 0.0 = off.
    router_audit_fraction: float = 0.0
    # Consecutive clean shadow passes that clear a replica's suspect
    # verdict (self-check rehabilitation).
    suspect_clear_passes: int = 3
    # Retained detection-event history (/integrityz `events`).
    history_events: int = 64

    def __post_init__(self):
        for name in ("screen_trips_per_window", "suspect_clear_passes",
                     "history_events"):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                raise ValueError(
                    f"[integrity] {name} must be a positive integer, "
                    f"got {v!r}"
                )
        for name in ("shadow_fraction", "router_audit_fraction"):
            v = getattr(self, name)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or not 0.0 <= v <= 1.0:
                raise ValueError(
                    f"[integrity] {name} must be in [0, 1], got {v!r}"
                )
        if not isinstance(self.screen_window_s, (int, float)) or isinstance(
            self.screen_window_s, bool
        ) or self.screen_window_s <= 0:
            raise ValueError(
                f"[integrity] screen_window_s must be a positive number, "
                f"got {self.screen_window_s!r}"
            )
        for name in ("screen_min", "screen_max"):
            v = getattr(self, name)
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                raise ValueError(
                    f"[integrity] {name} must be a number, got {v!r}"
                )
        if self.screen_max < self.screen_min:
            raise ValueError(
                f"[integrity] screen_max ({self.screen_max!r}) must be >= "
                f"screen_min ({self.screen_min!r}); use (0, 0) to disable "
                "the range check"
            )

    def build(self):
        from ..serving.integrity import IntegrityPlane

        return IntegrityPlane(self)


def _model_config_cls():
    from ..models.base import ModelConfig

    return ModelConfig


_SECTIONS = {
    "server": ServerConfig,
    "client": ClientConfig,
    "mesh": MeshConfig,
    "elastic": ElasticConfig,
    "batching": BatchingConfig,
    "transport": TransportConfig,
    "observability": ObservabilityConfig,
    "cache": CacheConfig,
    "overload": OverloadConfig,
    "utilization": UtilizationConfig,
    "quality": QualityConfig,
    "lifecycle": LifecycleConfig,
    "recovery": RecoveryConfig,
    "fleet": FleetConfig,
    "slo": SloConfig,
    "cascade": CascadeConfig,
    "integrity": IntegrityConfig,
}


def _coerce(cls, data: dict[str, Any]):
    fields = {f.name: f for f in dataclasses.fields(cls)}
    unknown = set(data) - set(fields)
    if unknown:
        raise ValueError(f"unknown {cls.__name__} keys: {sorted(unknown)}")
    kwargs = {}
    for key, value in data.items():
        if isinstance(value, list):
            value = tuple(value)
        elif isinstance(value, dict) and key == "version_labels":
            # TOML inline table -> the hashable pair form the frozen
            # dataclass stores.
            value = tuple(sorted((str(k), int(v)) for k, v in value.items()))
        kwargs[key] = value
    return cls(**kwargs)


def validate_model_config_entries(entries, source: str):
    """Shared shape validation for model_config_list entries — ONE rule
    set for startup (--model-config-file) and runtime reloads
    (HandleReloadConfigRequest), so the two paths cannot drift. Raises
    ValueError; returns the entries as a list."""
    seen = set()
    for mc in entries:
        if not mc.name or not mc.base_path:
            raise ValueError(
                f"{source}: every model config needs name and base_path "
                f"(got name={mc.name!r} base_path={mc.base_path!r})"
            )
        if mc.name in seen:
            raise ValueError(f"{source}: duplicate model {mc.name!r}")
        seen.add(mc.name)
    return list(entries)


def apply_batching_parameters(cfg: ServerConfig, path) -> ServerConfig:
    """Map a tensorflow_model_server --batching_parameters_file (text-format
    BatchingParameters, session_bundle_config.proto upstream) onto the
    ServerConfig's batcher knobs, so existing TF-Serving deployments bring
    their tuning file unchanged:

    - allowed_batch_sizes        -> the bucket ladder (upstream rule kept:
                                    when both are set, the largest allowed
                                    size must equal max_batch_size);
    - max_batch_size             -> max_batch_candidates (top bucket);
    - batch_timeout_micros       -> max_wait_us;
    - max_enqueued_batches       -> queue_capacity_candidates (upstream
                                    bounds queued BATCHES; ours bounds
                                    queued candidates, so x max_batch);
    - num_batch_threads          -> completion_workers (upstream's batch
                                    compute threads; device compute here is
                                    the XLA stream, so threads go to
                                    readback/delivery);
    - thread_pool_name, pad_variable_length_inputs: no analog (a named
      shared pool / ragged inputs don't exist here) — ignored, logged.
    """
    import logging

    from google.protobuf import text_format

    from ..proto import serving_apis_pb2 as apis

    log = logging.getLogger("dts_tpu.config")
    bp = text_format.Parse(
        pathlib.Path(path).read_text(), apis.BatchingParameters()
    )
    updates: dict[str, Any] = {}
    max_batch = bp.max_batch_size.value if bp.HasField("max_batch_size") else None
    if max_batch is not None and max_batch <= 0:
        raise ValueError(f"max_batch_size must be positive, got {max_batch}")
    if bp.allowed_batch_sizes:
        buckets = tuple(sorted(int(b) for b in bp.allowed_batch_sizes))
        if any(b <= 0 for b in buckets):
            raise ValueError(f"allowed_batch_sizes must be positive, got {buckets}")
        if max_batch is not None and buckets[-1] != max_batch:
            raise ValueError(
                f"largest allowed_batch_sizes entry ({buckets[-1]}) must equal "
                f"max_batch_size ({max_batch}) — the upstream batching rule"
            )
        updates["buckets"] = buckets
    elif max_batch is not None:
        kept = tuple(b for b in cfg.buckets if b < max_batch)
        updates["buckets"] = kept + (int(max_batch),)
    if bp.HasField("batch_timeout_micros"):
        updates["max_wait_us"] = int(bp.batch_timeout_micros.value)
    if bp.HasField("max_enqueued_batches"):
        top = max_batch or (updates.get("buckets") or cfg.buckets)[-1]
        updates["queue_capacity_candidates"] = int(
            bp.max_enqueued_batches.value * top
        )
    if bp.HasField("num_batch_threads"):
        threads = int(bp.num_batch_threads.value)
        if threads <= 0:
            raise ValueError(f"num_batch_threads must be positive, got {threads}")
        updates["completion_workers"] = threads
    for field in ("thread_pool_name", "pad_variable_length_inputs"):
        if bp.HasField(field):
            log.info("batching parameter %s has no analog here; ignored", field)
    return dataclasses.replace(cfg, **updates)


def load_config(path) -> dict[str, Any]:
    """Parse a TOML file with optional [server] / [client] / [model]
    sections. [model] carries the architecture knobs (ModelConfig) — present
    only when the file sets any, so callers can tell "explicit architecture"
    from "use defaults"."""
    raw = tomllib.loads(pathlib.Path(path).read_text())
    out: dict[str, Any] = {}
    for section, cls in _SECTIONS.items():
        out[section] = _coerce(cls, raw.get(section, {}))
    if "model" in raw:
        out["model"] = _coerce(_model_config_cls(), raw["model"])
    extra = set(raw) - set(_SECTIONS) - {"model"}
    if extra:
        raise ValueError(f"unknown config sections: {sorted(extra)}")
    return out
