"""Config system.

Reference equivalent: cmd/taskhandler/cfg.go:10-62 (viper: ./config.yaml +
``TFSC_``-prefixed env vars with ``.`` -> ``_`` mapping). Key design change
noted in SURVEY.md §2 C2: the reference reads viper keys ad-hoc deep inside
libraries; here the whole config is parsed once into typed dataclasses and
injected, so every component is constructible in tests without global state.

Env override: ``TPUSC_<KEY>`` where dots become underscores, e.g.
``TPUSC_CACHE_DISK_CAPACITY_BYTES=1000`` overrides ``cache.disk_capacity_bytes``
(mirrors reference cfg.go:15-17 semantics with the new prefix).
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field
from typing import Any

import yaml

ENV_PREFIX = "TPUSC_"


@dataclass
class ServingConfig:
    """In-process JAX serving runtime (replaces reference's external TF Serving
    block, config.yaml:29-37)."""

    max_concurrent_models: int = 16        # models resident in HBM at once
    hbm_capacity_bytes: int = 8 << 30      # HBM byte budget for pinned params
    warmup: bool = True                    # run one predict to pin+compile on load
    # persistent XLA compile cache directory. JAX_COMPILATION_CACHE_DIR, when
    # set, wins over this; "" = the fixed in-checkout default
    # (utils/compile_cache.py holds the rule)
    compile_cache_dir: str = ""
    # cold-load (fetch+compile) deadline; 0 disables. The reference hardcodes
    # a 10 s fetch timeout (main.go:122); XLA first-compiles can take longer,
    # so the default is looser. Enforced by CacheManager.ensure_servable.
    load_timeout_s: float = 30.0
    platform: str = ""                     # "" = default jax backend; "cpu" forces CPU
    # adaptive micro-batching of :predict (TF Serving --enable_batching
    # equivalent, in-process now; :generate has its own engine below): 0
    # disables; concurrent same-shape requests within the window coalesce
    # into one device call. Default 0 (OFF) per measured
    # evidence: on the chip (r5, tpu_runs/) LM REST loses consistently with
    # batching (36-66 vs 100-105 QPS) as does r2's mnist REST (-31%); the
    # wins are protocol/family-specific and window-noisy (r5 full run:
    # mnist REST batch 202 vs 161, mnist gRPC batch 199 vs 241 — the
    # OPPOSITE split of the same day's batcher_qps window). A default must
    # hold across families; off does. Enable per-deployment (set 1-2 ms)
    # only when profiling shows concurrent same-shape warm traffic whose
    # batched device call beats the window latency — e.g. many-client
    # fan-in on one cheap-decode model.
    batch_window_ms: float = 0.0
    batch_max_size: int = 64
    # Prefix KV cache for :generate (runtime/prefix_cache.py): byte budget
    # of device memory for reusable prompt-prefix K/V. 0 = off (default —
    # entries hold real HBM). Single-group runtimes only; B=1 requests.
    prefix_cache_bytes: int = 0
    # Pipelined cold load (runtime/model_runtime.py): AOT-compile the family
    # executable concurrently with the params transfer, double-buffer the
    # packed H2D chunks, and dequantize leaves as they land, so cold
    # wall-clock ≈ max(stage) instead of Σ(stages). False restores the
    # strictly serialized stage-after-stage path (identical results, one
    # flag away). Multi-PROCESS mesh runtimes always run serialized — the
    # cross-host lockstep device-op stream must not depend on host thread
    # timing; single-process meshes pipeline when mesh_fast_path is on.
    cold_load_pipeline: bool = True
    # Mesh parity for the fast path (ISSUE 20): single-process mesh
    # runtimes run the same pipelined cold load, host warm tier, packed
    # adoption, and :generate engine as single-chip runtimes, with params
    # and KV arenas sharded per the family's partition rules. False
    # restores the pre-parity behavior (serialized loads, every :generate
    # alone through runtime.generate). Multi-process (cross-host) groups
    # ignore the knob and stay lockstep: their device-op stream must not
    # depend on host thread timing.
    mesh_fast_path: bool = True
    # Host buffers the chunk assembler may run ahead of the H2D stream
    # (bounded queue depth; each slot holds up to one ~256 MB packed chunk).
    cold_pipeline_buffer_depth: int = 2
    # :generate has one engine (runtime/batcher.py ContinuousGenerateEngine):
    # a fixed array of lanes advanced by one compiled decode-chunk program
    # over a paged KV arena, with admission at chunk boundaries and per-row
    # retirement at EOS / max_new_tokens. It serves single-device runtimes
    # and single-process meshes (the arena shards over the KV heads). What
    # it cannot take runs alone through runtime.generate's whole-request
    # scan: requests with an explicit seed, models that are not
    # ModelDef.engine_ready, malformed input, and lockstep runtimes
    # (cross-process groups, or mesh_fast_path off: their device-op stream
    # must not depend on a host scheduler thread).
    # Slot count S of the continuous engine's decode array: one compiled
    # program serves all S lanes; S bounds concurrent decodes per model.
    generate_slots: int = 8
    # Decode steps per device dispatch (chunk size k). k=1 retires rows
    # with zero wasted steps; larger k amortizes host dispatch overhead at
    # the cost of up to k-1 overshoot steps per finishing row (PERF.md
    # "Continuous batching" discusses the tradeoff).
    generate_chunk_tokens: int = 8
    # Chunked prefill interleaving (ISSUE 19): 0 (default) prefills each admitted prompt in
    # one dispatch — a 2k-token prompt monopolizes the engine for the whole
    # prefill, inflating every other lane's inter-token latency and TTFT.
    # > 0 splits cold-miss prefills into fixed chunks of this many tokens
    # (clamped up to a pow2 so one compiled program serves every chunk):
    # the lane sits in a PREFILLING state and advances one chunk per
    # scheduler boundary while the other lanes keep decoding between
    # chunks. Prompts that fit one chunk, shared-prefix/resume hits, and
    # spec-draft engines take the single-dispatch path unchanged.
    prefill_chunk_tokens: int = 0
    # Page size of the engine's KV arena, in tokens: fixed pages handed out
    # by a free-list at admission (the row's full prompt + max_new budget is
    # pre-reserved) and recycled at retirement, so HBM is sized by tokens in
    # flight instead of worst case. Must be >= 1 (there is no other KV
    # layout); 16 is the size the chip cells run and the paged kernel was
    # checked at.
    kv_page_tokens: int = 16
    # Usable arena pages (one extra trash page is always added). 0 = auto:
    # generate_slots x ceil(max_seq / kv_page_tokens), every lane can hold
    # the longest request; shrink it to cap KV HBM, grow it (with
    # generate_slots) to admit more concurrent rows at the same budget.
    kv_arena_pages: int = 0
    # Cross-request shared-prefix KV over the paged arena
    # (runtime/prefix_cache.py PagePrefixIndex): byte budget of arena pages
    # the radix prefix index may pin for reuse. 0 = off (default). > 0
    # makes admission map the longest page-aligned
    # shared prompt prefix read-only into a new row's block table (refcount
    # bump, no prefill compute over the shared part, no page copy) and
    # reserve only the private suffix + max_new pages — N concurrent
    # same-system-prompt rows pay O(1) arena memory for the prefix. Pages a
    # lane would write into are copy-on-write; index-held pages are
    # reclaimed under admission pressure before a request is ever blocked.
    kv_share_prefix_bytes: int = 0
    # Fused Pallas paged-attention decode kernel (ops/attention.py
    # paged_attention): walk each lane's block table inside the kernel and
    # compute online-softmax attention straight from the page arena — one
    # pass over the KV bytes instead of paged_gather_kv's materialized
    # pages[tables] round-trip. true (default) uses the kernel on one TPU
    # chip when shapes qualify (the arena's stored row a multiple of 128
    # lanes, since PR 24: the kernel copies pages out of HBM in whole
    # 128-lane tiles. A head of 128 is one; so, since PR 34, is a head of
    # 64 with an even number of KV heads, whose arena stores two heads a
    # row; heads divisible by kv heads) and falls back to the gather+einsum
    # reference everywhere else (CPU, a mesh, a head of 64 with an odd
    # number of KV heads or in an int8 arena); false forces the reference
    # path unconditionally, the lever of the parity tests.
    kv_paged_kernel: bool = True
    # KV page arena element type. "" (default) stores pages in the model's
    # own dtype. "int8" quantizes pages symmetrically per (page, kv_head,
    # token) row with f32 scales riding beside the arena — rows dequantize
    # inside the decode kernel (or before the reference einsum), and the
    # auto-sized arena (kv_arena_pages == 0) grows to fill the SAME byte
    # budget the model-dtype arena would have used (~1.9x pages for bf16),
    # which is the capacity win. Page bookkeeping (reserve/CoW/census) is
    # count-based and identical under quantization.
    kv_arena_dtype: str = ""
    # In-engine speculative decoding
    # (runtime/batcher.py): name of the DRAFT model — "name" (highest
    # resident version) or "name@version". "" = off (default). When set,
    # each continuous scheduler attaches the draft to its paged slot state
    # (runtime.slot_attach_draft) and replaces plain decode chunks with
    # draft/verify rounds: the draft proposes spec_tokens greedy tokens per
    # lane, ONE multi-position verify pass scores them, and each lane
    # accepts a variable-length prefix — greedy streams stay byte-identical
    # to spec-off. Admission reserves spec_tokens of extra page headroom
    # per row in BOTH arenas, so requests sized to the exact arena edge may
    # need one more page than without spec. Lanes with temperature > 0
    # fall back to single-token emission inside the round.
    spec_draft_model: str = ""
    # Draft tokens proposed per verify round when spec_draft_model is set
    # (clamped to the pow2 bucket ladder {1, 2, 4, 8} at attach — bounds
    # the verify program count). Also the per-row page headroom reserved at
    # admission. Higher values win only when acceptance is high; the
    # runtime's acceptance health gate (_spec_admit) auto-disables a pair
    # that sustains low acceptance and re-auditions it periodically.
    spec_tokens: int = 4
    # Transparent crash recovery of the engine
    # (runtime/batcher.py): on an engine-thread death (device failure,
    # mid-decode eviction, injected kill) the crashed scheduler's in-flight
    # and queued rows requeue into a fresh scheduler thread instead of
    # failing — admission re-prefills each interrupted row's prompt plus
    # the tokens it already emitted (the prefix cache makes the replay
    # cheap; greedy streams stay token-identical), and every requeued row
    # counts in tpusc_requests_recovered_total{reason}. false restores the
    # fail-all-rows behavior.
    generate_recovery: bool = True
    # Per-row recovery budget: a row that survives this many engine crashes
    # fails on the next one (a poison prompt that deterministically crashes
    # the engine must not respawn scheduler threads forever).
    generate_max_recoveries: int = 2
    # Conversation KV tier of the engine
    # (cache/conversation_kv.py): host-RAM byte budget for PARKED decode
    # state. A `:generate` request carrying a conversation_id parks its
    # lane's live KV pages (raw arena dtype + int8 scales — half the
    # bytes under kv_arena_dtype=int8) at retirement; the conversation's
    # next turn resumes with a suffix-only prefill over the re-imported
    # pages — O(new tokens) TTFT instead of a full-history re-prefill,
    # token-identical under the exact-hit sampling discipline. 0 = off
    # (default — requests with conversation ids behave exactly as today).
    conversation_kv_bytes: int = 0
    # Disk spill level under the host budget: the coldest parked
    # conversations spill (LRU) to conversation_kv_dir instead of dropping
    # when conversation_kv_bytes overflows; a resume that finds its turn on
    # disk re-promotes it to host. 0 = no spill (cold conversations drop).
    conversation_kv_disk_bytes: int = 0
    # Directory for spilled conversation KV blobs (one file per parked
    # conversation, atomic tmp+rename writes). Cleared on tier close.
    conversation_kv_dir: str = "/tmp/tpusc_conv_kv"
    # ModelSpec.version_label resolution map: {model_name: {label: version}}.
    # TF Serving owns labels in its serving config (version_labels); the
    # reference forwards labeled specs verbatim for it to resolve
    # (tfservingproxy.go:246-250). Here the map lives in THIS config; a
    # labeled request for an unmapped label fails FAILED_PRECONDITION/412
    # instead of silently serving latest (VERDICT r3 missing #4).
    version_labels: dict = field(default_factory=dict)


@dataclass
class CacheConfig:
    """Disk artifact cache (reference config.yaml:25-27) plus the host-RAM
    warm tier that sits between it and the HBM slots."""

    base_dir: str = "/tmp/tpusc_models"
    disk_capacity_bytes: int = 10 << 30
    # Host-RAM warm tier (cache/host_tier.py): byte budget of host DRAM for
    # retaining evicted models' already-decoded, pre-packed transfer chunks
    # plus their executable handles, so re-admission skips provider fetch
    # and host decode entirely and pays only the H2D stream. 0 = off
    # (default — identical to the two-tier behavior). Mesh/multi-process
    # runtimes ignore it and always take the full load path.
    host_tier_bytes: int = 0


@dataclass
class ModelProviderConfig:
    """Reference config.yaml:1-23."""

    type: str = "disk"                 # disk | s3 | gcs | azblob
    base_dir: str = "./models"         # disk provider root
    # s3/gcs/azblob:
    bucket: str = ""
    base_path: str = ""
    region: str = ""
    endpoint: str = ""                 # custom endpoint (minio etc.)
    account_name: str = ""             # azblob
    account_key: str = ""
    container: str = ""


@dataclass
class ProxyConfig:
    """Router/front layer (reference config.yaml:38-43)."""

    rest_port: int = 8093
    grpc_port: int = 8100
    replicas_per_model: int = 1
    grpc_max_message_bytes: int = 16 << 20   # reference cachemanager.go:230-233
    # on membership change, pre-load owned models already in the local disk
    # cache (cluster/warmer.py; no reference counterpart — SURVEY §7 (a))
    warm_on_assignment: bool = True


@dataclass
class CacheNodePorts:
    rest_port: int = 8094
    grpc_port: int = 8095


@dataclass
class DiscoveryConfig:
    """Reference config.yaml:44-58 (serviceDiscovery.*)."""

    type: str = ""                     # "" = single-node cache-only mode | static | file | consul | etcd | kubernetes
    heartbeat_ttl_s: float = 5.0
    service_name: str = "tpuserve-cache"
    # static backend:
    nodes: list[str] = field(default_factory=list)   # "host:restPort:grpcPort"
    # file backend:
    path: str = ""
    poll_interval_s: float = 2.0
    # consul/etcd/k8s endpoints:
    address: str = ""                  # consul http addr or etcd grpc addr
    namespace: str = ""                # k8s namespace ("" = from serviceaccount)
    field_selector: str = ""           # k8s endpoints selector
    prefer_localhost: bool = False     # reference etcd.go:162-166 outbound-IP fallback


@dataclass
class ClusterConfig:
    """Fleet status plane (cluster/status.py): the cross-node residency/
    health exchange the router's p2c tie-breaks and soft route-around
    consume, surfaced at ``GET /monitoring/cluster``. No reference
    counterpart — the reference cluster exchanges membership only."""

    # master switch for the exchange (piggyback + poll). Off: the router
    # falls back to local-only warmth and load-only p2c (pre-PR7 behavior).
    status_exchange: bool = True
    # low-rate poll fallback for peers no routed traffic reaches; also the
    # freshness bar below which a peer is NOT re-polled (piggyback wins)
    status_poll_interval_s: float = 5.0
    # a status older than this is stale: its warmth advertisements stop
    # counting and the peer's health score starts decaying
    status_stale_after_s: float = 15.0
    # hard bound on the encoded piggyback payload; encode drops the
    # coldest models first to fit and stamps how many were cut
    status_byte_cap: int = 4096
    # most models a single NodeStatus advertises (warmest win)
    status_max_models: int = 64
    # most tenant accounting rows a single NodeStatus piggybacks (ordered
    # by dominant share; the byte cap trims these before models). 0 turns
    # the per-tenant fleet view off.
    status_max_tenants: int = 8
    # collection cache: piggybacking on every response re-collects at most
    # this often (a fresh collect is <1 ms, but per-response would still
    # be wasteful at high QPS)
    status_min_interval_s: float = 0.25
    # peers scoring below this are deprioritized in p2c replica ordering
    # (soft route-around; they stay in the ring and keep their keys)
    health_threshold: float = 0.5
    # EWMA weight for forward outcomes (higher = reacts faster, forgets
    # faster): at 0.3, three straight failures drop health to ~0.34 and
    # three straight successes recover it past 0.5
    health_error_alpha: float = 0.3
    # latency normalization: score factor = ref / (ref + latency_ewma)
    health_latency_ref_s: float = 1.0
    # -- peer param distribution (cache/providers/peer.py) ------------------
    # On a cold miss, stream another node's host-tier packed chunks over
    # gRPC instead of refetching from the provider (requires
    # status_exchange for the warmth map). Off: every miss goes to store.
    peer_fetch: bool = True
    # target size of one streamed chunk message (the sender re-frames the
    # ~256 MB pack-plan chunks into messages of at most this many bytes)
    peer_fetch_chunk_bytes: int = 2 << 20
    # outbound streams a single node serves per requesting peer at once;
    # excess fetches are refused (the asker falls back to the store)
    peer_fetch_max_inflight_per_peer: int = 2
    # end-to-end deadline for one peer fetch; on expiry the asker falls
    # back to the store (loud, never request-fatal)
    peer_fetch_timeout_s: float = 60.0
    # -- load-adaptive replication (cluster/replication.py) -----------------
    # ceiling for the per-model replica count the controller may grow to;
    # proxy.replicas_per_model stays the floor/default. 0 disables the
    # controller (static N, pre-PR8 behavior).
    max_replicas_per_model: int = 4
    # in-flight requests per replica (EWMA) that justify one more replica:
    # desired N = clamp(ceil(ewma / target), base, max)
    replica_load_target: float = 2.0
    # controller evaluation cadence
    replica_eval_interval_s: float = 2.0
    # shrink hysteresis: N decays only after this many CONSECUTIVE evals
    # wanting a lower N (growth applies immediately; ring assignment is
    # prefix-stable under N changes so only N itself needs damping)
    replica_decay_ticks: int = 3


@dataclass
class MeshConfig:
    """TPU chip-group topology — new territory (SURVEY.md §2 parallelism
    inventory: the reference has none). Models larger than one chip are
    sharded over a chip group; the ring assigns models to groups.

    Cross-host groups (chips_per_group > chips per host): set ``coordinator``
    (jax.distributed rendezvous, e.g. host0:8476), ``num_processes``,
    ``process_id``, and one ``worker_addrs`` "host:port" entry PER PROCESS —
    the group-work endpoint its leader broadcasts collective ops to
    (parallel/multihost.py). The group's leader process is its ring member."""

    chips_per_group: int = 1           # chip-group size for sharded models
    axis_names: tuple[str, ...] = ("data", "model")
    data_parallel: int = 1
    coordinator: str = ""              # jax.distributed coordinator address
    num_processes: int = 1
    process_id: int = 0
    worker_addrs: list[str] = field(default_factory=list)  # per-process host:port


@dataclass
class MetricsConfig:
    model_labels: bool = False         # per-model:version labels (reference cachemanager.go:251-258)
    path: str = "/monitoring/prometheus/metrics"
    # extra text-format exporters merged into this node's /metrics (reference
    # MetricsHandler scraping TF Serving live, pkg/taskhandler/metrics.go:16-53)
    scrape_targets: list[str] = field(default_factory=list)
    # cardinality guard for model_labels: after this many distinct
    # name:version values, NEW tenants fold into the "__other__" bucket so
    # a 1000-tenant churn can't explode every {model=...} family
    max_model_labels: int = 512
    # scrape_targets merge mode: sum counter series with identical label
    # sets across sources (per-tenant fleet aggregation) instead of the
    # default family-level dedup where the first exporter wins
    scrape_sum_counters: bool = False


@dataclass
class TracingConfig:
    """Always-on request tracing (utils/tracing.py; no reference
    counterpart — SURVEY.md §5 "no OpenTelemetry/pprof anywhere")."""

    capacity: int = 256                # completed traces kept in the ring
    # tail sampling: traces slower than this survive in a separate bounded
    # buffer even after fast traffic wraps the main ring; 0 disables
    slow_threshold_ms: float = 1000.0
    slow_capacity: int = 64


@dataclass
class ObservabilityConfig:
    """Engine flight recorder (utils/flight_recorder.py): per-step
    telemetry rings are always on (they're preallocated host lists — cost
    is bytes, not time); these knobs govern the anomaly-dump spool."""

    # Spool dir for anomaly dumps (SLO breach / page-exhaustion blocking /
    # engine-thread crash). "" disables dumps; the rings keep recording.
    flight_dir: str = "/tmp/tpusc_flight"
    # Per-model step-ring capacity: at a 10 ms chunk cadence 4096 entries
    # is the last ~40 s of engine history.
    ring_entries: int = 4096
    # Spool bound: oldest dump files beyond this count are deleted.
    max_dumps: int = 16
    # Rate limit for recurring triggers (page exhaustion); SLO-breach dumps
    # dedup per trace id instead.
    dump_cooldown_s: float = 60.0
    # -- per-tenant resource accounting (utils/accounting.py) ---------------
    # master switch for the cost-attribution ledger (step seconds, token
    # counts, byte-second / page-second gauge integrals, load latencies)
    tenant_accounting: bool = True
    # noisy-neighbor detector: a tenant holding at least this share of the
    # engine step-time window while OTHER tenants sit queued triggers one
    # "noisy_neighbor" flight dump (deduped by the recorder cooldown)
    noisy_neighbor_share: float = 0.8
    # sliding window the share is computed over
    noisy_neighbor_window_s: float = 5.0
    # windows with less than this much total step time never fire (an idle
    # node's only tenant trivially holds 100% of nothing)
    noisy_neighbor_min_step_s: float = 0.25
    # -- scenario-lab fault injector (lab/faults.py) ------------------------
    # "" (default) keeps the injector disarmed: every hook site in the
    # engine/manager/peer-receiver/fleet plane is a single-bool-read
    # passthrough. Set to a JSON list of fault specs to arm a chaos drill
    # at startup, e.g. '[{"kind": "freeze_scheduler", "after": 10,
    # "duration_s": 0.25}]' — kinds: kill_engine, freeze_scheduler,
    # stall_store, corrupt_peer_chunk, drop_peer. Reachable as the
    # TPUSC_OBSERVABILITY_LAB_FAULTS env override; a malformed spec fails
    # startup rather than silently running a no-op drill.
    lab_faults: str = ""


@dataclass
class LoggingConfig:
    level: str = "info"
    fmt: str = "text"                  # text | json (reference cfg.go:28-61)


@dataclass
class Config:
    serving: ServingConfig = field(default_factory=ServingConfig)
    cache: CacheConfig = field(default_factory=CacheConfig)
    model_provider: ModelProviderConfig = field(default_factory=ModelProviderConfig)
    proxy: ProxyConfig = field(default_factory=ProxyConfig)
    cache_node: CacheNodePorts = field(default_factory=CacheNodePorts)
    discovery: DiscoveryConfig = field(default_factory=DiscoveryConfig)
    cluster: ClusterConfig = field(default_factory=ClusterConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    metrics: MetricsConfig = field(default_factory=MetricsConfig)
    tracing: TracingConfig = field(default_factory=TracingConfig)
    observability: ObservabilityConfig = field(default_factory=ObservabilityConfig)
    logging: LoggingConfig = field(default_factory=LoggingConfig)
    # health probe model name (reference cfg.go:64-66 default)
    health_probe_model: str = "__TPUSC_PROBE_CHECK__"


def _coerce(value: str, target: Any) -> Any:
    """Coerce an env-var string to the type of the dataclass default."""
    if isinstance(target, bool):
        return value.lower() in ("1", "true", "yes", "on")
    if isinstance(target, int):
        return int(value)
    if isinstance(target, float):
        return float(value)
    if isinstance(target, (list, tuple)):
        parts = [p for p in value.split(",") if p]
        return type(target)(parts)
    return value


def _apply_mapping(cfg: Any, data: dict[str, Any], path: str = "") -> None:
    known = {f.name for f in dataclasses.fields(cfg)}
    unknown = set(data) - known
    if unknown:
        # loud but permissive: a typo'd or reference-style camelCase key must
        # not silently degrade to defaults
        import logging

        logging.getLogger("tpusc.config").warning(
            "ignoring unknown config key(s) %s under %r (known: %s)",
            sorted(unknown), path or ".", sorted(known),
        )
    for f in dataclasses.fields(cfg):
        if f.name not in data:
            continue
        val = data[f.name]
        cur = getattr(cfg, f.name)
        if dataclasses.is_dataclass(cur):
            if val is None:
                continue  # empty YAML section ("discovery:" with children commented out)
            if not isinstance(val, dict):
                raise ValueError(
                    f"config section {path}{f.name!s} must be a mapping, got {type(val).__name__}"
                )
            _apply_mapping(cur, val, f"{path}{f.name}.")
        elif isinstance(val, str) and not isinstance(cur, str):
            setattr(cfg, f.name, _coerce(val, cur))
        elif isinstance(cur, tuple) and isinstance(val, list):
            setattr(cfg, f.name, tuple(val))
        else:
            setattr(cfg, f.name, val)


def _apply_env(cfg: Any, prefix: str) -> None:
    for f in dataclasses.fields(cfg):
        cur = getattr(cfg, f.name)
        key = f"{prefix}{f.name.upper()}"
        if dataclasses.is_dataclass(cur):
            _apply_env(cur, f"{key}_")
        elif key in os.environ:
            try:
                setattr(cfg, f.name, _coerce(os.environ[key], cur))
            except ValueError as e:
                raise ValueError(f"invalid value for env {key}: {e}") from e


def load_config(path: str | None = None, env: bool = True) -> Config:
    """Load ``config.yaml`` (if present) and apply ``TPUSC_*`` env overrides.

    Mirrors reference cfg.go:10-27: missing file is fine (env/defaults only).
    """
    cfg = Config()
    if path is None and os.path.exists("config.yaml"):
        path = "config.yaml"
    if path and os.path.exists(path):
        with open(path) as fh:
            data = yaml.safe_load(fh) or {}
        _apply_mapping(cfg, data)
    if env:
        _apply_env(cfg, ENV_PREFIX)
    return cfg
