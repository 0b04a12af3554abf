"""Prometheus metrics.

Metric-name parity with the reference where the concept survives
(pkg/tfservingproxy/tfservingproxy.go:25-32, pkg/cachemanager/cachemanager.go:24-43),
plus TPU-native additions (compile time, HBM residency) that have no
reference counterpart. Per-model labels are optional to bound cardinality
(reference cachemanager.go:251-258 "all_models" fallback).
"""

from __future__ import annotations

import asyncio

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

ALL_MODELS = "all_models"
# Cardinality-overflow bucket: once max_model_labels distinct name:version
# values exist, NEW tenants fold here so a 1000-tenant churn cannot explode
# every {model=...} family. Established labels keep resolving.
OTHER_MODELS = "__other__"
DEFAULT_MAX_MODEL_LABELS = 512


class Metrics:
    """One instance per process; injected (no promauto-style globals so tests
    can build many nodes in-process without collisions)."""

    def __init__(
        self,
        model_labels: bool = False,
        max_model_labels: int = DEFAULT_MAX_MODEL_LABELS,
    ) -> None:
        self.registry = CollectorRegistry()
        self.model_labels = model_labels
        self.max_model_labels = max(1, int(max_model_labels))
        # distinct labels handed out; set.add is GIL-atomic, so a racy
        # concurrent first-sighting can overshoot the cap by a label or two
        # — acceptable, the cap bounds growth, it is not a hard quota
        self._seen_model_labels: set[str] = set()
        r = self.registry
        # Exposed names match the reference exactly (prometheus_client appends
        # "_total" to counters, so the constructor names omit it):
        #   tfservingcache_proxy_requests_total / _proxy_failures_total
        #     (reference tfservingproxy.go:25-32) — and unlike the reference,
        #     the failure counter only counts failures (SURVEY.md §2 C3 bug);
        #   tfservingcache_cache_total / _cache_hits_total / _cache_misses_total
        #     (reference cachemanager.go:24-35).
        self.request_count = Counter(
            "tfservingcache_proxy_requests", "The total number of requests", ["protocol"], registry=r
        )
        self.request_failures = Counter(
            "tfservingcache_proxy_failures", "The total number of failed requests", ["protocol"], registry=r
        )
        # End-to-end client-experienced latency (no reference counterpart:
        # its two histograms time only the ensure step). route=local is a
        # request this node served itself; route=forwarded left via the ring
        # to a hash-owned peer — the pair splits "the model was slow" from
        # "the hop was slow" without a trace in hand.
        self.request_duration = Histogram(
            "tpusc_request_duration_seconds",
            "End-to-end request latency as the client experienced it "
            "(protocol=rest|grpc, verb=predict|classify|regress|generate|"
            "metadata|status|..., outcome=ok|error, route=local|forwarded)",
            ["protocol", "verb", "outcome", "route"],
            registry=r,
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1, 2.5, 5, 10, 30, 60),
        )
        self.requests_in_flight = Gauge(
            "tpusc_requests_in_flight",
            "Requests currently being served (admitted, response not yet sent)",
            ["protocol"], registry=r,
        )
        self.batcher_queue_depth = Gauge(
            "tpusc_batcher_queue_depth",
            "Requests parked in a forming micro-batch, waiting for the "
            "device gate (kind = predict | generate)",
            ["kind"], registry=r,
        )
        self.cache_total = Counter(
            "tfservingcache_cache", "Cache lookups", ["model"], registry=r
        )
        self.cache_hits = Counter(
            "tfservingcache_cache_hits", "Cache hits", ["model"], registry=r
        )
        self.cache_misses = Counter(
            "tfservingcache_cache_misses", "Cache misses", ["model"], registry=r
        )
        self.cache_duration = Histogram(
            "tfservingcache_cache_duration_seconds",
            "Total time spent ensuring a model is servable",
            ["model"],
            registry=r,
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1, 2, 5, 10, 30, 60),
        )
        self.cache_fetch_duration = Histogram(
            "tfservingcache_cache_fetch_duration_seconds",
            "Time spent fetching model artifacts from the provider",
            ["model"],
            registry=r,
            buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1, 2, 5, 10, 30, 60),
        )
        # TPU-native additions (no reference counterpart)
        # The bring-up account (utils/bring_up.py): what jax traced, lowered
        # and compiled, by program, booked by jax.monitoring listeners where
        # the build happens. `program` is bounded (64 names a process, the
        # rest under "other": jax's own small helpers are many).
        self.program_build_seconds = Counter(
            "tpusc_program_build_seconds",
            "Seconds jax spent building a compiled program, by its jitted "
            "function's name and stage: trace (Python runs the function), "
            "lower (jaxpr to MLIR, kernel bodies included), compile (XLA, the "
            "compilation cache missed or is off) or cache_load (it hit). "
            "Nested traces count inside their outermost program",
            ["program", "stage"], registry=r,
        )
        self.program_builds = Counter(
            "tpusc_program_builds",
            "Compiled programs built, by name and what the persistent "
            "compilation cache did (cache = hit | miss | off). One after "
            "warm-up is a request that waited for a compile",
            ["program", "cache"], registry=r,
        )
        self.device_bytes = Gauge(
            "tpusc_device_bytes",
            "The allocator's own count on the runtime's fullest device, read "
            "once at the end of a bring-up stage (stage = load | engine_build "
            "| first_run:<program>): what = in_use (bytes_in_use) | peak "
            "(peak_bytes_in_use) | reserved (bytes_reserved: the scratch the "
            "loaded program with the largest temporaries keeps, which "
            "neither of the others counts). No sample on a backend without "
            "allocator statistics (the CPU)",
            ["stage", "what"], registry=r,
        )
        # labeled by chip group: one host may run several group runtimes,
        # each with its own HBM budget (ring members = chip groups)
        self.hbm_bytes_in_use = Gauge(
            "tpusc_hbm_bytes_in_use", "Bytes of HBM pinned by resident models",
            ["group"], registry=r,
        )
        # High-water twin of the gauge above: a scrape-interval peak instead
        # of an instant sample, so a between-scrapes residency spike is
        # visible. Backed by the flight recorder's watermarks — reading
        # GET /monitoring/engine resets the marks (reset-on-scrape; the
        # gauge then re-arms at the next update). See OBSERVABILITY.md.
        self.hbm_bytes_peak = Gauge(
            "tpusc_hbm_bytes_peak",
            "High-water HBM bytes pinned by resident models since the last "
            "/monitoring/engine scrape",
            ["group"], registry=r,
        )
        self.models_resident = Gauge(
            "tpusc_models_resident", "Models currently AVAILABLE in the runtime",
            ["group"], registry=r,
        )
        self.disk_bytes_in_use = Gauge(
            "tpusc_disk_cache_bytes_in_use", "Bytes used by the disk artifact cache", registry=r
        )
        self.evictions = Counter(
            "tpusc_evictions_total", "Evictions", ["tier"], registry=r
        )
        # multi-tier residency observability (cache/host_tier.py): which
        # tier satisfied each ensure_servable — hbm = already warm, host =
        # packed-chunk promotion (no fetch, no decode), disk = artifact
        # re-read + full load, store = provider fetch. The mix is the
        # direct answer to "what are my reloads costing".
        self.reload_source = Counter(
            "tpusc_reload_source",
            "ensure_servable resolutions by serving tier "
            "(tier = hbm | host | disk | store | peer)",
            ["tier"], registry=r,
        )
        self.host_tier_bytes = Gauge(
            "tpusc_host_tier_bytes",
            "Host DRAM held by the warm tier's packed parameter chunks",
            registry=r,
        )
        self.host_tier_bytes_peak = Gauge(
            "tpusc_host_tier_bytes_peak",
            "High-water warm-tier DRAM bytes since the last "
            "/monitoring/engine scrape (reset-on-scrape)",
            registry=r,
        )
        # the :predict micro-batcher (runtime/batcher.py MicroBatcher): how
        # often requests coalesce and how many ride each device call. The
        # kind label keeps its place; "predict" is its one value since the
        # :generate coalescer went
        self.coalesced_batches = Counter(
            "tpusc_coalesced_batches", "Multi-request device calls",
            ["kind"], registry=r,
        )
        self.coalesced_requests = Counter(
            "tpusc_coalesced_requests", "Requests served via a coalesced call",
            ["kind"], registry=r,
        )
        # iteration-level continuous batching (runtime/batcher.py
        # ContinuousGenerateEngine). The engine label keeps its place on
        # every family below; "continuous" is its one value since the
        # coalescer went (dashboards and the benchmark's readers key on it).
        # model label gated on the metrics.model_labels flag (same
        # cardinality rule as the cache counters): off = one "all_models"
        # series summed across models, on = per-model lane occupancy, so a
        # saturated model's lanes are attributable instead of hiding inside
        # a global sum.
        self.gen_slots_active = Gauge(
            "tpusc_gen_slots_active",
            "Decode slots currently occupied by in-flight generate requests "
            "(per model when model_labels is on, else one all_models series "
            "summed across models; capacity is serving.generate_slots per "
            "model)",
            ["model"], registry=r,
        )
        self.gen_wasted_steps = Counter(
            "tpusc_gen_wasted_steps",
            "Decode steps computed for a row AFTER its request already "
            "finished (EOS or its own max_new_tokens): the chunk overshoot, "
            "fewer than chunk size a retirement (engine=continuous)",
            ["engine"], registry=r,
        )
        self.gen_sample_steps = Counter(
            "tpusc_gen_sample_steps",
            "Decode steps of the engine (the flight recorder's chunk) by "
            "what the step's live lanes asked of the sampler: greedy = an "
            "argmax alone, sample = the categorical draw over every row, "
            "topk = the full-vocabulary sort before the draw; one lane that "
            "draws puts the whole step on its path",
            ["path"], registry=r,
        )
        self.gen_kv_write_steps = Counter(
            "tpusc_gen_kv_write_steps",
            "Decode steps of the engine (the flight recorder's chunk) by the "
            "lanes whose KV rows the step wrote into the arena: the chunk's "
            "live lanes rounded up to whole trips of the write's loop, four "
            "lanes a trip (the engine's built width for a speculation round)",
            ["lanes"], registry=r,
        )
        self.gen_chunks = Counter(
            "tpusc_gen_chunks",
            "Decode dispatches of the engine (boundaries whose ring entry "
            "has chunk > 0) by when the chunk was launched: ahead = before "
            "the chunk before it was fetched, so the device went from one "
            "to the next without waiting for the host; boundary = at its "
            "own boundary, after the last fetch (the first chunk after an "
            "admission or a retirement, every speculation round, every "
            "chunk on a mesh)",
            ["launch"], registry=r,
        )
        self.gen_admission_wait = Histogram(
            "tpusc_gen_admission_wait_seconds",
            "Time a generate request waited before decoding began on its "
            "behalf: the wait for a free lane and its pages "
            "(engine=continuous)",
            ["engine"], registry=r,
            buckets=(.0005, .001, .0025, .005, .01, .025, .05, .1, .25,
                     .5, 1, 2.5, 5, 10),
        )
        # gen_admission_wait only observes AT admission: a request stuck
        # behind page exhaustion is invisible until it finally admits. This
        # gauge is the live view — the age of the oldest still-queued row,
        # updated at every chunk boundary (0 when the queue is empty).
        self.gen_oldest_queued_age = Gauge(
            "tpusc_gen_oldest_queued_age_seconds",
            "Age of the oldest generate request still waiting for admission "
            "(slot or KV-page starvation shows here BEFORE the request "
            "admits; 0 = queue empty)",
            ["engine"], registry=r,
        )
        # Transparent crash recovery (runtime/batcher.py triage/_respawn,
        # serving.generate_recovery): rows requeued into a replacement
        # scheduler after an engine-thread death instead of failing.
        # reason=mid_decode rows re-prefill prompt + emitted tokens; queued
        # rows only changed queues. Zero in a healthy fleet — a nonzero
        # rate is a crash rate wearing its recovery hat.
        self.requests_recovered = Counter(
            "tpusc_requests_recovered",
            "Generate rows transparently requeued after an engine-thread "
            "crash (reason=mid_decode|queued)",
            ["reason"], registry=r,
        )
        # Scenario-lab chaos drills (lab/faults.py, armed only via
        # observability.lab_faults): one increment per fault firing. Always
        # zero unless an operator armed the injector; alert on nonzero in
        # any environment that should never run drills.
        self.fault_injected = Counter(
            "tpusc_fault_injected",
            "Scenario-lab fault injections fired (kind=kill_engine|"
            "freeze_scheduler|stall_store|corrupt_peer_chunk|drop_peer)",
            ["kind"], registry=r,
        )
        # Flight-recorder dumps (utils/flight_recorder.py dump()): files
        # written and requests for one held back by dedup or cooldown. An
        # operator alerts on `written`; `suppressed` says how many more
        # breaches the cooldown swallowed.
        self.flight_dumps = Counter(
            "tpusc_flight_dumps",
            "Flight-recorder anomaly dumps asked for (reason=slo_breach|"
            "page_exhaustion|engine_crash|..., outcome=written|suppressed: "
            "held back by trace-id dedup or the per-model cooldown)",
            ["reason", "outcome"], registry=r,
        )
        # The serving pool (protocol/local_backend.py _run): submit -> worker
        # start of every pool job. count/sum against the request rate give
        # the busy threads; a streamed :generate holds its thread until the
        # row ends, so this wait is where a saturated pool shows.
        self.pool_wait = Histogram(
            "tpusc_pool_wait_seconds",
            "Wait for one of the serving pool's threads, submit to worker "
            "start (what=predict|generate|ensure|session_run|..., codec for "
            "the REST parse/encode hops)",
            ["what"], registry=r,
            buckets=(.0001, .00025, .0005, .001, .0025, .005, .01, .025, .05,
                     .1, .25, .5, 1, 2.5, 5, 10, 30),
        )
        # Per-request phase attribution (runtime/batcher.py engines): where
        # a generate request's wall time went — admission queue, prompt
        # prefill, decode steps, or response assembly. The same clocks land
        # as attrs on the request's trace root, so /monitoring/traces
        # answers "where did the time go" without cross-referencing.
        # The per-priority `class` label rides the model_labels cardinality
        # gate (ISSUE 20 satellite: 3 classes x 4 phases x 2 engines is
        # cheap, but the flag keeps default deployments at the old arity);
        # callers go through observe_phase so neither arity leaks out.
        phase_labels = (
            ["phase", "engine", "class"] if model_labels
            else ["phase", "engine"]
        )
        self.request_phase = Histogram(
            "tpusc_request_phase_seconds",
            "Per-request latency attribution by phase "
            "(phase=queue|prefill|decode|respond, "
            "engine=continuous; class=high|normal|low "
            "when model_labels is on)",
            phase_labels, registry=r,
            buckets=(.0005, .001, .0025, .005, .01, .025, .05, .1, .25,
                     .5, 1, 2.5, 5, 10, 30),
        )
        # paged KV arena (serving.kv_page_tokens > 0): occupancy of the
        # shared page pool and the per-retirement waste that page granularity
        # + unconsumed max_new headroom cost — the observability the arena
        # sizing math in PERF.md "Paged KV" reads from.
        self.gen_kv_pages_used = Gauge(
            "tpusc_gen_kv_pages_used",
            "KV arena pages currently reserved by in-flight continuous "
            "generate rows (summed across models)",
            registry=r,
        )
        self.gen_kv_pages_total = Gauge(
            "tpusc_gen_kv_pages_total",
            "Usable KV arena pages (excluding the trash page), summed "
            "across models with live paged slot states",
            registry=r,
        )
        self.gen_kv_pages_used_peak = Gauge(
            "tpusc_gen_kv_pages_used_peak",
            "High-water KV arena pages reserved since the last "
            "/monitoring/engine scrape (reset-on-scrape)",
            registry=r,
        )
        self.gen_kv_pages_shared = Gauge(
            "tpusc_gen_kv_pages_shared",
            "KV arena pages currently referenced by MORE than one owner "
            "(shared-prefix pages mapped read-only into multiple lanes' "
            "block tables and/or held by the radix prefix index); each "
            "counted once — gen_kv_pages_used minus this is the private "
            "page population",
            registry=r,
        )
        self.gen_prefix_hits = Counter(
            "tpusc_gen_prefix_hits",
            "Continuous-engine admissions that reused prompt-prefix KV: "
            "kind=exact skipped prefill entirely (radix index full match, "
            "first token sampled from cached logits), kind=shared paid "
            "only a suffix prefill (radix partial match or dense "
            "prefix-cache reuse)",
            ["engine", "kind"], registry=r,
        )
        # SLO-aware engine (chunked prefill + priority classes + streaming):
        # per-class preemption pressure, how many partial-prefill dispatches
        # the interleaver issued, and streamed frames by protocol surface —
        # the attribution trail for the slo_engine bench arms.
        self.gen_preemptions = Counter(
            "tpusc_gen_preemptions",
            "Decoding lanes preempted by a higher-priority admission "
            "(KV parked through the conversation codec, lane requeued, "
            "resumed O(new tokens) when pages free), labeled by the "
            "priority class of the VICTIM lane",
            ["class"], registry=r,
        )
        self.gen_prefill_chunks = Counter(
            "tpusc_gen_prefill_chunks",
            "Partial-prefill dispatches issued by the continuous engine's "
            "chunked-prefill interleaver (serving.prefill_chunk_tokens > 0); "
            "one increment per chunk, so chunks/admission gauges how much "
            "long-prompt prefill was broken up",
            registry=r,
        )
        # the expert layer (ops/moe.py): both from the two numbers a decode
        # chunk's program returns with its tokens (flight recorder ring
        # fields experts_hit / expert_rows_max)
        self.moe_assignments = Counter(
            "tpusc_moe_assignments",
            "Token-to-expert assignments routed by decode chunks of models "
            "with expert layers, a layer (live lanes x top_k x steps)",
            ["model"], registry=r,
        )
        self.moe_expert_rows = Histogram(
            "tpusc_moe_expert_rows",
            "Rows the busiest expert of a layer took in one decode step "
            "(mean over a chunk's steps and layers): 1 = every routed expert "
            "read for a single row",
            ["model"], registry=r,
            buckets=(1, 1.5, 2, 3, 4, 6, 8, 12, 16, 24, 32, 64, 128, 256),
        )
        self.gen_stream_frames = Counter(
            "tpusc_gen_stream_frames",
            "Token frames written to streaming generate clients "
            "(protocol = sse | grpc)",
            ["protocol"], registry=r,
        )
        self.lane_state_bytes = Gauge(
            "tpusc_lane_state_bytes",
            "Device bytes of the fixed per-lane state a model's lane-state "
            "layers keep beside the paged KV arena (a gated short "
            "convolution's last rows; 0 for a model whose layers all keep "
            "pages)",
            ["model"], registry=r,
        )
        self.kv_arena_bytes = Gauge(
            "tpusc_kv_arena_bytes",
            "Device bytes of a model's paged KV arenas by kind: global (the "
            "layers that keep every row: pages handed out by the free list) "
            "and window (the layers that keep one window of rows: a ring of "
            "pages a lane, 0 for a model with no window layer)",
            ["model", "kind"], registry=r,
        )
        self.gen_window_rows_dropped = Counter(
            "tpusc_gen_window_rows_dropped_total",
            "Prompt rows an admission did not store in a model's window "
            "layers (rows older than a ring of pages holds, summed over the "
            "window layers)",
            ["model"], registry=r,
        )
        self.prefill_rows = Counter(
            "tpusc_prefill_rows_total",
            "Rows of the admission prefills by kind: real (the prompt's "
            "tokens the prefill ran), bucket (the power-of-two bucket they "
            "were padded to) and computed (the rows the prefill's token-wise "
            "stages ran: the row blocks that hold a real row, "
            "models/real_rows.rows_computed). computed / real is the pad "
            "still paid, bucket / real what it was",
            ["kind"], registry=r,
        )
        self.gen_kv_arena_bytes = Gauge(
            "tpusc_gen_kv_arena_bytes",
            "Device bytes allocated to the paged KV arena (pages plus, "
            "for dtype=int8, the f32 dequant scale buffers), labeled by "
            "arena element type (serving.kv_arena_dtype; the model dtype "
            "when unset) — capacity-vs-budget evidence for the int8 arena",
            ["dtype"], registry=r,
        )
        # conversation KV lifecycle (cache/conversation_kv.py): parked
        # decode state by residency tier, and how resume lookups resolve —
        # hit = served from host DRAM, spilled = read back from the disk
        # level (still O(new tokens) prefill, just a slower import), miss =
        # cold full prefill.
        self.kv_parked_bytes = Gauge(
            "tpusc_kv_parked_bytes",
            "Bytes of parked conversation KV state by residency tier "
            "(tier = host | disk)",
            ["tier"], registry=r,
        )
        self.kv_parked_conversations = Gauge(
            "tpusc_kv_parked_conversations",
            "Conversations with parked KV state across the host and disk "
            "levels of the conversation tier",
            registry=r,
        )
        self.kv_resume = Counter(
            "tpusc_kv_resume",
            "conversation_id resume lookups at continuous-engine admission "
            "(outcome = hit | spilled | miss)",
            ["outcome"], registry=r,
        )
        self.gen_kv_page_waste = Histogram(
            "tpusc_gen_kv_page_waste_tokens",
            "Per retired row: reserved page capacity minus tokens that "
            "actually occupied it (prompt + emitted) — internal "
            "fragmentation of fixed pages plus unconsumed max_new headroom",
            registry=r,
            buckets=(0, 1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024),
        )
        self.assignment_warms = Counter(
            "tpusc_assignment_warms_total",
            "Models pre-loaded by the ring-assignment warmer",
            registry=r,
        )
        # scrape_and_merge degrades gracefully when a sidecar exporter is
        # down — but "gracefully" must not mean "silently": this counts the
        # targets each merge dropped (unreachable or unparseable), so an
        # exporter that died weeks ago is an alertable signal, not a gap
        # someone notices during an incident.
        self.scrape_errors = Counter(
            "tpusc_scrape_errors",
            "Sidecar metrics targets dropped from a /metrics merge "
            "(unreachable, non-200, or unparseable)",
            registry=r,
        )
        self.prefix_cache_hits = Counter(
            "tpusc_prefix_cache_hits_total",
            "generate requests that reused a cached prompt-prefix KV",
            registry=r,
        )
        self.prefix_cache_misses = Counter(
            "tpusc_prefix_cache_misses_total",
            "generate requests that paid full prefill (prefix cache on)",
            registry=r,
        )
        self.prefix_cache_bytes = Gauge(
            "tpusc_prefix_cache_bytes",
            "Device bytes held by cached prompt-prefix KV entries",
            registry=r,
        )
        self.cold_stage_seconds = Histogram(
            "tpusc_cold_stage_seconds",
            "Per-stage cold-load time (provider_fetch/artifact_read/"
            "device_transfer/device_dequant/host_dequant/compile_warmup/"
            "transfer_sync; dequant stages appear for quantized artifacts "
            "only, so encodings stay separable) and the bring-up account's "
            "stages (server_start/backend_init/load/engine_build/first_run: "
            "each its wall LESS the program build seconds booked on its "
            "thread meanwhile, which tpusc_program_build_seconds holds; "
            "load_overlap: the seconds a disk load's stages ran beside one "
            "another, its children's sum less its wall): "
            "the in-production answer to 'where do my cold seconds go' and "
            "to the int8-vs-bf16 crossover (compare device_transfer + "
            "device_dequant across artifact encodings on YOUR link)",
            ["stage"], registry=r,
            buckets=(.005, .02, .05, .1, .25, .5, 1, 2, 5, 10, 30),
        )
        self.cold_overlap_ratio = Histogram(
            "tpusc_cold_overlap_ratio",
            "Σ(per-stage seconds)/wall seconds per runtime load: ~1.0 means "
            "the stages ran strictly back-to-back (serialized path), >1 "
            "means the pipelined cold load overlapped them (AOT compile and "
            "per-leaf dequant running during the transfer) — the higher, "
            "the more of the compile the transfer hid",
            registry=r,
            buckets=(0.8, 0.95, 1.0, 1.1, 1.2, 1.35, 1.5, 1.75, 2.0, 2.5, 3.0, 4.0),
        )
        self.group_reforms = Counter(
            "tpusc_group_reform_events_total",
            "Cross-host group failure-containment events",
            ["group", "event"], registry=r,  # event: torn_down | reformed
        )
        self.group_healthy = Gauge(
            "tpusc_group_healthy",
            "1 while the cross-host group serves; 0 while torn down/re-forming",
            ["group"], registry=r,
        )
        # fleet status plane (cluster/status.py): this node's view of its
        # peers. health is the router's soft route-around signal (error
        # EWMA x latency factor x staleness decay); age is how old the
        # peer's last NodeStatus is; replicas inverts the fleet residency
        # map ("how many nodes hold model M at tier T"), the input to
        # ROADMAP item 4's replication decisions. Peer label cardinality is
        # bounded by ring membership (departed peers are pruned); model
        # cardinality by cluster.status_max_models per peer.
        self.peer_health_score = Gauge(
            "tpusc_peer_health_score",
            "Composite per-peer health in [0,1] as THIS node scores it: "
            "forward-error EWMA x latency factor x status-staleness decay "
            "(peers below cluster.health_threshold are deprioritized in "
            "p2c replica ordering, never hard-dropped)",
            ["peer"], registry=r,
        )
        self.peer_status_age = Gauge(
            "tpusc_peer_status_age_seconds",
            "Seconds since this peer's last NodeStatus was received "
            "(piggybacked on a routed hop or polled)",
            ["peer"], registry=r,
        )
        self.fleet_model_replicas = Gauge(
            "tpusc_fleet_model_replicas",
            "Nodes currently advertising this model at this residency tier "
            "(tier = hbm | host | disk), from the fleet status exchange",
            ["model", "tier"], registry=r,
        )
        # peer param distribution (cache/providers/peer.py): cold misses
        # sourced from a warm peer's host tier instead of the store
        self.peer_fetch_bytes = Counter(
            "tpusc_peer_fetch_bytes",
            "Packed parameter bytes streamed FROM peers on cold misses "
            "(outcome = ok | error | not_found; error/not_found count the "
            "bytes received before the stream gave up and fell back to "
            "the store)",
            ["outcome"], registry=r,
        )
        # load-adaptive replication (cluster/replication.py): the
        # controller's desired per-model ring replica count N
        self.model_replicas_target = Gauge(
            "tpusc_model_replicas_target",
            "Per-model ring replica count N the replica controller "
            "currently targets (grows with in-flight load toward "
            "cluster.max_replicas_per_model, decays to the "
            "proxy.replicas_per_model floor with hysteresis)",
            ["model"], registry=r,
        )
        self.spec_draft_autodisabled = Counter(
            "tpusc_spec_draft_autodisabled_total",
            "Draft models auto-disabled after sustained low acceptance",
            registry=r,
        )
        # model label gated on metrics.model_labels (off = one all_models
        # series, last-write-wins across pairs exactly as before; on = the
        # TARGET model's acceptance is attributable per tenant)
        self.spec_tokens_per_round = Gauge(
            "tpusc_spec_tokens_per_round",
            "Most recent speculative acceptance (emitted tokens per verify "
            "round; spec_tokens+1 = every proposal accepted; labeled by "
            "target model when model_labels is on, else one all_models "
            "series)",
            ["model"], registry=r,
        )
        # cumulative acceptance by engine (engine = solo | continuous):
        # rate(accepted)/rate(rounds) is the fleet acceptance trend the
        # last-write-wins gauge above cannot provide
        self.spec_accepted_tokens = Counter(
            "tpusc_spec_accepted_tokens",
            "Tokens emitted by speculative verify rounds (accepted draft "
            "prefix + the target's own correction token)",
            ["engine"], registry=r,
        )
        self.spec_rounds = Counter(
            "tpusc_spec_rounds",
            "Speculative draft/verify rounds executed (per active lane "
            "under the continuous engine)",
            ["engine"], registry=r,
        )
        # per-tenant cost attribution (utils/accounting.py TenantLedger):
        # the ledger's monotonic integrals mirrored at scrape time via
        # LEDGER.publish() — series appear only when metrics.model_labels
        # is on (per-tenant cost without a model label is meaningless).
        # TPUSC004: family construction stays in this module.
        self.tenant_tokens = Counter(
            "tpusc_tenant_tokens",
            "Tokens attributed to this tenant (direction = in, prompt "
            "tokens admitted | out, tokens emitted)",
            ["model", "direction"], registry=r,
        )
        self.tenant_step_seconds = Counter(
            "tpusc_tenant_step_seconds",
            "Engine wall seconds spent on this tenant's rows "
            "(phase = prefill | decode); each scheduler dispatch is "
            "single-model, so step time lands wholly on its tenant",
            ["model", "phase"], registry=r,
        )
        self.tenant_kv_page_seconds = Counter(
            "tpusc_tenant_kv_page_seconds",
            "Integral of DISTINCT KV arena pages held by this tenant over "
            "time (a shared-prefix page counts once, per page_stats())",
            ["model"], registry=r,
        )
        self.tenant_byte_seconds = Counter(
            "tpusc_tenant_byte_seconds",
            "Integral of this tenant's residency bytes over time by tier "
            "(tier = hbm | host | disk)",
            ["model", "tier"], registry=r,
        )
        self.tenant_cold_load_seconds = Counter(
            "tpusc_tenant_cold_load_seconds",
            "Wall seconds of ensure_servable resolutions for this tenant "
            "by serving tier (tier = hbm | host | disk | peer | store)",
            ["model", "tier"], registry=r,
        )
        self.tenant_peer_bytes_served = Counter(
            "tpusc_tenant_peer_bytes_served",
            "Packed parameter bytes this node streamed TO peers on the "
            "tenant's behalf (work done for others, attributed not lost)",
            ["model"], registry=r,
        )
        self.tenant_dominant_share = Gauge(
            "tpusc_tenant_dominant_share",
            "Max over dimensions of this tenant's share of the node total "
            "(DRF-style dominant share in [0,1]; the noisy-neighbor signal)",
            ["model"], registry=r,
        )

    def observe_phase(
        self, phase: str, engine: str, cls: str, v: float
    ) -> None:
        """Observe one request-phase sample, routing the priority class to
        the extra label only when ``model_labels`` enabled it at
        construction — the one place that knows the histogram's arity."""
        if self.model_labels:
            self.request_phase.labels(phase, engine, cls or "normal").observe(v)
        else:
            self.request_phase.labels(phase, engine).observe(v)

    def model_label(self, name: str, version: int | str) -> str:
        if not self.model_labels:
            return ALL_MODELS
        label = f"{name}:{version}"
        seen = self._seen_model_labels
        if label in seen:
            return label
        if len(seen) >= self.max_model_labels:
            return OTHER_MODELS
        seen.add(label)
        return label

    def render(self) -> bytes:
        """Text exposition of this registry (served on the metrics path;
        reference merges TF Serving's scrape here too — metrics.go:16-53 —
        which disappears now that serving is in-process)."""
        return generate_latest(self.registry)


def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _escape_help(v: str) -> str:
    # exposition-format HELP escaping: backslash and newline only — the
    # parser unescaped these, so re-emitting raw would corrupt the merge
    return v.replace("\\", "\\\\").replace("\n", "\\n")


def _emit_families(families, skip: set[str]) -> tuple[list[str], set[str]]:
    """Re-emit parsed metric families as exposition text, skipping family
    names already emitted (cross-exporter duplicates like python_gc_* would
    otherwise make Prometheus reject the whole scrape)."""
    out: list[str] = []
    emitted: set[str] = set()
    for fam in families:
        if fam.name in skip:
            continue
        emitted.add(fam.name)
        out.append(f"# HELP {fam.name} {_escape_help(fam.documentation)}")
        out.append(f"# TYPE {fam.name} {fam.type}")
        for s in fam.samples:
            labels = ",".join(
                f'{k}="{_escape_label(str(v))}"' for k, v in sorted(s.labels.items())
            )
            label_part = f"{{{labels}}}" if labels else ""
            out.append(f"{s.name}{label_part} {s.value}")
    return out, emitted


def _merge_summed(texts: list[str], on_error) -> bytes:
    """Series-level merge: one HELP/TYPE per family, counter samples with
    identical label sets SUMMED across sources, everything else first-source
    -wins (sources are ordered own-first). This is the fleet-aggregation
    merge mode: peers exporting per-tenant counter series (model_labels on)
    combine into fleet totals instead of the first peer shadowing the rest."""
    from prometheus_client.parser import text_string_to_metric_families

    fams: dict[str, dict] = {}
    for text in texts:
        try:
            parsed = list(text_string_to_metric_families(text))
        except ValueError as e:
            on_error(e)
            continue
        for fam in parsed:
            ent = fams.get(fam.name)
            if ent is None:
                ent = fams[fam.name] = {
                    "doc": fam.documentation,
                    "type": fam.type,
                    "samples": {},
                }
            for s in fam.samples:
                key = (s.name, tuple(sorted(s.labels.items())))
                cur = ent["samples"].get(key)
                if cur is None:
                    ent["samples"][key] = s.value
                elif ent["type"] == "counter" and not s.name.endswith("_created"):
                    ent["samples"][key] = cur + s.value
                # non-counter duplicates (and _created stamps): first wins
    out: list[str] = []
    for name, ent in fams.items():
        # the parser strips the counter "_total" suffix from the family
        # name; re-emit it (generate_latest's plain-text convention) so a
        # re-parse reassociates the _total samples with their family
        # instead of orphaning them into untyped duplicates
        ename = name
        if ent["type"] == "counter" and all(
            sname.endswith(("_total", "_created"))
            for sname, _ in ent["samples"]
        ):
            ename = name + "_total"
        out.append(f"# HELP {ename} {_escape_help(ent['doc'])}")
        out.append(f"# TYPE {ename} {ent['type']}")
        for (sname, litems), value in ent["samples"].items():
            labels = ",".join(
                f'{k}="{_escape_label(str(v))}"' for k, v in litems
            )
            label_part = f"{{{labels}}}" if labels else ""
            out.append(f"{sname}{label_part} {value}")
    return ("\n".join(out) + "\n").encode()


async def scrape_and_merge(
    own: bytes,
    targets: list[str],
    timeout_s: float = 2.0,
    metrics: "Metrics | None" = None,
    sum_counters: bool = False,
) -> bytes:
    """Merge externally-scraped text-format metrics into one exposition.

    Reference equivalent: MetricsHandler's live scrape of TF Serving's
    metrics endpoint merged with the process's own registry
    (pkg/taskhandler/metrics.go:16-53). Serving moved in-process, but the
    same trick folds sidecar exporters (e.g. libtpu / node exporters) into
    this node's single /metrics endpoint. Targets are fetched concurrently
    (a down sidecar costs one timeout, not one per target), each body is
    parsed and re-emitted with cross-exporter duplicate families dropped
    (own registry wins), and unreachable/corrupt targets are skipped —
    counted in ``tpusc_scrape_errors_total`` and logged at warning, so a
    degraded merge is visible, not silent.

    ``sum_counters`` (config ``metrics.scrape_sum_counters``) switches to a
    series-level merge: counter samples with identical label sets are
    SUMMED across own+targets (per-tenant fleet aggregation), other types
    stay first-source-wins. Default off: the family-level dedup above is
    byte-stable and cheaper."""
    if not targets:
        return own
    import logging

    import aiohttp
    from prometheus_client.parser import text_string_to_metric_families

    async def fetch(session: aiohttp.ClientSession, url: str) -> str | None:
        try:
            async with session.get(url) as resp:
                if resp.status != 200:
                    raise ValueError(f"HTTP {resp.status}")
                return await resp.text()
        except Exception as e:  # noqa: BLE001 — degraded scrape is non-fatal
            logging.getLogger("tpusc.metrics").warning(
                "metrics scrape of %s failed: %s", url, e
            )
            if metrics is not None:
                metrics.scrape_errors.inc()
            return None

    async with aiohttp.ClientSession(
        timeout=aiohttp.ClientTimeout(total=timeout_s)
    ) as session:
        bodies = await asyncio.gather(*(fetch(session, url) for url in targets))

    if sum_counters:
        def _on_parse_error(e: Exception) -> None:
            logging.getLogger("tpusc.metrics").warning(
                "metrics merge source unparseable: %s", e
            )
            if metrics is not None:
                metrics.scrape_errors.inc()

        return _merge_summed(
            [own.decode()] + [b for b in bodies if b is not None],
            _on_parse_error,
        )

    seen = {f.name for f in text_string_to_metric_families(own.decode())}
    parts = [own.rstrip(b"\n")]
    for url, body in zip(targets, bodies):
        if body is None:
            continue
        try:
            lines, emitted = _emit_families(text_string_to_metric_families(body), seen)
        except ValueError as e:
            logging.getLogger("tpusc.metrics").warning(
                "metrics scrape of %s unparseable: %s", url, e
            )
            if metrics is not None:
                metrics.scrape_errors.inc()
            continue
        seen |= emitted
        if lines:
            parts.append("\n".join(lines).encode())
    return b"\n".join(parts) + b"\n"
