"""CacheManager — per-node JIT load orchestration.

Reference equivalent: pkg/cachemanager/cachemanager.go (C5 in SURVEY.md §2),
the heart of the system. Differences by design:

  - per-model singleflight instead of one global RW-mutex serializing all
    misses node-wide (the reference flags its big lock as a known todo,
    README.md:75 / cachemanager.go:114-115): concurrent misses on different
    models fetch+compile in parallel; concurrent requests for the same model
    coalesce into one fetch;
  - the "reload serving config and poll every 500 ms" step
    (cachemanager.go:167-195) is a direct in-process runtime.ensure_loaded;
  - hit/stale/miss decision tree kept: HIT = on disk + AVAILABLE in runtime;
    STALE = on disk but not loaded (e.g. HBM-evicted or restart) -> reload
    without re-fetch (cachemanager.go:133-143); MISS = fetch from provider
    (ensure free bytes first), then load.
"""

from __future__ import annotations

import os
import threading
import time

from tfservingcache_tpu.cache.disk_cache import ModelDiskCache
from tfservingcache_tpu.cache.providers.base import ModelProvider
from tfservingcache_tpu.lab import faults as lab_faults
from tfservingcache_tpu.runtime.base import BaseRuntime, LoadTimeoutError
from tfservingcache_tpu.types import Model, ModelId
from tfservingcache_tpu.utils.accounting import LEDGER
from tfservingcache_tpu.utils.lockcheck import lockchecked
from tfservingcache_tpu.utils.logging import get_logger
from tfservingcache_tpu.utils.metrics import Metrics
from tfservingcache_tpu.utils.tracing import TRACER

log = get_logger("cachemanager")


class VersionLabelError(LookupError):
    """A ModelSpec.version_label with no mapping in serving.version_labels.

    Surfaced as FAILED_PRECONDITION/412 — TF Serving fails unmapped labels
    the same way; silently serving latest is the one wrong option (VERDICT
    r3 missing #4)."""


def resolve_version_label(version_labels: dict, name: str,
                          label: str) -> int:
    """Shared by CacheManager and Router (which routes by name##version and
    so must resolve labels before consulting the ring)."""
    try:
        return int(version_labels[name][label])
    except (KeyError, TypeError, ValueError):
        raise VersionLabelError(
            f"version label {label!r} is not mapped for model {name!r} "
            "(serving.version_labels)"
        ) from None


@lockchecked
class CacheManager:
    # Guarded-field registry: checked statically by tools/tpusc_check
    # (TPUSC001) and dynamically under TPUSC_LOCKCHECK=1 (utils/lockcheck).
    _tpusc_guarded = {
        "_version_cache": "_version_cache_lock",
        "_negative_cache": "_version_cache_lock",
        "_load_workers": "_load_workers_lock",
    }

    def __init__(
        self,
        provider: ModelProvider,
        disk_cache: ModelDiskCache,
        runtime: BaseRuntime,
        metrics: Metrics | None = None,
        load_timeout_s: float | None = None,
        version_labels: dict | None = None,
    ) -> None:
        self.provider = provider
        self.disk_cache = disk_cache
        self.runtime = runtime
        self.metrics = metrics
        # cold-path deadline over fetch+compile (reference: hardcoded 10 s
        # fetch timeout, cmd/taskhandler/main.go:122). None/0 disables.
        self.load_timeout_s = load_timeout_s or None
        # {model_name: {label: version}} from serving.version_labels
        self.version_labels = version_labels or {}
        # resolve_version memo: an unversioned request for an unknown name
        # otherwise costs a full provider listing PER REQUEST — a hot-path
        # stall at 1000 tenants. Positive entries cache the provider's
        # latest; negative entries cache "name doesn't exist" briefly so a
        # storm of bad names can't hammer the store.
        self._version_cache: dict[str, tuple[int, float]] = {}
        self._negative_cache: dict[str, float] = {}
        self._version_cache_lock = threading.Lock()
        self.version_cache_ttl_s = 10.0
        self.negative_cache_ttl_s = 2.0
        # Deadline workers (see _with_deadline): tracked so close() can join
        # stragglers and a timeout storm can't pile up unbounded threads.
        self._load_workers: set[threading.Thread] = set()
        self._load_workers_lock = threading.Lock()
        self.max_load_workers = 64
        # a model evicted from the disk tier must not keep serving from HBM:
        # its artifact is gone, a restart would break the invariant that
        # resident => re-loadable (subscribe, don't overwrite: several
        # chip-group managers may share one host disk cache)
        disk_cache.add_evict_callback(self._on_disk_evict)

    def _on_disk_evict(self, model_id: ModelId) -> None:
        # unload_and_discard (not plain unload): the host tier is inclusive
        # in the disk tier, so an evicted artifact takes any retained packed
        # chunks down with it (duck-typed for runtimes without the method)
        discard = getattr(self.runtime, "unload_and_discard", None)
        if discard is not None:
            discard(model_id)
        else:
            self.runtime.unload(model_id)
        self._sync_disk_ledger()

    def _sync_disk_ledger(self) -> None:
        """Stamp per-tenant disk-cache levels into the cost ledger
        (owner-scoped: several managers sharing a process never zero each
        other's artifacts)."""
        levels: dict[str, float] = {}
        for mid in self.disk_cache.list_models():
            nbytes = self.disk_cache.size_of(mid)
            if nbytes:
                levels[str(mid)] = float(nbytes)
        LEDGER.gauge_sync("disk_bytes", levels, owner=f"disk:{id(self)}")

    # ------------------------------------------------------------------
    def ensure_servable(self, model_id: ModelId) -> Model:
        """Hit/stale/miss decision + fetch/load; blocks until AVAILABLE.

        Reference: fetchModel (cachemanager.go:91-152).
        """
        label = None
        if self.metrics is not None:
            label = self.metrics.model_label(model_id.name, model_id.version)
            self.metrics.cache_total.labels(label).inc()
        t0 = time.monotonic()

        # fast path outside the lock: fully warm
        model = self.disk_cache.get(model_id)
        if model is not None and self.runtime.is_loaded(model_id):
            if self.metrics is not None:
                self.metrics.cache_hits.labels(label).inc()
                self.metrics.reload_source.labels("hbm").inc()
                self.metrics.cache_duration.labels(label).observe(time.monotonic() - t0)
            LEDGER.note_load(str(model_id), "hbm", time.monotonic() - t0)
            return model

        deadline = t0 + self.load_timeout_s if self.load_timeout_s else None
        with TRACER.span("ensure_servable", model=str(model_id)) as span, \
                self.disk_cache.fetch_lock(model_id):  # per-model singleflight
            model = self.disk_cache.get(model_id)
            if model is not None:
                if self.runtime.is_loaded(model_id):
                    hit = True  # another waiter finished the work
                    source = "hbm"
                else:
                    # STALE: artifact cached, executable not resident — the
                    # runtime reports which tier actually revived it (host
                    # promotion vs full disk load; None = plain runtime)
                    log.info("stale %s: artifact cached, reloading runtime", model_id)
                    src = self._with_deadline(
                        lambda: self.runtime.ensure_loaded(model), deadline,
                        f"reload {model_id}",
                    )
                    hit = True
                    source = src if src in ("hbm", "host") else "disk"
            else:
                hit = False
                model = self._with_deadline(
                    lambda: self._fetch(model_id), deadline, f"fetch {model_id}"
                )
                # a PeerProvider stamps where the bytes actually came from:
                # "peer" = streamed from a warm node's host tier instead of
                # the store (cache/providers/peer.py)
                source = model.metadata.get("fetch_source", "store")
                if source not in ("peer", "store"):
                    source = "store"
                # a peer fetch also hands over the transfer-ready packed
                # chunks it assembled off the wire; the runtime promotes
                # from those directly instead of re-reading the artifact it
                # just wrote. POPPED unconditionally — a Model lives in the
                # disk-cache map, and a retained entry would pin the packed
                # bytes in RAM for as long as the artifact stays cached.
                packed = model.metadata.pop("packed_entry", None)
                if packed is not None:
                    adopt = getattr(self.runtime, "adopt_packed_entry", None)
                    if adopt is not None:
                        adopt(model_id, packed)
                self._with_deadline(
                    lambda: self.runtime.ensure_loaded(model), deadline,
                    f"load {model_id}",
                )
            span.attrs["reload_source"] = source
            if self.metrics is not None:
                (self.metrics.cache_hits if hit else self.metrics.cache_misses).labels(
                    label
                ).inc()
                self.metrics.reload_source.labels(source).inc()
                self.metrics.cache_duration.labels(label).observe(time.monotonic() - t0)
                self.metrics.disk_bytes_in_use.set(self.disk_cache.total_bytes)
            # cost ledger: which tier revived this tenant and what it cost;
            # disk levels re-stamped only on this slow path (a fetch may
            # have put/evicted artifacts), never on the per-request fast path
            LEDGER.note_load(str(model_id), source, time.monotonic() - t0)
            self._sync_disk_ledger()
            return model

    def residency_warmth(self, model_id: ModelId) -> int:
        """How warm is ``model_id`` on THIS node: 3 = HBM-resident,
        2 = host-tier packed (promotable in tens of ms), 1 = disk artifact,
        0 = cold. Advisory snapshot for the router's equal-load tie-break
        (cluster/router.py): a replica that can promote instead of
        refetching should win ties. Never raises — routing must not fail
        on a warmth probe."""
        try:
            if self.runtime.is_loaded(model_id):
                return 3
            contains = getattr(self.runtime, "host_tier_contains", None)
            if contains is not None and contains(model_id):
                return 2
            # size_of, not get: a warmth probe must not perturb LRU recency
            if self.disk_cache.size_of(model_id) is not None:
                return 1
        except Exception:  # noqa: BLE001 - advisory only
            pass
        return 0

    def _with_deadline(self, fn, deadline: float | None, desc: str):
        """Run ``fn`` under the shared cold-load deadline.

        Python can't interrupt a blocking provider download or XLA compile
        in-thread, so with a deadline set the work runs in a daemon worker
        while the request thread waits with a timeout: on expiry the request
        fails fast (LoadTimeoutError -> 504/DEADLINE_EXCEEDED) and its
        singleflight lock is released, while the orphaned worker runs to
        completion in the background. Its result still lands (disk index /
        runtime state machine, which the worker advances to AVAILABLE or END
        itself), so the spent work isn't wasted: the next request finds the
        model warm or STALE. Without a deadline the call runs inline."""
        if deadline is None:
            return fn()
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise LoadTimeoutError(
                f"{desc}: cold-load deadline ({self.load_timeout_s:.1f}s) already spent"
            )
        import contextvars

        ctx = contextvars.copy_context()  # keep TRACER span parentage in the worker
        box: dict = {}
        done = threading.Event()

        def work() -> None:
            try:
                box["value"] = ctx.run(fn)
            except BaseException as e:  # noqa: BLE001 - re-raised in caller
                box["error"] = e
            finally:
                done.set()
                with self._load_workers_lock:
                    self._load_workers.discard(threading.current_thread())

        worker = threading.Thread(target=work, daemon=True, name="tpusc-load-worker")
        with self._load_workers_lock:
            if len(self._load_workers) >= self.max_load_workers:
                raise LoadTimeoutError(
                    f"{desc}: {self.max_load_workers} cold-load workers already "
                    "in flight (deadline storm); failing fast instead of "
                    "spawning an unbounded thread pile"
                )
            self._load_workers.add(worker)
        worker.start()
        if not done.wait(remaining):
            log.warning("%s exceeded cold-load deadline (%.1fs); request fails 504, "
                        "work continues in background", desc, self.load_timeout_s)
            raise LoadTimeoutError(
                f"{desc} exceeded cold-load deadline ({self.load_timeout_s:.1f}s)"
            )
        if "error" in box:
            raise box["error"]
        return box.get("value")

    def prefetch(self, model_id: ModelId) -> Model:
        """Host-side half of a cold miss only: artifact onto local disk, the
        runtime untouched. Cross-host groups use this as a joinable phase 1
        (parallel/multihost.py) so provider/IO failures surface BEFORE any
        process enters a collective it could strand the others in."""
        with self.disk_cache.fetch_lock(model_id):
            model = self.disk_cache.get(model_id)
            if model is not None:
                return model
            return self._fetch(model_id)

    def _fetch(self, model_id: ModelId) -> Model:
        """MISS path: size -> evict-to-fit -> provider fetch -> index.
        Reference cachemanager.go:114-127 (minus its double-eviction quirk).

        With a pipelined runtime the fetch goes through the provider's
        streaming variant: the moment model.json lands on disk its manifest
        is handed to ``runtime.precompile_from_meta``, so the family's XLA
        compile overlaps the rest of the download — the widest overlap the
        cold pipeline gets, since provider fetch is usually its longest
        stage."""
        t0 = time.monotonic()
        # scenario-lab hook (lab/faults.py): stall_store sleeps here — a
        # hung object store, under whatever cold-load deadline the caller
        # wrapped this fetch in. Disarmed it is one bool read.
        lab_faults.fire("store_fetch", model=str(model_id))
        on_file = None
        if getattr(self.runtime, "cold_pipeline_enabled", False):
            runtime = self.runtime

            def on_file(rel: str, local_path: str) -> None:
                if os.path.basename(rel) != "model.json":
                    return
                try:
                    from tfservingcache_tpu.models.registry import (
                        load_artifact_meta,
                    )

                    runtime.precompile_from_meta(load_artifact_meta(local_path))
                except Exception as e:  # noqa: BLE001 - advisory hint only
                    log.warning("early precompile for %s skipped: %s", model_id, e)

        with TRACER.span("provider_fetch", model=str(model_id)):
            size = self.provider.model_size(model_id.name, model_id.version)
            self.disk_cache.ensure_free_bytes(size)
            # duck-typed: fake providers that only implement load_model
            # (tests, external plugins) keep working without the overlap
            stream = getattr(self.provider, "load_model_streaming", None)
            if on_file is not None and stream is not None:
                model = stream(
                    model_id.name, model_id.version,
                    self.disk_cache.model_path(model_id), on_file=on_file,
                )
            else:
                model = self.provider.load_model(
                    model_id.name, model_id.version,
                    self.disk_cache.model_path(model_id),
                )
        self.disk_cache.put(model)
        if self.metrics is not None:
            self.metrics.cache_fetch_duration.labels(
                self.metrics.model_label(model_id.name, model_id.version)
            ).observe(time.monotonic() - t0)
            # the fetch stage of the cold-stage histogram family (its device
            # siblings are recorded by the runtime's load span)
            self.metrics.cold_stage_seconds.labels("provider_fetch").observe(
                time.monotonic() - t0
            )
        log.info(
            "fetched %s (%d bytes) in %.2fs", model_id, model.size_on_disk, time.monotonic() - t0
        )
        return model

    # ------------------------------------------------------------------
    def resolve_version(self, name: str, version: int | None,
                        label: str | None = None) -> int:
        """Map "no version given" (gRPC ModelSpec with unset Int64Value reads
        as 0 — reference taskhandler clientForSpec, tfservingproxy.go:246-250)
        to the newest known version: prefer what's resident, fall back to the
        provider listing. A ``version_label`` resolves through the serving
        config's ``version_labels`` map or fails (never silently latest)."""
        if label:
            return resolve_version_label(self.version_labels, name, label)
        if version:
            return version
        known = [m.version for m in self.disk_cache.list_models() if m.name == name]
        loaded = [m.version for m, s in self.runtime.states_for(name).items() if s == 30]
        if loaded:
            return max(loaded)
        if known:
            return max(known)
        from tfservingcache_tpu.cache.providers.base import ModelNotFoundError

        now = time.monotonic()
        with self._version_cache_lock:
            hit = self._version_cache.get(name)
            if hit is not None and hit[1] > now:
                return hit[0]
            neg = self._negative_cache.get(name)
            if neg is not None and neg > now:
                raise ModelNotFoundError(f"model {name!r} not found (cached)")
        try:
            latest = self.provider.latest_version(name)
        except ModelNotFoundError:
            with self._version_cache_lock:
                if len(self._negative_cache) > 4096:
                    self._negative_cache.clear()
                self._negative_cache[name] = now + self.negative_cache_ttl_s
            raise
        with self._version_cache_lock:
            if len(self._version_cache) > 4096:
                self._version_cache.clear()
            self._version_cache[name] = (latest, now + self.version_cache_ttl_s)
        return latest

    def available_versions(self, name: str) -> list[int]:
        """All versions the node could serve, ascending: the provider's
        listing, falling back to disk-cached versions when the provider can't
        enumerate (backs ReloadConfig's latest/all version policies)."""
        from tfservingcache_tpu.cache.providers.base import ModelNotFoundError

        try:
            return self.provider.list_versions(name)
        except ModelNotFoundError:
            cached = sorted(m.version for m in self.disk_cache.list_models() if m.name == name)
            if cached:
                return cached
            raise

    def is_healthy(self) -> bool:
        """Provider + runtime probes (reference IsHealthy,
        cachemanager.go:76-89, where "TF Serving answers NOT_FOUND for the
        probe model" meant alive; in-process we just probe directly)."""
        try:
            self.provider.check()
            self.runtime.check()
            return True
        except Exception as e:  # noqa: BLE001
            log.warning("health check failed: %s", e)
            return False

    def list_cached(self) -> list[ModelId]:
        return self.disk_cache.list_models()

    def close(self) -> None:
        self.runtime.close()
        # Orphaned deadline workers (request timed out, work still landing):
        # give them a bounded window to finish so shutdown doesn't race their
        # disk-index/runtime writes, then let daemons die with the process.
        with self._load_workers_lock:
            stragglers = list(self._load_workers)
        for t in stragglers:
            t.join(timeout=5.0)
        self.disk_cache.close()
