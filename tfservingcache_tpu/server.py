"""Process wiring: build and run a cache node (+ router when discovery is
configured).

Reference equivalent: cmd/taskhandler/main.go:20-113 — serveCache always
runs; serveProxy only when ``discovery.type`` is set (main.go:88-105:
single-node "cache-only" mode otherwise); a 30 s health loop pushes status
into every gRPC health server (main.go:35-42).
"""

from __future__ import annotations

import asyncio
import os
import signal

from tfservingcache_tpu.cache.disk_cache import ModelDiskCache
from tfservingcache_tpu.cache.manager import CacheManager
from tfservingcache_tpu.cache.providers import create_provider
from tfservingcache_tpu.cluster.status import StatusCollector
from tfservingcache_tpu.config import Config
from tfservingcache_tpu.protocol.grpc_server import GrpcServingServer
from tfservingcache_tpu.protocol.local_backend import LocalServingBackend
from tfservingcache_tpu.protocol.rest import RestServingServer
from tfservingcache_tpu.utils import bring_up
from tfservingcache_tpu.utils.accounting import LEDGER
from tfservingcache_tpu.utils.flight_recorder import RECORDER
from tfservingcache_tpu.utils.logging import get_logger
from tfservingcache_tpu.utils.metrics import Metrics
from tfservingcache_tpu.utils.net import outbound_ip
from tfservingcache_tpu.utils.tracing import TRACER

log = get_logger("server")

HEALTH_LOOP_PERIOD_S = 30.0  # reference main.go:41


class ServingGroup:
    """One chip group's full serving stack: group mesh -> runtime -> manager
    -> backend -> its own REST/gRPC server pair. A group is a ring member
    (SURVEY.md §7 step 8: the ring assigns models to chip GROUPS, not hosts;
    the group's distinct ports make (host, group) addressable by peers)."""

    def __init__(self, index: int, manager: CacheManager, backend, rest, grpc) -> None:
        self.index = index
        self.manager = manager
        self.backend = backend
        self.rest = rest
        self.grpc = grpc
        self.rest_port = 0
        self.grpc_port = 0
        self.status: StatusCollector | None = None  # fleet status plane


class CacheNode:
    """One serving host: provider + disk cache shared across its chip-group
    runtimes, each group behind its own REST/gRPC protocol servers."""

    def __init__(self, cfg: Config, runtime=None) -> None:
        self.cfg = cfg
        self.metrics = Metrics(
            model_labels=cfg.metrics.model_labels,
            max_model_labels=cfg.metrics.max_model_labels,
        )
        # the bring-up account (utils/bring_up.py): its listeners before
        # anything here touches jax, then the node's whole construction as
        # its ``server_start`` stage (the runtime's first device discovery is
        # the ``backend_init`` child; binding the sockets, milliseconds,
        # follows in ``start``)
        bring_up.install(self.metrics)
        with bring_up.stage("server_start", self.metrics):
            self._build(cfg, runtime)

    def _build(self, cfg: Config, runtime) -> None:
        provider = create_provider(cfg.model_provider)
        if cfg.cluster.peer_fetch:
            # peer param distribution: front the store with the peer path
            # (cache/providers/peer.py). Constructed UNBOUND — pure
            # pass-through — until a Router arms it with the fleet view
            # (single-node deployments never bind, and lose nothing).
            from tfservingcache_tpu.cache.providers.peer import PeerProvider

            provider = PeerProvider(
                provider,
                chunk_bytes=cfg.cluster.peer_fetch_chunk_bytes,
                timeout_s=cfg.cluster.peer_fetch_timeout_s,
                max_message_bytes=cfg.proxy.grpc_max_message_bytes,
            )
        self.provider = provider
        disk_cache = ModelDiskCache(cfg.cache.base_dir, cfg.cache.disk_capacity_bytes)
        self.disk_cache = disk_cache

        self.work_handler = None   # follower work service (cross-host groups)
        self.work_server = None
        self._follower_managers: list[CacheManager] = []
        if runtime is not None:
            runtimes = [(0, runtime)]
        else:
            from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime

            if cfg.mesh.coordinator and cfg.mesh.num_processes > 1:
                # multi-controller deployment: rendezvous BEFORE any backend
                # use so jax.devices() sees the whole slice (probing
                # jax.process_count() first would itself init the backend)
                import jax

                if (cfg.serving.platform or os.environ.get(
                        "JAX_PLATFORMS", "")).startswith("cpu"):
                    # the CPU backend only runs cross-process programs over
                    # gloo collectives, and jax no longer defaults to them —
                    # without this every partitioned op in a CPU group fails
                    # with "Multiprocess computations aren't implemented"
                    jax.config.update(
                        "jax_cpu_collectives_implementation", "gloo"
                    )
                try:
                    jax.distributed.initialize(
                        cfg.mesh.coordinator,
                        num_processes=cfg.mesh.num_processes,
                        process_id=cfg.mesh.process_id,
                    )
                except RuntimeError as e:
                    if "already initialized" not in str(e).lower():
                        raise
            if cfg.mesh.chips_per_group > 1:
                import numpy as np

                import jax
                from jax.sharding import Mesh

                from tfservingcache_tpu.parallel.mesh import chip_groups

                devices = jax.devices()
                me = jax.process_index()
                runtimes = []
                followers_of: dict[int, TPUModelRuntime] = {}
                for gi, gdevs in enumerate(chip_groups(devices, cfg.mesh.chips_per_group)):
                    procs = sorted({d.process_index for d in gdevs})
                    if me not in procs:
                        continue  # this process owns none of the group's chips
                    mesh = Mesh(np.array(gdevs), ("model",))
                    leader = gdevs[0].process_index
                    if leader == me and len(procs) > 1:
                        from tfservingcache_tpu.parallel.multihost import (
                            MultiHostGroupRuntime,
                        )

                        addrs = [cfg.mesh.worker_addrs[p] for p in procs if p != me]
                        runtimes.append((gi, MultiHostGroupRuntime(
                            cfg.serving, self.metrics, mesh=mesh, group=gi,
                            followers=addrs, group_index=gi,
                        )))
                    elif leader == me:
                        runtimes.append((gi, TPUModelRuntime(
                            cfg.serving, self.metrics, mesh=mesh, group=gi
                        )))
                    else:
                        # follower: participate in the group's collectives via
                        # the work service; the LEADER is the ring member
                        followers_of[gi] = TPUModelRuntime(
                            cfg.serving, self.metrics, mesh=mesh, group=gi
                        )
                if followers_of:
                    from tfservingcache_tpu.parallel.multihost import (
                        GroupWorkHandler,
                        GroupWorkServer,
                    )

                    self.work_handler = GroupWorkHandler()
                    for gi, rt in followers_of.items():
                        mgr = CacheManager(
                            provider, disk_cache, rt, self.metrics,
                            load_timeout_s=cfg.serving.load_timeout_s,
                            version_labels=cfg.serving.version_labels,
                        )
                        self.work_handler.register(gi, mgr, rt)
                        self._follower_managers.append(mgr)
                    self.work_server = GroupWorkServer(self.work_handler)
            else:
                # host tier is single-chip only (mesh runtimes above keep the
                # deterministic full-load path, so the knob is not plumbed)
                runtimes = [(0, TPUModelRuntime(
                    cfg.serving, self.metrics,
                    host_tier_bytes=cfg.cache.host_tier_bytes,
                ))]

        if runtime is None:
            self._log_device_use(runtimes)
        self.groups: list[ServingGroup] = []
        for pos, (i, rt) in enumerate(runtimes):
            manager = CacheManager(
                provider, disk_cache, rt, self.metrics,
                load_timeout_s=cfg.serving.load_timeout_s,
                version_labels=cfg.serving.version_labels,
            )
            backend = LocalServingBackend(
                manager,
                batch_window_ms=cfg.serving.batch_window_ms,
                batch_max_size=cfg.serving.batch_max_size,
                generate_slots=cfg.serving.generate_slots,
                generate_chunk_tokens=cfg.serving.generate_chunk_tokens,
                kv_page_tokens=cfg.serving.kv_page_tokens,
                kv_arena_pages=cfg.serving.kv_arena_pages,
                kv_share_prefix_bytes=cfg.serving.kv_share_prefix_bytes,
                kv_paged_kernel=cfg.serving.kv_paged_kernel,
                kv_arena_dtype=cfg.serving.kv_arena_dtype,
                spec_draft_model=cfg.serving.spec_draft_model,
                spec_tokens=cfg.serving.spec_tokens,
                generate_recovery=cfg.serving.generate_recovery,
                generate_max_recoveries=cfg.serving.generate_max_recoveries,
                conversation_kv_bytes=cfg.serving.conversation_kv_bytes,
                conversation_kv_disk_bytes=cfg.serving.conversation_kv_disk_bytes,
                conversation_kv_dir=cfg.serving.conversation_kv_dir,
                prefill_chunk_tokens=cfg.serving.prefill_chunk_tokens,
            )
            # every group records into the SHARED Metrics registry (request/
            # error/latency counters must cover all groups); only the first
            # local group mounts the /metrics exposition endpoint for the host
            rest = RestServingServer(
                backend,
                self.metrics,
                require_version=False,
                metrics_path=cfg.metrics.path if pos == 0 else None,
                metrics_scrape_targets=cfg.metrics.scrape_targets,
                metrics_sum_counters=cfg.metrics.scrape_sum_counters,
            )
            grpc = GrpcServingServer(
                backend, self.metrics, cfg.proxy.grpc_max_message_bytes
            )
            if cfg.cluster.peer_fetch:
                # outbound half of the peer path: serve this group's
                # host-tier packed entries to cold peers (the handler
                # answers NOT_FOUND when the tier is off or empty)
                from tfservingcache_tpu.protocol.peer_transfer import PeerSource

                grpc.peer_source = PeerSource(
                    rt,
                    chunk_bytes=cfg.cluster.peer_fetch_chunk_bytes,
                    max_inflight_per_peer=cfg.cluster.peer_fetch_max_inflight_per_peer,
                )
            # conversation KV migration (ISSUE 18): expose this group's
            # parked decode state over FetchParkedConversation so a peer
            # that inherits a conversation after a ring rebalance resumes
            # it with O(new tokens) prefill instead of a cold re-prefill
            gen_tier = getattr(
                getattr(backend, "_generator", None), "conversation_tier", None
            )
            if gen_tier is not None:
                grpc.conversation_tier = gen_tier
            group = ServingGroup(i, manager, backend, rest, grpc)
            if cfg.cluster.status_exchange:
                # per-group status collector for the fleet exchange; built
                # with a placeholder ident (ports aren't bound yet) that the
                # Router rebinds to the ring ident once they are
                group.status = StatusCollector(
                    f"group{i}", manager, metrics=self.metrics,
                    byte_cap=cfg.cluster.status_byte_cap,
                    max_models=cfg.cluster.status_max_models,
                    min_interval_s=cfg.cluster.status_min_interval_s,
                    max_tenants=cfg.cluster.status_max_tenants,
                )
                rest.status_collector = group.status
                grpc.status_collector = group.status
            self.groups.append(group)
        self._health_task: asyncio.Task | None = None

    def _log_device_use(self, runtimes) -> None:
        """Say at start how much of the host this node drives: the default
        ``mesh.chips_per_group: 1`` builds ONE runtime that places everything
        on the first local device, whatever the host holds."""
        import jax

        used = set()
        for _i, rt in runtimes:
            mesh = getattr(rt, "mesh", None)
            if mesh is not None:
                used.update(d for d in mesh.devices.flat
                            if d.process_index == jax.process_index())
            else:
                used.update(getattr(rt, "_devices", [])[:1])
        local = jax.local_devices()
        log.info(
            "using %d of %d local %s devices in %d chip group(s) "
            "(mesh.chips_per_group=%d)",
            len(used), len(local), local[0].platform, len(runtimes),
            self.cfg.mesh.chips_per_group,
        )
        if len(used) < len(local):
            log.warning(
                "%d local devices stay idle: one runtime serves from one "
                "device or one chip group; set mesh.chips_per_group to shard "
                "models over more chips", len(local) - len(used),
            )

    # group-0 aliases: the single-group shape most callers/tests use
    @property
    def manager(self) -> CacheManager:
        return self.groups[0].manager

    @property
    def backend(self):
        return self.groups[0].backend

    async def start(self) -> tuple[int, int]:
        """Start every group's servers. Group i binds base_port + i (or an
        ephemeral port when the base is 0). Returns the first local group's
        ports (0, 0 for a pure-follower process)."""
        for g in self.groups:
            rest_base = self.cfg.cache_node.rest_port
            grpc_base = self.cfg.cache_node.grpc_port
            g.rest_port = await g.rest.start(rest_base + g.index if rest_base else 0)
            g.grpc_port = await g.grpc.start(grpc_base + g.index if grpc_base else 0)
            if g.status is not None:
                # rebind the placeholder ident to the ring ident peers will
                # see — a standalone node (no colocated Router) must still
                # advertise a routable identity in its piggybacked status
                host = ("127.0.0.1" if self.cfg.discovery.prefer_localhost
                        else outbound_ip())
                g.status.ident = f"{host}:{g.rest_port}:{g.grpc_port}"
        if self.work_server is not None:
            # follower work endpoint: advertised to leaders via
            # mesh.worker_addrs[process_id]
            me = self.cfg.mesh.process_id
            addrs = self.cfg.mesh.worker_addrs
            port = 0
            if me < len(addrs) and ":" in addrs[me]:
                port = int(addrs[me].rsplit(":", 1)[1])
            bound = await self.work_server.start(port)
            log.info("group work service on :%d (follower groups %s)",
                     bound, self.work_handler.group_indexes)
        self._health_task = asyncio.create_task(self._health_loop())
        if not self.groups:
            return 0, 0
        return self.groups[0].rest_port, self.groups[0].grpc_port

    def is_healthy(self) -> bool:
        return all(g.manager.is_healthy() for g in self.groups)

    async def _health_loop(self) -> None:
        while True:
            healthy = await asyncio.get_running_loop().run_in_executor(None, self.is_healthy)
            for g in self.groups:
                g.grpc.set_health(healthy)
            await asyncio.sleep(HEALTH_LOOP_PERIOD_S)

    async def close(self) -> None:
        if self._health_task is not None:
            self._health_task.cancel()
        for g in self.groups:
            g.backend.close()
            await g.rest.close()
            await g.grpc.close()
            g.manager.close()
        if self.work_server is not None:
            await self.work_server.close()
        for mgr in self._follower_managers:
            mgr.close()
        self.disk_cache.close()
        close_provider = getattr(self.provider, "close", None)
        if close_provider is not None:
            close_provider()


async def serve(cfg: Config) -> None:
    # the process-wide tracer is configured once at server startup (tests
    # construct Tracer instances directly and never pass through here)
    TRACER.configure(
        capacity=cfg.tracing.capacity,
        slow_threshold_s=cfg.tracing.slow_threshold_ms / 1000.0,
        slow_capacity=cfg.tracing.slow_capacity,
    )
    # flight-recorder rings are always on; anomaly dumps arm here, and every
    # slow-retained root (SLO breach) now also snapshots the engine
    RECORDER.configure(
        flight_dir=cfg.observability.flight_dir or None,
        ring_entries=cfg.observability.ring_entries,
        max_dumps=cfg.observability.max_dumps,
        dump_cooldown_s=cfg.observability.dump_cooldown_s,
    )
    RECORDER.install_slow_hook(TRACER)
    # per-tenant cost-attribution ledger (utils/accounting.py): the engine,
    # runtime, and cache tiers feed the process-global LEDGER; the knobs
    # here only tune the noisy-neighbor detector and the master switch
    LEDGER.configure(
        enabled=cfg.observability.tenant_accounting,
        noisy_share=cfg.observability.noisy_neighbor_share,
        noisy_window_s=cfg.observability.noisy_neighbor_window_s,
        noisy_min_step_s=cfg.observability.noisy_neighbor_min_step_s,
    )
    node = CacheNode(cfg)
    RECORDER.configure(metrics=node.metrics)   # tpusc_flight_dumps_total
    if cfg.observability.lab_faults:
        # scenario-lab chaos drill (lab/faults.py): armed ONLY when the
        # operator set observability.lab_faults (or its env override) — the
        # injector hooks are single-bool-read passthroughs otherwise
        from tfservingcache_tpu.lab import faults as lab_faults

        lab_faults.arm_json(cfg.observability.lab_faults, metrics=node.metrics)
    rest_port, grpc_port = await node.start()
    log.info(
        "cache node up: REST :%d, gRPC :%d (provider=%s, cache=%s)",
        rest_port, grpc_port, cfg.model_provider.type, cfg.cache.base_dir,
    )
    router = None
    if cfg.discovery.type:
        from tfservingcache_tpu.cluster.router import Router

        router = Router(cfg, node)
        await router.start()
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:  # non-unix
            pass
    await stop.wait()
    log.info("shutting down")
    if router is not None:
        await router.close()
    await node.close()


def run_server(cfg: Config) -> None:
    if cfg.serving.platform:
        # must happen before backend init
        import jax

        jax.config.update("jax_platforms", cfg.serving.platform)
    asyncio.run(serve(cfg))
