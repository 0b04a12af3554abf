"""Observability layer: distributed trace stitching across two in-process
nodes, REST/gRPC counter parity, slow-trace retention, trace-correlated JSON
logs, metric-name stability, and the tracer's overhead budget.

The two-node topology follows the reference DiscoveryServiceMock pattern
(cluster_test.go:12-49): membership is pushed, the router short-circuits its
colocated backend, and requests whose hash lands on the peer cross a real
HTTP/gRPC hop — exactly the hop the traceparent/subtree contract covers.
"""

import asyncio
import io
import json
import logging
import os
import re
import statistics
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import asynccontextmanager

import aiohttp
import grpc
import numpy as np
import pytest

from tfservingcache_tpu.cache.disk_cache import ModelDiskCache
from tfservingcache_tpu.cache.manager import CacheManager
from tfservingcache_tpu.cache.providers.disk import DiskModelProvider
from tfservingcache_tpu.cluster.cluster import ClusterConnection
from tfservingcache_tpu.cluster.discovery.base import DiscoveryService
from tfservingcache_tpu.cluster.router import RoutingBackend
from tfservingcache_tpu.protocol import codec
from tfservingcache_tpu.protocol.grpc_client import ServingStub, make_channel
from tfservingcache_tpu.protocol.grpc_server import (
    PREDICTION_SERVICE,
    GrpcServingServer,
)
from tfservingcache_tpu.protocol.local_backend import LocalServingBackend
from tfservingcache_tpu.protocol.protos import grpc_health_pb2 as health_pb
from tfservingcache_tpu.protocol.protos import tf_serving_pb2 as sv
from tfservingcache_tpu.protocol.rest import RestServingServer
from tfservingcache_tpu.runtime.batcher import MicroBatcher
from tfservingcache_tpu.runtime.fake import FakeRuntime
from tfservingcache_tpu.types import Model, ModelId, NodeInfo
from tfservingcache_tpu.utils.logging import JsonFormatter
from tfservingcache_tpu.utils.metrics import Metrics
from tfservingcache_tpu.utils.tracing import (
    TRACER,
    WIRE_TRACE_LIMIT,
    Span,
    Tracer,
    deserialize_span,
    format_traceparent,
    parse_traceparent,
    remote_parent,
    serialize_span,
)


class DiscoveryServiceMock(DiscoveryService):
    async def register(self, self_node, is_healthy):
        pass

    async def unregister(self):
        pass

    def push(self, nodes: list[NodeInfo]) -> None:
        self._publish(nodes)


def make_store(root, models):
    for name, version in models:
        d = root / name / str(version)
        d.mkdir(parents=True)
        (d / "params.bin").write_bytes(b"x" * 64)


@asynccontextmanager
async def observed_node(tmp_path, name, store):
    """cache_node (test_cluster.py) + its OWN Metrics registry, so per-node
    counters are assertable in a multi-node test."""
    metrics = Metrics()
    cache = ModelDiskCache(str(tmp_path / f"cache_{name}"), capacity_bytes=1 << 20)
    manager = CacheManager(DiskModelProvider(str(store)), cache, FakeRuntime())
    backend = LocalServingBackend(manager)
    rest = RestServingServer(backend, metrics, require_version=False)
    gsrv = GrpcServingServer(backend, metrics)
    rport = await rest.start(0, host="127.0.0.1")
    gport = await gsrv.start(0, host="127.0.0.1")
    try:
        yield NodeInfo("127.0.0.1", rport, gport), metrics, backend
    finally:
        backend.close()
        await rest.close()
        await gsrv.close()


def predict_request(name: str, x: float) -> sv.PredictRequest:
    req = sv.PredictRequest()
    req.model_spec.name = name
    req.model_spec.version.value = 1
    req.inputs["x"].dtype = 1
    req.inputs["x"].tensor_shape.dim.add(size=1)
    req.inputs["x"].float_val.append(x)
    return req


def span_names(d: dict) -> set[str]:
    names = {d["name"]}
    for c in d.get("children", ()):
        names |= span_names(c)
    return names


def hist_count(metrics: Metrics, protocol: str, verb: str, outcome: str, route: str):
    return metrics.registry.get_sample_value(
        "tpusc_request_duration_seconds_count",
        {"protocol": protocol, "verb": verb, "outcome": outcome, "route": route},
    )


# -- distributed stitching ---------------------------------------------------

async def test_two_node_stitched_trace_and_route_labels(tmp_path):
    """A request landing on the router but hash-owned by the peer yields ONE
    trace: router root -> route span -> the peer's grafted subtree, all under
    one trace id, with the SLO histogram labeled route=forwarded on the
    router and route=local on the serving peer."""
    store = tmp_path / "store"
    make_store(store, [(f"tenant{i}", 1) for i in range(16)])
    async with observed_node(tmp_path, "a", store) as (info_a, _metrics_a, backend_a):
        async with observed_node(tmp_path, "b", store) as (info_b, metrics_b, _):
            mock = DiscoveryServiceMock()
            cluster = ClusterConnection(mock, replicas_per_model=1)
            connect = asyncio.create_task(
                cluster.connect(info_a, lambda: True, wait_ready_s=2)
            )
            await asyncio.sleep(0.05)
            mock.push([info_a, info_b])
            await connect
            # router colocated with node A: A-owned keys short-circuit
            router_metrics = Metrics()
            routing = RoutingBackend(cluster, {info_a.ident: backend_a})
            router_rest = RestServingServer(routing, router_metrics, require_version=True)
            router_grpc = GrpcServingServer(routing, router_metrics)
            rr_port = await router_rest.start(0, host="127.0.0.1")
            rg_port = await router_grpc.start(0, host="127.0.0.1")
            try:
                owner = {
                    name: cluster.find_nodes_for_key(ModelId(name, 1).key)[0].ident
                    for name in (f"tenant{i}" for i in range(16))
                }
                name_b = next(n for n, o in owner.items() if o == info_b.ident)
                name_a = next(n for n, o in owner.items() if o == info_a.ident)

                TRACER.clear()
                async with aiohttp.ClientSession() as s:
                    url = f"http://127.0.0.1:{rr_port}/v1/models/{name_b}/versions/1:predict"
                    async with s.post(url, json={"instances": [1.0]}) as resp:
                        assert resp.status == 200
                        assert (await resp.json())["predictions"] == [1.0]

                    traces = TRACER.recent(10)
                    router_root = next(
                        d for d in traces
                        if d["name"] == "rest"
                        and any(c["name"] == "route" for c in d.get("children", ()))
                    )
                    peer_root = next(
                        d for d in traces if d["name"] == "rest" and d.get("parent_id")
                    )
                    assert router_root["attrs"]["route"] == "forwarded"
                    route_sp = next(
                        c for c in router_root["children"] if c["name"] == "route"
                    )
                    assert route_sp["attrs"]["peer"] == info_b.ident
                    grafted = next(c for c in route_sp["children"] if c.get("remote"))
                    # one trace id across both nodes; the graft IS the peer's root
                    tid = router_root["trace_id"]
                    assert peer_root["trace_id"] == tid
                    assert grafted["trace_id"] == tid
                    assert peer_root["parent_id"] == route_sp["span_id"]
                    assert grafted["span_id"] == peer_root["span_id"]
                    # the peer's cold-load work is visible from the router side
                    assert "ensure_servable" in span_names(grafted)

                    # the stitched trace is queryable by id through the API
                    async with s.get(
                        f"http://127.0.0.1:{rr_port}/monitoring/traces?trace_id={tid}"
                    ) as resp:
                        got = (await resp.json())["traces"]
                    assert {t["trace_id"] for t in got} == {tid} and len(got) == 2

                    # A-owned key: same router, local short-circuit
                    url = f"http://127.0.0.1:{rr_port}/v1/models/{name_a}/versions/1:predict"
                    async with s.post(url, json={"instances": [2.0]}) as resp:
                        assert resp.status == 200

                # SLO histogram: the router saw one forwarded and one local
                # request; the serving peer saw its hop as local
                assert hist_count(router_metrics, "rest", "predict", "ok", "forwarded") == 1
                assert hist_count(router_metrics, "rest", "predict", "ok", "local") == 1
                assert hist_count(metrics_b, "rest", "predict", "ok", "local") == 1

                # same stitch over the gRPC hop
                TRACER.clear()
                ch = make_channel(f"127.0.0.1:{rg_port}")
                stub = ServingStub(ch)
                resp = await stub.method(PREDICTION_SERVICE, "Predict")(
                    predict_request(name_b, 3.0)
                )
                assert codec.tensorproto_to_numpy(resp.outputs["y"]).tolist() == [3.0]
                await ch.close()
                traces = TRACER.recent(10)
                g_root = next(
                    d for d in traces
                    if d["name"] == "grpc"
                    and any(c["name"] == "route" for c in d.get("children", ()))
                )
                assert g_root["attrs"]["route"] == "forwarded"
                route_sp = next(c for c in g_root["children"] if c["name"] == "route")
                grafted = next(c for c in route_sp["children"] if c.get("remote"))
                assert grafted["trace_id"] == g_root["trace_id"]
                assert hist_count(router_metrics, "grpc", "predict", "ok", "forwarded") == 1
                assert hist_count(metrics_b, "grpc", "predict", "ok", "local") == 1

                # no request left behind in the in-flight gauges
                for m in (router_metrics, metrics_b):
                    for proto in ("rest", "grpc"):
                        assert m.registry.get_sample_value(
                            "tpusc_requests_in_flight", {"protocol": proto}
                        ) == 0
            finally:
                TRACER.clear()
                await routing.close()
                await router_rest.close()
                await router_grpc.close()
                await cluster.disconnect()


# -- REST/gRPC accounting parity ---------------------------------------------

async def test_rest_grpc_counter_parity(tmp_path):
    """The same traffic mix — one success, one unknown model, one garbage
    request — must move the request/failure counters and the SLO histogram
    identically for both protocols (gRPC's unknown-method fallback mirrors
    REST's unparseable-URL 404; health stays uncounted on both)."""
    store = tmp_path / "store"
    make_store(store, [("m", 1)])
    async with observed_node(tmp_path, "p", store) as (info, metrics, _):
        reg = metrics.registry
        async with aiohttp.ClientSession() as s:
            base = f"http://127.0.0.1:{info.rest_port}"
            async with s.post(
                f"{base}/v1/models/m/versions/1:predict", json={"instances": [1.0]}
            ) as resp:
                assert resp.status == 200
            async with s.post(
                f"{base}/v1/models/nope/versions/1:predict", json={"instances": [1.0]}
            ) as resp:
                assert resp.status == 404
            async with s.post(f"{base}/v1/bogus", data=b"{}") as resp:
                assert resp.status == 404
            async with s.get(f"{base}/healthz") as resp:  # uncounted
                assert resp.status == 200

        ch = make_channel(f"127.0.0.1:{info.grpc_port}")
        stub = ServingStub(ch)
        await stub.method(PREDICTION_SERVICE, "Predict")(predict_request("m", 1.0))
        with pytest.raises(grpc.aio.AioRpcError) as ei:
            await stub.method(PREDICTION_SERVICE, "Predict")(predict_request("nope", 1.0))
        assert ei.value.code() == grpc.StatusCode.NOT_FOUND
        bogus = ch.unary_unary(
            f"/{PREDICTION_SERVICE}/Bogus",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b,
        )
        with pytest.raises(grpc.aio.AioRpcError) as ei:
            await bogus(b"")
        assert ei.value.code() == grpc.StatusCode.UNIMPLEMENTED
        health = ch.unary_unary(  # uncounted
            "/grpc.health.v1.Health/Check",
            request_serializer=health_pb.HealthCheckRequest.SerializeToString,
            response_deserializer=health_pb.HealthCheckResponse.FromString,
        )
        await health(health_pb.HealthCheckRequest())
        await ch.close()

        for proto in ("rest", "grpc"):
            labels = {"protocol": proto}
            assert reg.get_sample_value("tfservingcache_proxy_requests_total", labels) == 3
            assert reg.get_sample_value("tfservingcache_proxy_failures_total", labels) == 2
            assert hist_count(metrics, proto, "predict", "ok", "local") == 1
            assert hist_count(metrics, proto, "predict", "error", "local") == 1
            assert hist_count(metrics, proto, "invalid", "error", "local") == 1
            assert reg.get_sample_value(
                "tpusc_requests_in_flight", {"protocol": proto}
            ) == 0


# -- slow-trace retention ----------------------------------------------------

def test_slow_trace_survives_ring_wrap():
    """capacity+1 fast requests wrap the main ring; the one >threshold trace
    must still be findable (the outlier you debug is exactly the one chatty
    traffic evicts first)."""
    tr = Tracer(capacity=4, slow_threshold_s=0.01, slow_capacity=8)
    with tr.span("slow"):
        time.sleep(0.02)
    for i in range(5):
        with tr.span(f"fast{i}"):
            pass
    merged = [d["name"] for d in tr.recent(50)]
    assert "slow" in merged
    assert "fast0" not in merged  # genuinely wrapped out of the main ring
    assert [d["name"] for d in tr.query(min_duration_s=0.01)] == ["slow"]


async def test_monitoring_traces_min_ms_and_trace_id_params():
    TRACER.clear()
    prior = TRACER.slow_threshold_s
    TRACER.configure(slow_threshold_s=0.005)
    try:
        with TRACER.span("slowreq"):
            time.sleep(0.01)
        with TRACER.span("fastreq") as sp:
            fast_tid = sp.trace_id
        rest = RestServingServer(None, require_version=True)
        port = await rest.start(0, host="127.0.0.1")
        try:
            async with aiohttp.ClientSession() as s:
                base = f"http://127.0.0.1:{port}/monitoring/traces"
                async with s.get(f"{base}?min_ms=8") as resp:
                    names = [t["name"] for t in (await resp.json())["traces"]]
                assert names == ["slowreq"]
                async with s.get(f"{base}?trace_id={fast_tid}") as resp:
                    names = [t["name"] for t in (await resp.json())["traces"]]
                assert names == ["fastreq"]
                async with s.get(f"{base}?min_ms=nope") as resp:
                    assert resp.status == 400
        finally:
            await rest.close()
    finally:
        TRACER.configure(slow_threshold_s=prior)
        TRACER.clear()


# -- trace-correlated JSON logs ----------------------------------------------

def test_json_logs_carry_trace_ids_and_extras():
    logger = logging.getLogger("tpusc.test_observability")
    logger.setLevel(logging.INFO)
    logger.propagate = False
    stream = io.StringIO()
    handler = logging.StreamHandler(stream)
    handler.setFormatter(JsonFormatter())
    logger.addHandler(handler)
    try:
        tr = Tracer()
        with tr.span("req") as sp:
            logger.info("inside", extra={"model": "m:1", "attempt": 2})
            tid, sid = sp.trace_id, sp.span_id
        logger.info("outside")
    finally:
        logger.removeHandler(handler)
    inside, outside = [json.loads(l) for l in stream.getvalue().splitlines()]
    assert inside["trace_id"] == tid and inside["span"] == sid
    assert inside["model"] == "m:1" and inside["attempt"] == 2
    assert inside["msg"] == "inside" and inside["level"] == "info"
    # outside a request: fields ABSENT, not empty strings
    assert "trace_id" not in outside and "span" not in outside


# -- wire-format units -------------------------------------------------------

def test_traceparent_parse_format_roundtrip():
    assert parse_traceparent(None) is None
    assert parse_traceparent("") is None
    assert parse_traceparent("not-a-traceparent") is None
    assert parse_traceparent("00-" + "0" * 32 + "-" + "1" * 16 + "-01") is None
    assert parse_traceparent("00-" + "a" * 32 + "-" + "0" * 16 + "-01") is None
    hdr = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    assert parse_traceparent(hdr.upper()) == ("ab" * 16, "cd" * 8)

    assert format_traceparent() is None  # no open span -> omit the header
    tr = Tracer()
    with remote_parent(("ab" * 16, "cd" * 8)):
        with tr.span("adopted") as sp:
            assert sp.trace_id == "ab" * 16
            assert sp.parent_id == "cd" * 8
            assert parse_traceparent(format_traceparent()) == (sp.trace_id, sp.span_id)
    # adoption is consumed with the context, not sticky
    with tr.span("fresh") as sp:
        assert sp.trace_id != "ab" * 16 and sp.parent_id == ""


def test_serialize_span_degrades_within_wire_limit():
    root = Span(name="root", attrs={"blob": "x" * 32768}, start_s=1.0, duration_s=2.0,
                trace_id="ab" * 16, span_id="cd" * 8)
    for i in range(300):
        root.children.append(
            Span(name=f"c{i}", attrs={"pad": f"{i}" * 40}, start_s=1.0,
                 span_id=f"{i:016x}")
        )
    blob = serialize_span(root)
    assert len(blob) <= WIRE_TRACE_LIMIT
    back = deserialize_span(blob)
    assert back is not None and back.name == "root" and back.trace_id == "ab" * 16

    small = Span(name="s", attrs={"k": "v"}, start_s=1.0, duration_s=0.5,
                 trace_id="22" * 16, span_id="11" * 8)
    rt = deserialize_span(serialize_span(small))
    assert rt.attrs == {"k": "v"} and rt.trace_id == "22" * 16 and rt.span_id == "11" * 8
    assert deserialize_span("!!not-base64!!") is None
    assert deserialize_span("") is None


# -- gauges ------------------------------------------------------------------

def test_batcher_queue_depth_gauge_balances_to_zero():
    m = Metrics()
    rt = FakeRuntime()
    mid = ModelId("m", 1)
    rt.ensure_loaded(Model(identifier=mid, path="/nowhere"))
    b = MicroBatcher(rt, max_batch=4, metrics=m)
    xs = [np.array([float(i)], np.float32) for i in range(8)]
    with ThreadPoolExecutor(8) as ex:
        outs = list(ex.map(lambda x: b.predict(mid, {"x": x}), xs))
    for x, out in zip(xs, outs):
        assert out["y"].tolist() == x.tolist()
    assert m.registry.get_sample_value(
        "tpusc_batcher_queue_depth", {"kind": "predict"}
    ) == 0


# -- profiler endpoint -------------------------------------------------------

@asynccontextmanager
async def profiler_node(tmp_path, monkeypatch):
    """Minimal REST server for /monitoring/profiler tests: the endpoint
    never touches the backend, so the FakeRuntime node from make_store is
    more than enough."""
    import os

    monkeypatch.setenv("TPUSC_PROFILER_DIR", str(tmp_path / "profiles"))
    store = tmp_path / "store"
    make_store(store, [("m", 1)])
    async with observed_node(tmp_path, "p", store) as (info, metrics, _backend):
        yield info, metrics


async def test_profiler_invalid_duration_is_400(tmp_path, monkeypatch):
    async with profiler_node(tmp_path, monkeypatch) as (info, _):
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{info.rest_port}/monitoring/profiler"
                "?duration_s=nope"
            ) as resp:
                assert resp.status == 400
                assert "duration_s" in (await resp.json())["error"]


async def test_profiler_rejects_concurrent_capture(tmp_path, monkeypatch):
    """One capture at a time: the JAX profiler is a process-wide global, so
    a second start_trace would corrupt the first. The server serializes on
    _profile_lock — hold it and the endpoint must 409 without touching the
    profiler at all."""
    store = tmp_path / "store2"
    make_store(store, [("m", 1)])
    cache = ModelDiskCache(str(tmp_path / "cache_prof"), capacity_bytes=1 << 20)
    backend = LocalServingBackend(
        CacheManager(DiskModelProvider(str(store)), cache, FakeRuntime())
    )
    rest = RestServingServer(backend, Metrics(), require_version=False)
    port = await rest.start(0, host="127.0.0.1")
    try:
        assert rest._profile_lock.acquire(blocking=False)
        try:
            async with aiohttp.ClientSession() as s:
                async with s.post(
                    f"http://127.0.0.1:{port}/monitoring/profiler?duration_s=0.01"
                ) as resp:
                    assert resp.status == 409
                    assert "in progress" in (await resp.json())["error"]
        finally:
            rest._profile_lock.release()
    finally:
        await rest.close()


async def test_profiler_creates_trace_dir_under_env_base(tmp_path, monkeypatch):
    """A successful capture lands under $TPUSC_PROFILER_DIR/<label>/ (the
    label is sandboxed to a simple name — the server, not the client, picks
    the base dir)."""
    import os

    async with profiler_node(tmp_path, monkeypatch) as (info, _):
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{info.rest_port}/monitoring/profiler"
                "?duration_s=0.05&label=smoke"
            ) as resp:
                body = await resp.json()
                assert resp.status == 200, body
                assert body["dir"] == str(tmp_path / "profiles" / "smoke")
        assert os.path.isdir(tmp_path / "profiles" / "smoke")
        # bad label never escapes the base dir
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{info.rest_port}/monitoring/profiler"
                "?duration_s=0.01&label=../escape"
            ) as resp:
                assert resp.status == 400


# -- scrape_and_merge degradation --------------------------------------------

async def test_scrape_and_merge_counts_dropped_targets(caplog):
    """A down sidecar degrades the merge, not the scrape — but the drop is
    counted (tpusc_scrape_errors_total) and logged at warning, never
    silent."""
    from tfservingcache_tpu.utils.metrics import scrape_and_merge

    m = Metrics()
    own = m.render()
    with caplog.at_level(logging.WARNING, logger="tpusc.metrics"):
        merged = await scrape_and_merge(
            own,
            ["http://127.0.0.1:1/metrics", "http://127.0.0.1:2/metrics"],
            timeout_s=0.5,
            metrics=m,
        )
    # both targets dropped; own exposition survives intact
    assert merged.startswith(own.rstrip(b"\n"))
    assert m.registry.get_sample_value("tpusc_scrape_errors_total") == 2
    assert any("scrape" in r.message for r in caplog.records)


# -- metric-name stability ---------------------------------------------------

# The exposition surface is an API: renames break every dashboard and alert
# pointed at this server. Additions belong here too — deliberately.
EXPECTED_METRIC_FAMILIES = {
    "tfservingcache_cache",
    "tfservingcache_cache_duration_seconds",
    "tfservingcache_cache_fetch_duration_seconds",
    "tfservingcache_cache_hits",
    "tfservingcache_cache_misses",
    "tfservingcache_proxy_failures",
    "tfservingcache_proxy_requests",
    "tpusc_assignment_warms",
    "tpusc_batcher_queue_depth",
    "tpusc_coalesced_batches",
    "tpusc_coalesced_requests",
    "tpusc_cold_overlap_ratio",
    "tpusc_cold_stage_seconds",
    "tpusc_device_bytes",
    "tpusc_disk_cache_bytes_in_use",
    "tpusc_evictions",
    "tpusc_gen_admission_wait_seconds",
    "tpusc_gen_chunks",
    "tpusc_gen_kv_arena_bytes",
    "tpusc_lane_state_bytes",
    "tpusc_kv_arena_bytes",
    "tpusc_gen_window_rows_dropped",
    "tpusc_prefill_rows",
    "tpusc_gen_kv_page_waste_tokens",
    "tpusc_gen_kv_pages_shared",
    "tpusc_gen_kv_pages_total",
    "tpusc_gen_kv_pages_used",
    "tpusc_gen_kv_pages_used_peak",
    "tpusc_gen_kv_write_steps",
    "tpusc_gen_preemptions",
    "tpusc_gen_prefill_chunks",
    "tpusc_moe_assignments",
    "tpusc_moe_expert_rows",
    "tpusc_gen_prefix_hits",
    "tpusc_gen_oldest_queued_age_seconds",
    "tpusc_gen_sample_steps",
    "tpusc_gen_stream_frames",
    "tpusc_gen_slots_active",
    "tpusc_gen_wasted_steps",
    "tpusc_group_healthy",
    "tpusc_group_reform_events",
    "tpusc_kv_parked_bytes",
    "tpusc_kv_parked_conversations",
    "tpusc_kv_resume",
    "tpusc_hbm_bytes_in_use",
    "tpusc_hbm_bytes_peak",
    "tpusc_host_tier_bytes",
    "tpusc_host_tier_bytes_peak",
    "tpusc_fleet_model_replicas",
    "tpusc_flight_dumps",
    "tpusc_model_replicas_target",
    "tpusc_models_resident",
    "tpusc_peer_fetch_bytes",
    "tpusc_peer_health_score",
    "tpusc_peer_status_age_seconds",
    "tpusc_pool_wait_seconds",
    "tpusc_reload_source",
    "tpusc_prefix_cache_bytes",
    "tpusc_program_build_seconds",
    "tpusc_program_builds",
    "tpusc_prefix_cache_hits",
    "tpusc_prefix_cache_misses",
    "tpusc_request_duration_seconds",
    "tpusc_request_phase_seconds",
    "tpusc_requests_in_flight",
    "tpusc_requests_recovered",
    "tpusc_fault_injected",
    "tpusc_scrape_errors",
    "tpusc_spec_accepted_tokens",
    "tpusc_spec_draft_autodisabled",
    "tpusc_spec_rounds",
    "tpusc_spec_tokens_per_round",
    "tpusc_tenant_byte_seconds",
    "tpusc_tenant_cold_load_seconds",
    "tpusc_tenant_dominant_share",
    "tpusc_tenant_kv_page_seconds",
    "tpusc_tenant_peer_bytes_served",
    "tpusc_tenant_step_seconds",
    "tpusc_tenant_tokens",
}


def test_metric_family_names_are_stable():
    assert {f.name for f in Metrics().registry.collect()} == EXPECTED_METRIC_FAMILIES


def test_metric_families_match_observability_doc():
    """Docs-sync lint: every family registered in utils/metrics.py appears
    in OBSERVABILITY.md's family table, and the table lists nothing that
    isn't registered — the reference doc cannot silently rot. Counters are
    documented with the ``_total`` suffix prometheus_client appends at
    exposition, so the registry names are mapped the same way."""
    doc = os.path.join(os.path.dirname(__file__), "..", "OBSERVABILITY.md")
    with open(doc) as fh:
        documented = {
            m.group(1)
            for m in re.finditer(
                r"^\| `((?:tpusc|tfservingcache)_[a-z0-9_]+)` \|",
                fh.read(), re.MULTILINE,
            )
        }
    registered = {
        f.name + "_total" if f.type == "counter" else f.name
        for f in Metrics().registry.collect()
    }
    missing_from_doc = registered - documented
    stale_in_doc = documented - registered
    assert not missing_from_doc, (
        f"families registered but absent from OBSERVABILITY.md: "
        f"{sorted(missing_from_doc)}"
    )
    assert not stale_in_doc, (
        f"families documented but not registered: {sorted(stale_in_doc)}"
    )


# -- overhead budget ---------------------------------------------------------

def test_tracer_overhead_per_span_budget():
    """Always-on tracing must stay negligible next to even a warm ~1 ms
    inference: < 25 us median per completed span (batch-of-1000 medians to
    ride out CI scheduler noise)."""
    tr = Tracer(capacity=64)
    for _ in range(1000):  # warm allocator and code paths
        with tr.span("warm"):
            pass
    per_span = []
    for _ in range(10):
        t0 = time.perf_counter()
        for _ in range(1000):
            with tr.span("op"):
                pass
        per_span.append((time.perf_counter() - t0) / 1000)
    assert statistics.median(per_span) < 25e-6, per_span

def test_model_label_cardinality_cap():
    """max_model_labels bounds per-model series cardinality: once the cap's
    worth of distinct name:version values exist, NEW tenants fold into the
    __other__ bucket while every already-seen label keeps resolving to
    itself (a churning tenant population cannot explode the registry)."""
    from tfservingcache_tpu.utils.metrics import ALL_MODELS, OTHER_MODELS

    m = Metrics(model_labels=True, max_model_labels=3)
    assert m.model_label("a", 1) == "a:1"
    assert m.model_label("b", 1) == "b:1"
    assert m.model_label("c", 2) == "c:2"
    # cap reached: overflow tenants share one bucket ...
    assert m.model_label("d", 1) == OTHER_MODELS
    assert m.model_label("e", 9) == OTHER_MODELS
    # ... and existing labels still resolve (overflow never evicts)
    assert m.model_label("a", 1) == "a:1"
    assert m.model_label("c", 2) == "c:2"
    # the per-tenant publish path lands overflow on the bucket series
    m.tenant_kv_page_seconds.labels(m.model_label("d", 1)).inc(2.5)
    assert m.registry.get_sample_value(
        "tpusc_tenant_kv_page_seconds_total", {"model": OTHER_MODELS}
    ) == 2.5
    # model_labels off: everything folds to all_models, cap irrelevant
    assert Metrics().model_label("a", 1) == ALL_MODELS
