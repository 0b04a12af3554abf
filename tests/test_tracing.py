"""Per-stage tracing (greenfield — SURVEY.md §5: the reference has none)."""

from __future__ import annotations

import json
import threading

import aiohttp

from tfservingcache_tpu.utils.tracing import TRACER, Tracer


def test_span_nesting_and_ring_buffer():
    t = Tracer(capacity=3)
    with t.span("root", model="m:1"):
        with t.span("fetch"):
            pass
        with t.span("infer"):
            pass
    traces = t.recent()
    assert len(traces) == 1
    root = traces[0]
    assert root["name"] == "root" and root["attrs"] == {"model": "m:1"}
    assert [c["name"] for c in root["children"]] == ["fetch", "infer"]
    assert all(c["duration_s"] >= 0 for c in root["children"])
    for i in range(5):
        with t.span(f"r{i}"):
            pass
    assert len(t.recent()) == 3  # capacity bounds the buffer
    assert t.recent()[0]["name"] == "r4"  # most recent first


def test_span_error_recorded():
    t = Tracer()
    try:
        with t.span("boom"):
            raise ValueError("busted")
    except ValueError:
        pass
    assert t.recent()[0]["error"] == "ValueError: busted"


def test_annotate_attaches_to_open_span():
    t = Tracer()
    with t.span("load"):
        t.annotate(hbm_bytes=42)
    assert t.recent()[0]["attrs"]["hbm_bytes"] == 42


def test_cross_thread_spans_join_via_copy_context():
    """The serving pool runs JAX work in threads; copy_context (as
    LocalServingBackend._run does) must parent those spans correctly."""
    import contextvars

    t = Tracer()
    with t.span("request"):
        ctx = contextvars.copy_context()

        def work():
            with t.span("thread_stage"):
                pass

        th = threading.Thread(target=lambda: ctx.run(work))
        th.start()
        th.join()
    root = t.recent()[0]
    assert [c["name"] for c in root["children"]] == ["thread_stage"]


async def test_e2e_trace_through_rest(tmp_path):
    """One REST predict produces one root trace with ensure/fetch/load/infer
    stages under it, visible on /monitoring/traces."""
    from tfservingcache_tpu.cache.disk_cache import ModelDiskCache
    from tfservingcache_tpu.cache.manager import CacheManager
    from tfservingcache_tpu.cache.providers.disk import DiskModelProvider
    from tfservingcache_tpu.config import ServingConfig
    from tfservingcache_tpu.models.registry import export_artifact
    from tfservingcache_tpu.protocol.local_backend import LocalServingBackend
    from tfservingcache_tpu.protocol.rest import RestServingServer
    from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime

    TRACER.clear()
    export_artifact("half_plus_two", str(tmp_path / "store"), name="hpt", version=1)
    manager = CacheManager(
        DiskModelProvider(str(tmp_path / "store")),
        ModelDiskCache(str(tmp_path / "cache"), 1 << 30),
        TPUModelRuntime(ServingConfig(platform="cpu")),
    )
    backend = LocalServingBackend(manager)
    rest = RestServingServer(backend, require_version=False)
    port = await rest.start(0)
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{port}/v1/models/hpt/versions/1:predict",
                data=json.dumps({"instances": [1.0, 3.0]}),
            ) as resp:
                assert resp.status == 200, await resp.text()
            async with s.get(f"http://127.0.0.1:{port}/monitoring/traces") as resp:
                traces = (await resp.json())["traces"]
    finally:
        await rest.close()
        backend.close()
        manager.close()

    rest_roots = [t for t in traces if t["name"] == "rest"]
    assert rest_roots, traces
    flat = json.dumps(rest_roots)
    for stage in ("ensure_servable", "provider_fetch", "load", "infer"):
        assert stage in flat, f"missing stage {stage}: {flat[:500]}"
    # cold-path sanity: the fetch+load happened inside the rest request span
    names = {c["name"] for c in rest_roots[-1].get("children", [])}
    assert "ensure_servable" in names or "infer" in names


# -- ISSUE 23: streamed roots, the serving pool's wait, host_span -------------

TINY_LM = {
    "vocab_size": 97, "d_model": 48, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 2, "d_ff": 96, "max_seq": 64,
}


def _lm_backend(tmp_path, metrics=None, **kw):
    from tfservingcache_tpu.cache.disk_cache import ModelDiskCache
    from tfservingcache_tpu.cache.manager import CacheManager
    from tfservingcache_tpu.cache.providers.disk import DiskModelProvider
    from tfservingcache_tpu.config import ServingConfig
    from tfservingcache_tpu.models.registry import export_artifact
    from tfservingcache_tpu.protocol.local_backend import LocalServingBackend
    from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime

    export_artifact("transformer_lm", str(tmp_path / "store"), name="lm",
                    version=1, config=TINY_LM)
    manager = CacheManager(
        DiskModelProvider(str(tmp_path / "store")),
        ModelDiskCache(str(tmp_path / "cache"), 1 << 30),
        TPUModelRuntime(ServingConfig(platform="cpu"), metrics),
        metrics,
    )
    return LocalServingBackend(manager, **kw), manager


def _find(span: dict, name: str) -> list[dict]:
    hits = [span] if span["name"] == name else []
    for c in span.get("children", ()):
        hits += _find(c, name)
    return hits


async def test_streamed_generate_is_one_root_as_long_as_the_stream(tmp_path):
    """A streamed ``:generate`` through the REST app is ONE ``rest`` root:
    it stays open until the last frame, the stream's pool job runs in its
    context (``pool_wait``, ``ensure_servable``/``load`` and the engine's
    ``phase_*``/``ttft_ms`` attrs sit on it), and slow retention keeps it."""
    import time

    from tfservingcache_tpu.protocol.rest import RestServingServer

    backend, manager = _lm_backend(
        tmp_path, generate_slots=2,
        generate_chunk_tokens=2,
    )
    rest = RestServingServer(backend, require_version=False)
    port = await rest.start(0, host="127.0.0.1")
    old_threshold = TRACER.slow_threshold_s
    TRACER.clear()
    TRACER.configure(slow_threshold_s=1e-3)
    try:
        url = f"http://127.0.0.1:{port}"
        async with aiohttp.ClientSession() as s:
            t0 = time.monotonic()
            async with s.post(
                f"{url}/v1/models/lm:generate", params={"stream": "true"},
                json={"input_ids": [list(range(1, 13))], "max_new_tokens": 8},
            ) as r:
                assert r.status == 200
                t_headers = time.monotonic() - t0
                raw = await r.read()
            streamed_s = time.monotonic() - t0
            assert raw.count(b'"token"') == 8 and b'"done": true' in raw
            # fast traffic wraps the main ring; the slow tier keeps the stream
            TRACER.configure(capacity=1)
            async with s.get(f"{url}/v1/models/lm") as r:
                assert r.status == 200
            async with s.get(f"{url}/monitoring/traces?min_ms=1") as r:
                traces = (await r.json())["traces"]
    finally:
        TRACER.configure(capacity=256, slow_threshold_s=old_threshold)
        await rest.close()
        backend.close()
        manager.close()
    roots = [t for t in traces
             if t["name"] == "rest" and ":generate" in t["attrs"]["path"]]
    assert len(roots) == 1, [t["name"] for t in traces]
    root = roots[0]
    # the root covers the drain: longer than the time to the status line
    # (a cold load), no longer than what the client saw
    assert t_headers < root["duration_s"] <= streamed_s + 0.05
    assert _find(root, "pool_wait"), root
    assert {s["attrs"]["what"] for s in _find(root, "pool_wait")} == {
        "codec", "generate"}
    assert _find(root, "ensure_servable") and _find(root, "load")
    attrs = root["attrs"]
    for key in ("phase_queue_ms", "phase_prefill_ms", "phase_decode_ms",
                "ttft_ms", "priority", "model"):
        assert key in attrs, attrs
    # the cold load is not an orphan root of its own
    assert not [t for t in traces if t["name"] in ("load", "ensure_servable")]


async def test_pool_of_one_makes_the_second_job_wait(tmp_path):
    """With one worker the second job's ``pool_wait`` is at least the first
    job's run time, and ``tpusc_pool_wait_seconds`` holds two observations."""
    import asyncio
    import time

    from tfservingcache_tpu.utils.metrics import Metrics

    metrics = Metrics()
    backend, manager = _lm_backend(tmp_path, metrics=metrics, max_workers=1)
    TRACER.clear()
    try:
        def slow():
            time.sleep(0.15)
            return "a"

        with TRACER.span("request") as root:
            first = asyncio.ensure_future(backend._run(slow, what="predict"))
            await asyncio.sleep(0.01)     # the first job holds the one thread
            second = asyncio.ensure_future(
                backend._run(lambda: "b", what="predict"))
            assert await first == "a" and await second == "b"
    finally:
        backend.close()
        manager.close()
    waits = [c for c in root.children if c.name == "pool_wait"]
    assert len(waits) == 2
    assert waits[0].duration_s < 0.05
    assert waits[1].duration_s >= 0.13, waits[1].duration_s
    count = metrics.registry.get_sample_value(
        "tpusc_pool_wait_seconds_count", {"what": "predict"})
    total = metrics.registry.get_sample_value(
        "tpusc_pool_wait_seconds_sum", {"what": "predict"})
    assert count == 2 and total >= 0.13


def test_host_span_costs_under_5us_with_no_capture():
    """``host_span`` is a TraceMe that finds no capture running: it must
    stay far under a span's own cost, and write nothing to the ring."""
    import statistics
    import time

    from tfservingcache_tpu.utils.tracing import host_span

    TRACER.clear()
    for _ in range(1000):
        with host_span("warm"):
            pass
    per = []
    for _ in range(10):
        t0 = time.perf_counter()
        for _ in range(1000):
            with host_span("boundary"):
                pass
        per.append((time.perf_counter() - t0) / 1000)
    assert statistics.median(per) < 5e-6, per
    assert TRACER.recent() == []
