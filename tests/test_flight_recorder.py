"""Engine flight recorder (utils/flight_recorder.py): ring semantics, the
< 50 us/step recording budget, the /monitoring/engine surface on a live
two-model workload, anomaly-dump triggers (SLO breach dedup, spool
bounding), phase-attribution reconciliation, and the engine_dump tool."""

import asyncio
import importlib.util
import json
import os
import statistics
import time

import aiohttp
import numpy as np
import pytest

from tfservingcache_tpu.cache.disk_cache import ModelDiskCache
from tfservingcache_tpu.cache.manager import CacheManager
from tfservingcache_tpu.cache.providers.disk import DiskModelProvider
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import export_artifact
from tfservingcache_tpu.protocol.local_backend import LocalServingBackend
from tfservingcache_tpu.protocol.rest import RestServingServer
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId
from tfservingcache_tpu.utils.flight_recorder import (
    RECORDER,
    STEP_FIELDS,
    FlightRecorder,
    _Ring,
)
from tfservingcache_tpu.utils.metrics import Metrics
from tfservingcache_tpu.utils.tracing import TRACER
from tests.test_continuous_batching import _StubRuntime

TINY = {
    "vocab_size": 97,
    "d_model": 48,
    "n_layers": 2,
    "n_heads": 4,
    "n_kv_heads": 2,
    "d_ff": 96,
    "max_seq": 64,
}


def _load(tmp_path, name="lm", config=TINY, metrics=None, **serving_kw):
    export_artifact("transformer_lm", str(tmp_path), name=name, version=1, config=config)
    rt = TPUModelRuntime(ServingConfig(platform="cpu", **serving_kw), metrics)
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


@pytest.fixture(autouse=True)
def _clean_recorder():
    """The recorder is process-global (like TRACER): every test starts from
    empty rings and disarmed dumps, and leaves them that way."""
    RECORDER.clear()
    RECORDER.configure(flight_dir="")
    yield
    RECORDER.clear()
    RECORDER.configure(flight_dir="")
    RECORDER.metrics = None


# -- ring semantics -----------------------------------------------------------

def test_ring_wraps_and_tail_is_oldest_first():
    r = _Ring(8)
    for i in range(20):
        r.append((i,))
    assert r.written == 20
    assert [e[0] for e in r.tail(5)] == [15, 16, 17, 18, 19]
    # a tail larger than the ring is clamped to what survived the wrap
    assert [e[0] for e in r.tail(100)] == list(range(12, 20))
    # before the wrap, only what was written comes back
    r2 = _Ring(8)
    r2.append(("only",))
    assert r2.tail(100) == [("only",)]


def test_snapshot_window_goodput():
    fr = FlightRecorder(ring_entries=64)
    # 4 lanes x 8-step chunks, 8 wasted of 64 computed step-slots
    fr.record("m@1", "continuous", step_ms=2.0, chunk=8, active=4,
              admitted=1, retired=1, wasted=4, queue_depth=2,
              oldest_wait_ms=7.5)
    fr.record("m@1", "continuous", step_ms=2.0, chunk=8, active=4,
              admitted=0, retired=2, wasted=4)
    snap = fr.snapshot(tail=16)
    win = snap["models"]["m@1"]["window"]
    assert win["step_slots"] == 64
    assert win["wasted_steps"] == 8
    assert win["goodput"] == pytest.approx((64 - 8) / 64)
    assert win["max_queue_depth"] == 2
    assert win["max_oldest_wait_ms"] == 7.5
    step = snap["models"]["m@1"]["steps"][0]
    assert set(step) == set(STEP_FIELDS)


def test_watermarks_reset_on_scrape():
    fr = FlightRecorder()
    assert fr.observe_watermark("hbm", 100.0) == 100.0
    assert fr.observe_watermark("hbm", 40.0) == 100.0  # peak holds
    assert fr.watermarks(reset=True) == {"hbm": 100.0}
    assert fr.watermarks() == {}                        # consumed
    assert fr.observe_watermark("hbm", 40.0) == 40.0    # re-arms


# -- overhead budget ----------------------------------------------------------

def test_record_overhead_under_50us():
    """The ring is always on: one record per dispatched chunk must stay
    invisible next to even a stub decode step (< 50 us median, batch-of-1000
    medians to ride out CI scheduler noise — the tracer guard's shape)."""
    fr = FlightRecorder()
    for _ in range(1000):  # warm allocator and code paths
        fr.record("warm@1", "continuous", 1.0, 8, 4, 0, 0)
    per_rec = []
    for _ in range(10):
        t0 = time.perf_counter()
        for _ in range(1000):
            fr.record("m@1", "continuous", step_ms=1.0, chunk=8, active=4,
                      admitted=1, retired=1, pages_used=3, pages_free=5,
                      wasted=2, queue_depth=1, oldest_wait_ms=2.0)
        per_rec.append((time.perf_counter() - t0) / 1000)
    assert statistics.median(per_rec) < 50e-6, per_rec


def test_stub_engine_records_every_chunk_within_budget():
    """With the ring enabled by default (no opt-in anywhere), the stub
    engine must both populate the per-model ring AND hold the existing
    < 1 ms/chunk host budget — recording rides inside it."""
    slots = 8
    eng = ContinuousGenerateEngine(_StubRuntime(slots), slots=slots, chunk_tokens=8)
    try:
        mid = ModelId("stub", 1)
        ids = np.ones((64, 4), np.int32)
        t0 = time.perf_counter()
        out = eng.generate(mid, ids, max_new_tokens=16)
        elapsed = time.perf_counter() - t0
        assert out.shape == (64, 16)
        assert eng.chunks > 0
        assert elapsed / eng.chunks < 1e-3
    finally:
        eng.close()
    snap = RECORDER.snapshot(tail=RECORDER.ring_entries)
    data = snap["models"]["stub@1"]
    # every dispatched chunk left a ring entry (prefill-only boundaries may
    # add more, never fewer)
    dispatched = [s for s in data["steps"] if s["chunk"] > 0]
    assert len(dispatched) == eng.chunks
    assert data["window"]["goodput"] <= 1.0
    # phase clocks observed for the request rows
    assert snap["phases"]["stub@1"]


# -- /monitoring/engine on a live two-model workload --------------------------

async def test_monitoring_engine_two_model_workload(tmp_path):
    store = tmp_path / "store"
    for name in ("alpha", "beta"):
        export_artifact("transformer_lm", str(store), name=name, version=1, config=TINY)
    metrics = Metrics()
    runtime = TPUModelRuntime(ServingConfig(platform="cpu"), metrics)
    manager = CacheManager(
        DiskModelProvider(str(store)),
        ModelDiskCache(str(tmp_path / "cache"), capacity_bytes=1 << 30),
        runtime, metrics,
    )
    backend = LocalServingBackend(manager)
    rest = RestServingServer(backend, metrics, require_version=False)
    rport = await rest.start(0, host="127.0.0.1")
    try:
        async with aiohttp.ClientSession() as s:
            for name in ("alpha", "beta"):
                async with s.post(
                    f"http://127.0.0.1:{rport}/v1/models/{name}:generate",
                    json={"input_ids": [[3, 5, 7], [2, 4, 6]],
                          "max_new_tokens": 6},
                ) as r:
                    assert r.status == 200, await r.text()
                    assert len((await r.json())["tokens"]) == 2
            # peek (reset=0), then consume, then confirm consumed
            async with s.get(
                f"http://127.0.0.1:{rport}/monitoring/engine?reset=0"
            ) as r:
                assert r.status == 200
                snap = await r.json()
            for key in ("alpha@1", "beta@1"):
                data = snap["models"][key]
                assert data["recorded_steps"] > 0
                assert 0.0 < data["window"]["goodput"] <= 1.0
                assert data["steps"], key
                assert snap["phases"][key]
            assert any(k.startswith("hbm_bytes") for k in snap["watermarks"])
            assert "dumps" in snap
            async with s.get(
                f"http://127.0.0.1:{rport}/monitoring/engine"
            ) as r:
                assert (await r.json())["watermarks"]  # consumed this scrape
            async with s.get(
                f"http://127.0.0.1:{rport}/monitoring/engine?reset=0"
            ) as r:
                assert (await r.json())["watermarks"] == {}
            async with s.get(
                f"http://127.0.0.1:{rport}/monitoring/engine?n=bogus"
            ) as r:
                assert r.status == 400
        # per-request phase attribution flowed into the histogram
        for phase in ("queue", "prefill", "decode", "respond"):
            assert metrics.registry.get_sample_value(
                "tpusc_request_phase_seconds_count",
                {"phase": phase, "engine": "continuous"},
            ) >= 4, phase
    finally:
        backend.close()
        await rest.close()
        manager.close()


def test_snapshot_model_filter_and_engine_stats():
    """?model= backing: snapshot(model=...) restricts both the ring and the
    phase sections to one tenant (unknown -> empty, not an error); and the
    status plane's engine_stats() aggregate matches the rings."""
    fr = FlightRecorder()
    fr.record("alpha@1", "continuous", step_ms=1.0, chunk=8, active=4,
              admitted=1, retired=0, wasted=4, queue_depth=2,
              oldest_wait_ms=12.5)
    fr.record("beta@1", "continuous", step_ms=1.0, chunk=8, active=2,
              admitted=1, retired=1, wasted=0, queue_depth=1,
              oldest_wait_ms=40.0)
    fr.note_phases("alpha@1", "continuous", {"decode": 0.01})
    fr.note_phases("beta@1", "continuous", {"decode": 0.02})
    snap = fr.snapshot(model="alpha@1")
    assert set(snap["models"]) == {"alpha@1"}
    assert set(snap["phases"]) == {"alpha@1"}
    assert fr.snapshot(model="nope@9")["models"] == {}
    assert set(fr.snapshot()["models"]) == {"alpha@1", "beta@1"}
    stats = fr.engine_stats()
    assert stats["queue_depth"] == 3               # summed current depths
    assert stats["oldest_wait_ms"] == 40.0         # worst current wait
    # goodput over both rings: 48 step-slots computed, 4 wasted
    assert stats["goodput"] == pytest.approx((48 - 4) / 48)
    assert FlightRecorder().engine_stats() == {
        "goodput": 1.0, "queue_depth": 0, "oldest_wait_ms": 0.0,
        "spec_acceptance": 0.0,
    }


async def test_monitoring_engine_model_query_filter(tmp_path):
    """The REST surface of the filter: ?model=name@version returns only
    that tenant's sections and peeking stays non-destructive."""
    for name in ("alpha", "beta"):
        RECORDER.record(f"{name}@1", "continuous", step_ms=1.0, chunk=4,
                        active=1, admitted=1, retired=1)
    rest = RestServingServer(None, require_version=False)
    rport = await rest.start(0, host="127.0.0.1")
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(
                f"http://127.0.0.1:{rport}/monitoring/engine"
                "?model=alpha@1&reset=0"
            ) as r:
                assert r.status == 200
                snap = await r.json()
            assert set(snap["models"]) == {"alpha@1"}
            async with s.get(
                f"http://127.0.0.1:{rport}/monitoring/engine?reset=0"
            ) as r:
                assert set((await r.json())["models"]) == {"alpha@1", "beta@1"}
    finally:
        await rest.close()


def test_oldest_queued_age_gauge_returns_to_zero_after_drain():
    """Regression (stale-gauge lie): while rows overflow the slot count the
    oldest-queued-age gauge must have risen, and once the queue drains it
    must read 0 — not hold the last nonzero age through an idle period."""
    metrics = Metrics()
    slots = 2
    eng = ContinuousGenerateEngine(_StubRuntime(slots), slots=slots,
                                   chunk_tokens=4, metrics=metrics)
    try:
        # 16 rows through 2 slots: most of them wait in the admission queue
        out = eng.generate(ModelId("q", 1), np.ones((16, 3), np.int32),
                           max_new_tokens=8)
        assert out.shape == (16, 8)
    finally:
        eng.close()
    # the queue existed (some step recorded a positive oldest wait) ...
    steps = RECORDER.snapshot(tail=RECORDER.ring_entries)["models"]["q@1"]["steps"]
    assert max(s["queue_depth"] for s in steps) > 0
    # ... and the live gauge drained back to exactly 0 with the queue
    assert metrics.registry.get_sample_value(
        "tpusc_gen_oldest_queued_age_seconds", {"engine": "continuous"}
    ) == 0.0


async def test_engine_dump_tool_renders_live_node(tmp_path, capsys):
    """--url renders a LIVE node's /monitoring/engine (peeking with
    reset=0), with --model narrowing to one tenant."""
    for name in ("alpha", "beta"):
        RECORDER.record(f"{name}@1", "continuous", step_ms=1.5, chunk=8,
                        active=4, admitted=1, retired=1, wasted=2,
                        queue_depth=1, oldest_wait_ms=30.0)
    RECORDER.observe_watermark("hbm_bytes:g0", 777.0)
    rest = RestServingServer(None, require_version=False)
    rport = await rest.start(0, host="127.0.0.1")
    mod = _load_engine_dump_module()
    url = f"http://127.0.0.1:{rport}"
    try:
        assert await asyncio.to_thread(mod.main, ["--url", url]) == 0
        out = capsys.readouterr().out
        assert "flight dump: snapshot" in out
        assert "alpha@1" in out and "beta@1" in out
        assert "goodput=" in out
        assert await asyncio.to_thread(
            mod.main, ["--url", url, "--model", "alpha@1"]
        ) == 0
        out = capsys.readouterr().out
        assert "alpha@1" in out and "beta@1" not in out
        # peeks must not have consumed the node's watermarks
        assert RECORDER.watermarks() == {"hbm_bytes:g0": 777.0}
    finally:
        await rest.close()


# -- anomaly dumps ------------------------------------------------------------

def _phase_hist_sum(metrics, phase):
    return metrics.registry.get_sample_value(
        "tpusc_request_phase_seconds_sum",
        {"phase": phase, "engine": "continuous"},
    )


def test_slo_breach_dumps_once_and_phases_reconcile(tmp_path):
    """An induced SLO breach (threshold below any real request) produces
    exactly ONE dump via the tracer's slow-retention hook, and the dump's
    phase notes reconcile with the request's tpusc_request_phase_seconds
    observations — same clocks, two sinks."""
    flight = tmp_path / "flight"
    metrics = Metrics()
    rt, mid = _load(tmp_path, metrics=metrics)
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=2, metrics=metrics)
    old_threshold = TRACER.slow_threshold_s
    old_hook = TRACER.slow_hook
    RECORDER.configure(flight_dir=str(flight), metrics=metrics)
    RECORDER.install_slow_hook(TRACER)
    TRACER.configure(slow_threshold_s=1e-6)
    try:
        with TRACER.span("rest", path="/v1/models/lm:generate") as sp:
            sp.attrs["model"] = str(mid)   # the backend stamps it on the root
            eng.generate(mid, np.array([[3, 5, 7]], np.int32), max_new_tokens=6)
        dumps = [f for f in os.listdir(flight) if "slo_breach" in f]
        assert len(dumps) == 1, dumps
        with open(flight / dumps[0]) as fh:
            payload = json.load(fh)
        assert payload["reason"] == "slo_breach"
        assert payload["context"]["trace_id"]
        notes = payload["phases"][str(mid)]
        assert len(notes) == 1  # one row -> one phase note
        for phase in ("queue", "prefill", "decode", "respond"):
            got = notes[0]["phases"][phase]
            want = _phase_hist_sum(metrics, phase)
            assert got == pytest.approx(want, abs=1e-3), phase
        # the ring made it into the dump too
        assert payload["models"][str(mid)]["recorded_steps"] > 0
        # a second breach of the same model inside the cooldown writes
        # nothing and counts as suppressed
        with TRACER.span("rest", path="/v1/models/lm:generate") as sp:
            sp.attrs["model"] = str(mid)
            eng.generate(mid, np.array([[3, 5, 7]], np.int32), max_new_tokens=6)
        assert len([f for f in os.listdir(flight) if "slo_breach" in f]) == 1

        def dumps(outcome):
            return metrics.registry.get_sample_value(
                "tpusc_flight_dumps_total",
                {"reason": "slo_breach", "outcome": outcome})
        assert dumps("written") == 1
        assert dumps("suppressed") == 1
    finally:
        TRACER.slow_hook = old_hook
        TRACER.configure(slow_threshold_s=old_threshold)
        eng.close()
        rt.close()


def test_dump_dedup_cooldown_and_spool_bound(tmp_path):
    metrics = Metrics()
    fr = FlightRecorder(flight_dir=str(tmp_path), max_dumps=3,
                        dump_cooldown_s=60.0)
    fr.configure(metrics=metrics)
    fr.record("m@1", "continuous", 1.0, 8, 4, 1, 0)
    # dedup key: one incident = at most one file ...
    assert fr.dump("slo_breach", dedup_key=("slo", "t1"), model="m@1") is not None
    assert fr.dump("slo_breach", dedup_key=("slo", "t1"), model="m@1") is None
    # ... and a keyed dump obeys the (reason, model) cooldown like any other:
    # a second breach of the same model inside it writes nothing
    assert fr.dump("slo_breach", dedup_key=("slo", "t2"), model="m@1") is None
    assert fr.dump("slo_breach", dedup_key=("slo", "t3"), model="other@1") is not None
    # cooldown per (reason, model), unkeyed
    assert fr.dump("page_exhaustion", model="m@1") is not None
    assert fr.dump("page_exhaustion", model="m@1") is None
    assert fr.dump("page_exhaustion", model="other@1") is not None
    # both outcomes are counted
    def dumps(reason, outcome):
        return metrics.registry.get_sample_value(
            "tpusc_flight_dumps_total", {"reason": reason, "outcome": outcome})
    assert dumps("slo_breach", "written") == 2
    assert dumps("slo_breach", "suppressed") == 2
    assert dumps("page_exhaustion", "written") == 2
    assert dumps("page_exhaustion", "suppressed") == 1
    # once the cooldown has passed the same model dumps again
    fr.dump_cooldown_s = 0.0
    assert fr.dump("slo_breach", dedup_key=("slo", "t4"), model="m@1") is not None
    # spool bounded at max_dumps, oldest pruned
    for i in range(4):
        assert fr.dump("engine_crash", dedup_key=("c", i)) is not None
    files = fr.list_dumps()
    assert len(files) == 3
    assert all("engine_crash" in f for f in files[-3:])
    # disabled dir -> no-op, never raises
    off = FlightRecorder()
    assert off.dump("slo_breach") is None


def test_engine_crash_writes_dump(tmp_path):
    """A scheduler-thread failure (here: a runtime whose decode dies after
    admission) fails the in-flight rows AND leaves a flight dump."""

    class _CrashingRuntime(_StubRuntime):
        def slot_decode_chunk(self, state, chunk):
            raise RuntimeError("device fell over")

    RECORDER.configure(flight_dir=str(tmp_path / "flight"))
    eng = ContinuousGenerateEngine(_CrashingRuntime(2), slots=2, chunk_tokens=2)
    try:
        with pytest.raises(Exception, match="device fell over"):
            eng.generate(ModelId("m", 1), np.ones((1, 3), np.int32),
                         max_new_tokens=8)
    finally:
        eng.close()
    dumps = [f for f in os.listdir(tmp_path / "flight") if "engine_crash" in f]
    assert len(dumps) == 1
    with open(tmp_path / "flight" / dumps[0]) as fh:
        assert "device fell over" in json.load(fh)["context"]["error"]


# -- engine_dump tool ---------------------------------------------------------

def _load_engine_dump_module():
    path = os.path.join(os.path.dirname(__file__), "..", "tools", "engine_dump.py")
    spec = importlib.util.spec_from_file_location("engine_dump", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_engine_dump_tool_renders_dump(tmp_path, capsys):
    fr = FlightRecorder(flight_dir=str(tmp_path))
    for i in range(6):
        fr.record("m@1", "continuous", step_ms=1.5, chunk=8, active=4,
                  admitted=1, retired=1, wasted=2,
                  queue_depth=(2 if 1 <= i <= 3 else 0),
                  oldest_wait_ms=(30.0 if 1 <= i <= 3 else 0.0))
    fr.note_phases("m@1", "continuous",
                   {"queue": 0.001, "prefill": 0.002, "decode": 0.01,
                    "respond": 0.0005}, trace_id="abc123")
    fr.observe_watermark("hbm_bytes:g0", 12345.0)
    path = fr.dump("slo_breach", dedup_key=("slo", "abc123"),
                   trace_id="abc123", duration_s=1.25)
    assert path is not None
    mod = _load_engine_dump_module()
    assert mod.main([path]) == 0
    out = capsys.readouterr().out
    assert "flight dump: slo_breach" in out
    assert "goodput=" in out
    assert "stall spans" in out          # the queued steps form one span
    assert "steps [1..3]" in out
    assert "decode=10.00ms" in out
    assert "hbm_bytes:g0" in out
    # --latest resolves the newest dump in a dir
    assert mod.main(["--latest", str(tmp_path)]) == 0
    assert mod.main(["--latest", str(tmp_path / "empty")]) == 1


def test_ring_and_dump_render_shared_prefix_split(tmp_path, capsys):
    """Shared-prefix telemetry rides the step ring: the window summarizes
    radix hit rate over admissions and peak shared pages, and the dump
    tool renders the shared/private/free page split per step plus the
    hit-rate line."""
    fr = FlightRecorder(flight_dir=str(tmp_path))
    fr.record("m@1", "continuous", step_ms=1.0, chunk=8, active=2,
              admitted=2, retired=0, pages_used=6, pages_free=10,
              pages_shared=2, prefix_hits=1)
    fr.record("m@1", "continuous", step_ms=1.0, chunk=8, active=3,
              admitted=2, retired=1, pages_used=8, pages_free=8,
              pages_shared=3, prefix_hits=2)
    snap = fr.snapshot(tail=16)
    win = snap["models"]["m@1"]["window"]
    assert win["admitted"] == 4
    assert win["prefix_hits"] == 3
    assert win["prefix_hit_rate"] == pytest.approx(3 / 4)
    assert win["max_pages_shared"] == 3
    path = fr.dump("slo_breach", dedup_key=("slo", "share"))
    mod = _load_engine_dump_module()
    assert mod.main([path]) == 0
    out = capsys.readouterr().out
    assert "prefix sharing: 3/4 admissions hit (rate=0.750)" in out
    assert "max shared pages=3" in out
    assert "pages=3s+5p/8f" in out  # 8 used = 3 shared + 5 private, 8 free

def test_ring_and_dump_render_speculation(tmp_path, capsys):
    """Speculation telemetry rides the step ring: drafted/accepted counts
    aggregate into the window, the acceptance rate normalizes by emission
    capacity over spec steps only, engine_stats() exposes the same rate,
    and the dump tool renders the speculation line."""
    fr = FlightRecorder(flight_dir=str(tmp_path))
    # spec round: 2 active rows x chunk 5 (spec_tokens 4 + carry) = 10
    # emission capacity; 7 tokens actually emitted
    fr.record("m@1", "continuous", step_ms=1.0, chunk=5, active=2,
              admitted=0, retired=0, drafted=8, accepted=7)
    # plain chunk contributes NOTHING to the acceptance denominator
    fr.record("m@1", "continuous", step_ms=1.0, chunk=4, active=2,
              admitted=0, retired=0)
    snap = fr.snapshot(tail=16)
    win = snap["models"]["m@1"]["window"]
    assert win["drafted"] == 8
    assert win["accepted"] == 7
    assert win["spec_acceptance"] == pytest.approx(7 / 10)
    stats = fr.engine_stats()
    assert stats["spec_acceptance"] == pytest.approx(7 / 10)
    path = fr.dump("slo_breach", dedup_key=("slo", "spec"))
    mod = _load_engine_dump_module()
    assert mod.main([path]) == 0
    out = capsys.readouterr().out
    assert "speculation: 7 tokens emitted / 8 drafted" in out
    assert "acceptance=0.700" in out


def test_snapshot_and_engine_stats_under_5ms_with_128_rings():
    """Read-side scaling pin: a busy multi-tenant node (128 model rings,
    every ring fully wrapped) must answer the status plane's
    engine_stats() and a default /monitoring/engine snapshot() in < 5 ms
    each — the reads window the rings (slice-based tail, single-pass
    aggregation), they never copy whole 4096-entry buffers."""
    fr = FlightRecorder()
    rec = (time.time(), "continuous", 1.0, 8, 4, 1, 1, 3, 5, 2, 1, 2.0, 1, 1)
    for i in range(128):
        ring = fr._ring(f"tenant{i}@1")
        for _ in range(fr.ring_entries + 64):  # wrap: written > entries
            ring.append(rec)
    # thread CPU time, not wall time: the pin is the read path's WORK
    # (window the rings, never copy whole 4096-entry buffers), and a
    # loaded CI box preempting the thread mid-snapshot would measure the
    # scheduler; median-of-9 rides out GC pauses from earlier tests'
    # garbage (the snapshot materializes ~2k step dicts per call)
    stats_t = []
    snap_t = []
    for _ in range(9):
        t0 = time.thread_time()
        stats = fr.engine_stats()
        stats_t.append(time.thread_time() - t0)
        t0 = time.thread_time()
        snap = fr.snapshot()
        snap_t.append(time.thread_time() - t0)
    assert len(snap["models"]) == 128
    assert stats["queue_depth"] == 128
    assert statistics.median(stats_t) < 5e-3, stats_t
    assert statistics.median(snap_t) < 5e-3, snap_t


def test_snapshot_model_found_marker():
    """?model= on an unknown tenant is distinguishable from an idle one:
    the filtered snapshot stamps model_filter + model_found, and an
    unfiltered snapshot carries neither key (payload stays byte-compatible
    for consumers that never filter)."""
    fr = FlightRecorder()
    fr.record("real@1", "continuous", step_ms=1.0, chunk=4, active=1,
              admitted=1, retired=1)
    hit = fr.snapshot(model="real@1")
    assert hit["model_found"] is True and hit["model_filter"] == "real@1"
    miss = fr.snapshot(model="ghost@7")
    assert miss["model_found"] is False and miss["model_filter"] == "ghost@7"
    assert miss["models"] == {} and miss["phases"] == {}
    # a tenant known only through phase notes still counts as found
    fr.note_phases("notes@1", "continuous", {"decode": 0.01})
    assert fr.snapshot(model="notes@1")["model_found"] is True
    plain = fr.snapshot()
    assert "model_found" not in plain and "model_filter" not in plain


async def test_engine_dump_tool_marks_unknown_model(capsys):
    """--url --model with a tenant the node has never recorded renders an
    explicit "no such model" marker instead of an empty timeline."""
    RECORDER.record("real@1", "continuous", step_ms=1.0, chunk=4, active=1,
                    admitted=1, retired=1)
    rest = RestServingServer(None, require_version=False)
    rport = await rest.start(0, host="127.0.0.1")
    mod = _load_engine_dump_module()
    url = f"http://127.0.0.1:{rport}"
    try:
        assert await asyncio.to_thread(
            mod.main, ["--url", url, "--model", "ghost@7"]
        ) == 0
        out = capsys.readouterr().out
        assert "no such model: ghost@7" in out
        assert "timeline" not in out
        # a known tenant still renders normally through the same path
        assert await asyncio.to_thread(
            mod.main, ["--url", url, "--model", "real@1"]
        ) == 0
        out = capsys.readouterr().out
        assert "no such model" not in out and "real@1" in out
    finally:
        await rest.close()


# -- the boundary split (ISSUE 23) ---------------------------------------------

def test_ring_split_fields_sit_at_the_end():
    """Appended, never inserted: the 16 older names keep their positions
    (older dumps and tools index by them), the split is the last three."""
    assert STEP_FIELDS[16:19] == ("prefill_ms", "chunk_ms", "emit_ms")
    assert STEP_FIELDS[:3] == ("t_wall", "engine", "step_ms")
    assert STEP_FIELDS[14:16] == ("drafted", "accepted")
    fr = FlightRecorder()
    fr.record("m@1", "continuous", step_ms=9.0, chunk=8, active=4, admitted=1,
              retired=0, prefill_ms=2.0, chunk_ms=5.0, emit_ms=0.5)
    step = fr.snapshot()["models"]["m@1"]["steps"][0]
    assert list(step) == list(STEP_FIELDS)
    assert (step["prefill_ms"], step["chunk_ms"], step["emit_ms"]) == (2.0, 5.0, 0.5)


def test_stub_engine_split_sums_under_step_ms():
    """prefill_ms + chunk_ms + emit_ms are parts of step_ms on every
    boundary: what is left is the engine's self time, never negative."""
    slots = 4
    eng = ContinuousGenerateEngine(_StubRuntime(slots), slots=slots, chunk_tokens=4)
    try:
        eng.generate(ModelId("stub", 1), np.ones((12, 4), np.int32),
                     max_new_tokens=9)
    finally:
        eng.close()
    steps = RECORDER.snapshot(tail=RECORDER.ring_entries)["models"]["stub@1"]["steps"]
    assert any(s["chunk"] > 0 for s in steps) and any(s["admitted"] for s in steps)
    for s in steps:
        parts = s["prefill_ms"] + s["chunk_ms"] + s["emit_ms"]
        assert min(s["prefill_ms"], s["chunk_ms"], s["emit_ms"]) >= 0.0
        # each field is rounded to 1e-4 ms on its own
        assert parts <= s["step_ms"] + 4e-4, s
        if s["chunk"] > 0:
            assert s["chunk_ms"] > 0.0
        if s["admitted"]:
            assert s["prefill_ms"] > 0.0


def test_sixteen_field_dump_still_renders(tmp_path, capsys):
    """A dump written before the split (16-field tuples in its ring) goes
    through the zip fallback and the tool prints it without the split."""
    fr = FlightRecorder(flight_dir=str(tmp_path))
    old = (time.time(), "continuous", 1.5, 8, 4, 1, 1, 3, 5, 2, 1, 2.0, 1, 1, 0, 0)
    assert len(old) == 16
    ring = fr._ring("m@1")
    ring.append(old)
    fr.record("m@1", "continuous", step_ms=2.5, chunk=8, active=4, admitted=0,
              retired=0, prefill_ms=0.0, chunk_ms=2.0, emit_ms=0.25)
    steps = fr.snapshot()["models"]["m@1"]["steps"]
    assert "chunk_ms" not in steps[0] and steps[0]["accepted"] == 0
    assert steps[1]["chunk_ms"] == 2.0
    path = fr.dump("slo_breach", dedup_key=("slo", "old"))
    mod = _load_engine_dump_module()
    assert mod.main([path]) == 0
    out = capsys.readouterr().out
    assert "step=    1.50ms chunk=  8" in out          # the 16-field row
    assert "(prefill=0.00 chunk=2.00 launch=0.00 uploads=0 emit=0.25 self=0.25)" in out


def test_record_with_split_fields_under_50us():
    """The three more fields ride inside record()'s budget."""
    fr = FlightRecorder()
    per_rec = []
    for _ in range(10):
        t0 = time.perf_counter()
        for _ in range(1000):
            fr.record("m@1", "continuous", step_ms=1.0, chunk=8, active=4,
                      admitted=1, retired=1, pages_used=3, pages_free=5,
                      wasted=2, queue_depth=1, oldest_wait_ms=2.0,
                      prefill_ms=0.31234, chunk_ms=0.54321, emit_ms=0.01234)
        per_rec.append((time.perf_counter() - t0) / 1000)
    assert statistics.median(per_rec) < 50e-6, per_rec


# -- the chunk's launch path (ISSUE 35) -----------------------------------------

def test_launch_ms_is_the_last_ring_field():
    """Appended, never inserted: the 23 older names keep their positions
    (``uploads``, ISSUE 36, ``window_pages``, ISSUE 39, ``ahead``, ISSUE 40,
    ``shared_pages``, ISSUE 41, and ``state_lanes``, ISSUE 46, came after it
    the same way)."""
    assert STEP_FIELDS[23:] == ("launch_ms", "uploads", "window_pages", "ahead",
                                "shared_pages", "state_lanes")
    assert len(STEP_FIELDS) == 29
    assert STEP_FIELDS[16:19] == ("prefill_ms", "chunk_ms", "emit_ms")
    assert STEP_FIELDS[19:23] == ("experts_hit", "expert_rows_max",
                                  "expert_rows_local", "write_lanes")
    fr = FlightRecorder()
    fr.record("m@1", "continuous", step_ms=9.0, chunk=8, active=4, admitted=0,
              retired=0, chunk_ms=5.0, launch_ms=1.23456, uploads=2)
    step = fr.snapshot()["models"]["m@1"]["steps"][0]
    assert list(step) == list(STEP_FIELDS) and step["launch_ms"] == 1.2346
    assert step["uploads"] == 2
    # a caller that does not know the field (a spec round, a boundary without
    # a chunk) records 0.0
    fr.record("m@1", "continuous", step_ms=9.0, chunk=0, active=0, admitted=1,
              retired=0)
    last = fr.snapshot()["models"]["m@1"]["steps"][1]
    assert last["launch_ms"] == 0.0 and last["uploads"] == 0


@pytest.mark.parametrize("width", [19, 23, 24])
def test_a_dump_of_an_older_ring_still_renders(width, tmp_path, capsys):
    """Dumps written before ``uploads`` (24 fields), before ``launch_ms`` (23)
    and before the expert fields (19) go through the zip fallback; the tool
    prints the split without ``launch=`` / ``uploads=``, and with them for a
    row of today's width."""
    fr = FlightRecorder(flight_dir=str(tmp_path))
    fr.record("m@1", "continuous", step_ms=3.0, chunk=8, active=4, admitted=0,
              retired=0, prefill_ms=0.0, chunk_ms=2.5, emit_ms=0.25,
              experts_hit=7.5, write_lanes=4, launch_ms=0.75, uploads=3)
    ring = fr._ring("m@1")
    full = ring.tail(1)[0]
    ring.append(full[:width])
    new, old = fr.snapshot()["models"]["m@1"]["steps"]
    assert list(old) == list(STEP_FIELDS[:width]) and "uploads" not in old
    assert ("launch_ms" in old) is (width == 24)
    assert ("write_lanes" in old) is (width >= 23)
    path = fr.dump("slo_breach", dedup_key=("slo", f"old{width}"))
    assert _load_engine_dump_module().main([path]) == 0
    out = capsys.readouterr().out
    assert "(prefill=0.00 chunk=2.50 launch=0.75 uploads=3 emit=0.25 self=0.25)" in out
    assert ("(prefill=0.00 chunk=2.50 launch=0.75 emit=0.25 self=0.25)" if width == 24
            else "(prefill=0.00 chunk=2.50 emit=0.25 self=0.25)") in out
    assert out.count("write_lanes=4") == (2 if width >= 23 else 1)


def test_stub_engine_records_no_launch_time():
    """A runtime that keeps no ``launched_t`` (the stub) records 0.0, never a
    negative or a stale number."""
    slots = 4
    eng = ContinuousGenerateEngine(_StubRuntime(slots), slots=slots, chunk_tokens=4)
    try:
        eng.generate(ModelId("stub", 1), np.ones((6, 4), np.int32), max_new_tokens=9)
    finally:
        eng.close()
    steps = RECORDER.snapshot(tail=RECORDER.ring_entries)["models"]["stub@1"]["steps"]
    assert any(s["chunk"] > 0 for s in steps)
    assert {s["launch_ms"] for s in steps} == {0.0}
    assert {s["uploads"] for s in steps} == {0}          # nor an upload count
