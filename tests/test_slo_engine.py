"""SLO-aware engine (ISSUE 19): chunked prefill interleaving, priority
classes with preempt-park-resume, and streamed tokens (REST SSE + gRPC
server-streaming), all pinned to byte-identical outputs vs the pre-SLO
engine paths."""

import json
import threading
import time

import aiohttp
import numpy as np
import pytest

from tfservingcache_tpu.cache.disk_cache import ModelDiskCache
from tfservingcache_tpu.cache.manager import CacheManager
from tfservingcache_tpu.cache.providers.disk import DiskModelProvider
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.lab import faults as lab_faults
from tfservingcache_tpu.models.registry import export_artifact
from tfservingcache_tpu.protocol import codec
from tfservingcache_tpu.protocol.local_backend import LocalServingBackend
from tfservingcache_tpu.protocol.protos import tf_serving_pb2 as sv
from tfservingcache_tpu.protocol.rest import RestServingServer
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId
from tfservingcache_tpu.utils.metrics import Metrics

TINY = {
    "vocab_size": 97,
    "d_model": 48,
    "n_layers": 2,
    "n_heads": 4,
    "n_kv_heads": 2,
    "d_ff": 96,
    "max_seq": 64,
}


def _load(tmp_path, name="lm", config=TINY, metrics=None):
    export_artifact(
        "transformer_lm", str(tmp_path), name=name, version=1, config=config
    )
    rt = TPUModelRuntime(ServingConfig(platform="cpu"), metrics)
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


def _backend(tmp_path, metrics=None, **kw):
    export_artifact(
        "transformer_lm", str(tmp_path / "store"), name="lm", version=1,
        config=TINY,
    )
    manager = CacheManager(
        DiskModelProvider(str(tmp_path / "store")),
        ModelDiskCache(str(tmp_path / "cache"), capacity_bytes=1 << 30),
        TPUModelRuntime(ServingConfig(platform="cpu"), metrics),
        metrics,
    )
    kw.setdefault("generate_slots", 4)
    kw.setdefault("generate_chunk_tokens", 2)
    return LocalServingBackend(manager, **kw), manager


def _sse_events(raw: bytes) -> list[dict]:
    events = []
    for line in raw.split(b"\n"):
        if line.startswith(b"data: "):
            events.append(json.loads(line[len(b"data: "):]))
    return events


# ---------------------------------------------------------------- chunked


def test_chunked_prefill_greedy_identity(tmp_path):
    """A cold prefill split into fixed chunks must sample the exact token
    sequence the monolithic single-dispatch prefill samples — chunking
    changes WHEN prompt K/V is written, never what gets written."""
    metrics = Metrics()
    rt, mid = _load(tmp_path, metrics=metrics)
    prompt = np.arange(1, 31, dtype=np.int32)[None]  # 30 > chunk of 8
    try:
        mono = ContinuousGenerateEngine(
            rt, slots=2, chunk_tokens=2, page_tokens=8, arena_pages=32
        )
        try:
            want = mono.generate(mid, prompt, max_new_tokens=6)
        finally:
            mono.close()
        rt.drop_slot_state(mid)

        chunked = ContinuousGenerateEngine(
            rt, slots=2, chunk_tokens=2, page_tokens=8, arena_pages=32,
            prefill_chunk_tokens=8, metrics=metrics,
        )
        try:
            got, stats = chunked.generate(
                mid, prompt, max_new_tokens=6, return_stats=True
            )
        finally:
            chunked.close()
        assert (got == want).all()
        assert stats[0]["prefill_tokens"] == 30
        # 30 tokens at chunk 8 -> 4 boundary-spread dispatches
        assert metrics.gen_prefill_chunks._value.get() >= 4
    finally:
        rt.close()


def test_prefill_chunking_off_by_default(tmp_path):
    """The knob defaults OFF and the default engine is byte-identical to
    the pre-SLO decoder: prompts shorter than the chunk (and engines with
    prefill_chunk_tokens=0) keep the single-dispatch prefill path."""
    assert ServingConfig().prefill_chunk_tokens == 0
    rt, mid = _load(tmp_path)
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=2)
    try:
        assert eng.prefill_chunk_tokens == 0
        prompt = np.arange(1, 25, dtype=np.int32)[None]
        got = eng.generate(mid, prompt, max_new_tokens=6)
        want = rt.generate(mid, prompt, max_new_tokens=6, seed=0)
        assert (got == want).all()
    finally:
        eng.close()
        rt.close()


# --------------------------------------------------------------- priority


def test_priority_admission_jumps_fifo(tmp_path):
    """With one lane busy and two queued rows, the later-submitted high
    row must admit before the earlier normal row; FIFO survives inside a
    class (the all-normal ordering is pinned by the existing continuous-
    batching suite)."""
    rt, mid = _load(tmp_path)
    eng = ContinuousGenerateEngine(rt, slots=1, chunk_tokens=1)
    first_tok_at: dict[str, float] = {}
    lock = threading.Lock()

    def run(tag, prompt, priority, max_new=4):
        def on_tok(_t, _tag=tag):
            with lock:
                first_tok_at.setdefault(_tag, time.monotonic())

        eng.generate(
            mid, np.asarray(prompt, np.int32)[None],
            max_new_tokens=max_new, priority=priority, on_token=on_tok,
        )

    def queued(n, deadline=30.0):
        # wall-clock sleeps are a flake on a loaded 1-core host; sync on the
        # scheduler's own pending queue instead
        sched = eng._scheds[mid]
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline:
            with sched.cv:
                if len(sched.pending) >= n:
                    return
            time.sleep(0.002)
        raise AssertionError(f"queue never reached depth {n}")

    try:
        # freeze the scheduler for a beat right after the blocker admits so
        # both queued rows are provably pending before any retirement can
        # trigger the admission decision under test
        lab_faults.arm([lab_faults.FaultSpec(
            kind="freeze_scheduler", after=2, count=1, duration_s=2.0,
        )])
        blocker = threading.Thread(
            target=run, args=("blocker", [1, 2, 3], "normal", 24)
        )
        blocker.start()
        while eng.admitted < 1:
            time.sleep(0.002)
        t_normal = threading.Thread(
            target=run, args=("normal", [4, 5, 6], "normal")
        )
        t_normal.start()
        queued(1)  # normal is queued first ...
        t_high = threading.Thread(target=run, args=("high", [7, 8, 9], "high"))
        t_high.start()
        queued(2)  # ... high second, while the blocker still holds the lane
        assert eng.admitted == 1, "blocker retired before both rows queued"
        for t in (blocker, t_normal, t_high):
            t.join(timeout=60)
        assert first_tok_at["high"] < first_tok_at["normal"]
    finally:
        lab_faults.disarm()
        eng.close()
        rt.close()


def test_preemption_parks_and_resumes_token_exact(tmp_path):
    """A high-class arrival with no free pages parks the lowest-class
    decoding lane; the victim resumes O(new tokens) later — its prefill
    bill is the cold prompt plus ONE resume-suffix token, and its sampled
    stream is identical to a never-preempted run."""
    metrics = Metrics()
    rt, mid = _load(tmp_path, metrics=metrics)
    low_prompt = np.arange(1, 17, dtype=np.int32)[None]   # 16 tokens
    high_prompt = np.arange(20, 28, dtype=np.int32)[None]  # 8 tokens
    try:
        ref_eng = ContinuousGenerateEngine(
            rt, slots=2, chunk_tokens=1, page_tokens=8, arena_pages=8
        )
        try:
            want = ref_eng.generate(mid, low_prompt, max_new_tokens=48)
        finally:
            ref_eng.close()
        rt.drop_slot_state(mid)

        eng = ContinuousGenerateEngine(
            rt, slots=2, chunk_tokens=1, page_tokens=8, arena_pages=8,
            metrics=metrics,
        )
        results = {}
        low_started = threading.Event()

        def run_low():
            # 16 prompt + 48 new = 64 tokens = all 8 pages: the next
            # arrival can only get in by preempting this lane
            out, stats = eng.generate(
                mid, low_prompt, max_new_tokens=48, priority="low",
                return_stats=True, on_token=lambda _t: low_started.set(),
            )
            results["low"] = (out, stats[0])

        def run_high():
            out, stats = eng.generate(
                mid, high_prompt, max_new_tokens=8, priority="high",
                return_stats=True,
            )
            results["high"] = (out, stats[0])

        try:
            tl = threading.Thread(target=run_low)
            tl.start()
            assert low_started.wait(timeout=60)  # decoding, not prefilling
            th = threading.Thread(target=run_high)
            th.start()
            tl.join(timeout=120)
            th.join(timeout=120)
        finally:
            eng.close()
        out_low, stats_low = results["low"]
        assert stats_low["preemptions"] == 1
        # 16 cold prompt tokens + the single resume-suffix token: the park
        # covered prompt + every emitted token except the last sampled one
        assert stats_low["prefill_tokens"] == 17
        assert (out_low == want).all()
        assert results["high"][1]["priority"] == "high"
        assert metrics.gen_preemptions.labels("low")._value.get() == 1
    finally:
        rt.close()


# -------------------------------------------------------------- streaming


async def test_rest_sse_stream_parity_greedy(tmp_path):
    """`:generate?stream=true` over real HTTP: the per-token SSE frames
    concatenated AND the terminal done-frame matrix must be byte-identical
    to the buffered (non-stream) response for the same greedy request."""
    metrics = Metrics()
    backend, manager = _backend(tmp_path, metrics=metrics)
    rest = RestServingServer(backend, require_version=False)
    port = await rest.start(0, host="127.0.0.1")
    try:
        base = f"http://127.0.0.1:{port}/v1/models/lm"
        payload = {
            "input_ids": [list(range(1, 21))],
            "max_new_tokens": 10,
            "temperature": 0.0,
        }
        async with aiohttp.ClientSession() as s:
            async with s.post(f"{base}:generate", json=payload) as r:
                assert r.status == 200, await r.text()
                buffered = (await r.json())["tokens"]
            async with s.post(
                f"{base}:generate", json=payload, params={"stream": "true"}
            ) as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith(
                    "text/event-stream"
                )
                events = _sse_events(await r.read())
        toks = [e["token"] for e in events if "token" in e]
        done = [e for e in events if e.get("done")]
        assert toks == buffered[0]
        assert len(done) == 1 and done[0]["tokens"] == buffered
        sse_frames = metrics.gen_stream_frames.labels("sse")._value.get()
        assert sse_frames == len(toks) + 1
    finally:
        await rest.close()
        backend.close()
        manager.close()


async def test_rest_sse_stream_parity_seeded(tmp_path):
    """Seeded sampling rides the solo dispatch (no per-token boundary to
    hook): the stream must replay the finished row so frames still concat
    to exactly the buffered seeded output."""
    backend, manager = _backend(tmp_path)
    try:
        payload = {
            "input_ids": [[3, 1, 4, 1, 5, 9, 2, 6]],
            "max_new_tokens": 8,
            "temperature": 0.8,
            "seed": 7,
        }
        body = json.dumps(payload).encode()
        buffered = await backend.handle_rest(
            "POST", "lm", 1, "generate", body
        )
        want = json.loads(buffered.body)["tokens"]
        streamed = await backend.handle_rest(
            "POST", "lm", 1, "generate", body, query={"stream": "true"}
        )
        raw = b""
        async for frame in streamed.token_stream:
            raw += frame
        events = _sse_events(raw)
        assert [e["token"] for e in events if "token" in e] == want[0]
        assert events[-1]["done"] and events[-1]["tokens"] == want
    finally:
        backend.close()
        manager.close()


async def test_rest_stream_validation(tmp_path):
    """Oversized prompts, bad priorities, and multi-row streams all fail
    LOUDLY at submit — never after the 200 + headers are on the wire."""
    backend, manager = _backend(tmp_path)
    try:
        from tfservingcache_tpu.protocol.backend import BackendError

        with pytest.raises(BackendError) as ei:
            await backend.handle_rest(
                "POST", "lm", 1, "generate",
                json.dumps({
                    "input_ids": [[1, 2, 3]], "priority": "urgent",
                }).encode(),
            )
        assert ei.value.http_status == 400

        with pytest.raises(BackendError) as ei:
            await backend.handle_rest(
                "POST", "lm", 1, "generate",
                json.dumps({
                    "input_ids": [[1, 2], [3, 4]], "max_new_tokens": 4,
                }).encode(),
                query={"stream": "1"},
            )
        assert ei.value.http_status == 400
        assert "single-row" in str(ei.value)
    finally:
        backend.close()
        manager.close()


async def test_grpc_generate_stream_parity(tmp_path):
    """GenerateStream (server-streaming Predict with signature "generate"):
    per-token scalar responses concat to the unary result, and the terminal
    response carries the identical padded matrix."""
    metrics = Metrics()
    backend, manager = _backend(tmp_path, metrics=metrics)
    try:
        req = sv.PredictRequest()
        req.model_spec.name = "lm"
        req.model_spec.version.value = 1
        req.inputs["input_ids"].CopyFrom(
            codec.numpy_to_tensorproto(
                np.arange(1, 13, dtype=np.int32)[None]
            )
        )
        req.inputs["max_new_tokens"].CopyFrom(
            codec.numpy_to_tensorproto(np.asarray(9, np.int32))
        )
        buffered = await backend.handle_rest(
            "POST", "lm", 1, "generate",
            json.dumps({
                "input_ids": [list(range(1, 13))], "max_new_tokens": 9,
                "temperature": 0.0,
            }).encode(),
        )
        want = json.loads(buffered.body)["tokens"]

        toks, final = [], None
        async for resp in backend.generate_stream(req):
            assert resp.model_spec.signature_name == "generate"
            if "token" in resp.outputs:
                toks.append(int(codec.tensorproto_to_numpy(
                    resp.outputs["token"]
                )))
            else:
                final = codec.tensorproto_to_numpy(
                    resp.outputs["tokens"]
                ).tolist()
        assert toks == want[0]
        assert final == want
        assert metrics.gen_stream_frames.labels("grpc")._value.get() == (
            len(toks) + 1
        )
    finally:
        backend.close()
        manager.close()


def test_mid_stream_kill_engine_token_exact(tmp_path):
    """Scenario-lab kill_engine mid-decode: crash recovery re-prefills
    prompt + emitted tokens on a fresh scheduler, and the token stream the
    callback saw continues EXACTLY — no dropped, repeated, or diverged
    tokens vs an unfaulted run."""
    rt, mid = _load(tmp_path)
    prompt = np.arange(1, 11, dtype=np.int32)[None]
    try:
        ref = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=1)
        try:
            want = ref.generate(mid, prompt, max_new_tokens=12)
        finally:
            ref.close()

        eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=1)
        streamed: list[int] = []
        lab_faults.arm(
            [lab_faults.FaultSpec(kind="kill_engine", after=6, count=1)]
        )
        try:
            out = eng.generate(
                mid, prompt, max_new_tokens=12, on_token=streamed.append
            )
        finally:
            lab_faults.disarm()
            eng.close()
        assert (out == want).all()
        assert streamed == want[0].tolist()
    finally:
        rt.close()


# ----------------------------------------------------------- ring routing


async def test_router_conversation_affinity_pins_replica():
    """Resume-aware routing: a :generate carrying a conversation_id must
    hash to the SAME replica every turn (parked KV lives on the node that
    served turn 1), while id-less traffic keeps the p2c spread."""
    import asyncio

    from tfservingcache_tpu.cluster.cluster import ClusterConnection
    from tfservingcache_tpu.cluster.discovery.base import DiscoveryService
    from tfservingcache_tpu.cluster.router import RoutingBackend
    from tfservingcache_tpu.types import NodeInfo

    class Mock(DiscoveryService):
        async def register(self, self_node, is_healthy):
            pass

        async def unregister(self):
            pass

        def push(self, nodes):
            self._publish(nodes)

    mock = Mock()
    cluster = ClusterConnection(mock, replicas_per_model=4)
    connect = asyncio.create_task(
        cluster.connect(
            NodeInfo("10.0.0.9", 9900, 9990), lambda: True, wait_ready_s=2
        )
    )
    await asyncio.sleep(0.05)
    mock.push([NodeInfo(f"10.0.0.{i}", 9000 + i, 9100 + i) for i in range(4)])
    await connect
    routing = RoutingBackend(cluster)
    try:
        body = json.dumps({
            "input_ids": [[1, 2]], "conversation_id": "conv-42",
        }).encode()
        cid = routing._conversation_affinity("generate", body)
        assert cid == "conv-42"
        # no id, wrong verb, or unparseable body -> no affinity
        assert routing._conversation_affinity("generate", b"{}") is None
        assert routing._conversation_affinity("predict", body) is None

        picks = {
            routing._candidates("m", 1, affinity=cid)[0].ident
            for _ in range(20)
        }
        assert len(picks) == 1  # deterministic: p2c sampling is bypassed
        # the rotation keeps every replica as failover, nothing dropped
        assert len(routing._candidates("m", 1, affinity=cid)) == len(
            routing._candidates("m", 1)
        )
        # distinct conversations spread over replicas (crc32, not pinned
        # to one hot node)
        firsts = {
            routing._candidates("m", 1, affinity=f"conv-{i}")[0].ident
            for i in range(32)
        }
        assert len(firsts) > 1
    finally:
        await routing.close()
        await cluster.disconnect()


# ----------------------------------------------------------------- submit


def test_oversized_prompt_fails_at_submit(tmp_path):
    """The engine must reject prompt + max_new > max_seq LOUDLY at submit,
    in the caller's thread: before a scheduler exists, before the row
    queues behind others for a lane, before any page is reserved."""
    rt, mid = _load(tmp_path)
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=2)
    try:
        ids = np.arange(1, 61, dtype=np.int32)[None]  # 60 + 16 > 64
        with pytest.raises(ValueError, match="max_seq"):
            eng.generate(mid, ids, max_new_tokens=16)
        assert eng._scheds == {} and eng.admitted == 0
        assert mid not in rt._slot_states
        # the TRUE length counts, not the padded width: 8 real tokens of a
        # 60-wide row fit
        out = eng.generate(mid, ids, prompt_lengths=[8], max_new_tokens=16)
        assert out.shape == (1, 16) and eng.admitted == 1
    finally:
        eng.close()
        rt.close()
