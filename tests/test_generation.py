"""KV-cached generation: exact parity with naive re-forward decoding, ragged
prompt lengths, runtime bucketing, and the ``:generate`` REST extension."""

import json

import jax
import numpy as np
import pytest

from tfservingcache_tpu.models.generation import generate
from tfservingcache_tpu.models.registry import build, export_artifact
from tfservingcache_tpu.runtime.base import RuntimeError_
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.types import Model, ModelId

TINY = {
    "vocab_size": 97,
    "d_model": 48,
    "n_layers": 2,
    "n_heads": 4,
    "n_kv_heads": 2,   # GQA path must stay exact
    "d_ff": 96,
    "max_seq": 64,
}


def _naive_greedy(model, params, prompt: list[int], new: int) -> list[int]:
    seq = list(prompt)
    outs = []
    for _ in range(new):
        logits = model.apply(params, {"input_ids": np.array([seq], np.int32)})["logits"]
        nxt = int(np.argmax(logits[0, -1]))
        outs.append(nxt)
        seq.append(nxt)
    return outs


def test_cached_greedy_matches_naive_reforward():
    model = build("transformer_lm", TINY)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    lens = [5, 3]
    ids = np.zeros((2, 5), np.int32)
    for b, L in enumerate(lens):
        ids[b, :L] = rng.integers(1, TINY["vocab_size"], L)

    want = [_naive_greedy(model, params, list(ids[b, :L]), 6) for b, L in enumerate(lens)]
    got = np.asarray(generate(model, params, ids, prompt_lengths=lens, max_new_tokens=6))
    assert got.tolist() == want


def test_sampled_generation_in_vocab_and_deterministic_per_seed():
    model = build("transformer_lm", TINY)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.ones((2, 4), np.int32)
    a = np.asarray(generate(model, params, ids, max_new_tokens=5,
                            temperature=0.7, top_k=8, rng=jax.random.PRNGKey(3)))
    b = np.asarray(generate(model, params, ids, max_new_tokens=5,
                            temperature=0.7, top_k=8, rng=jax.random.PRNGKey(3)))
    assert (a == b).all()
    assert a.shape == (2, 5) and (0 <= a).all() and (a < TINY["vocab_size"]).all()


def test_sampling_config_never_recompiles():
    """temperature/top_k are traced, so novel sampling configs reuse ONE
    compiled program (the round-1 static args were a compile-DoS vector on
    the unauthenticated :generate verb — ADVICE.md)."""
    from tfservingcache_tpu.models.generation import _generate_jit

    model = build("transformer_lm", TINY)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.ones((2, 4), np.int32)
    generate(model, params, ids, max_new_tokens=4, temperature=0.0, top_k=0)
    before = _generate_jit._cache_size()
    for temp, k in [(0.31, 3), (0.77, 17), (1.5, 0), (0.0, 5), (2.25, 96)]:
        out = np.asarray(
            generate(model, params, ids, max_new_tokens=4, temperature=temp, top_k=k)
        )
        assert out.shape == (2, 4)
        assert (0 <= out).all() and (out < TINY["vocab_size"]).all()
    assert _generate_jit._cache_size() == before, "sampling config caused a recompile"


def test_top_k_at_or_beyond_vocab_is_safe():
    # top_k >= vocab must behave like no filtering, not crash (ADVICE.md low)
    model = build("transformer_lm", TINY)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.ones((1, 4), np.int32)
    for k in (TINY["vocab_size"], TINY["vocab_size"] + 50, 10**9):
        out = np.asarray(
            generate(model, params, ids, max_new_tokens=3, temperature=0.8, top_k=k,
                     rng=jax.random.PRNGKey(2))
        )
        assert out.shape == (1, 3)
        assert (0 <= out).all() and (out < TINY["vocab_size"]).all()


def test_greedy_via_traced_temperature_matches_argmax_semantics():
    # temperature=0 through the traced path must still be exact greedy
    model = build("transformer_lm", TINY)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.ones((1, 5), np.int32)
    a = np.asarray(generate(model, params, ids, max_new_tokens=4, temperature=0.0,
                            top_k=7, rng=jax.random.PRNGKey(0)))
    b = np.asarray(generate(model, params, ids, max_new_tokens=4, temperature=0.0,
                            top_k=0, rng=jax.random.PRNGKey(9)))
    assert (a == b).all()  # rng/top_k are irrelevant at temperature 0


def test_generate_rejects_overflow_and_wrong_family():
    model = build("transformer_lm", TINY)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="max_seq"):
        generate(model, params, np.ones((1, 60), np.int32), max_new_tokens=10)
    hpt = build("half_plus_two")
    with pytest.raises(ValueError, match="transformer_lm"):
        generate(hpt, hpt.init(jax.random.PRNGKey(0)), np.ones((1, 4), np.int32))


def test_runtime_generate_buckets_and_truncates(tmp_path):
    export_artifact("transformer_lm", str(tmp_path), name="lm", version=1, config=TINY)
    rt = TPUModelRuntime(ServingConfig(platform="cpu"))
    try:
        mid = ModelId("lm", 1)
        rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / "lm" / "1")))
        out = rt.generate(mid, np.ones((2, 5), np.int32), max_new_tokens=6)
        assert out.shape == (2, 6)  # bucketed to 8 internally, truncated back
        assert out.dtype == np.int32
        # batch axis buckets too: B=3 pads to 4 internally, returns 3 rows —
        # and the padded rows must not change the real rows' greedy output
        out2 = rt.generate(mid, np.ones((2, 5), np.int32), max_new_tokens=6)
        out3 = rt.generate(mid, np.ones((3, 5), np.int32), max_new_tokens=6)
        assert out3.shape == (3, 6)
        assert (out3[:2] == out2).all()
        with pytest.raises(RuntimeError_):
            rt.generate(mid, np.ones((1, 60), np.int32), max_new_tokens=10)
        with pytest.raises(RuntimeError_):
            rt.generate(mid, np.ones((3,), np.int32))  # 1-D input
        with pytest.raises(RuntimeError_):
            rt.generate(mid, np.ones((1, 4), np.int32), temperature=float("nan"))
        with pytest.raises(RuntimeError_):
            rt.generate(mid, np.ones((1, 4), np.int32), temperature=-1.0)
        with pytest.raises(RuntimeError_):
            rt.generate(mid, np.ones((1, 4), np.int32), top_k=-3)
    finally:
        rt.close()


async def test_rest_generate_verb(tmp_path):
    from tfservingcache_tpu.cache.disk_cache import ModelDiskCache
    from tfservingcache_tpu.cache.manager import CacheManager
    from tfservingcache_tpu.cache.providers.disk import DiskModelProvider
    from tfservingcache_tpu.protocol.local_backend import LocalServingBackend

    store = tmp_path / "store"
    export_artifact("transformer_lm", str(store), name="lm", version=1, config=TINY)
    mgr = CacheManager(
        DiskModelProvider(str(store)),
        ModelDiskCache(str(tmp_path / "cache"), capacity_bytes=1 << 30),
        TPUModelRuntime(ServingConfig(platform="cpu")),
    )
    backend = LocalServingBackend(mgr)
    try:
        body = json.dumps(
            {"input_ids": [[1, 2, 3]], "max_new_tokens": 4, "seed": 1}
        ).encode()
        resp = await backend.handle_rest("POST", "lm", 1, "generate", body)
        assert resp.status == 200
        toks = json.loads(resp.body)["tokens"]
        assert len(toks) == 1 and len(toks[0]) == 4
        # invalid body -> 400-class BackendError
        from tfservingcache_tpu.protocol.backend import BackendError

        with pytest.raises(BackendError):
            await backend.handle_rest("POST", "lm", 1, "generate", b'{"input_ids": 5}')
    finally:
        backend.close()
        mgr.close()


async def test_rest_predict_base64_output_encoding(tmp_path):
    """tpusc binary output path: {"output_encoding": "base64"} answers raw
    little-endian tensor bytes + dtype + shape (VERDICT r2 #4b)."""
    import base64

    from tfservingcache_tpu.cache.disk_cache import ModelDiskCache
    from tfservingcache_tpu.cache.manager import CacheManager
    from tfservingcache_tpu.cache.providers.disk import DiskModelProvider
    from tfservingcache_tpu.protocol.backend import BackendError
    from tfservingcache_tpu.protocol.local_backend import LocalServingBackend

    store = tmp_path / "store"
    export_artifact("transformer_lm", str(store), name="lm", version=1, config=TINY)
    mgr = CacheManager(
        DiskModelProvider(str(store)),
        ModelDiskCache(str(tmp_path / "cache"), capacity_bytes=1 << 30),
        TPUModelRuntime(ServingConfig(platform="cpu")),
    )
    backend = LocalServingBackend(mgr)
    try:
        body = json.dumps(
            {
                "inputs": {"input_ids": [[1, 2, 3]]},
                "output_filter": ["logits"],
                "output_encoding": "base64",
            }
        ).encode()
        resp = await backend.handle_rest("POST", "lm", 1, "predict", body)
        assert resp.status == 200
        spec = json.loads(resp.body)["outputs"]
        assert spec["dtype"] == "float32"
        arr = np.frombuffer(base64.b64decode(spec["b64"]), np.float32).reshape(
            spec["shape"]
        )
        # parity with the JSON path
        jbody = json.dumps(
            {"inputs": {"input_ids": [[1, 2, 3]]}, "output_filter": ["logits"]}
        ).encode()
        jresp = await backend.handle_rest("POST", "lm", 1, "predict", jbody)
        want = np.asarray(json.loads(jresp.body)["outputs"], np.float32)
        np.testing.assert_allclose(arr, want, atol=1e-6)
        with pytest.raises(BackendError):
            await backend.handle_rest(
                "POST", "lm", 1, "predict",
                json.dumps(
                    {"inputs": {"input_ids": [[1]]}, "output_encoding": "hex"}
                ).encode(),
            )
    finally:
        backend.close()
        mgr.close()


def _lm_stack(tmp_path, **serving_kw):
    from tfservingcache_tpu.cache.disk_cache import ModelDiskCache
    from tfservingcache_tpu.cache.manager import CacheManager
    from tfservingcache_tpu.cache.providers.disk import DiskModelProvider

    store = tmp_path / "store"
    export_artifact("transformer_lm", str(store), name="lm", version=1, config=TINY)
    rt = TPUModelRuntime(ServingConfig(platform="cpu", **serving_kw))
    mgr = CacheManager(
        DiskModelProvider(str(store)),
        ModelDiskCache(str(tmp_path / "cache"), capacity_bytes=1 << 30),
        rt,
    )
    return mgr, rt


def _engine_threads(eng, mid, reqs):
    """Run every (ids, prompt_lengths, max_new) of ``reqs`` through
    ``eng.generate`` on its own thread; -> results in order."""
    import threading

    got: list = [None] * len(reqs)
    errors: list = []

    def call(i):
        ids, pl, new = reqs[i]
        try:
            got[i] = eng.generate(mid, ids, prompt_lengths=pl, max_new_tokens=new)
        except BaseException as e:  # noqa: BLE001
            errors.append((i, e))

    ts = [threading.Thread(target=call, args=(i,)) for i in range(len(reqs))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in ts)
    assert not errors, errors
    return got


def test_engine_shares_decode_steps_between_concurrent_requests(tmp_path):
    """Concurrent unseeded :generate requests ride the SAME decode steps
    (one admission boundary, every chunk computed for all three lanes);
    ragged prompts keep per-row lengths; greedy output matches each
    request's solo run exactly."""
    from tfservingcache_tpu.lab import faults as lab_faults
    from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
    from tfservingcache_tpu.types import ModelId
    from tfservingcache_tpu.utils.flight_recorder import RECORDER

    mgr, rt = _lm_stack(tmp_path)
    mid = ModelId("lm", 1)
    mgr.ensure_servable(mid)
    eng = ContinuousGenerateEngine(rt, slots=4, chunk_tokens=2)
    try:
        reqs = [
            (np.array([[1, 2, 3, 0]], np.int32), [3], 4),   # ragged: true len 3
            (np.array([[4, 5, 6, 7]], np.int32), None, 4),
            (np.array([[9, 9, 2, 1]], np.int32), None, 4),
        ]
        solo = [
            rt.generate(mid, ids, prompt_lengths=pl, max_new_tokens=new)
            for ids, pl, new in reqs
        ]
        # a busy device: the scheduler sleeps through its first boundary, so
        # all three are pending when it admits
        lab_faults.arm([lab_faults.FaultSpec(
            kind="freeze_scheduler", count=1, duration_s=1.0,
        )])
        RECORDER.clear()
        got = _engine_threads(eng, mid, reqs)
        for g, w in zip(got, solo):
            np.testing.assert_array_equal(g, w)  # greedy = deterministic
        assert eng.admitted == 3 and eng.peak_active == 3
        steps = RECORDER.snapshot(tail=64)["models"][str(mid)]["steps"]
        assert [s["admitted"] for s in steps if s["admitted"]] == [3]
        chunks = [s for s in steps if s["chunk"] > 0]
        assert chunks and all(s["active"] == 3 for s in chunks)
        assert eng.chunks == len(chunks) < 3 * len(chunks)
    finally:
        lab_faults.disarm()
        eng.close()
        mgr.close()


def test_engine_seeded_runs_solo(tmp_path):
    """An explicit seed promises a reproducible solo sample stream — it must
    bypass the engine's shared steps, and differ by seed."""
    from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
    from tfservingcache_tpu.types import ModelId

    mgr, rt = _lm_stack(tmp_path)
    mid = ModelId("lm", 1)
    mgr.ensure_servable(mid)
    eng = ContinuousGenerateEngine(rt)
    try:
        ids = np.array([[1, 2, 3]], np.int32)
        a = eng.generate(mid, ids, max_new_tokens=8, temperature=0.9, seed=7)
        b = eng.generate(mid, ids, max_new_tokens=8, temperature=0.9, seed=7)
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(
            a, rt.generate(mid, ids, max_new_tokens=8, temperature=0.9, seed=7)
        )
        others = [
            eng.generate(mid, ids, max_new_tokens=8, temperature=0.9, seed=s)
            for s in (8, 9, 10)
        ]
        assert any((o != a).any() for o in others)
        # never entered the engine: no scheduler, no arena, no admission
        assert eng.admitted == 0 and eng._scheds == {}
        assert mid not in rt._slot_states
    finally:
        eng.close()
        mgr.close()


async def test_rest_generate_deadline_504(tmp_path, monkeypatch):
    """A hung generate answers 504 DEADLINE_EXCEEDED at load_timeout_s
    instead of wedging the client (VERDICT r2 weak #7)."""
    import time as _time

    from tfservingcache_tpu.protocol.backend import BackendError
    from tfservingcache_tpu.protocol.local_backend import LocalServingBackend

    mgr, rt = _lm_stack(tmp_path)
    mgr.load_timeout_s = 0.5

    def slow_generate(*a, **kw):
        _time.sleep(5.0)
        raise AssertionError("unreachable in test")

    monkeypatch.setattr(rt, "generate", slow_generate)
    backend = LocalServingBackend(mgr, batch_window_ms=0.0)
    try:
        # seeded: the one kind of request that reaches runtime.generate
        body = json.dumps(
            {"input_ids": [[1, 2, 3]], "max_new_tokens": 2, "seed": 1}
        ).encode()
        with pytest.raises(BackendError) as ei:
            await backend.handle_rest("POST", "lm", 1, "generate", body)
        assert ei.value.http_status == 504
    finally:
        backend.close()
        mgr.close()


def test_engine_concurrent_stress(tmp_path):
    """Unsynchronized concurrent load: 32 requests from 16 threads (more
    than lanes, more than cores) with ragged prompts and mixed budgets all
    complete, greedy results match solo runs, rows did share steps, and the
    arena drains."""
    import sys
    import threading

    from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
    from tfservingcache_tpu.types import ModelId

    mgr, rt = _lm_stack(tmp_path)
    mid = ModelId("lm", 1)
    mgr.ensure_servable(mid)
    eng = ContinuousGenerateEngine(rt, slots=4, chunk_tokens=2)
    old_switch = sys.getswitchinterval()
    try:
        rng = np.random.default_rng(0)
        reqs = []
        for _ in range(32):
            width = int(rng.integers(2, 7))
            L = int(rng.integers(1, width + 1))     # ragged: true len <= width
            ids = np.zeros((1, width), np.int32)
            ids[0, :L] = rng.integers(1, 97, L)
            reqs.append((ids, [L], int(rng.choice([3, 4, 7]))))
        want = [
            rt.generate(mid, ids, prompt_lengths=pl, max_new_tokens=new)
            for ids, pl, new in reqs
        ]
        got: list = [None] * len(reqs)
        errors: list = []

        def worker(k: int) -> None:
            for j in range(k, len(reqs), 16):
                ids, pl, new = reqs[j]
                try:
                    got[j] = eng.generate(
                        mid, ids, prompt_lengths=pl, max_new_tokens=new
                    )
                except BaseException as e:  # noqa: BLE001
                    errors.append((j, e))

        sys.setswitchinterval(1e-5)
        ts = [threading.Thread(target=worker, args=(k,)) for k in range(16)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
        assert not errors, errors
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        assert eng.admitted == 32
        # the stress is pointless if rows never shared a step: 16 threads
        # over 4 lanes must have filled more than one
        assert eng.peak_active >= 2
        st = rt._slot_states[mid]
        st.check_page_conservation()
        assert len(st.free_pages) == st.arena_pages and not st.lane_pages
    finally:
        sys.setswitchinterval(old_switch)
        eng.close()
        mgr.close()


MOE_TINY = {
    "vocab_size": 97, "d_model": 32, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 2, "d_ff": 64, "n_experts": 4, "top_k": 2,
    "aux_loss_weight": 0.01, "max_seq": 64, "dtype": "bfloat16",
}


def test_moe_lm_generation(tmp_path):
    """KV-cached decode for the MoE family: first sampled token must equal
    the full-forward argmax (the dropless top-2 layer is row-invariant, so
    the padded prefill and the unpadded forward agree), greedy
    decode is deterministic, and the REST :generate verb serves it."""
    import jax

    from tfservingcache_tpu.models.generation import generate as gen
    from tfservingcache_tpu.models.registry import build

    model = build("moe_lm", MOE_TINY)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.array([[5, 9, 2]], np.int32)
    toks = np.asarray(gen(model, params, ids, max_new_tokens=4))
    assert toks.shape == (1, 4)
    # first token == argmax of the full (uncached) forward's last position
    full = model.apply(params, {"input_ids": ids})["logits"]
    want_first = int(np.argmax(np.asarray(full)[0, -1]))
    assert int(toks[0, 0]) == want_first
    # greedy is deterministic
    toks2 = np.asarray(gen(model, params, ids, max_new_tokens=4))
    np.testing.assert_array_equal(toks, toks2)


async def test_rest_generate_moe(tmp_path):
    from tfservingcache_tpu.cache.disk_cache import ModelDiskCache
    from tfservingcache_tpu.cache.manager import CacheManager
    from tfservingcache_tpu.cache.providers.disk import DiskModelProvider
    from tfservingcache_tpu.protocol.local_backend import LocalServingBackend

    store = tmp_path / "store"
    export_artifact("moe_lm", str(store), name="moe", version=1, config=MOE_TINY)
    mgr = CacheManager(
        DiskModelProvider(str(store)),
        ModelDiskCache(str(tmp_path / "cache"), capacity_bytes=1 << 30),
        TPUModelRuntime(ServingConfig(platform="cpu")),
    )
    backend = LocalServingBackend(mgr)
    try:
        body = json.dumps({"input_ids": [[1, 2, 3]], "max_new_tokens": 3}).encode()
        resp = await backend.handle_rest("POST", "moe", 1, "generate", body)
        assert resp.status == 200, resp.body
        toks = json.loads(resp.body)["tokens"]
        assert len(toks) == 1 and len(toks[0]) == 3
    finally:
        backend.close()
        mgr.close()


async def test_rest_and_grpc_predict_deadline_504(tmp_path, monkeypatch):
    """A wedged device call in PREDICT (e.g. the accelerator transport
    dropping mid-serving) answers 504 at load_timeout_s on both protocols
    instead of holding the connection forever — same bound :generate and
    the cold path already honor."""
    import threading

    from tfservingcache_tpu.protocol.backend import BackendError
    from tfservingcache_tpu.protocol.local_backend import LocalServingBackend
    from tfservingcache_tpu.protocol.protos import tf_serving_pb2 as sv
    from tfservingcache_tpu.types import ModelId

    mgr, rt = _lm_stack(tmp_path)
    mgr.ensure_servable(ModelId("lm", 1))
    mgr.load_timeout_s = 0.5
    release = threading.Event()  # frees the wedged threads at teardown

    def slow_predict(*a, **kw):
        release.wait(30.0)
        raise RuntimeError("released")

    monkeypatch.setattr(rt, "predict", slow_predict)
    backend = LocalServingBackend(mgr, batch_window_ms=0.0)
    try:
        body = json.dumps({"inputs": {"input_ids": [[1, 2, 3]]}}).encode()
        with pytest.raises(BackendError) as ei:
            await backend.handle_rest("POST", "lm", 1, "predict", body)
        assert ei.value.http_status == 504

        req = sv.PredictRequest()
        req.model_spec.name = "lm"
        req.model_spec.version.value = 1
        t = req.inputs["input_ids"]
        t.dtype = 9  # DT_INT64
        t.tensor_shape.dim.add().size = 1
        t.tensor_shape.dim.add().size = 3
        t.int64_val.extend([1, 2, 3])
        with pytest.raises(BackendError) as ei:
            await backend.predict(req)
        assert ei.value.http_status == 504
    finally:
        release.set()
        backend.close()
        mgr.close()
