"""Mesh parity for the fast path (ISSUE 20): the continuous paged engine,
pipelined cold load, and host warm tier run on a single-process TP mesh and
emit EXACTLY the tokens the single-device path emits. Runs on the virtual
multi-device CPU backend (conftest forces >= 2 devices via
--xla_force_host_platform_device_count); tools/ci_check.sh additionally
re-runs this module with the count pinned to exactly 2."""

import io

import aiohttp
import numpy as np

from tfservingcache_tpu.cache.disk_cache import ModelDiskCache
from tfservingcache_tpu.cache.manager import CacheManager
from tfservingcache_tpu.cache.providers.disk import DiskModelProvider
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import export_artifact
from tfservingcache_tpu.parallel.mesh import make_mesh
from tfservingcache_tpu.protocol.local_backend import LocalServingBackend
from tfservingcache_tpu.protocol.rest import RestServingServer
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
from tfservingcache_tpu.types import ModelId
from tfservingcache_tpu.utils.metrics import Metrics

# float32 everywhere: TP matmul reductions on the same values in the same
# dtype reassociate identically on the CPU backend, so mesh-vs-single parity
# is exact token equality (precedent: test_multichip_serving greedy tests)
SMALL = {
    "vocab_size": 128,
    "d_model": 64,
    "n_layers": 2,
    "n_heads": 4,
    "n_kv_heads": 2,
    "d_ff": 128,
    "max_seq": 128,
    "dtype": "float32",
}
MID = ModelId("lm", 1)
PT = 16  # page_tokens for every paged engine in this module


def _store(tmp_path):
    store = tmp_path / "store"
    export_artifact(
        "transformer_lm", str(store), name="lm", version=1, config=SMALL
    )
    return store


def _stack(tmp_path, store, tag, mesh=None, metrics=None,
           host_tier_bytes=0, **cfg_kw):
    cfg_kw.setdefault("platform", "cpu")
    rt = TPUModelRuntime(
        ServingConfig(**cfg_kw), metrics, mesh=mesh,
        host_tier_bytes=host_tier_bytes,
    )
    mgr = CacheManager(
        DiskModelProvider(str(store)),
        ModelDiskCache(str(tmp_path / f"cache_{tag}"), capacity_bytes=1 << 30),
        rt, metrics,
    )
    mgr.ensure_servable(MID)
    return rt, mgr


def _engine(rt, **kw):
    kw.setdefault("page_tokens", PT)
    kw.setdefault("share_prefix_bytes", 1 << 20)
    return ContinuousGenerateEngine(rt, slots=4, chunk_tokens=4, **kw)


def _shard_device_count(arr):
    return len({s.device for s in arr.addressable_shards})


# -- tentpole: continuous paged :generate parity on a 2-device mesh -----------

def test_continuous_paged_generate_greedy_parity_on_mesh(tmp_path):
    """Greedy continuous-engine decode on a forced 2-device TP mesh emits
    exactly the single-device tokens — across a chunked prefill, a shared
    prefix-cache hit, and a multi-turn conversation resume — with the paged
    arena actually sharded over the KV-head axis and the Pallas kernel
    forced off (the bitwise-pinned gather+einsum mesh branch)."""
    store = _store(tmp_path)
    rt1, _ = _stack(tmp_path, store, "one")
    mesh = make_mesh({"model": 2})
    rt2, _ = _stack(tmp_path, store, "mesh", mesh=mesh)
    assert rt2.mesh_lockstep is False  # single-process group -> fast path
    assert rt2.cold_pipeline_enabled is True
    assert rt2.mesh_topology() == {
        "mesh_devices": 2,
        "mesh_axes": {"model": 2},
        "mesh_fast_path": True,
    }
    assert rt1.mesh_topology() is None

    # mesh engine ASKS for the kernel; the mesh branch must refuse it and
    # still match the single-device kernel-off reference bitwise
    eng1 = _engine(rt1, prefill_chunk_tokens=8,
                   conversation_kv_bytes=16 << 20, paged_kernel=False)
    eng2 = _engine(rt2, prefill_chunk_tokens=8,
                   conversation_kv_bytes=16 << 20, paged_kernel=True)
    rng = np.random.default_rng(7)
    # 24 tokens: > page_tokens (a full page enters the prefix index) and
    # 3 chunks of the chunked-prefill interleaver (prefill_chunk_tokens=8)
    p1 = rng.integers(1, SMALL["vocab_size"], 24).astype(np.int32)
    try:
        out1 = eng1.generate(MID, p1[None, :], max_new_tokens=8,
                             conversation_id="conv")
        out2 = eng2.generate(MID, p1[None, :], max_new_tokens=8,
                             conversation_id="conv")
        np.testing.assert_array_equal(out1, out2)

        st = rt2._slot_states[MID]
        assert st.kernel is False  # mesh refuses the Pallas kernel
        assert _shard_device_count(st.k) == 2
        spec = st.k.sharding.spec
        assert "model" in tuple(spec), spec  # KV-head axis is partitioned

        # prefix-cache hit on the sharded arena: the identical prompt
        # (fresh conversation) prefills only the sub-page tail
        r1, s1 = eng1.generate(MID, p1[None, :], max_new_tokens=8,
                               return_stats=True)
        r2, s2 = eng2.generate(MID, p1[None, :], max_new_tokens=8,
                               return_stats=True)
        np.testing.assert_array_equal(r1, r2)
        assert s2[0]["prefill_tokens"] < p1.shape[0]
        assert s1[0]["prefill_tokens"] == s2[0]["prefill_tokens"]

        # conversation resume: turn 2 replays parked sharded pages
        extra = rng.integers(1, SMALL["vocab_size"], 5).astype(np.int32)
        p2 = np.concatenate([p1, out1[0].astype(np.int32), extra])
        t1 = eng1.generate(MID, p2[None, :], max_new_tokens=8,
                           conversation_id="conv")
        t2 = eng2.generate(MID, p2[None, :], max_new_tokens=8,
                           conversation_id="conv")
        np.testing.assert_array_equal(t1, t2)

        rt2._slot_states[MID].check_page_conservation()
        rt1._slot_states[MID].check_page_conservation()
    finally:
        eng1.close()
        eng2.close()
        rt1.close()
        rt2.close()


def test_seeded_sampling_parity_on_mesh_solo_path(tmp_path):
    """Seeded sampling goes through the deterministic solo path (the
    continuous engine rolls its own first-token seed), where mesh-vs-single
    parity is exact for the same (seed, temperature, top_k)."""
    store = _store(tmp_path)
    rt1, _ = _stack(tmp_path, store, "one")
    rt2, _ = _stack(tmp_path, store, "mesh", mesh=make_mesh({"model": 2}))
    ids = np.array([[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8, 1, 8]],
                   np.int32)
    try:
        for temp, top_k in ((0.7, 8), (1.0, 0)):
            a = rt1.generate(MID, ids, max_new_tokens=10, temperature=temp,
                             top_k=top_k, seed=1234)
            b = rt2.generate(MID, ids, max_new_tokens=10, temperature=temp,
                             top_k=top_k, seed=1234)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        rt1.close()
        rt2.close()


def test_mesh_fast_path_off_restores_lockstep_solo_dispatch(tmp_path):
    """serving.mesh_fast_path=false is the A/B lever back to the old
    behavior: lockstep mesh, serialized cold load, no host tier, and the
    continuous engine routing every request down the solo path — with the
    same tokens (the fallback is slower, never different)."""
    store = _store(tmp_path)
    rt1, _ = _stack(tmp_path, store, "one")
    rt2, _ = _stack(tmp_path, store, "mesh", mesh=make_mesh({"model": 2}),
                    mesh_fast_path=False, host_tier_bytes=64 << 20)
    assert rt2.mesh_lockstep is True
    assert rt2.cold_pipeline_enabled is False
    assert rt2._host_tier is None  # lockstep keeps the warm tier off
    assert rt2.mesh_topology()["mesh_fast_path"] is False
    eng1 = _engine(rt1)
    eng2 = _engine(rt2)
    p = np.array([[5, 17, 40, 3, 9, 61, 2, 11]], np.int32)
    try:
        out1 = eng1.generate(MID, p, max_new_tokens=8)
        out2 = eng2.generate(MID, p, max_new_tokens=8)
        np.testing.assert_array_equal(out1, out2)
        # lockstep dispatch never builds a paged slot arena
        assert MID not in rt2._slot_states
        assert MID in rt1._slot_states
    finally:
        eng1.close()
        eng2.close()
        rt1.close()
        rt2.close()


async def test_lockstep_runtime_gets_no_engine_and_still_answers_generate(tmp_path):
    """A lockstep runtime's device-op stream must not depend on a host
    scheduler thread: its backend builds no engine, and an unseeded REST
    :generate goes alone through runtime.generate — same tokens as the
    engine serves on one device."""
    import json

    store = _store(tmp_path)
    rt1, mgr1 = _stack(tmp_path, store, "one")
    rt2, mgr2 = _stack(tmp_path, store, "mesh", mesh=make_mesh({"model": 2}),
                       mesh_fast_path=False)
    assert rt2.mesh_lockstep is True
    one = LocalServingBackend(mgr1, kv_page_tokens=PT)
    lock = LocalServingBackend(mgr2, kv_page_tokens=PT)
    body = json.dumps(
        {"input_ids": [[5, 17, 40, 3, 9, 61, 2, 11]], "max_new_tokens": 8}
    ).encode()
    try:
        assert isinstance(one._generator, ContinuousGenerateEngine)
        assert lock._generator is None
        want = await one.handle_rest("POST", "lm", None, "generate", body)
        got = await lock.handle_rest("POST", "lm", None, "generate", body)
        assert got.status == want.status == 200
        assert json.loads(got.body) == json.loads(want.body)
        assert MID in rt1._slot_states and MID not in rt2._slot_states
    finally:
        one.close()
        lock.close()
        rt1.close()
        rt2.close()


# -- cold load: pipelined vs serialized on the mesh ---------------------------

def test_cold_load_pipelined_vs_serialized_parity_on_mesh(tmp_path):
    """The per-host packed-chunk streaming loader feeds each device only its
    own shards; the result must be indistinguishable from the serialized
    shard_params transfer — same shardings, same params bytes, same tokens.
    The host warm tier rides the same path: demote then re-promote through
    the sharded packed replay and generate again, exactly."""
    store = _store(tmp_path)
    mesh = make_mesh({"model": 2})
    rt_pipe, mgr_pipe = _stack(
        tmp_path, store, "pipe", mesh=mesh,
        cold_load_pipeline=True, host_tier_bytes=256 << 20,
    )
    rt_ser, _ = _stack(tmp_path, store, "ser", mesh=mesh,
                       cold_load_pipeline=False)
    assert rt_pipe.cold_pipeline_enabled is True
    assert rt_ser.cold_pipeline_enabled is False
    ids = np.array([[3, 1, 4, 1, 5, 9, 2, 6]], np.int32)
    try:
        import jax

        wq_pipe = rt_pipe._resident.get(MID).params["layers"][0]["attn"]["wq"]
        wq_ser = rt_ser._resident.get(MID).params["layers"][0]["attn"]["wq"]
        assert _shard_device_count(wq_pipe) == 2
        assert wq_pipe.sharding.spec == wq_ser.sharding.spec
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(wq_pipe)),
            np.asarray(jax.device_get(wq_ser)),
        )
        a = np.asarray(rt_pipe.generate(MID, ids, max_new_tokens=10))
        b = np.asarray(rt_ser.generate(MID, ids, max_new_tokens=10))
        np.testing.assert_array_equal(a, b)

        # demote to the host tier, then promote through the sharded replay
        rt_pipe.unload(MID)
        rt_pipe.drain_demotions()
        assert rt_pipe.host_tier_contains(MID)
        mgr_pipe.ensure_servable(MID)
        wq_back = rt_pipe._resident.get(MID).params["layers"][0]["attn"]["wq"]
        assert _shard_device_count(wq_back) == 2
        c = np.asarray(rt_pipe.generate(MID, ids, max_new_tokens=10))
        np.testing.assert_array_equal(a, c)
    finally:
        rt_pipe.close()
        rt_ser.close()


# -- sharded arena census + per-shard byte accounting -------------------------

def test_sharded_arena_census_and_per_shard_bytes_gauge(tmp_path):
    """A mixed-priority burst on the sharded int8 arena keeps the page
    refcount census green, and tpusc_gen_kv_arena_bytes reports ACTUAL
    addressable shard bytes (sum over shards), not the logical global
    array size — the capacity number an operator budgets HBM against."""
    metrics = Metrics(model_labels=True)
    store = _store(tmp_path)
    mesh = make_mesh({"model": 2})
    rt, _ = _stack(tmp_path, store, "mesh", mesh=mesh, metrics=metrics,
                   kv_arena_dtype="int8")
    eng = _engine(rt, metrics=metrics)
    rng = np.random.default_rng(3)
    try:
        for i, pr in enumerate(("high", "normal", "low", "normal")):
            p = rng.integers(1, SMALL["vocab_size"], 10 + 3 * i)
            out = eng.generate(MID, p[None, :].astype(np.int32),
                               max_new_tokens=6, priority=pr)
            assert out.shape == (1, 6)
        st = rt._slot_states[MID]
        st.check_page_conservation()
        assert _shard_device_count(st.k) == 2

        def actual(arr):
            shards = getattr(arr, "addressable_shards", None) or ()
            return (sum(int(s.data.nbytes) for s in shards)
                    if shards else int(arr.nbytes))

        expect = actual(st.k) + actual(st.v)
        if st.scales is not None:
            expect += sum(actual(a) for a in st.scales.values())
        got = metrics.registry.get_sample_value(
            "tpusc_gen_kv_arena_bytes", {"dtype": "int8"}
        )
        assert got == expect, (got, expect)

        # per-class phase attribution (ISSUE 20 satellite): the class label
        # appears when model_labels is on, and each priority that ran has
        # decode samples under its own class
        for cls, n in (("high", 1), ("normal", 2), ("low", 1)):
            v = metrics.registry.get_sample_value(
                "tpusc_request_phase_seconds_count",
                {"phase": "decode", "engine": "continuous", "class": cls},
            )
            assert v is not None and v >= n, (cls, v)
    finally:
        eng.close()
        rt.close()


def test_phase_histogram_arity_without_model_labels(tmp_path):
    """model_labels=False keeps the old two-label series (no class label):
    cardinality-conscious deployments see the exact pre-ISSUE-20 schema."""
    metrics = Metrics()
    store = _store(tmp_path)
    rt, _ = _stack(tmp_path, store, "one", metrics=metrics)
    eng = _engine(rt, metrics=metrics)
    try:
        eng.generate(MID, np.array([[3, 5, 7, 9]], np.int32),
                     max_new_tokens=4, priority="high")
        v = metrics.registry.get_sample_value(
            "tpusc_request_phase_seconds_count",
            {"phase": "decode", "engine": "continuous"},
        )
        assert v is not None and v >= 1
    finally:
        eng.close()
        rt.close()


# -- traces: per-class TTFT pivot + /monitoring/engine mesh stamp -------------

def test_trace_roots_carry_priority_and_slo_report_pivots(tmp_path):
    """Generate trace roots carry priority + ttft_ms, and
    tools/slo_report.py --classes derives the same per-class pivot from a
    /monitoring/traces-style dump — the histogram/traces agreement check."""
    from tfservingcache_tpu.utils.tracing import TRACER

    from tools.slo_report import _classes_from_traces, render_classes

    store = _store(tmp_path)
    rt, _ = _stack(tmp_path, store, "one")
    eng = _engine(rt)
    rng = np.random.default_rng(11)
    try:
        for pr in ("high", "normal", "normal", "low"):
            p = rng.integers(1, SMALL["vocab_size"], 8).astype(np.int32)
            with TRACER.span("request", verb="generate"):
                eng.generate(MID, p[None, :], max_new_tokens=4, priority=pr)
        traces = TRACER.query(n=16)
        roots = [t for t in traces
                 if (t.get("attrs") or {}).get("priority") is not None]
        assert len(roots) >= 4
        for t in roots:
            attrs = t["attrs"]
            assert attrs["priority"] in ("high", "normal", "low")
            assert attrs["ttft_ms"] >= 0.0

        by_class = _classes_from_traces(traces)
        assert set(by_class) >= {"high", "normal", "low"}
        assert by_class["normal"]["n"] >= 2
        out = io.StringIO()
        render_classes({"traces": traces}, out=out)
        text = out.getvalue()
        assert "traces" in text
        for cls in ("high", "normal", "low"):
            assert cls in text
    finally:
        eng.close()
        rt.close()


async def test_monitoring_engine_reports_mesh_topology(tmp_path):
    """/monitoring/engine stamps the mesh topology on mesh runtimes — the
    observability surface that says WHICH fast path a node is running."""
    metrics = Metrics()
    store = _store(tmp_path)
    rt, mgr = _stack(tmp_path, store, "mesh", mesh=make_mesh({"model": 2}),
                     metrics=metrics)
    backend = LocalServingBackend(mgr)
    rest = RestServingServer(backend, metrics, require_version=False)
    rport = await rest.start(0, host="127.0.0.1")
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{rport}/v1/models/lm:generate",
                json={"input_ids": [[3, 5, 7, 9]], "max_new_tokens": 4},
            ) as r:
                assert r.status == 200, await r.text()
            async with s.get(
                f"http://127.0.0.1:{rport}/monitoring/engine?reset=0"
            ) as r:
                assert r.status == 200
                snap = await r.json()
        assert snap["mesh"] == {
            "mesh_devices": 2,
            "mesh_axes": {"model": 2},
            "mesh_fast_path": True,
        }
    finally:
        backend.close()
        await rest.close()
        rt.close()
