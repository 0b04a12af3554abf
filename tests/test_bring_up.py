"""The bring-up account (utils/bring_up.py, ISSUE 51): every compiled program
by name, every stage of a model's way onto the chip, the device's bytes at a
stage's end, all on the tracer, the flight recorder and the metric families
the repo has."""

from __future__ import annotations

import statistics
import threading
import time

import aiohttp
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfservingcache_tpu.utils import bring_up
from tfservingcache_tpu.utils.bring_up import ACCOUNT, BUILT, STAGE_OF
from tfservingcache_tpu.utils.flight_recorder import RECORDER
from tfservingcache_tpu.utils.metrics import Metrics
from tfservingcache_tpu.utils.tracing import (
    TRACER,
    deserialize_span,
    serialize_span,
)

TRACE, LOWER, COMPILE = STAGE_OF     # the three build events, in jax's order
# no other test file's tiny model (a config another test of this worker ran
# would find its programs built): 83 tokens, and a ``d_ff`` of its own a test
TINY_LM = {
    "vocab_size": 83, "d_model": 48, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 2, "d_ff": 96, "max_seq": 64,
}


@pytest.fixture
def account():
    """A registry of this test's own behind the process's listeners, the
    recorder's bring-up records emptied, the label budget fresh."""
    metrics = Metrics()
    before, labels = ACCOUNT.metrics, set(ACCOUNT.labels)
    bring_up.install(metrics)
    ACCOUNT.labels.clear()
    RECORDER.clear()
    try:
        yield metrics
    finally:
        ACCOUNT.metrics = before
        ACCOUNT.labels.clear()
        ACCOUNT.labels.update(labels)


def seconds(metrics, program, stage):
    return metrics.registry.get_sample_value(
        "tpusc_program_build_seconds_total",
        {"program": program, "stage": stage}) or 0.0


def builds(metrics, program, cache):
    return metrics.registry.get_sample_value(
        "tpusc_program_builds_total", {"program": program, "cache": cache}) or 0.0


def records(program):
    return [r for r in RECORDER.bring_up()["programs"] if r["program"] == program]


def find(span, name):
    hits = [span] if span.name == name else []
    for c in span.children:
        hits += find(c, name)
    return hits


# -- every compiled program, by name ------------------------------------------

def test_a_fresh_jit_is_booked_under_its_name_and_its_second_call_books_nothing(account):
    @jax.jit
    def bring_up_case_fresh(x):
        return jnp.sin(x) * 2 + jnp.where(x > 0, x, 0.0)     # jitted helpers nest

    x = jnp.ones((8,))
    RECORDER.clear()
    bring_up_case_fresh(x).block_until_ready()
    name = "bring_up_case_fresh"
    rec, = records(name)
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0 and rec["compile_s"] > 0
    assert rec["thread"] == threading.current_thread().name
    assert abs(rec["t_wall"] - time.time()) < 60
    assert seconds(account, name, "trace") == pytest.approx(rec["trace_s"])
    assert seconds(account, name, "lower") == pytest.approx(rec["lower_s"])
    built = seconds(account, name, "compile") + seconds(account, name, "cache_load")
    assert built == pytest.approx(rec["compile_s"])
    assert sum(builds(account, name, c) for c in ("hit", "miss", "off")) == 1
    # the helpers traced INSIDE it (sin, multiply, _where, add) are part of it
    assert not records("_where") and seconds(account, "_where", "trace") == 0.0
    total = account.render()
    bring_up_case_fresh(x).block_until_ready()
    assert account.render() == total and len(records(name)) == 1


def test_a_persistent_cache_turns_the_next_build_into_a_hit(account, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    def make():
        @jax.jit
        def bring_up_case_cached(x):
            return jnp.cos(x) + 3

        return bring_up_case_cached

    prior = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs,
             jax.config.jax_persistent_cache_min_entry_size_bytes)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cc"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        x = jnp.ones((4,))
        x.block_until_ready()
        make()(x).block_until_ready()
        make()(x).block_until_ready()       # the same module again: a rebuild
    finally:
        for key, value in zip(("jax_compilation_cache_dir",
                               "jax_persistent_cache_min_compile_time_secs",
                               "jax_persistent_cache_min_entry_size_bytes"), prior):
            jax.config.update(key, value)
        cc.reset_cache()
    name = "bring_up_case_cached"
    first, second = records(name)
    assert (first["cache"], second["cache"]) == ("miss", "hit")
    assert builds(account, name, "miss") == 1 and builds(account, name, "hit") == 1
    assert seconds(account, name, "compile") == pytest.approx(first["compile_s"])
    assert seconds(account, name, "cache_load") == pytest.approx(second["compile_s"])


def test_a_nameless_event_goes_to_the_threads_last_traced_program(account):
    """The compilation cache's events carry no name: fed to the listeners by
    hand, between a trace's end and a compile's, they land on the program
    whose trace last ended on this thread."""
    ACCOUNT.on_begin(TRACE, 0.0, fun_name="case_nameless")
    ACCOUNT.on_end(TRACE, 0.25, fun_name="case_nameless")
    ACCOUNT.on_event("/jax/compilation_cache/compile_requests_use_cache")
    ACCOUNT.on_event("/jax/compilation_cache/cache_hits")
    ACCOUNT.on_end("/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    ACCOUNT.on_begin(COMPILE, 0.0)
    ACCOUNT.on_end(COMPILE, 0.75)            # no name either
    rec, = records("case_nameless")
    assert rec["cache"] == "hit" and rec["compile_s"] == 0.75
    assert seconds(account, "case_nameless", "cache_load") == 0.75
    assert seconds(account, "case_nameless", "trace") == 0.25
    assert builds(account, "case_nameless", "hit") == 1


def test_a_nested_trace_books_nothing_of_its_own(account):
    ACCOUNT.on_begin(TRACE, 0.0, fun_name="case_outer")
    ACCOUNT.on_begin(TRACE, 0.0, fun_name="case_inner")
    ACCOUNT.on_end(TRACE, 0.125, fun_name="case_inner")
    ACCOUNT.on_end(TRACE, 0.5, fun_name="case_outer")
    ACCOUNT.on_begin(LOWER, 0.0, fun_name="jit(case_outer)")
    ACCOUNT.on_begin(TRACE, 0.0, fun_name="case_kernel_helper")   # a kernel body's
    ACCOUNT.on_end(TRACE, 0.0625, fun_name="case_kernel_helper")
    ACCOUNT.on_end(LOWER, 0.25, fun_name="jit(case_outer)")
    ACCOUNT.on_begin(COMPILE, 0.0, fun_name="jit(case_outer)")
    ACCOUNT.on_end(COMPILE, 1.0, fun_name="jit(case_outer)")
    rec, = records("case_outer")
    assert (rec["trace_s"], rec["lower_s"], rec["compile_s"]) == (0.5, 0.25, 1.0)
    assert rec["cache"] == "off"
    assert builds(account, "case_outer", "off") == 1
    for inner in ("case_inner", "case_kernel_helper"):
        assert seconds(account, inner, "trace") == 0.0 and not records(inner)


def test_the_program_label_is_bounded(account):
    for i in range(bring_up.MAX_PROGRAM_LABELS + 6):
        ACCOUNT.on_begin(TRACE, 0.0, fun_name=f"case_many_{i}")
        ACCOUNT.on_end(TRACE, 1.0, fun_name=f"case_many_{i}")
    programs = {s.labels["program"] for f in account.registry.collect()
                if f.name == "tpusc_program_build_seconds"
                for s in f.samples if s.name.endswith("_total")}
    assert len(programs) == bring_up.MAX_PROGRAM_LABELS + 1
    assert seconds(account, bring_up.OTHER, "trace") == 6.0


def test_a_build_under_a_request_span_is_in_the_serialised_trace(account):
    @jax.jit
    def bring_up_case_traced(x):
        return x * 5

    x = jnp.ones((3,))
    x.block_until_ready()
    with TRACER.span("rest", path="/v1/models/m:predict") as root:
        with TRACER.span("infer"):
            bring_up_case_traced(x).block_until_ready()
    wire = deserialize_span(serialize_span(root))
    child, = [c for c in find(wire, "program_build")
              if c.attrs["program"] == "bring_up_case_traced"]
    assert child.attrs["cache"] in ("hit", "miss", "off")
    rec, = records("bring_up_case_traced")
    assert child.duration_s == pytest.approx(
        rec["trace_s"] + rec["lower_s"] + rec["compile_s"], abs=1e-5)
    assert find(wire, "infer")[0].children[-1].name == "program_build"


# -- every stage, and the device's bytes at its end -----------------------------

class StubDevice:
    def __init__(self, in_use, peak, reserved):
        self.stats = {"bytes_in_use": in_use, "peak_bytes_in_use": peak,
                      "bytes_reserved": reserved, "bytes_limit": 1 << 34}

    def memory_stats(self):
        return self.stats


def gauge(metrics, stage, what):
    return metrics.registry.get_sample_value(
        "tpusc_device_bytes", {"stage": stage, "what": what})


def test_a_stage_is_its_wall_less_the_builds_on_its_thread(account):
    with bring_up.stage("engine_build", account, model="m@1") as late:
        time.sleep(0.02)
        ACCOUNT.on_begin(TRACE, 0.0, fun_name="case_inside_a_stage")
        ACCOUNT.on_end(TRACE, 0.015, fun_name="case_inside_a_stage")
        late["owned"] = 7
    rec = RECORDER.bring_up()["stages"][-1]
    assert rec["stage"] == "engine_build" and rec["model"] == "m@1"
    assert rec["build_s"] == pytest.approx(0.015) and rec["owned"] == 7
    assert rec["wall_s"] >= 0.02
    assert rec["seconds"] == pytest.approx(rec["wall_s"] - 0.015)
    assert account.registry.get_sample_value(
        "tpusc_cold_stage_seconds_sum", {"stage": "engine_build"}
    ) == pytest.approx(rec["seconds"])
    assert not BUILT.flag           # the builds inside are the stage's
    assert "bytes_in_use" not in rec    # no devices given, nothing read


def test_stubbed_memory_stats_gauges_watermark_and_record_agree(account):
    devices = [StubDevice(10, 20, 0), StubDevice(300, 700, 4096), StubDevice(5, 900, 0)]
    with TRACER.span("rest") as root:
        with bring_up.stage("load", account, devices, model="m@1"):
            pass
    rec = RECORDER.bring_up()["stages"][-1]
    # the FULLEST device (by bytes in use), all three numbers from it
    assert (rec["bytes_in_use"], rec["peak"], rec["reserved"]) == (300, 700, 4096)
    assert gauge(account, "load", "in_use") == 300
    assert gauge(account, "load", "peak") == 700
    assert gauge(account, "load", "reserved") == 4096
    assert RECORDER.watermarks()["device_bytes_peak"] == 700.0
    child, = find(root, "load")
    assert child.attrs["peak"] == 700 and child.attrs["reserved"] == 4096
    # a backend without allocator statistics sets nothing
    none = type("Cpu", (), {"memory_stats": lambda self: None})()
    with bring_up.stage("engine_build", account, [none]):
        pass
    assert gauge(account, "engine_build", "peak") is None
    assert "peak" not in RECORDER.bring_up()["stages"][-1]


def test_a_first_run_is_its_calls_wall_less_the_build(account):
    @jax.jit
    def bring_up_case_first(x):
        return (x @ x).sum()

    x = jnp.ones((64, 64))
    x.block_until_ready()
    BUILT.flag = False
    devices = [StubDevice(1000, 2000, 512)]
    with TRACER.span("rest") as root:
        out = bring_up_case_first(x)
        assert BUILT.flag                   # the call sites' one test
        rec = bring_up.first_run(("bring_up_case_first",), out, account,
                                 devices, chunk=4)
    build, = records("bring_up_case_first")
    built = build["trace_s"] + build["lower_s"] + build["compile_s"]
    assert rec["program"] == "bring_up_case_first" and rec["chunk"] == 4
    assert rec["build_s"] == pytest.approx(built)
    assert rec["wall_s"] >= built and rec["seconds"] == pytest.approx(
        rec["wall_s"] - built)
    assert gauge(account, "first_run:bring_up_case_first", "reserved") == 512
    child, = find(root, "first_run")
    assert child.attrs["program"] == "bring_up_case_first"
    assert child.duration_s == pytest.approx(rec["seconds"])
    # the next call builds nothing: the flag stays down, nothing is booked
    assert not BUILT.flag
    bring_up_case_first(x).block_until_ready()
    assert not BUILT.flag
    assert len([s for s in RECORDER.bring_up()["stages"]
                if s["stage"] == "first_run"]) == 1


def test_another_programs_build_only_clears_the_flag(account):
    @jax.jit
    def bring_up_case_helper(x):
        return x - 1

    out = bring_up_case_helper(jnp.ones((2,)))
    assert BUILT.flag
    assert bring_up.first_run(("_paged_decode_chunk_jit",), out, account) is None
    assert not BUILT.flag
    assert not [s for s in RECORDER.bring_up()["stages"] if s["stage"] == "first_run"]


# Mellum2's layers in small (tests/test_chunk_ahead.py's): three window layers
# and a global one, so the engine keeps a ring arena beside the pages
WINDOWED = {
    "vocab_size": 83, "d_model": 48, "n_layers": 4, "n_heads": 4, "n_kv_heads": 2,
    "head_dim": 16, "d_ff": 40, "n_experts": 4, "top_k": 2, "norm_topk_prob": True,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": 16, "max_seq": 128, "rope_theta": 10000.0,
    "rope_full": {"yarn": 4.0, "original_max": 32, "attention_factor": 1.1},
    "dtype": "float32"}


def test_a_first_run_reads_what_is_owned_from_a_state_whose_arrays_were_donated(
        tmp_path, account):
    """A decode chunk DONATES the arenas, the ring and the lane state: its
    first run books what the runtime owns right after the call, and a
    monitoring thread may ask between a launch and the state's rebinding. The
    count comes from shapes and shardings, never from buffers that are gone
    (my chip run, PR 51: a window model's engine crashed twice a set-up on a
    deleted ring, and recovery hid it from every check)."""
    from tfservingcache_tpu.config import ServingConfig
    from tfservingcache_tpu.models.registry import export_artifact
    from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
    from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
    from tfservingcache_tpu.types import Model, ModelId

    export_artifact("moe_lm", str(tmp_path), name="win", version=1, config=WINDOWED)
    rt = TPUModelRuntime(ServingConfig(platform="cpu"), account)
    mid = ModelId("win", 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / "win" / "1")))
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4, page_tokens=8,
                                   arena_pages=32, metrics=account)
    try:
        for n in (5, 9, 3, 2):          # chunks of 4, 4 + 4, 2, 1: four programs
            eng.generate(mid, np.arange(1, 6, dtype=np.int32)[None, :],
                         max_new_tokens=n)
        state = rt._slot_states[mid]
        owned = rt.owned_device_bytes()
        assert owned["arenas"] > 0
        for arr in (state.k, state.v, *state.window):
            arr.delete()                # as a launch leaves them, donated
        assert rt.owned_device_bytes() == owned
    finally:
        eng.close()
        rt.close()
    recovered = sum(
        s.value for f in account.registry.collect()
        if f.name == "tpusc_requests_recovered" for s in f.samples
        if s.name.endswith("_total"))
    assert recovered == 0           # no engine crash hidden by the recovery
    chunks = sorted(s["chunk"] for s in RECORDER.bring_up()["stages"]
                    if s.get("program") == "_paged_decode_chunk_jit")
    assert chunks == [1, 2, 4]
    assert [s["stage"] for s in RECORDER.bring_up()["stages"]].count(
        "engine_build") == 1


# -- one load and one :generate through a node -----------------------------------

def _node(tmp_path, d_ff):
    """A node over one tiny LM. Each test gives ``d_ff`` a value of its own: a
    config another test of this process ran would find its programs built."""
    from tfservingcache_tpu.config import Config
    from tfservingcache_tpu.models.registry import export_artifact
    from tfservingcache_tpu.server import CacheNode

    export_artifact("transformer_lm", str(tmp_path / "store"), name="lm",
                    version=1, config=dict(TINY_LM, d_ff=d_ff))
    cfg = Config()
    cfg.model_provider.type = "disk"
    cfg.model_provider.base_dir = str(tmp_path / "store")
    cfg.cache.base_dir = str(tmp_path / "cache")
    cfg.cache_node.rest_port = cfg.cache_node.grpc_port = 0
    cfg.serving.platform = "cpu"
    cfg.serving.generate_slots = 2
    cfg.serving.generate_chunk_tokens = 2
    return cfg, CacheNode(cfg)


async def _generate(session, url, n_prompt, max_new=4):
    async with session.post(
        f"{url}/v1/models/lm/versions/1:generate",
        json={"input_ids": [list(range(1, n_prompt + 1))], "max_new_tokens": max_new},
        headers={"traceparent": "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"},
    ) as resp:
        assert resp.status == 200, await resp.text()
        return deserialize_span(resp.headers["x-tpusc-trace"])


async def test_one_load_and_one_generate_fill_the_account(tmp_path, account):
    cfg, node = _node(tmp_path, d_ff=104)
    port, _ = await node.start()
    url = f"http://127.0.0.1:{port}"
    try:
        async with aiohttp.ClientSession() as s:
            await _generate(s, url, 5)
            async with s.get(f"{url}/monitoring/engine?reset=0") as resp:
                kept = (await resp.json())["bring_up"]
            async with s.get(f"{url}{cfg.metrics.path}") as resp:
                text = await resp.text()
    finally:
        await node.close()
    programs = {r["program"] for r in kept["programs"]}
    assert {"_slot_prefill_jit", "_paged_insert_jit",
            "_paged_decode_chunk_jit"} <= programs
    assert all(set(r) == {"program", "t_wall", "thread", "trace_s", "lower_s",
                          "compile_s", "cache"} for r in kept["programs"])
    stages = [r["stage"] for r in kept["stages"]]
    for stage in ("server_start", "backend_init", "load", "engine_build", "first_run"):
        assert stage in stages, stages
    firsts = {r["program"] for r in kept["stages"] if r["stage"] == "first_run"}
    assert {"_slot_prefill_jit", "_paged_decode_chunk_jit"} <= firsts
    owned = kept["owned"]
    assert owned["weights"] > 0 and owned["arenas"] > 0 and owned["lane_state"] == 0
    build = next(r for r in kept["stages"] if r["stage"] == "engine_build")
    assert build["owned"] == owned["weights"] + owned["arenas"]
    assert kept["listener"]["events"] > 0

    def sample(prefix, *labels):
        return [ln for ln in text.splitlines() if ln.startswith(prefix)
                and all(lab in ln for lab in labels)]

    for stage in ("engine_build", "server_start", "load", "first_run"):
        assert sample("tpusc_cold_stage_seconds_count", f'stage="{stage}"'), stage
    assert sample("tpusc_program_build_seconds_total",
                  'program="_paged_decode_chunk_jit"', 'stage="trace"')
    assert sample("tpusc_program_builds_total", 'program="_slot_prefill_jit"')
    # the CPU keeps no allocator statistics: no sample at all
    assert not sample("tpusc_device_bytes{")


async def test_a_compile_inside_a_window_is_named(tmp_path, account):
    """A prompt bucket the warm-up never sent: the build is in
    ``bring_up.programs`` with its wall time, counted, and a ``first_run``
    record names the bucket. ``?programs=1`` asks the kept executables for
    their temporaries (itself a build, booked like one)."""
    cfg, node = _node(tmp_path, d_ff=88)
    port, _ = await node.start()
    url = f"http://127.0.0.1:{port}"
    metrics = node.metrics

    def prefill_builds():
        return sum(builds(metrics, "_slot_prefill_jit", c)
                   for c in ("hit", "miss", "off"))

    try:
        async with aiohttp.ClientSession() as s:
            await _generate(s, url, 5)              # the warm-up: bucket 8
            await _generate(s, url, 6)              # the same bucket: no build
            warm, t_window = prefill_builds(), time.time()
            await _generate(s, url, 20)             # bucket 32: a compile
            window = prefill_builds()
            async with s.get(
                    f"{url}/monitoring/engine?reset=0&programs=1") as resp:
                kept = (await resp.json())["bring_up"]
    finally:
        await node.close()
    assert (warm, window) == (1, 2)
    late = [r for r in kept["programs"]
            if r["program"] == "_slot_prefill_jit" and r["t_wall"] >= t_window]
    assert len(late) == 1 and late[0]["thread"].startswith("tpusc-cdecode")
    first = [r for r in kept["stages"] if r["stage"] == "first_run"
             and r.get("program") == "_slot_prefill_jit"]
    assert [r["bucket"] for r in first] == [8, 32]
    memory = kept["program_memory"]
    assert {"_slot_prefill_jit", "_paged_decode_chunk_jit"} <= {
        m["program"] for m in memory}
    assert all(m["temp_bytes"] >= 0 and "error" not in m for m in memory)


async def test_a_build_on_the_requests_thread_is_in_its_trace(tmp_path, account):
    """``:predict`` runs on the serving pool under the request's context: the
    family program's build (a new batch bucket) is a ``program_build`` child
    of the request's own trace, by name."""
    cfg, node = _node(tmp_path, d_ff=120)
    port, _ = await node.start()
    url = f"http://127.0.0.1:{port}"
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"{url}/v1/models/lm/versions/1:predict",
                json={"inputs": {"input_ids": [[1, 2, 3, 4, 5]]}},
                headers={"traceparent": "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"},
            ) as resp:
                assert resp.status == 200, await resp.text()
                span = deserialize_span(resp.headers["x-tpusc-trace"])
    finally:
        await node.close()
    built = {c.attrs["program"] for c in find(span, "program_build")}
    assert "apply" in built, [c.attrs for c in find(span, "program_build")]
    load, = find(span, "load")
    assert "build_s" in load.attrs


# -- what the account costs -------------------------------------------------------

def test_the_hot_paths_test_costs_under_a_fifth_of_a_microsecond():
    """The form of ``test_host_span_costs_under_5us_with_no_capture``: the
    engine's call sites read one thread-local attribute after their program
    call."""
    flag = BUILT.flag
    BUILT.flag = False
    try:
        hits, per = 0, []
        for _ in range(10):
            t0 = time.perf_counter()
            for _ in range(100_000):
                if BUILT.flag:
                    hits += 1
            per.append((time.perf_counter() - t0) / 100_000)
    finally:
        BUILT.flag = flag
    assert hits == 0 and statistics.median(per) < 0.2e-6, per


def test_a_listener_call_costs_under_20us(account):
    def one_build(i):
        name = f"case_cost_{i % 8}"
        ACCOUNT.on_begin(TRACE, 0.0, fun_name=name)
        ACCOUNT.on_begin(TRACE, 0.0, fun_name="nested")
        ACCOUNT.on_end(TRACE, 1e-4, fun_name="nested")
        ACCOUNT.on_end(TRACE, 1e-3, fun_name=name)
        ACCOUNT.on_begin(LOWER, 0.0, fun_name=f"jit({name})")
        ACCOUNT.on_end(LOWER, 1e-3, fun_name=f"jit({name})")
        ACCOUNT.on_begin(COMPILE, 0.0, fun_name=f"jit({name})")
        ACCOUNT.on_event("/jax/compilation_cache/compile_requests_use_cache")
        ACCOUNT.on_event("/jax/compilation_cache/cache_hits")
        ACCOUNT.on_end(COMPILE, 1e-3, fun_name=f"jit({name})")
        return 10

    for i in range(200):
        one_build(i)
    per = []
    for _ in range(10):
        t0 = time.perf_counter()
        events = sum(one_build(i) for i in range(200))
        per.append((time.perf_counter() - t0) / events)
    assert statistics.median(per) < 20e-6, per
    assert len(RECORDER.bring_up()["programs"]) <= 256     # the newest only
