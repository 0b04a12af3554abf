"""The engine keeps ONE decode chunk in flight (ISSUE 40): on a decode-only
boundary chunk n+1 is launched before chunk n is fetched, so the device never
waits for the host's fetch, emission and launch.

  (i)   an engine run of mixed lengths, greedy and drawing lanes, gives every
        stream the tokens of the same run with every chunk launched at its own
        boundary (``_chain`` patched out: no option switches it off), for
        ``transformer_lm``, ``moe_lm`` with window layers and ``hybrid_lm``;
  (ii)  a lane that meets EOS inside chunk n with n+1 in flight ends at the
        EOS, the dropped steps are counted ``wasted``, its pages serve the next
        admission, whose stream equals its solo decode;
  (iii) a request that arrives while a chunk is in flight is admitted at the
        boundary after that chunk's fetch; ring ``ahead`` reads 0 for the
        first chunk after it and 1 for the chained ones;
  (iv)  ``ModelNotLoadedError`` and a ``lab_faults`` crash with a chunk in
        flight: the survivors re-prefill and continue token-identical;
  (v)   a mesh, an attached draft, a PREFILLING lane and a runtime with only
        ``slot_decode_chunk`` never chain;
  (vi)  a CPU profiler capture: chunk n+1's program call falls inside a
        ``tpusc.chunk_launch`` that opens before chunk n's
        ``tpusc.chunk_fetch``.
"""

import importlib.util
import itertools
import os
import re
import time

import jax
import numpy as np
import pytest

import tfservingcache_tpu.runtime.batcher as batcher_mod
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.lab import faults as lab_faults
from tfservingcache_tpu.lab.faults import FaultSpec
from tfservingcache_tpu.models.registry import export_artifact
from tfservingcache_tpu.runtime.base import ModelNotLoadedError
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import SlotDecodeState, TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId
from tfservingcache_tpu.utils.flight_recorder import RECORDER
from tfservingcache_tpu.utils.metrics import Metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PT = 8
TINY = {"vocab_size": 97, "d_model": 48, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 96, "max_seq": 64}
# Mellum2's layers in small: three window layers (16) and a global one, experts
WINDOWED = {
    "vocab_size": 97, "d_model": 48, "n_layers": 4, "n_heads": 4, "n_kv_heads": 2,
    "head_dim": 16, "d_ff": 32, "n_experts": 4, "top_k": 2, "norm_topk_prob": True,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": 16, "max_seq": 128, "rope_theta": 10000.0,
    "rope_full": {"yarn": 4.0, "original_max": 32, "attention_factor": 1.1},
    "dtype": "float32"}


def _hybrid_config():
    """LFM2's layers in small (``tests/test_hybrid_lm.py``'s): c c A c c c."""
    spec = importlib.util.spec_from_file_location(
        "bench_family_lfm2_moe_ahead",
        os.path.join(ROOT, "benchmark", "families", "lfm2_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    types = ["conv", "conv", "full_attention", "conv", "conv", "conv",
             "full_attention", "conv"]
    return mod.program_config({
        "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8,
        "num_experts_per_tok": 2, "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": 1.0, "conv_L_cache": 3, "conv_bias": False,
        "layer_types": types, "num_dense_layers": 2, "num_hidden_layers": 6,
        "vocab_size": 97, "norm_eps": 1e-5, "rope_theta": 1000000,
        "max_position_embeddings": 64, "torch_dtype": "float32",
        "assumed": {"head_dim": {"value": 16}, "gate_norm_eps": {"value": 1e-6}},
    })


FAMILIES = {
    "transformer_lm": lambda: ("transformer_lm", TINY),
    "moe_lm-window": lambda: ("moe_lm", WINDOWED),
    "hybrid_lm": lambda: ("hybrid_lm", _hybrid_config()),
}


def _load(tmp_path, name="lm", family="transformer_lm", config=TINY, mesh=None,
          metrics=None, **serving):
    export_artifact(family, str(tmp_path), name=name, version=1, config=config)
    rt = TPUModelRuntime(ServingConfig(platform="cpu", **serving), metrics, mesh=mesh)
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


def _steps(mid):
    models = RECORDER.snapshot(tail=RECORDER.ring_entries)["models"]
    return models.get(str(mid), {"steps": []})["steps"]


def _ran(mid):
    return [s for s in _steps(mid) if s["chunk"] > 0]


def _settle(eng, mid):
    """Until every launched chunk has its ring entry (a request is done at its
    last token's emission, a boundary before a chunk launched ahead is)."""
    deadline = time.monotonic() + 20.0
    while eng._scheds[mid]._flight is not None:
        assert time.monotonic() < deadline
        time.sleep(0.002)


def _unchained(monkeypatch):
    """Every chunk at its own boundary: the engine as it was."""
    monkeypatch.setattr(batcher_mod._ContinuousScheduler, "_chain",
                        lambda self, *_a: None)


def _req(prompt, max_new, temperature=0.0, top_k=0):
    return batcher_mod._ContinuousReq(prompt=np.asarray(prompt, np.int32),
                                      max_new=max_new, temperature=temperature,
                                      top_k=top_k)


def _finish(reqs, timeout=120.0):
    for r in reqs:
        assert r.done.wait(timeout) and r.error is None, r.error
    return [list(r.tokens) for r in reqs]


# -- (i) the tokens are the unchained engine's -------------------------------------

def _mixed_run(rt, mid, monkeypatch, vocab):
    """Seven rows over three lanes, queued before the first boundary: lengths
    from 3 to 30 tokens (a chunk is 4), greedy and drawing lanes side by side,
    admissions behind retirements. -> (tokens, ring steps that ran a chunk)"""
    seeds = itertools.count()
    monkeypatch.setattr(batcher_mod.secrets, "randbits", lambda _b: next(seeds))
    RECORDER.clear()
    eng = ContinuousGenerateEngine(rt, slots=3, chunk_tokens=4, page_tokens=PT,
                                   arena_pages=40)
    rng = np.random.default_rng(40)
    sampling = [(0.0, 0), (0.8, 5), (0.0, 0), (1.3, 3), (0.0, 0), (0.8, 0), (0.0, 0)]
    lengths = (30, 9, 17, 3, 26, 12, 5)
    try:
        reqs = [_req(rng.integers(1, vocab, 3 + 2 * i), n, t, k)
                for i, (n, (t, k)) in enumerate(zip(lengths, sampling))]
        eng._sched(mid).submit(reqs)
        out = _finish(reqs)
        _settle(eng, mid)
        rt._slot_states[mid].check_page_conservation()
        return out, _ran(mid)
    finally:
        eng.close()
        rt.drop_slot_state(mid)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_every_stream_is_the_unchained_engines(tmp_path, monkeypatch, family):
    name, config = FAMILIES[family]()
    rt, mid = _load(tmp_path, family=name, config=config)
    try:
        chained, ring = _mixed_run(rt, mid, monkeypatch, config["vocab_size"])
        _unchained(monkeypatch)
        plain, plain_ring = _mixed_run(rt, mid, monkeypatch, config["vocab_size"])
    finally:
        rt.close()
    assert chained == plain
    assert [len(t) for t in chained] == [30, 9, 17, 3, 26, 12, 5]
    # the same chunks in the same order, launched at another moment
    assert [(s["chunk"], s["active"]) for s in ring] == [
        (s["chunk"], s["active"]) for s in plain_ring]
    assert not any(s["ahead"] for s in plain_ring)
    ahead = [s["ahead"] for s in ring]
    assert ahead[0] == 0 and sum(ahead) >= len(ahead) // 3
    for prev, s in zip(ring, ring[1:]):
        # a retirement or an admission breaks the chain, nothing else does
        assert s["ahead"] == int(not prev["retired"] and not s["admitted"]), (prev, s)
    # no EOS: nothing was computed for a finished row beyond its chunk's end
    assert sum(s["wasted"] for s in ring) == sum(s["wasted"] for s in plain_ring)


# -- (ii) EOS inside chunk n with n+1 in flight --------------------------------------

def test_b_eos_mid_chain_drops_the_next_chunks_steps(tmp_path):
    """Lanes A and B decode, C waits for a lane. A meets EOS inside a chunk
    with the next one launched: A's stream ends AT the EOS, the chunk in
    flight computed ``chunk`` steps for it that are dropped and counted, and C
    is admitted on A's pages (the arena holds no others) and answers what the
    solo decoder answers."""
    probe, probe_mid = _load(tmp_path / "probe")
    prompts = [np.array(p, np.int32) for p in ([5, 17, 40], [8, 2, 61], [33, 9, 4])]
    try:
        rolls = [np.asarray(probe.generate(probe_mid, p[None], max_new_tokens=24,
                                           seed=0))[0] for p in prompts]
    finally:
        probe.close()
    # an EOS that A meets mid-stream and B never emits
    at, eos = next((i, int(t)) for i, t in enumerate(rolls[0])
                   if i >= 5 and t not in rolls[0][:i] and t not in rolls[1])
    assert at < 14
    metrics = Metrics()
    rt, mid = _load(tmp_path / "eos", config={**TINY, "eos_id": eos}, metrics=metrics)
    RECORDER.clear()
    # A: 3 + 16 tokens = 3 pages, B: 3 + 24 = 4, C: 3 + 16 = 3; the arena has 7
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4, page_tokens=PT,
                                   arena_pages=7, metrics=metrics)
    try:
        solo_c = np.asarray(rt.generate(mid, prompts[2][None], max_new_tokens=16,
                                        seed=0))[0]
        reqs = [_req(prompts[0], 16), _req(prompts[1], 24), _req(prompts[2], 16)]
        eng._sched(mid).submit(reqs)
        a, b, c = _finish(reqs)
        _settle(eng, mid)
        state = rt._slot_states[mid]
        state.check_page_conservation()
        assert len(state.free_pages) == state.arena_pages == 7
    finally:
        eng.close()
        rt.close()
    assert a == rolls[0][:at + 1].tolist() and a[-1] == eos
    assert b == rolls[1].tolist()
    stop = solo_c.tolist().index(eos) + 1 if eos in solo_c else len(solo_c)
    assert c == solo_c[:stop].tolist()
    ring = _steps(mid)
    found = next(i for i, s in enumerate(ring) if s["retired"])
    # token 0 is the prefill's; A's decode token at - 1 fell at place
    # (at - 1) % 4 of its chunk, whose remaining steps are the old waste
    assert ring[found]["wasted"] == 3 - (at - 1) % 4 and ring[found]["ahead"] == 1
    # the chunk in flight: both lanes live at its launch, A's four steps dropped
    nxt = ring[found + 1]
    assert (nxt["ahead"], nxt["active"], nxt["chunk"], nxt["wasted"]) == (1, 2, 4, 4)
    assert nxt["admitted"] == 0 and ring[found + 2]["admitted"] == 1
    assert ring[found + 2]["ahead"] == 0
    counted = metrics.gen_wasted_steps.labels("continuous")._value.get()
    assert counted == sum(s["wasted"] for s in ring)


# -- (iii) an arrival while a chunk is in flight -------------------------------------

def test_c_an_arrival_waits_for_the_chunk_in_flight(tmp_path):
    rt, mid = _load(tmp_path)
    RECORDER.clear()
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4, page_tokens=PT,
                                   arena_pages=16)
    late = _req([7, 8, 9, 10], 9)
    fetches = []
    real = rt.slot_decode_chunk_fetch

    def fetch(state, flight):
        fetches.append(len(_ran(mid)))
        if len(fetches) == 3:
            # the third chunk's fetch: the fourth is up already
            assert eng._scheds[mid]._flight is not None
            eng._sched(mid).submit([late])
        return real(state, flight)

    rt.slot_decode_chunk_fetch = fetch
    try:
        solo = np.asarray(rt.generate(mid, late.prompt[None], max_new_tokens=9,
                                      seed=0))[0]
        first = _req([1, 2, 3], 40)
        eng._sched(mid).submit([first])
        _finish([first, late])
        _settle(eng, mid)
    finally:
        eng.close()
        rt.close()
    assert late.tokens == solo.tolist()
    ring = _ran(mid)
    assert fetches[2] == 2                  # it arrived during boundary 2 (from 0)
    # boundary 2 fetches its chunk, boundary 3 the one in flight, and only
    # boundary 4 admits: one chunk later than an engine that never chains
    assert [s["admitted"] for s in ring[:6]] == [1, 0, 0, 0, 1, 0]
    assert [s["ahead"] for s in ring[:6]] == [0, 1, 1, 1, 0, 1]
    for prev, s in zip(ring[4:], ring[5:]):      # then only its retirement breaks it
        assert s["ahead"] == int(not prev["retired"]), (prev, s)
    counted = sum(s["ahead"] for s in ring)
    assert 0 < counted < len(ring)


# -- (iv) a crash with a chunk in flight ---------------------------------------------

def _crash_run(rt, mid, arm=None):
    RECORDER.clear()
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=2, page_tokens=PT,
                                   arena_pages=48)
    held = []
    try:
        if arm is not None:
            arm(eng, held)
        ids = np.arange(1, 16, dtype=np.int32).reshape(3, 5)
        try:
            out = np.asarray(eng.generate(mid, ids, max_new_tokens=12))
        finally:
            lab_faults.disarm()
        return out, held, _ran(mid)
    finally:
        eng.close()
        rt.drop_slot_state(mid)


def _arm_kill(eng, held):
    real = lab_faults.fire

    def fire(site, **kw):
        try:
            return real(site, **kw)
        except BaseException:
            held.append(next(iter(eng._scheds.values()))._flight is not None)
            raise

    batcher_mod.lab_faults.fire = fire
    lab_faults.arm([FaultSpec(kind="kill_engine", after=3, count=1)])


def _arm_evicted(rt):
    def arm(eng, held):
        real = rt.slot_decode_chunk_launch
        calls = itertools.count(1)

        def launch(state, chunk):
            # the fourth launch goes up ahead of the third chunk's fetch
            if next(calls) == 4:
                held.append(state.resident["tok"][1] is None)
                raise ModelNotLoadedError(f"model {state.model_id} is not loaded")
            return real(state, chunk)

        rt.slot_decode_chunk_launch = launch
    return arm


@pytest.mark.parametrize("how", ["lab_faults-kill_engine", "ModelNotLoadedError"])
def test_d_survivors_of_a_crash_with_a_chunk_in_flight_continue_token_identical(
        tmp_path, how):
    rt, mid = _load(tmp_path)
    real_fire, real_launch = lab_faults.fire, rt.slot_decode_chunk_launch
    try:
        want, _held, calm = _crash_run(rt, mid)
        got, held, ring = _crash_run(
            rt, mid, _arm_kill if how.startswith("lab") else _arm_evicted(rt))
    finally:
        batcher_mod.lab_faults.fire = real_fire
        rt.slot_decode_chunk_launch = real_launch
        rt.close()
    assert held == [True]                   # a chunk was in flight when it died
    assert (want == got).all()
    # the chunk in flight was never emitted: its tokens were decoded again on
    # the fresh state, after a re-prefill of prompt + emitted
    assert sum(s["admitted"] for s in ring) > sum(s["admitted"] for s in calm)
    assert any(s["ahead"] for s in ring)


# -- (v) what never chains ------------------------------------------------------------

def test_e_a_mesh_never_chains(tmp_path):
    from tfservingcache_tpu.parallel.mesh import make_mesh

    rt, mid = _load(tmp_path, mesh=make_mesh({"model": 2}))
    RECORDER.clear()
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4, page_tokens=PT,
                                   arena_pages=32)
    try:
        eng.generate(mid, np.arange(1, 6, dtype=np.int32)[None, :], max_new_tokens=17)
    finally:
        eng.close()
        rt.close()
    assert [s["ahead"] for s in _ran(mid)] == [0] * 4


def test_e_an_attached_draft_never_chains(tmp_path):
    """Speculation rounds while a greedy lane is live, plain chunks once only
    drawing lanes are: neither is launched ahead with a draft attached."""
    rt, mid = _load(tmp_path, kv_page_tokens=PT)
    export_artifact("transformer_lm", str(tmp_path), name="draft", version=1, seed=3,
                    config=dict(TINY, d_model=24, n_layers=1, n_heads=2,
                                n_kv_heads=1, d_ff=48))
    rt.ensure_loaded(Model(identifier=ModelId("draft", 1),
                           path=str(tmp_path / "draft" / "1")))
    RECORDER.clear()
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4,
                                   spec_draft_model="draft", spec_tokens=3)
    try:
        reqs = [_req([1, 2, 3], 8), _req([4, 5, 6], 30, temperature=0.9)]
        eng._sched(mid).submit(reqs)
        _finish(reqs)
        assert rt._slot_states[mid].spec_draft is not None
    finally:
        eng.close()
        rt.close()
    ring = _ran(mid)
    assert any(s["drafted"] for s in ring) and any(not s["drafted"] for s in ring)
    assert not any(s["ahead"] for s in ring)


def test_e_a_prefilling_lane_never_chains(tmp_path, monkeypatch):
    rt, mid = _load(tmp_path)
    RECORDER.clear()
    asked = []
    real = batcher_mod._ContinuousScheduler._chain

    def chain(self, rt_, state, lanes, cur):
        nxt = real(self, rt_, state, lanes, cur)
        asked.append((any(l is not None and l.pf_pos is not None for l in lanes),
                      nxt is not None))
        return nxt

    monkeypatch.setattr(batcher_mod._ContinuousScheduler, "_chain", chain)
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=2, page_tokens=PT,
                                   arena_pages=32, prefill_chunk_tokens=8)
    try:
        reqs = [_req([1, 2, 3], 24), _req(np.arange(1, 41) % 90 + 1, 6)]
        eng._sched(mid).submit(reqs)
        _finish(reqs)
    finally:
        eng.close()
        rt.close()
    assert sum(prefilling for prefilling, _up in asked) >= 3   # 40 tokens, 8 a chunk
    assert not any(up for prefilling, up in asked if prefilling)
    assert any(up for _prefilling, up in asked)                # and chains after it


class _WholeChunkRuntime:
    """A runtime that offers ``slot_decode_chunk`` alone (the tests' stubs)."""

    mesh = None

    def __init__(self, slots):
        pps = 16
        self.state = SlotDecodeState(
            model_id=ModelId("stub", 1), cfg_key=(("vocab_size", 97),), family="stub",
            slots=slots, max_seq=pps * PT, k=None, v=None,
            tok=np.zeros(slots, np.int32), pos=np.zeros(slots, np.int32),
            active=np.zeros(slots, bool), temps=np.zeros(slots, np.float32),
            topks=np.zeros(slots, np.int32), page_tokens=PT,
            arena_pages=slots * pps, pages_per_slot=pps,
            block_tables=np.zeros((slots, pps), np.int32),
            free_pages=list(range(1, slots * pps + 1)))
        # as if operands were resident: the missing split alone must hold it
        self.state.resident = {"tok": (None, None)}

    def engine_ready_of(self, _m):
        return True

    def eos_id_of(self, _m):
        return None

    def slot_decode_state(self, _m, _slots):
        return self.state

    def drop_slot_state(self, _m):
        pass

    def slot_prefill(self, _m, prompt, temperature, top_k, seed):
        return 1, None, None, False

    def slot_decode_chunk(self, state, chunk):
        state.pos = state.pos + state.active.astype(np.int32) * chunk
        return np.full((state.tok.shape[0], chunk), 2, np.int32)


def test_e_a_runtime_without_the_split_never_chains():
    rt = _WholeChunkRuntime(2)
    mid = ModelId("stub", 1)
    RECORDER.clear()
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4)
    try:
        out = eng.generate(mid, np.ones((2, 4), np.int32), max_new_tokens=13)
    finally:
        eng.close()
    assert out.shape == (2, 13) and (out[:, 1:] == 2).all()
    ring = _ran(mid)
    assert len(ring) == 3 and not any(s["ahead"] for s in ring)
    assert all(s["launch_ms"] == 0.0 and s["uploads"] == 0 for s in ring)


# -- (vi) the capture ------------------------------------------------------------------

def test_f_a_capture_holds_the_next_launch_before_the_fetch(tmp_path):
    """Beside ``test_chunk_operands.test_d_a_capture_holds_one_program_and_no_
    upload_a_decode_only_launch``: three chunks; every program call of the
    chunk falls inside a ``tpusc.chunk_launch``, and the launch of chunk n+1
    opens before chunk n's ``tpusc.chunk_fetch`` does (on the chip the
    benchmark's ``capture_programs.py`` ties chunk n+1's ``DoEnqueueProgram``
    to that launch span, and so to the boundary that fetched chunk n)."""
    from jax.profiler import ProfileData

    rt, mid = _load(tmp_path, "capture")
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4, page_tokens=PT,
                                   arena_pages=32)
    prompt = np.arange(1, 6, dtype=np.int32)[None, :]
    try:
        eng.generate(mid, prompt, max_new_tokens=5)           # compiled before the capture
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=options)
        try:
            eng.generate(mid, prompt, max_new_tokens=13)      # a prefill token + three chunks
            # ``generate`` returns from inside the engine's last boundary (the
            # emission wakes it) and the profiler keeps an event only once it
            # has ENDED: on a loaded machine the capture stopped first and the
            # last ``tpusc.boundary`` was missing. The engine's thread is
            # joined first, so every span it opened is in the capture
            eng.close()
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close()
        rt.close()
    path, = (tmp_path / "trace").rglob("*.xplane.pb")
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            mine = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name) for ev in line.events]
            if any(name == "tpusc.boundary" for _s, _e, name in mine):   # the engine's thread
                events = sorted(mine, key=lambda ev: (ev[0], -ev[1]))   # an outer span first
    spans = lambda name: [(s, e) for s, e, n in events if n == name]   # noqa: E731
    launches, fetches = spans("tpusc.chunk_launch"), spans("tpusc.chunk_fetch")
    calls = [s for s, _e, n in events if n == "PjitFunction(_paged_decode_chunk_jit)"]
    assert len(launches) == len(fetches) == len(spans("tpusc.decode_chunk")) == 3
    # every call of the program (the tracer names a call more than once) is
    # some launch's, and every launch has one
    held = [[c for c in calls if ls <= c < le] for ls, le in launches]
    assert all(held) and sum(map(len, held)) == len(calls)
    # the ORDER in which the engine's thread opened its spans (one thread: what
    # opens later inside a span nests in it; no wall-clock containment, which
    # a loaded machine's clock reads cannot be held to): a boundary, its
    # decode_chunk, then launch 1, launch 2, fetch 1 | launch 3, fetch 2 |
    # fetch 3, each boundary's chunk after the boundary opened and before the
    # next one did. Boundaries that ran no chunk (the drain) may follow
    letter = {"tpusc.boundary": "b", "tpusc.decode_chunk": "d",
              "tpusc.chunk_launch": "l", "tpusc.chunk_fetch": "f"}
    opened = "".join(letter[n] for _s, _e, n in events if n in letter)
    assert re.fullmatch(r"b*bdllfb+dlfb+dfb*", opened), opened
    for n in (0, 1):
        assert launches[n + 1][1] <= fetches[n][0]
    assert [s["ahead"] for s in _ran(mid)][-3:] == [0, 1, 1]


# -- the counter ------------------------------------------------------------------------

def test_g_the_counter_counts_what_the_ring_says(tmp_path):
    metrics = Metrics()
    rt, mid = _load(tmp_path, metrics=metrics)
    RECORDER.clear()
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4, page_tokens=PT,
                                   arena_pages=32, metrics=metrics)
    try:
        eng.generate(mid, np.arange(1, 11, dtype=np.int32).reshape(2, 5),
                     max_new_tokens=21)
    finally:
        eng.close()
        rt.close()
    ring = _ran(mid)
    count = lambda label: metrics.gen_chunks.labels(label)._value.get()   # noqa: E731
    assert count("ahead") == sum(s["ahead"] for s in ring) == len(ring) - 1
    assert count("boundary") == 1
    # the launches a boundary made: its own and the next one's, the next one's, none
    assert ring[0]["uploads"] == 7 and all(s["uploads"] == 0 for s in ring[1:])
    assert ring[-1]["launch_ms"] == 0.0 and all(s["launch_ms"] > 0 for s in ring[:-1])
    for s in ring:
        assert s["launch_ms"] <= s["chunk_ms"] + 1e-4
        assert s["prefill_ms"] + s["chunk_ms"] + s["emit_ms"] <= s["step_ms"] + 3e-4


def test_g_engine_dump_prints_the_field(tmp_path, capsys):
    import sys

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import engine_dump
    finally:
        sys.path.pop(0)
    row = {"engine": "continuous", "step_ms": 30.0, "chunk": 8, "active": 3,
           "prefill_ms": 0.0, "chunk_ms": 29.0, "emit_ms": 0.2, "launch_ms": 1.5,
           "uploads": 0}
    assert "ahead=1" in engine_dump._fmt_step(dict(row, ahead=1))
    assert "ahead" not in engine_dump._fmt_step(dict(row, ahead=0))
    assert "ahead" not in engine_dump._fmt_step(row)        # a dump of 26 fields
