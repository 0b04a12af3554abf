"""A decode-only boundary launches ONE program whose operands are already on
the device (ISSUE 36):

  (i)   the chunk's keys, derived inside the program from the chunk counter,
        are bit for bit ``jax.random.split(jax.random.PRNGKey(c), chunk)``;
  (ii)  an engine run with admissions, retirements, a shared-prefix CoW and a
        park / resume gives the tokens of the same run with every operand
        uploaded every chunk (greedy and drawing lanes; a K/V and an int8
        arena);
  (iii) ``uploads`` counts what a boundary changed: 0 on a decode-only one, and
        a write to a mirror by ANY of its writers is sent before the next
        chunk; the ring carries the count; a mesh uploads as before;
  (iv)  a CPU profiler capture of back-to-back chunks holds no program
        execution and no upload between them but the chunk;
  (v)   a model with WINDOW layers (ISSUE 39) brings no operand of its own:
        what a window call reads of a lane's ring is worked out from ``pos``
        inside the program, so its decode-only boundary uploads nothing too.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tfservingcache_tpu.models.generation as generation
import tfservingcache_tpu.runtime.batcher as batcher_mod
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import export_artifact
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import (
    CHUNK_OPERANDS,
    TPUModelRuntime,
)
from tfservingcache_tpu.types import Model, ModelId
from tfservingcache_tpu.utils.flight_recorder import RECORDER

TINY = {"vocab_size": 97, "d_model": 48, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 96, "max_seq": 64}
PT = 8
NAMES = (*CHUNK_OPERANDS, "counter")


# SmolLM2's attention in small: 5 KV heads of 64 (an odd count does not pack;
# ``:generate`` takes gather + einsum over a tile a head)
HEAD64 = dict(TINY, d_model=320, n_heads=5, n_kv_heads=5)


def _load(tmp_path, name, mesh=None, config=TINY, **serving):
    export_artifact("transformer_lm", str(tmp_path), name=name, version=1, config=config)
    rt = TPUModelRuntime(ServingConfig(platform="cpu", **serving), None, mesh=mesh)
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


def _steps(mid):
    return RECORDER.snapshot(tail=RECORDER.ring_entries)["models"][str(mid)]["steps"]


# -- (i) the keys ---------------------------------------------------------------

COUNTERS = (1, 2, 3, 1000, 2**31 - 1, 2**31, 2**32 + 5)


class TestKeys:
    @pytest.fixture(scope="class")
    def key_program(self, tmp_path_factory):
        """The decode chunk with a sampler that returns its KEY: lane 0 emits
        the step's first key word, lane 1 its second (as int32 bits)."""
        def key_words(logits, rng, temperature, top_k, active):
            words = jax.lax.bitcast_convert_type(jax.random.key_data(rng), jnp.int32)
            return jnp.resize(words.reshape(-1), (logits.shape[0],))

        real = generation._sample_per_row
        generation._sample_per_row = key_words
        generation._paged_decode_chunk_jit.clear_cache()
        rt, mid = _load(tmp_path_factory.mktemp("keys"), "keys")
        try:
            yield rt, mid
        finally:
            rt.close()
            generation._sample_per_row = real
            generation._paged_decode_chunk_jit.clear_cache()

    @pytest.mark.parametrize("chunk", [1, 2, 4, 8])
    @pytest.mark.parametrize("counter", COUNTERS)
    def test_a_the_programs_keys_are_the_hosts_bit_for_bit(
            self, key_program, counter, chunk):
        rt, mid = key_program
        state = rt.slot_decode_state(mid, 2, page_tokens=PT, arena_pages=16)
        state.active[:] = True
        state.tok[:] = 0
        state.chunk_counter = counter - 1      # the launch takes the next number
        toks = rt.slot_decode_chunk(state, chunk)
        state.active[:] = False
        want = np.asarray(jax.random.split(jax.random.PRNGKey(counter), chunk))
        assert want.dtype == np.uint32 and want.shape == (chunk, 2)
        np.testing.assert_array_equal(toks.T.astype(np.int32).view(np.uint32), want)
        # and the counter the program hands on is the next chunk's
        kept, holds = state.resident["counter"]
        assert int(np.asarray(kept)) == int(holds) == (counter + 1) & 0xFFFFFFFF


# -- (ii) the engine's tokens ---------------------------------------------------

def _scenario(tmp_path, monkeypatch, every_chunk, arena_dtype="", config=TINY):
    """Five rows over three lanes with one system prompt (admissions behind
    retirements; row 1 repeats row 0: an exact hit whose boundary page is
    copied on its first write), greedy and drawing lanes, then two turns of
    one conversation (park at retirement, resume). -> (tokens, ring steps)"""
    seeds = itertools.count()
    monkeypatch.setattr(batcher_mod.secrets, "randbits", lambda _b: next(seeds))
    rt, mid = _load(tmp_path, "lm", config=config, kv_arena_dtype=arena_dtype)
    if every_chunk:
        # the engine calls the launch half itself (ISSUE 40); forgetting what
        # the device holds is only sound with no chunk in flight, so this side
        # also launches every chunk at its own boundary
        real = rt.slot_decode_chunk_launch

        def forget_then_launch(state, chunk):
            state.resident.clear()
            return real(state, chunk)

        monkeypatch.setattr(rt, "slot_decode_chunk_launch", forget_then_launch)
        monkeypatch.setattr(batcher_mod._ContinuousScheduler, "_chain",
                            lambda self, *_a: None)
    RECORDER.clear()
    eng = ContinuousGenerateEngine(
        rt, slots=3, chunk_tokens=4, page_tokens=PT, arena_pages=48,
        share_prefix_bytes=0 if arena_dtype else 1 << 30,
        conversation_kv_bytes=32 << 20)
    rng = np.random.default_rng(5)
    system = rng.integers(1, TINY["vocab_size"], 2 * PT)
    prompts = [np.concatenate([system, rng.integers(1, TINY["vocab_size"], 3)])
               .astype(np.int32) for _ in range(4)]
    prompts.insert(1, prompts[0].copy())
    sampling = [(0.0, 0), (0.8, 5), (1.3, 3), (0.0, 0), (0.8, 0)]
    try:
        reqs = [batcher_mod._ContinuousReq(prompt=p, max_new=5 + 4 * i,
                                           temperature=t, top_k=k)
                for i, (p, (t, k)) in enumerate(zip(prompts, sampling))]
        eng._sched(mid).submit(reqs)
        for r in reqs:
            assert r.done.wait(120.0) and r.error is None
        out = [list(r.tokens) for r in reqs]
        turn1 = rng.integers(1, TINY["vocab_size"], 11).astype(np.int32)
        first = eng.generate(mid, turn1[None, :], max_new_tokens=6, conversation_id="c")
        turn2 = np.concatenate([turn1, first[0], [7, 9]]).astype(np.int32)
        second, stats = eng.generate(mid, turn2[None, :], max_new_tokens=6, temperature=0.7,
                                     top_k=4, conversation_id="c", return_stats=True)
        assert stats[0]["prefill_tokens"] < turn2.shape[0]      # it resumed
        state = rt._slot_states[mid]
        if state.prefix_index is not None:
            assert state.prefix_index.exact_hits >= 1
        state.check_page_conservation()
        return out + [first[0].tolist(), second[0].tolist()], _steps(mid)
    finally:
        eng.close()
        rt.close()


@pytest.mark.parametrize("arena_dtype,config", [("", TINY), ("int8", TINY), ("", HEAD64)],
                         ids=["model-dtype", "int8", "five-heads-of-64"])
def test_b_engine_tokens_equal_those_of_uploading_everything(
        tmp_path, monkeypatch, arena_dtype, config):
    kept, kept_steps = _scenario(tmp_path / "kept", monkeypatch, False, arena_dtype, config)
    every, every_steps = _scenario(tmp_path / "every", monkeypatch, True, arena_dtype, config)
    assert kept == every
    ran = lambda steps: [s["uploads"] for s in steps if s["chunk"] > 0]  # noqa: E731
    assert set(ran(every_steps)) == {len(NAMES)}
    assert 0 in ran(kept_steps) and max(ran(kept_steps)) == len(NAMES)
    assert sum(ran(kept_steps)) < sum(ran(every_steps)) / 2


# -- (iii) what is uploaded -----------------------------------------------------

def _two_lanes(rt, mid):
    """Two lanes with prefilled requests, one chunk already run (so every
    operand is resident) -> state."""
    state = rt.slot_decode_state(mid, 3, page_tokens=PT, arena_pages=32)
    for lane, prompt in enumerate((np.arange(1, 8), np.arange(3, 13))):
        assert state.reserve_pages(lane, 40)
        tok, pk, pv, _hit = rt.slot_prefill(mid, prompt, 0.0, 0, seed=1)
        rt.slot_admit(state, lane, pk, pv)
        state.tok[lane], state.pos[lane], state.active[lane] = tok, len(prompt), True
    rt.slot_decode_chunk(state, 4)
    assert state.uploads == len(NAMES)          # a state's first chunk sends all
    return state


def _retire(state):
    state.active[1] = False
    state.release_pages(1)


def _admit_mirrors(state):
    # batcher.py's admission writes, a lane that draws
    state.tok[2], state.pos[2], state.active[2] = 11, 5, True
    state.temps[2], state.topks[2] = 0.7, 3
    assert state.reserve_pages(2, 24)


def _chunked_prefill_mirrors(state):
    state.active[2] = False
    state.pos[2] = state.pages_per_slot * state.page_tokens
    state.temps[2], state.topks[2] = 0.5, 2


def _cow(state):
    state.page_refs[state.block_tables[0, 0]] += 1          # someone shares it
    assert state.cow_page(0, 0) is not None


def _rebind(state):
    # park / resume / a spec round REPLACE a mirror by a new array
    state.tok = np.array(state.tok)
    state.tok[0] = 5
    state.pos = np.array(state.pos)


def _through_an_alias(state):
    # the draft state's mirrors alias the target's (slot_attach_draft)
    alias = state.pos
    alias[0] -= 1


def _spec_round(state):
    state.chunk_counter += 1


WRITERS = {
    "nothing": (lambda state: None, ()),
    "retirement": (_retire, ("block_tables", "active")),
    "active_alone": (lambda state: state.active.__setitem__(1, False), ("active",)),
    "admission": (_admit_mirrors,
                  ("block_tables", "tok", "pos", "active", "temps", "topks")),
    "chunked_prefill": (_chunked_prefill_mirrors, ("pos", "temps", "topks")),
    "reserve_pages": (lambda state: state.reserve_pages(2, 8), ("block_tables",)),
    "release_pages": (lambda state: state.release_pages(1), ("block_tables",)),
    "cow_page": (_cow, ("block_tables",)),
    "rebound_mirror": (_rebind, ("tok",)),
    "alias_of_a_mirror": (_through_an_alias, ("pos",)),
    "spec_round_counter": (_spec_round, ("counter",)),
}


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    rt, mid = _load(tmp_path_factory.mktemp("writers"), "writers")
    yield rt, mid
    rt.close()


@pytest.mark.parametrize("writer", sorted(WRITERS))
def test_c_a_write_to_a_mirror_is_sent_before_the_next_chunk(
        loaded, monkeypatch, writer):
    rt, mid = loaded
    write, sent = WRITERS[writer]
    rt.drop_slot_state(mid)
    state = _two_lanes(rt, mid)
    rt.slot_decode_chunk(state, 4)
    assert state.uploads == 0                   # decode-only: nothing changed
    before = {name: state.resident[name][0] for name in NAMES}
    write(state)
    mirrors = {name: np.array(getattr(state, name)) for name in CHUNK_OPERANDS}
    mirrors["counter"] = np.uint32(state.chunk_counter + 1)
    calls = []
    real = generation._paged_decode_chunk_jit
    monkeypatch.setattr(generation, "_paged_decode_chunk_jit",
                        lambda *a, **kw: calls.append(a) or real(*a, **kw))
    rt.slot_decode_chunk(state, 4)
    (args,), order = calls, ("block_tables", "tok", "pos", "active", "counter",
                             "temps", "topks")
    for name, got in zip(order, args[4:11]):
        # whatever was written, the device computes on the mirrors' values
        np.testing.assert_array_equal(np.asarray(got), mirrors[name], err_msg=name)
        assert (got is before[name]) == (name not in sent), name
    assert state.uploads == len(sent)


def test_c_the_ring_carries_the_count(tmp_path):
    rt, mid = _load(tmp_path, "ring")
    RECORDER.clear()
    eng = ContinuousGenerateEngine(rt, slots=3, chunk_tokens=4, page_tokens=PT,
                                   arena_pages=32)
    try:
        # three greedy rows that retire chunks apart; a fourth is admitted
        # behind the first retirement, the later ones leave their lanes empty
        reqs = [batcher_mod._ContinuousReq(prompt=np.arange(1, 6, dtype=np.int32) + i,
                                           max_new=n, temperature=0.0, top_k=0)
                for i, n in enumerate((26, 6, 10, 4))]
        eng._sched(mid).submit(reqs)
        for r in reqs:
            assert r.done.wait(120.0) and r.error is None
    finally:
        eng.close()
        rt.close()
    steps = _steps(mid)
    ran = [s for s in steps if s["chunk"] > 0]
    assert ran[0]["uploads"] == len(NAMES)
    assert all(s["uploads"] == 0 for s in steps if s["chunk"] == 0)
    quiet = retired = admitted = 0
    for prev, s in zip(ran, ran[1:]):
        if s["admitted"]:
            # what the admission wrote and the device does not hold: tables,
            # tok, pos (greedy keeps temps and topks; the lane's ``active``
            # is True again before its False was ever sent)
            assert s["uploads"] == 3, s
            admitted += 1
        elif prev["retired"]:
            assert s["uploads"] == 2, s         # active and the freed table row
            retired += 1
        else:
            assert s["uploads"] == 0, s
            quiet += 1
    assert quiet >= 2 and retired >= 1 and admitted >= 1


def test_c_a_recovered_engine_builds_a_fresh_state_and_uploads_everything(tmp_path):
    """After ``engine_crash`` the scheduler respawns on a NEW state: nothing is
    resident, so its first chunk sends all seven, and the streams are those of
    an undisturbed run."""
    from tfservingcache_tpu.lab import faults as lab_faults
    from tfservingcache_tpu.lab.faults import FaultSpec

    rt, mid = _load(tmp_path, "crash")
    ids = np.arange(1, 21, dtype=np.int32).reshape(4, 5)

    def run(fault):
        RECORDER.clear()
        eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=2, page_tokens=PT,
                                       arena_pages=48)
        try:
            if fault is not None:
                lab_faults.arm([fault])
            try:
                out = np.asarray(eng.generate(mid, ids, max_new_tokens=10))
            finally:
                lab_faults.disarm()
            return out, [s["uploads"] for s in _steps(mid) if s["chunk"] > 0]
        finally:
            eng.close()
            rt.drop_slot_state(mid)

    try:
        want, calm = run(None)
        got, crashed = run(FaultSpec(kind="kill_engine", after=3, count=1))
    finally:
        rt.close()
    assert (want == got).all()
    assert calm.count(len(NAMES)) == 1 and crashed.count(len(NAMES)) == 2


def test_c_a_mesh_uploads_as_before(tmp_path):
    from tfservingcache_tpu.parallel.mesh import make_mesh

    rt, mid = _load(tmp_path, "mesh", mesh=make_mesh({"model": 2}))
    RECORDER.clear()
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4, page_tokens=PT,
                                   arena_pages=32)
    try:
        eng.generate(mid, np.arange(1, 6, dtype=np.int32)[None, :], max_new_tokens=9)
        assert rt._slot_states[mid].resident == {}
    finally:
        eng.close()
        rt.close()
    assert {s["uploads"] for s in _steps(mid) if s["chunk"] > 0} == {len(NAMES)}


# -- (iv) nothing runs between two chunks ---------------------------------------

def test_d_a_capture_holds_one_program_and_no_upload_a_decode_only_launch(tmp_path):
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
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close()
        rt.close()
    ring = _steps(mid)
    path, = (tmp_path / "trace").rglob("*.xplane.pb")
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            mine = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name) for ev in line.events]
            if any(name == "tpusc.boundary" for _s, _e, name in mine):   # the engine's thread
                events = sorted(mine)
    launches = [(s, e) for s, e, name in events if name == "tpusc.chunk_launch"]
    assert len(launches) == 3
    programs = lambda lo, hi: sorted({name for s, _e, name in events  # noqa: E731
                                      if lo <= s < hi and name.startswith("PjitFunction(")})
    # a transfer to the device: ``DevicePutWithSharding`` (``jax.device_put``)
    # or ``DevicePut`` (a NumPy argument of a program call)
    uploads = lambda lo, hi: [name for s, _e, name in events          # noqa: E731
                              if lo <= s < hi and name.startswith("DevicePut")]
    # from the first chunk's launch to the third's end: the chunk, three times
    assert programs(launches[0][0], launches[2][1]) == [
        "PjitFunction(_paged_decode_chunk_jit)"]
    # the first follows an admission and sends what the ring says it sent; the
    # second and third are decode-only: one enqueue of resident operands
    counted = [s["uploads"] for s in ring if s["chunk"] > 0][-3:]
    assert counted[0] >= 3 and counted[1:] == [0, 0]
    assert [len(uploads(*span)) for span in launches] == counted
    assert uploads(launches[0][1], launches[2][1]) == []


# -- (v) a window model's decode-only boundary --------------------------------------

WINDOWED = {
    "vocab_size": 97, "d_model": 48, "n_layers": 4, "n_heads": 4, "n_kv_heads": 2,
    "head_dim": 16, "d_ff": 32, "n_experts": 4, "top_k": 2, "norm_topk_prob": True,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": 16, "max_seq": 128, "rope_theta": 10000.0,
    "rope_full": {"yarn": 4.0, "original_max": 32, "attention_factor": 1.1},
    "dtype": "float32"}


def test_e_a_window_models_decode_only_boundary_uploads_nothing(tmp_path,
                                                                 monkeypatch):
    """Two lanes decoding through several turns of their rings (window 16,
    pages of 8: a ring of 3 pages): the first chunk sends all seven operands,
    every decode-only one after it none, and no chunk takes an operand beyond
    the seven and the ring arena itself. The ring field says what a window
    call read."""
    export_artifact("moe_lm", str(tmp_path), name="windowed", version=1,
                    config=WINDOWED)
    rt = TPUModelRuntime(ServingConfig(platform="cpu"), None)
    mid = ModelId("windowed", 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / "windowed" / "1")))
    try:
        state = rt.slot_decode_state(mid, 3, page_tokens=PT, arena_pages=48)
        assert state.window is not None and state.ring_pages == 3
        for lane, prompt in enumerate((np.arange(1, 8), np.arange(3, 43))):
            assert state.reserve_pages(lane, 120)
            tok, pk, pv, _hit = rt.slot_prefill(mid, prompt, 0.0, 0, seed=1)
            rt.slot_admit(state, lane, pk, pv)
            state.tok[lane], state.pos[lane], state.active[lane] = (
                tok, len(prompt), True)
        calls = []
        real = generation._paged_decode_chunk_jit
        monkeypatch.setattr(generation, "_paged_decode_chunk_jit",
                            lambda *a, **kw: calls.append(a) or real(*a, **kw))
        rt.slot_decode_chunk(state, 4)
        assert state.uploads == len(NAMES)      # a state's first chunk sends all
        for _ in range(12):                      # 48 steps: the rings turn twice
            before = {name: state.resident[name][0] for name in NAMES}
            rt.slot_decode_chunk(state, 4)
            assert state.uploads == 0
            for name, got in zip(("block_tables", "tok", "pos", "active",
                                  "counter", "temps", "topks"), calls[-1][4:11]):
                assert got is before[name], name
        # params, the global arena (k, v, scales), the seven, the lane state
        # (None), the ring arena: nothing else goes in
        assert len(calls[-1]) == 13 and calls[-1][11] is None
        assert isinstance(calls[-1][12], tuple) and len(calls[-1][12]) == 2
    finally:
        rt.close()
