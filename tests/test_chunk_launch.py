"""The decode chunk's launch path measured inside the program (ISSUE 35):

  (b) the ring: ``0 <= launch_ms <= chunk_ms`` on every boundary of a CPU engine
      run that ran a chunk, 0 on one that ran none;
  (c) a CPU profiler capture of a two-chunk ``:generate`` holds
      ``tpusc.chunk_launch`` and ``tpusc.chunk_fetch`` nested in
      ``tpusc.decode_chunk``, a chunk's fetch after its launch (and, since
      ISSUE 40, the second chunk's launch before the first one's fetch);
  (e) the spans and the clock change nothing the device sees: the decode chunk
      is called with the parent's operands but for the keys (ISSUE 36: the
      chunk counter goes in and the program derives the parent's keys) and
      its tokens are bit for bit the parent's.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

import tfservingcache_tpu.models.generation as generation
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import export_artifact
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId
from tfservingcache_tpu.utils.flight_recorder import RECORDER

TINY = {"vocab_size": 97, "d_model": 48, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 96, "max_seq": 64}
PT = 4


def _load(tmp_path, name):
    export_artifact("transformer_lm", str(tmp_path), name=name, version=1, config=TINY)
    rt = TPUModelRuntime(ServingConfig(platform="cpu"), None)
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


def _generate(rt, mid, rows, max_new):
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4, page_tokens=PT,
                                   arena_pages=32)
    try:
        return eng.generate(mid, np.arange(1, 1 + 5 * rows, dtype=np.int32
                                           ).reshape(rows, 5), max_new_tokens=max_new)
    finally:
        eng.close()


def test_b_launch_ms_is_a_part_of_chunk_ms(tmp_path):
    rt, mid = _load(tmp_path, "launch_ring")
    RECORDER.clear()
    try:
        _generate(rt, mid, rows=3, max_new=9)
    finally:
        rt.close()
    steps = RECORDER.snapshot(tail=RECORDER.ring_entries)["models"][str(mid)]["steps"]
    ran = [s for s in steps if s["chunk"] > 0]
    assert len(ran) >= 2
    for s in ran:
        # each field is rounded to 1e-4 ms on its own; a boundary whose chunk
        # the last one launched ahead (ISSUE 40) launches the next or nothing
        assert 0.0 <= s["launch_ms"] <= s["chunk_ms"] + 1e-4, s
        assert s["launch_ms"] > 0.0 or s["ahead"] == 1, s
    assert ran[0]["ahead"] == 0 and any(s["ahead"] for s in ran)
    assert all(s["launch_ms"] == 0.0 for s in steps if s["chunk"] == 0)


def test_c_a_capture_holds_the_two_child_spans_inside_decode_chunk(tmp_path):
    from jax.profiler import ProfileData

    rt, mid = _load(tmp_path, "launch_spans")
    try:
        _generate(rt, mid, rows=1, max_new=3)            # compiled before the capture
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=options)
        try:
            _generate(rt, mid, rows=1, max_new=9)        # a prefill token + two chunks of 4
        finally:
            jax.profiler.stop_trace()
    finally:
        rt.close()
    path, = (tmp_path / "trace").rglob("*.xplane.pb")
    spans: dict[str, list] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            marks = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for ev in line.events if ev.name.startswith("tpusc.")]
            if any(n == "tpusc.boundary" for n, _s, _e in marks):   # the engine's thread
                for n, s, e in marks:
                    spans.setdefault(n, []).append((s, e))
    chunks = sorted(spans["tpusc.decode_chunk"])
    launches, fetches = sorted(spans["tpusc.chunk_launch"]), sorted(spans["tpusc.chunk_fetch"])
    # a boundary fetches one chunk; since ISSUE 40 the first one launches two
    # (its own and the next, ahead of its fetch) and the second none
    assert len(chunks) == len(launches) == len(fetches) == 2
    inside = lambda span: [c for c in chunks if c[0] <= span[0] and span[1] <= c[1]]  # noqa: E731
    for (ls, le), (fs, fe) in zip(launches, fetches):
        assert len(inside((ls, le))) == len(inside((fs, fe))) == 1
        assert le <= fs                      # a chunk's fetch follows its launch
    assert launches[1][1] <= fetches[0][0]   # chunk 2 is up before chunk 1 is fetched
    assert inside(launches[1]) == inside(fetches[0]) == [chunks[0]]
    assert inside(fetches[1]) == [chunks[1]]


def _admitted_state(rt, mid):
    """A lane with a prefilled request, ready for its first decode chunk."""
    state = rt.slot_decode_state(mid, 2, page_tokens=PT, arena_pages=32)
    assert state.reserve_pages(0, 24)
    tok, pk, pv, _hit = rt.slot_prefill(mid, np.arange(1, 8), 0.0, 0, seed=1)
    rt.slot_admit(state, 0, pk, pv)
    state.tok[0], state.pos[0], state.active[0] = tok, 7, True
    return state


@functools.partial(jax.jit, static_argnames=("cfg_key", "family", "page_tokens"))
def _parent_chunk(params, k, v, tables, tok, pos, active, rngs, temperature, top_k,
                  *, cfg_key, family, page_tokens):
    """The parent's decode chunk (2bfac44): the same scan over keys the HOST
    split from the chunk counter."""
    cfg = dict(cfg_key)
    live = generation._live_lanes(active)

    def step(carry, rng):
        cache, tok, pos = carry
        logits, cache = generation._paged_forward_step(
            params, tok, cache, tables, pos, cfg, family, page_tokens,
            active=active, live=live)
        nxt = generation._sample_per_row(logits[:, 0], rng, temperature, top_k, active)
        nxt = jnp.where(active, nxt, tok)
        return (cache, nxt, pos + active.astype(jnp.int32)), nxt

    (_cache, tok, pos), toks = jax.lax.scan(
        step, (generation._arena_cache(k, v, None, None), tok, pos), rngs)
    return tok, pos, toks.T


def test_e_the_decode_chunk_gets_the_parents_operands_and_gives_its_tokens(
        tmp_path, monkeypatch):
    """ISSUE 36: the chunk takes the COUNTER where the parent took keys the
    host had split from it, and the mirrors' values as device arrays; tokens,
    ``tok`` and ``pos`` are bit for bit the parent's call's, greedy and drawn."""
    rt, mid = _load(tmp_path, "launch_same")
    try:
        calls = []
        real = generation._paged_decode_chunk_jit

        def spy(*args, **kw):
            calls.append((args, kw))
            return real(*args, **kw)

        monkeypatch.setattr(generation, "_paged_decode_chunk_jit", spy)
        state = _admitted_state(rt, mid)
        state.temps[0], state.topks[0] = 0.9, 5          # a lane that draws
        before = {k: np.array(getattr(state, k)) for k in
                  ("tok", "pos", "active", "temps", "topks", "block_tables")}
        counter = state.chunk_counter
        toks = rt.slot_decode_chunk(state, 4)
        (args, kw), = calls
        monkeypatch.setattr(generation, "_paged_decode_chunk_jit", real)

        # the parent's call, as 2bfac44 wrote it: these operands, in this
        # order, but for the keys, which it split on the host from the chunk
        # counter; on a fresh state (the runtime keeps ONE state a model)
        rt.drop_slot_state(mid)
        twin = _admitted_state(rt, mid)
        assert twin is not state
        loaded = rt._resident.get(mid)
        parent_args = (loaded.params, twin.k, twin.v, twin.scales,
                       before["block_tables"], before["tok"], before["pos"],
                       before["active"], np.uint32(counter + 1), before["temps"],
                       before["topks"], twin.lane_state)
        parent_kw = dict(cfg_key=twin.cfg_key, family=twin.family, chunk=4,
                         page_tokens=twin.page_tokens, kernel=twin.kernel)
        assert kw == parent_kw and len(args) == len(parent_args)
        shapes = lambda tree: [(x.shape, str(x.dtype)) for x in  # noqa: E731
                               jax.tree_util.tree_leaves(tree)]
        assert shapes(args) == shapes(parent_args)
        for got, want in zip(args[4:11], parent_args[4:11]):
            np.testing.assert_array_equal(np.asarray(got), want)
        # the counter in, the parent's keys derived
        rngs = jax.random.split(jax.random.PRNGKey(counter + 1), 4)
        tok, pos, want = _parent_chunk(
            loaded.params, twin.k, twin.v, before["block_tables"], before["tok"],
            before["pos"], before["active"], rngs, before["temps"], before["topks"],
            cfg_key=twin.cfg_key, family=twin.family, page_tokens=twin.page_tokens)
        np.testing.assert_array_equal(toks, np.asarray(want))
        np.testing.assert_array_equal(state.tok, np.asarray(tok))
        np.testing.assert_array_equal(state.pos, np.asarray(pos))
        assert state.launched_t > 0.0
    finally:
        rt.close()
