"""Paged KV arena of the continuous decode engine
(`serving.kv_page_tokens`): greedy parity with the solo decoder (ragged
prompts, prefix-cache-hit admission), per-lane sampling params across page
sizes, page recycling under churn (free-list conservation, no cross-slot KV bleed),
admission blocking — not failing — on arena exhaustion, and the
slot-state first-admission once-guard."""

import threading
import time

import numpy as np
import pytest

import tfservingcache_tpu.models.generation as generation
import tfservingcache_tpu.runtime.batcher as batcher_mod
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import export_artifact
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

# page size dividing max_seq: the gathered logical length equals the solo
# decoder's cache row, so the attention reductions are shape-identical and
# greedy parity is exact (see paged_decode_attention)
PT = 8


def _load(tmp_path, name="lm", config=TINY, metrics=None, **serving_kw):
    export_artifact("transformer_lm", str(tmp_path), name=name, version=1,
                    config=config)
    rt = TPUModelRuntime(ServingConfig(platform="cpu", **serving_kw), metrics)
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


def _ragged_prompts(rows=6, width=11, seed=0):
    rng = np.random.default_rng(seed)
    lens = list(int(x) for x in rng.integers(2, width + 1, rows))
    ids = np.zeros((rows, width), np.int32)
    for b, length in enumerate(lens):
        ids[b, :length] = rng.integers(1, TINY["vocab_size"], length)
    return ids, lens


def _slot_state(rt, mid):
    return rt._slot_states[mid]


def _assert_arena_clean(st):
    """Every page back on the free-list, exactly once, and every lane
    parked on the trash page — conservation after a full drain."""
    assert sorted(st.free_pages) == list(range(1, st.arena_pages + 1))
    assert not st.lane_pages
    assert (st.block_tables == 0).all()


# -- op-level parity ----------------------------------------------------------

def test_paged_attention_op_matches_dense_math():
    """paged_decode_attention over a scattered page layout must equal the
    dense masked-GQA computation on the logically-assembled K/V."""
    import jax.numpy as jnp

    from tfservingcache_tpu.ops.attention import paged_decode_attention

    rng = np.random.default_rng(3)
    lanes, hq, hkv, d, pps, pt = 3, 4, 2, 8, 4, 4
    n_pages = lanes * pps + 1
    logical_len = pps * pt
    # per-lane logical K/V, scattered into a shuffled page assignment
    k_log = rng.standard_normal((lanes, hkv, logical_len, d)).astype(np.float32)
    v_log = rng.standard_normal((lanes, hkv, logical_len, d)).astype(np.float32)
    perm = rng.permutation(np.arange(1, n_pages))
    tables = perm.reshape(lanes, pps).astype(np.int32)
    k_pages = np.zeros((n_pages, hkv, pt, d), np.float32)
    v_pages = np.zeros((n_pages, hkv, pt, d), np.float32)
    for s in range(lanes):
        for j in range(pps):
            k_pages[tables[s, j]] = k_log[s][:, j * pt:(j + 1) * pt, :]
            v_pages[tables[s, j]] = v_log[s][:, j * pt:(j + 1) * pt, :]
    q = rng.standard_normal((lanes, hq, 1, d)).astype(np.float32)
    pos = np.array([5, 11, 2], np.int32)

    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
        jnp.asarray(tables), jnp.asarray(pos), pt,
    ))

    # dense reference on the logical layout
    g = hq // hkv
    qg = q.reshape(lanes, hkv, g, 1, d)
    s = np.einsum("bkgqd,bkld->bkgql", qg, k_log) / np.sqrt(d)
    mask = np.arange(logical_len)[None, :] <= pos[:, None]      # (S, L)
    s = np.where(mask[:, None, None, None, :], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    want = np.einsum("bkgql,bkld->bkgqd", p, v_log).reshape(lanes, hq, 1, d)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- engine-level greedy parity ----------------------------------------------

def test_greedy_parity_paged_vs_solo(tmp_path):
    """Token-for-token greedy parity on ragged prompts: the paged engine
    must be indistinguishable from the solo decoder."""
    ids, lens = _ragged_prompts()
    rt, mid = _load(tmp_path)
    eng = ContinuousGenerateEngine(rt, slots=4, chunk_tokens=4,
                                   page_tokens=PT, arena_pages=32)
    try:
        want = rt.generate(mid, ids, prompt_lengths=lens,
                           max_new_tokens=8, seed=0)
        paged = eng.generate(mid, ids, prompt_lengths=lens, max_new_tokens=8)
        assert (paged == want).all()
        st = _slot_state(rt, mid)
        assert st.page_tokens == PT and st.arena_pages == 32
        _assert_arena_clean(st)
    finally:
        eng.close()
        rt.close()


def test_greedy_parity_with_prefix_cache_hit(tmp_path):
    """Admission through a prefix-cache hit (the from-cache prefill variant)
    must emit what the solo decoder emits for the whole prompt, cold, on a
    runtime with no prefix cache."""
    # long enough that the stored pow2-floor entry clears the cache's
    # 16-row storage minimum: 12 prompt + 8 completion -> 16 rows stored
    rng = np.random.default_rng(7)
    prefix = rng.integers(1, 96, size=(1, 12)).astype(np.int32)

    metrics = Metrics()
    rt, mid = _load(tmp_path / "paged", metrics=metrics,
                    prefix_cache_bytes=32 << 20)
    rt_cold, _ = _load(tmp_path / "cold")
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4,
                                   metrics=metrics, page_tokens=PT,
                                   arena_pages=24)
    try:
        # populate: the cache stores the first 16 rows of prefix +
        # greedy completion; a query extending THAT sequence hits
        comp = rt.generate(mid, prefix, max_new_tokens=8, seed=0)
        prompt = np.concatenate(
            [prefix[0], comp[0, :4], [56]]
        )[None, :].astype(np.int32)
        want = rt_cold.generate(mid, prompt, max_new_tokens=6, seed=0)
        before = metrics.registry.get_sample_value(
            "tpusc_prefix_cache_hits_total") or 0
        got = eng.generate(mid, prompt, max_new_tokens=6)
        after = metrics.registry.get_sample_value(
            "tpusc_prefix_cache_hits_total") or 0
    finally:
        eng.close()
        rt.close()
        rt_cold.close()
    assert after - before == 1  # actually admitted through the hit path
    assert (got == want).all()


def test_per_lane_sampling_parity(tmp_path, monkeypatch):
    """Lanes carrying different temperature/top_k must sample identically
    whatever the arena's page size: prefill seeds are pinned
    (secrets.randbits patched to a replayed counter), chunk rngs are already
    deterministic (PRNGKey(chunk_counter)), and rows are submitted in one
    FIFO batch so lane assignment matches arm-for-arm. The greedy lane also
    equals the solo decoder."""
    ids, lens = _ragged_prompts(rows=3, width=7, seed=5)
    sampling = [(0.0, 0), (0.8, 5), (1.3, 3)]

    def run(arm_dir, **kw):
        counter = iter(range(1000))
        monkeypatch.setattr(
            batcher_mod.secrets, "randbits", lambda _b: next(counter)
        )
        rt, mid = _load(arm_dir)
        eng = ContinuousGenerateEngine(rt, slots=4, chunk_tokens=4, **kw)
        try:
            reqs = [
                batcher_mod._ContinuousReq(
                    prompt=ids[r, : lens[r]].copy(), max_new=6,
                    temperature=t, top_k=k,
                )
                for r, (t, k) in enumerate(sampling)
            ]
            eng._sched(mid).submit(reqs)
            for r in reqs:
                assert r.done.wait(60.0)
                assert r.error is None
            solo = rt.generate(mid, ids[:1, : lens[0]], max_new_tokens=6, seed=0)
            return [list(r.tokens) for r in reqs], solo[0].tolist()
        finally:
            eng.close()
            rt.close()

    small, solo = run(tmp_path / "pt8", page_tokens=PT, arena_pages=32)
    large, _ = run(tmp_path / "pt16", page_tokens=2 * PT, arena_pages=16)
    assert small == large
    assert small[0] == solo
    assert all(0 <= t < TINY["vocab_size"] for row in small for t in row)


# -- recycling / admission gating --------------------------------------------

def test_page_recycling_stress(tmp_path):
    """Churn far more requests than the arena holds at once: every row
    completes with greedy parity to the solo decoder (any cross-slot bleed
    would corrupt tokens), and afterwards the free-list holds every page
    exactly once."""
    ids, lens = _ragged_prompts(rows=16, width=7, seed=9)
    metrics = Metrics()
    rt, mid = _load(tmp_path, metrics=metrics)
    # 6 usable pages; each row needs 2 (prompt <= 7 + max_new 6 = 13 tokens)
    # -> at most 3 rows hold pages at once, 16 rows churn through
    eng = ContinuousGenerateEngine(rt, slots=4, chunk_tokens=4,
                                   metrics=metrics,
                                   page_tokens=PT, arena_pages=6)
    try:
        want = rt.generate(mid, ids, prompt_lengths=lens, max_new_tokens=6,
                           seed=0)
        paged = eng.generate(mid, ids, prompt_lengths=lens, max_new_tokens=6)
        assert (paged == want).all()
        st = _slot_state(rt, mid)
        _assert_arena_clean(st)
        # occupancy gauges drained back to zero; waste observed per retirement
        assert metrics.registry.get_sample_value("tpusc_gen_kv_pages_used") == 0
        assert metrics.registry.get_sample_value("tpusc_gen_kv_pages_total") == 6
        waste_n = metrics.registry.get_sample_value(
            "tpusc_gen_kv_page_waste_tokens_count")
        assert waste_n == 16
    finally:
        eng.close()
        rt.close()


def test_admission_blocks_on_page_exhaustion(tmp_path):
    """With an arena that fits exactly one row's budget, a second row must
    WAIT (queue blocks, never fails) and admit only after the first retires
    — observable as peak concurrency 1 with both rows completing."""
    rng = np.random.default_rng(2)
    ids = rng.integers(1, 96, size=(2, 6)).astype(np.int32)
    rt, mid = _load(tmp_path)
    # budget per row: 6 + 8 = 14 tokens -> 2 pages; arena holds exactly 2
    eng = ContinuousGenerateEngine(rt, slots=4, chunk_tokens=4,
                                   page_tokens=PT, arena_pages=2)
    try:
        out = eng.generate(mid, ids, max_new_tokens=8)
        assert out.shape == (2, 8)
        assert eng.admitted == 2
        assert eng.peak_active == 1  # never both in flight
        _assert_arena_clean(_slot_state(rt, mid))
    finally:
        eng.close()
        rt.close()


def test_oversized_request_fails_loudly(tmp_path):
    """A row whose budget exceeds the WHOLE arena can never be satisfied by
    waiting — it must fail with a clear error instead of deadlocking."""
    rng = np.random.default_rng(4)
    ids = rng.integers(1, 96, size=(1, 20)).astype(np.int32)
    rt, mid = _load(tmp_path)
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4,
                                   page_tokens=PT, arena_pages=2)
    try:
        with pytest.raises(Exception, match="KV pages"):
            eng.generate(mid, ids, max_new_tokens=20)  # 40 tokens = 5 pages
    finally:
        eng.close()
        rt.close()


# -- speculative rounds: ragged acceptance vs page conservation ---------------

def test_spec_round_census_under_recycling_stress(tmp_path, monkeypatch):
    """Ragged per-row acceptance must leave BOTH arenas (target + draft)
    exactly conserved: 16 rows churn through a 6-page arena with spec
    rounds enabled, the trash-unreachable guard armed on every chunk, and
    the drained free-lists must hold every page exactly once. Greedy output
    stays byte-identical to the solo decoder's throughout."""
    import tfservingcache_tpu.runtime.model_runtime as mr

    monkeypatch.setattr(mr, "_PAGECHECK", True)
    ids, lens = _ragged_prompts(rows=16, width=7, seed=9)
    rt_p, mid = _load(tmp_path / "paged")
    draft_cfg = dict(TINY, d_model=24, n_layers=1, n_heads=2, n_kv_heads=1,
                     d_ff=48)
    export_artifact("transformer_lm", str(tmp_path / "paged"), name="draft",
                    version=1, config=draft_cfg, seed=3)
    d_mid = ModelId("draft", 1)
    rt_p.ensure_loaded(
        Model(identifier=d_mid, path=str(tmp_path / "paged" / "draft" / "1"))
    )
    # budget per row: prompt <= 7 + max_new 6 + spec headroom 2 = 15 tokens
    # -> 2 pages, so at most 3 rows hold target pages at once while 16 churn
    eng_p = ContinuousGenerateEngine(rt_p, slots=4, chunk_tokens=4,
                                     page_tokens=PT, arena_pages=6,
                                     spec_draft_model="draft", spec_tokens=2)
    try:
        want = rt_p.generate(mid, ids, prompt_lengths=lens, max_new_tokens=6,
                             seed=0)
        paged = eng_p.generate(mid, ids, prompt_lengths=lens, max_new_tokens=6)
        assert (paged == want).all()
        st = _slot_state(rt_p, mid)
        assert st.spec_draft is not None
        _assert_arena_clean(st)
        _assert_arena_clean(st.spec_draft)
        st.check_page_conservation()
        st.spec_draft.check_page_conservation()
    finally:
        eng_p.close()
        rt_p.close()


# -- satellite: first-admission once-guard ------------------------------------

def test_slot_state_allocated_once_under_race(tmp_path, monkeypatch):
    """Concurrent first admissions must allocate the (potentially
    gigabytes of) arena exactly once: the per-model once-guard
    serializes allocation, every thread gets the same state object."""
    rt, mid = _load(tmp_path)
    calls = []
    real = generation.init_paged_cache

    def slow_init(*args, **kw):
        calls.append(threading.get_ident())
        time.sleep(0.05)  # widen the race window the guard must close
        return real(*args, **kw)

    monkeypatch.setattr(generation, "init_paged_cache", slow_init)
    states = [None] * 8
    barrier = threading.Barrier(8)

    def grab(i):
        barrier.wait()
        states[i] = rt.slot_decode_state(mid, 4)

    threads = [threading.Thread(target=grab, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        assert len(calls) == 1
        assert all(s is states[0] for s in states)
        assert not rt._slot_init_guards  # guard pruned after first build
    finally:
        rt.close()
