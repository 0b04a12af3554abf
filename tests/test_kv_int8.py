"""int8-quantized KV pages (`serving.kv_arena_dtype: int8`): arena + scale
buffer allocation, byte-matched auto-sizing (more pages for the same
budget — the capacity win), decode quality vs the unquantized arena
(top-1 agreement >= 99% on seeded prompts), page-conservation census
under shared-prefix CoW churn with quantized pages, the
`tpusc_gen_kv_arena_bytes{dtype}` gauge, and the TPUSC_PAGECHECK
silent-junk guard for `paged_gather_kv`'s trash-page hazard."""

import numpy as np
import pytest

import tfservingcache_tpu.runtime.model_runtime as mr
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models import generation
from tfservingcache_tpu.models.registry import export_artifact, static_config
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId
from tfservingcache_tpu.utils.metrics import Metrics

# default model dtype (bfloat16): the quality bound below is exactly the
# deployment question — does int8 KV move greedy tokens vs the bf16 arena?
TINY = {
    "vocab_size": 97,
    "d_model": 48,
    "n_layers": 2,
    "n_heads": 4,
    "n_kv_heads": 2,
    "d_ff": 96,
    "max_seq": 64,
}

PT = 8


def _load(tmp_path, name="lm", metrics=None):
    export_artifact("transformer_lm", str(tmp_path), name=name, version=1,
                    config=TINY)
    rt = TPUModelRuntime(ServingConfig(platform="cpu"), metrics)
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


def _ragged_prompts(rows, width=11, seed=0):
    rng = np.random.default_rng(seed)
    lens = list(int(x) for x in rng.integers(2, width + 1, rows))
    ids = np.zeros((rows, width), np.int32)
    for b, length in enumerate(lens):
        ids[b, :length] = rng.integers(1, TINY["vocab_size"], length)
    return ids, lens


def test_int8_arena_allocates_scales_and_gauge(tmp_path):
    """int8 slot state carries int8 pages + f32 per-row scales, and the
    arena-bytes gauge reports payload + scales under the int8 label."""
    metrics = Metrics()
    rt, mid = _load(tmp_path, metrics=metrics)
    try:
        st = rt.slot_decode_state(mid, 4, page_tokens=PT, arena_pages=32,
                                  arena_dtype="int8")
        assert str(st.k.dtype) == "int8" and str(st.v.dtype) == "int8"
        assert st.scales is not None
        assert str(st.scales["k"].dtype) == "float32"
        # scales: one f32 per (layer, page, kv_head, token) row
        assert st.scales["k"].shape == st.k.shape[:-1]
        want = (int(st.k.nbytes) + int(st.v.nbytes)
                + int(st.scales["k"].nbytes) + int(st.scales["v"].nbytes))
        g = metrics.gen_kv_arena_bytes.labels(dtype="int8")
        assert int(g._value.get()) == want
        rt.drop_slot_state(mid)
        assert int(g._value.get()) == 0
    finally:
        rt.close()


def test_int8_auto_size_grows_to_byte_budget(tmp_path):
    """kv_arena_pages == 0 + int8: the arena must hold MORE pages for the
    dense arena's byte budget — admission capacity scales with the page
    count, so this is where int8 doubles admitted slots. The growth factor
    is the honest per-row byte ratio (hd x dense itemsize vs hd int8 + one
    f32 scale), and the grown arena must not exceed the dense budget."""
    rt, mid = _load(tmp_path)
    try:
        st = rt.slot_decode_state(mid, 4, page_tokens=PT, arena_pages=0,
                                  arena_dtype="int8")
        slots, pps = 4, -(-TINY["max_seq"] // PT)
        dense_equiv = slots * pps
        hd = TINY["d_model"] // TINY["n_heads"]
        dense_item = 2  # bf16 model dtype
        want = dense_equiv * hd * dense_item // (hd + 4)
        assert st.arena_pages == want
        assert st.arena_pages > dense_equiv  # strictly more admission room
        # and the free-list really hands out the grown population
        assert len(st.free_pages) == st.arena_pages
        dense_bytes = (dense_equiv + 1) * 2 * TINY["n_kv_heads"] * PT * hd \
            * dense_item * TINY["n_layers"]
        int8_bytes = (int(st.k.nbytes) + int(st.v.nbytes)
                      + int(st.scales["k"].nbytes)
                      + int(st.scales["v"].nbytes))
        assert int8_bytes <= dense_bytes
    finally:
        rt.close()


def _decision_logits(rt, mid, history, arena_dtype):
    """The logits ``(V,)`` f32 of the decision after ``history``, every row
    of it written to and read from a fresh arena of ``arena_dtype`` by one
    forward of the model step (no engine, no sampling)."""
    loaded = rt._resident.get(mid)
    cfg = dict(static_config(loaded.model_def))
    pages = -(-len(history) // PT)
    logits, _ = generation._paged_verify_step(
        loaded.params, np.asarray(history, np.int32)[None],
        generation.init_paged_cache(cfg, pages + 1, PT, arena_dtype=arena_dtype),
        np.arange(1, pages + 1, dtype=np.int32)[None], np.zeros(1, np.int32),
        cfg, loaded.model_def.family, PT)
    return np.asarray(logits[0, -1], np.float32)


def test_int8_top1_agreement_vs_bf16(tmp_path):
    """Quality bound from ISSUE 14: greedy decode over an int8 arena must
    agree with the bf16 arena on >= 99% of top-1 decisions across seeded
    prompts (CPU reference path — dequant math is identical in-kernel).

    Agreement is counted per DECISION: once a row's sampled token differs,
    the two arms' histories differ and later steps are no longer the same
    decision — a single in-envelope flip must not be amplified by the
    autoregressive cascade into 'every tail token disagreed'. Counted at
    the kernel-qualifying head_dim (64): per-row symmetric quantization
    error averages down with head width, so this is also the deployment
    shape's noise level, not the toy's.

    A flip is a DISAGREEMENT only where the decision was one to get wrong:
    these are random weights, whose top two logits lie one or two bf16 steps
    apart (0.016 at a logit of 4) in about one decision of a hundred, and
    there either token is the model's answer. So at a flip both arms' logits
    are worked out again for the history the arms share (``_decision_logits``)
    and the flip is a TIE, which counts as agreement, where the two picks lie
    no further apart in the bf16 arm's logits than twice the int8 row's
    largest logit error (each of two logits may move by it). The error itself
    is held to a bar wherever it excuses a flip: a tenth of the logits'
    standard deviation, so int8 rows that move logits far enough to flip a
    clear decision fail here, not pass as ties."""
    cfg = dict(TINY, d_model=256, d_ff=256)  # head_dim 64
    engines = {}
    try:
        for arm, dtype in (("bf16", ""), ("int8", "int8")):
            export_artifact("transformer_lm", str(tmp_path / arm), name="lm",
                            version=1, config=cfg)
            rt = TPUModelRuntime(ServingConfig(platform="cpu"))
            mid = ModelId("lm", 1)
            rt.ensure_loaded(
                Model(identifier=mid, path=str(tmp_path / arm / "lm" / "1"))
            )
            eng = ContinuousGenerateEngine(rt, slots=3, chunk_tokens=4,
                                           page_tokens=PT, arena_pages=24,
                                           arena_dtype=dtype)
            engines[arm] = (eng, rt, mid)
        agree = total = 0
        ties = []  # (the int8 row's largest logit error, the logits' std) a flip
        for seed in range(6):
            ids, lens = _ragged_prompts(rows=6, seed=seed)
            toks = {}
            for arm, (eng, rt, mid) in engines.items():
                toks[arm] = eng.generate(mid, ids, prompt_lengths=lens,
                                         max_new_tokens=8)
            eq = toks["bf16"] == toks["int8"]
            for r, row in enumerate(eq):
                if row.all():
                    agree += row.size
                    total += row.size
                    continue
                first = int(np.argmin(row))  # decisions after this differ
                total += first + 1
                history = np.concatenate(
                    [ids[r, :lens[r]], toks["bf16"][r, :first]])
                _, rt, mid = engines["bf16"]
                exact, quantized = (
                    _decision_logits(rt, mid, history, dtype)
                    for dtype in ("", "int8"))
                error = float(np.abs(exact - quantized).max())
                picks = [int(toks[arm][r, first]) for arm in ("bf16", "int8")]
                tie = abs(float(exact[picks[0]] - exact[picks[1]])) <= 2 * error
                ties.append((error, float(exact.std())))
                agree += first + tie
        for _, rt, mid in engines.values():
            rt._slot_states[mid].check_page_conservation()
    finally:
        for eng, rt, _ in engines.values():
            eng.close()
            rt.close()
    assert agree / total >= 0.99, (
        f"int8 top-1 agreement {agree}/{total} = {agree/total:.3f} < 0.99"
    )
    assert all(error <= 0.1 * std for error, std in ties), ties


def test_int8_conservation_under_shared_prefix_churn(tmp_path):
    """Census stays green with quantized pages through the shared-prefix
    machinery: same system prompt across waves (radix hits, CoW on the
    boundary page, reclaim pressure), scales travel with every page copy.
    This is the tier-1 stand-in for the chip zipf soak."""
    rng = np.random.default_rng(11)
    system = rng.integers(1, TINY["vocab_size"], 2 * PT).astype(np.int32)
    rt, mid = _load(tmp_path)
    eng = ContinuousGenerateEngine(
        rt, slots=3, chunk_tokens=4, page_tokens=PT, arena_pages=24,
        share_prefix_bytes=1 << 30, arena_dtype="int8",
    )
    try:
        for wave in range(4):
            rows = 3
            ids = np.zeros((rows, 2 * PT + 3), np.int32)
            for r in range(rows):
                ids[r] = np.concatenate(
                    [system, rng.integers(1, TINY["vocab_size"], 3)]
                )
            eng.generate(mid, ids, prompt_lengths=[ids.shape[1]] * rows,
                         max_new_tokens=6)
            st = rt._slot_states[mid]
            st.check_page_conservation()
        assert st.scales is not None  # the quantized path really ran
    finally:
        eng.close()
        rt.close()


def test_pagecheck_fires_on_trash_below_pos(tmp_path):
    """TPUSC_PAGECHECK guard (paged_gather_kv's silent-junk hazard): a
    live lane whose block table maps trash page 0 below its pos must fail
    loudly before the chunk dispatches, and a healthy engine run under the
    guard must stay silent."""
    rt, mid = _load(tmp_path)
    try:
        st = rt.slot_decode_state(mid, 2, page_tokens=PT, arena_pages=16)
        st.active[0] = True
        st.pos[0] = 2 * PT + 1          # needs 3 live pages
        st.block_tables[0, :3] = [3, 0, 5]
        with pytest.raises(AssertionError, match="trash page 0"):
            mr._check_trash_unreachable(st)
        st.block_tables[0, :3] = [3, 4, 5]
        mr._check_trash_unreachable(st)  # healthy table: no raise
    finally:
        rt.close()


def test_pagecheck_clean_through_engine(tmp_path, monkeypatch):
    """With the guard armed, real admissions never trip it — the admission
    protocol reserves every live page before a lane activates."""
    monkeypatch.setattr(mr, "_PAGECHECK", True)
    ids, lens = _ragged_prompts(rows=4, seed=7)
    rt, mid = _load(tmp_path)
    eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4,
                                   page_tokens=PT, arena_pages=16)
    try:
        out = eng.generate(mid, ids, prompt_lengths=lens, max_new_tokens=6)
        assert out.shape == (4, 6)
    finally:
        eng.close()
        rt.close()
