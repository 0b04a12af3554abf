"""A latent-attention (MLA) expert decoder through the program: the
``mla_moe_lm`` family (one shared cache row a token, a shared expert, one
chip's share of the routed experts) against the plain reference the benchmark
keeps (``benchmark/families/mla_moe.py``), at a small size on the CPU.

  (a) the family's ``apply`` logits (expanded attention) against the
      reference, and that each of the mistakes the tolerance is there to
      catch lands orders above it;
  (b) prefill, then 16 decode steps through the paged LATENT arena (the
      Pallas kernel in interpret mode and the gather + einsum reference),
      logits against the reference's full forward at every position, then
      the decode chunk program's greedy tokens and routing stats;
  (c) the absorbed form against the expanded one, on the same rows;
  (d) through ``ContinuousGenerateEngine``: the engine answers what the solo
      decoder (the dense latent cache) answers, also under chunked prefill,
      shared-prefix pages, and the ring carries ``expert_rows_local``;
  (e) what the family cannot do yet is refused by name: the int8 arena, a
      ``draft_model``, a chip-group mesh.

THE TOLERANCE. Every comparison with the reference is of float32 models at
logits level, ``atol`` 1e-4 of logits whose spread is about 1: what is left
is the order of float32 sums (the absorbed form multiplies ``q_n W_kvb,K``
first where the reference multiplies ``c_kv W_kvb`` first). A router run in
bf16 moves a score by 1e-3 and the logits by 2e-3, twenty times the tolerance
(where it also flips a selection, by tenths); softmax for
sigmoid, a missing ``kv_a`` norm, YaRN without its softmax scale or its
blended frequencies, a dropped shared expert or a selection without its bias
each land whole tenths away (asserted in (a)). A near-tie between the k-th
and the next biased score is the one place two correct float32 programs may
choose differently; the weights are seeded so that no such gap is under 1e-4
(asserted where the reference is used).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tfservingcache_tpu.models.generation as generation
import tfservingcache_tpu.models.mla_moe_lm as mla
import tfservingcache_tpu.ops.attention as attention_ops
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import (
    build,
    export_artifact,
    static_config,
)
from tfservingcache_tpu.ops import moe
from tfservingcache_tpu.ops.attention import dispatch_tally
from tfservingcache_tpu.runtime.base import RuntimeError_
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId
from tfservingcache_tpu.utils.flight_recorder import RECORDER, STEP_FIELDS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _family():
    spec = importlib.util.spec_from_file_location(
        "bench_family_mla_moe",
        os.path.join(ROOT, "benchmark", "families", "mla_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAMILY = _family()
# hidden 64, 4 heads of (16 | 16), latent 24 + 16, 4 of 8 experts held (the
# second half), 2 a token, YaRN factor 4 over 32 positions, 2 layers
PUBLISHED = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 24, "qk_head_dim": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 1.5, "vocab_size": 97,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 64,
    "num_hidden_layers": 2, "torch_dtype": "float32",
    "source_values": {"n_routed_experts": 8},
    "assumed": {"scoring_func": {"value": "sigmoid"},
                "expert_first": {"value": 4}},
    "rope_parameters": {
        "rope_type": "yarn", "rope_theta": 10000, "factor": 4, "beta_fast": 32,
        "beta_slow": 1, "original_max_position_embeddings": 32, "mscale": 1,
        "mscale_all_dim": 1, "llama_4_scaling_beta": 0.1},
}
MC = FAMILY.program_config(PUBLISHED)
PT = 8


def _tree(seed=0, mc=MC):
    """Seeded weights in the benchmark's layout, every gain random too (a
    gain of one would hide a norm applied to the wrong tensor) and the
    selection bias large enough to change a selection in three."""
    rng = np.random.default_rng(seed)
    leaves = {name: (rng.standard_normal(shape) / np.sqrt(fan_in)).astype(np.float32)
              for name, (shape, fan_in) in FAMILY.leaf_shapes(mc).items()}
    tree = FAMILY.to_tree(mc, leaves)
    gain = lambda a: (1.0 + 0.2 * rng.standard_normal(a.shape)).astype(np.float32)  # noqa: E731
    tree["ln_f"] = gain(tree["ln_f"])
    for lp in tree["layers"]:
        lp["ln1"], lp["ln2"] = gain(lp["ln1"]), gain(lp["ln2"])
        lp["attn"]["q_a_norm"] = gain(lp["attn"]["q_a_norm"])
        lp["attn"]["kv_a_norm"] = gain(lp["attn"]["kv_a_norm"])
        lp["moe"]["bias"] = (5 * lp["moe"]["bias"]).astype(np.float32)
    return tree


def _biased_scores(tree, ids, mc=MC):
    """The reference's own sorted biased scores ``g + b`` of every token of
    every layer -> list of ``(tokens, E)``; asserts that no k-th and (k+1)-th
    are within 1e-4 of each other."""
    project, attend_block, residual, gates, add_experts, _ = FAMILY._fns(
        tuple(sorted(mc.items())))
    out = []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tree["embed"][np.asarray(ids)], jnp.float32)
        for lp in tree["layers"]:
            q, k, v = project(x, lp["attn"], lp["ln1"])
            h = residual(x, attend_block(q, 0, k, v), lp["attn"]["wo"])
            z, y, weight = gates(h, lp["ln2"], lp["moe"]["router"],
                                 lp["moe"]["bias"], lp["moe"]["shared"])
            g = np.asarray(jax.nn.sigmoid(
                z @ jnp.asarray(lp["moe"]["router"], jnp.float32)))
            out.append(g)
            p = np.sort(g + lp["moe"]["bias"], -1)
            assert np.min(p[:, -mc["top_k"]] - p[:, -mc["top_k"] - 1]) > 1e-4
            x = add_experts(y, z, weight, lp["moe"]["w1"], lp["moe"]["w3"],
                            lp["moe"]["w2"])
    return out


def _apply(mc, tree, ids):
    return np.asarray(build("mla_moe_lm", mc).apply(
        tree, {"input_ids": np.asarray(ids)[None]})["logits"])[0]


# -- (a) apply against the reference, and what the tolerance catches ----------

def _bf16_router(monkeypatch, _tree_):
    real = moe.route
    monkeypatch.setattr(moe, "route", lambda x, router, *a, **kw: real(
        x.astype(jnp.bfloat16), router.astype(jnp.bfloat16), *a, **kw))
    return MC, _tree_


def _no_kv_a_norm(monkeypatch, _tree_):
    real = mla._rmsnorm
    monkeypatch.setattr(mla, "_rmsnorm", lambda x, gain, eps: (
        x if x.shape[-1] == MC["kv_lora_rank"] else real(x, gain, eps)))
    return MC, _tree_


MISTAKES = {
    "bf16_router": _bf16_router,
    "softmax_for_sigmoid": lambda mp, t: (dict(MC, route_score="softmax"), t),
    "no_kv_a_norm": _no_kv_a_norm,
    "yarn_without_softmax_scale": lambda mp, t: (
        dict(MC, rope_mscale=0.0, rope_mscale_all_dim=0.0), t),
    "plain_rotary_frequencies": lambda mp, t: (dict(MC, rope_factor=1.0), t),
    "no_shared_expert": lambda mp, t: (MC, dict(t, layers=[
        dict(lp, moe={k: v for k, v in lp["moe"].items() if k != "shared"})
        for lp in t["layers"]])),
    "selection_without_bias": lambda mp, t: (MC, dict(t, layers=[
        dict(lp, moe={k: v for k, v in lp["moe"].items() if k != "bias"})
        for lp in t["layers"]])),
    "gates_not_scaled": lambda mp, t: (dict(MC, route_scale=1.0), t),
    "every_expert_held": lambda mp, t: (dict(MC, expert_first=0), t),
}


@pytest.mark.parametrize("mistake", [None, *MISTAKES])
def test_a_apply_logits_match_the_reference(monkeypatch, mistake):
    tree = _tree(0)
    ids = np.random.default_rng(11).integers(1, MC["vocab_size"], 45)
    _biased_scores(tree, ids)
    want = FAMILY.logits_many(MC, tree, [ids.tolist()], last=len(ids))[0]
    assert want.std() > 0.5
    if mistake is None:
        np.testing.assert_allclose(_apply(MC, tree, ids), want, atol=1e-4, rtol=0)
        # positions past original_max (32) are in: the query factor is not 1
        factor = np.asarray(mla.query_factor(MC, jnp.asarray([0, 40])))
        assert factor[0] > 1.25 and factor[1] > 1.05 * factor[0]
        return
    mc, bad_tree = MISTAKES[mistake](monkeypatch, tree)
    bad = _apply(mc, bad_tree, ids)
    # a router in bf16 moves the gates and not the selection: 2e-3 here, twenty
    # times the tolerance; every other mistake is another model
    assert np.max(np.abs(bad - want)) > (1e-3 if mistake == "bf16_router" else 1e-2), mistake


# -- (b) prefill, then decode through the paged latent arena ------------------

def _paged_setup(tree, prompt, lanes=4, lane=1, pages=24):
    """Prefill ``prompt`` and insert its latent rows into lane ``lane`` of a
    fresh one-sided arena -> (cfg, cache, tables, pos, first token, the last
    prompt position's logits)."""
    model = build("mla_moe_lm", MC)
    cfg = dict(static_config(model))    # the config with the ModelDef's row
    p_pad = 16
    ids = np.zeros((1, p_pad), np.int32)
    ids[0, :len(prompt)] = prompt
    tok, pk, pv, last, _lane = generation._slot_prefill_jit(
        tree, ids, np.asarray([len(prompt)], np.int32), jax.random.PRNGKey(0),
        np.float32(0.0), np.int32(0), cfg_key=tuple(sorted(cfg.items())),
        family="mla_moe_lm")
    assert pv is None and pk.shape == (2, 1, 1, p_pad, 128)
    cache = generation.init_paged_cache(cfg, pages, PT, row=model.cache_row)
    assert list(cache) == ["k"] and cache["k"].shape == (2, pages, 1, PT, 128)
    pps = MC["max_seq"] // PT
    tables = np.zeros((lanes, pps), np.int32)
    tables[lane, :5] = 1 + 5 * lane + np.arange(5)       # 40 tokens a lane
    k, v, scales = generation._paged_insert_jit(
        cache["k"], None, None, pk, pv, tables[lane], np.int32(0),
        page_tokens=PT)
    assert v is None and scales is None
    pos = np.zeros((lanes,), np.int32)
    pos[lane] = len(prompt)
    return cfg, {"k": k}, tables, pos, int(tok[0]), np.asarray(last)[0]


@pytest.mark.parametrize("kernel", [False, True], ids=["reference", "kernel"])
def test_b_prefill_then_paged_decode_matches_the_reference_at_every_position(
        monkeypatch, kernel):
    monkeypatch.setattr(attention_ops, "PAGED_KERNEL_INTERPRET", kernel)
    tree = _tree(2)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, MC["vocab_size"], 11)
    forced = rng.integers(1, MC["vocab_size"], 16)        # teacher-forced tail
    seq = np.concatenate([prompt, forced])
    _biased_scores(tree, seq)
    want = FAMILY.logits_many(MC, tree, [seq.tolist()], last=len(seq))[0]
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    lane = 1
    cfg, cache, tables, pos, _tok, last = _paged_setup(dev, prompt, lane=lane)
    np.testing.assert_allclose(last, want[len(prompt) - 1], atol=1e-4, rtol=0)
    active = np.arange(4) == lane
    before = dict(dispatch_tally())
    step = jax.jit(lambda cache, tok, pos: generation._paged_forward_step(
        dev, tok, cache, tables, pos, cfg, "mla_moe_lm", PT, kernel=kernel,
        active=active))
    tok = np.zeros((4,), np.int32)
    for j, t in enumerate(forced):
        tok[lane] = t
        logits, cache = step(cache, tok, pos)
        np.testing.assert_allclose(np.asarray(logits)[lane, 0],
                                   want[len(prompt) + j], atol=1e-4, rtol=0)
        pos[lane] += 1
    branch = ("kernel", "interpret") if kernel else ("reference", "kernel=False")
    key = ("paged_latent_attention", *branch)
    assert dispatch_tally().get(key, 0) > before.get(key, 0)
    # the decode chunk program itself: greedy tokens are the argmax chain of
    # the reference, and the routing stats come back with them
    cfg, cache, tables, pos, first, _ = _paged_setup(dev, prompt, lane=lane)
    tok = np.zeros((4,), np.int32)
    tok[lane] = first
    k, v, scales, *_, toks, stats, _lane, _next = generation._paged_decode_chunk_jit(
        dev, cache["k"], None, None, tables, tok, pos, active, np.uint32(1),
        np.zeros((4,), np.float32), np.zeros((4,), np.int32),
        cfg_key=tuple(sorted(cfg.items())), family="mla_moe_lm", chunk=4,
        page_tokens=PT, kernel=kernel)
    assert v is None and scales is None and k.shape == cache["k"].shape
    chain = [first]
    for _ in range(4):
        ref = FAMILY.logits_many(
            MC, tree, [prompt.tolist() + chain], last=1)[0][0]
        chain.append(int(np.argmax(ref)))
    assert np.asarray(toks)[lane].tolist() == chain[1:]
    hit, rows_max, local = np.asarray(stats)
    # one live row, 2 assignments a layer of which those to experts 4..7 land
    assert 0.0 <= hit <= 2.0 and rows_max <= 1.0 and local == hit


# -- (c) the two forms of one attention --------------------------------------

@pytest.mark.parametrize("start", [0, 5])
def test_c_absorbed_attention_equals_expanded(start):
    """Same rows, same queries: the absorbed form over a dense latent cache
    (queries ``start..S-1`` against rows ``0..S-1``) answers what the
    expanded form answers at those positions."""
    cfg = build("mla_moe_lm", MC).config
    attn = jax.tree_util.tree_map(jnp.asarray, _tree(4)["layers"][0]["attn"])
    s = 37
    a = jnp.asarray(np.random.default_rng(5).standard_normal((1, s, 64)), jnp.float32)
    positions = jnp.arange(s)[None]
    q_n, q_r, rows = mla.latent_project(attn, a, positions, cfg)
    assert rows.shape == (1, s, 128) and not np.asarray(rows[..., 40:]).any()
    want = np.asarray(mla.expanded_attention(attn, q_n, q_r, rows, cfg))
    out = mla.dense_absorbed_attention(
        mla.absorbed_query(attn, q_n[:, start:], q_r[:, start:], cfg), rows,
        positions[:, start:], cfg)
    got = np.asarray(mla.absorbed_output(attn, out, cfg, jnp.float32))
    np.testing.assert_allclose(got, want[:, start:], atol=2e-5, rtol=0)


# -- (d) through the engine ----------------------------------------------------

def _load(tmp_path, name="mla", config=None, seed=0, **serving_kw):
    export_artifact("mla_moe_lm", str(tmp_path), name=name, version=1,
                    config=config or MC, seed=seed)
    rt = TPUModelRuntime(ServingConfig(platform="cpu", **serving_kw), None)
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


def _ring(mid):
    return RECORDER.snapshot(tail=RECORDER.ring_entries)["models"].get(
        f"{mid.name}@{mid.version}", {"steps": []})["steps"]


@pytest.mark.parametrize("variant", ["plain", "chunked_prefill", "shared_prefix"])
def test_d_engine_answers_as_the_solo_decoder(tmp_path, variant):
    """Two requests share decode steps through the paged latent arena and
    each answers what the solo decoder (``runtime.generate``: the dense
    latent cache) answers; the same where a prompt goes in by chunks (the
    T > 1 reference forward) and where a prefix's pages are shared."""
    knobs = {"chunked_prefill": dict(prefill_chunk_tokens=8),
             "shared_prefix": dict(share_prefix_bytes=1 << 20)}.get(variant, {})
    rt, mid = _load(tmp_path, name=f"mla_{variant}")
    rng = np.random.default_rng(8)
    shared = rng.integers(1, MC["vocab_size"], 16)
    ids = np.stack([np.concatenate([shared, rng.integers(1, MC["vocab_size"], 5)])
                    for _ in range(2)]).astype(np.int32)
    try:
        solo = [np.asarray(rt.generate(mid, ids[r:r + 1], max_new_tokens=9, seed=1))[0]
                for r in range(2)]
        eng = ContinuousGenerateEngine(rt, slots=4, chunk_tokens=4, page_tokens=PT,
                                       arena_pages=32, **knobs)
        try:
            both = eng.generate(mid, ids, max_new_tokens=9)
            again = eng.generate(mid, ids[:1], max_new_tokens=9)
            state = rt._slot_states[mid]
            state.check_page_conservation()
            assert state.v is None and state.moe_stats is not None
        finally:
            eng.close()
    finally:
        rt.close()
    np.testing.assert_array_equal(both[0], solo[0])
    np.testing.assert_array_equal(both[1], solo[1])
    np.testing.assert_array_equal(again[0], solo[0])
    assert STEP_FIELDS[21] == "expert_rows_local"
    steps = [s for s in _ring(mid) if s["chunk"] > 0 and s["active"] == 2]
    assert steps
    for s in steps:
        # 2 rows x 2 assignments a layer; those to the 4 held experts land
        assert 0.0 <= s["expert_rows_local"] <= 4.0, s
        assert s["experts_hit"] <= s["expert_rows_local"], s


# -- (e) refused by name -------------------------------------------------------

@pytest.mark.parametrize("what", ["int8_arena", "draft_model", "mesh"])
def test_e_what_the_latent_family_cannot_do_is_refused_by_name(
        tmp_path, monkeypatch, what):
    rt, mid = _load(tmp_path)
    ids = np.ones((1, 4), np.int32)
    try:
        if what == "int8_arena":
            with pytest.raises(RuntimeError_, match="latent attention.*int8 arena"):
                rt.slot_decode_state(mid, 4, arena_dtype="int8")
            with pytest.raises(ValueError, match="no int8 form"):
                model = build("mla_moe_lm", MC)
                generation.init_paged_cache(model.config, 4, PT, "int8",
                                            row=model.cache_row)
        elif what == "draft_model":
            export_artifact("transformer_lm", str(tmp_path), name="draft", version=1,
                            config={"vocab_size": MC["vocab_size"], "d_model": 32,
                                    "n_layers": 1, "n_heads": 2, "n_kv_heads": 2,
                                    "d_ff": 64, "max_seq": 64, "dtype": "float32"})
            draft = ModelId("draft", 1)
            rt.ensure_loaded(Model(identifier=draft,
                                   path=str(tmp_path / "draft" / "1")))
            with pytest.raises(RuntimeError_, match="latent attention.*draft_model"):
                rt.generate(mid, ids, max_new_tokens=2, seed=1,
                            draft_model_id=draft)
            state = rt.slot_decode_state(mid, 4)
            with pytest.raises(RuntimeError_, match="latent attention.*draft_model"):
                rt.slot_attach_draft(state, draft)
        else:
            monkeypatch.setattr(rt, "mesh", object())
            with pytest.raises(RuntimeError_, match="latent attention.*mesh"):
                rt.generate(mid, ids, max_new_tokens=2, seed=1)
            with pytest.raises(RuntimeError_, match="latent attention.*mesh"):
                rt.slot_decode_state(mid, 4)
    finally:
        monkeypatch.undo()
        rt.close()


# -- hardware-gated rows (tools/tpu_kernel_check.py) --------------------------

ON_TPU = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)",
)
# Mistral-Small-4-119B's latent layer as the benchmark's cell holds it
H, RANK, ROPE, W, PAGE, LAYERS, LANES = 32, 256, 64, 384, 16, 6, 32
PPS = 8192 // PAGE


def _latent_case(live, ctx, pages=16384, seed=0):
    """``live`` of 32 lanes at ``ctx`` cached tokens each over an arena of the
    cell's size; a lane's pages are scattered over the arena."""
    rng = np.random.default_rng(seed)
    arena = jax.random.normal(
        jax.random.PRNGKey(seed), (LAYERS, pages + 1, 1, PAGE, W), jnp.bfloat16)
    arena = arena.at[..., RANK + ROPE:].set(0)
    q = jax.random.normal(jax.random.PRNGKey(seed + 1), (LANES, H, 1, W), jnp.bfloat16)
    q = q.at[..., RANK + ROPE:].set(0)
    need = -(-(ctx + 1) // PAGE)
    tables = np.zeros((LANES, PPS), np.int32)
    perm = rng.permutation(np.arange(1, pages + 1))
    for s in range(live):
        tables[s, :need] = perm[s * need:(s + 1) * need]
    pos = np.where(np.arange(LANES) < live, ctx, 0).astype(np.int32)
    active = np.arange(LANES) < live
    return q, arena, jnp.asarray(tables), jnp.asarray(pos), jnp.asarray(active)


def _split_storage_kernel(q, c_pages, r_pages, tables, pos, active, *, layer):
    """TEST-ONLY: the latent kernel with the row stored the OTHER way the
    issue allows, ``c_kv`` (256 wide) and ``rope(k_r)`` (a 128-lane tile of
    its own) as two arrays: two page copies a page and two score products.
    Here for the storage decision's measurement (PERF.md section 4), not a
    path of the program."""
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_pages = attention_ops.LATENT_BLOCK_TOKENS // PAGE
    block_tokens = block_pages * PAGE

    def kernel(tables_ref, pos_ref, active_ref, qc_ref, qr_ref, c_hbm, r_hbm,
               o_ref, c_buf, r_buf, sem, acc_s, m_s, l_s):
        lane = pl.program_id(0)
        p_lane = pos_ref[lane]
        n_live = jnp.minimum(p_lane // PAGE + 1, tables_ref.shape[1])
        n_blocks = jnp.where(active_ref[lane] != 0, pl.cdiv(n_live, block_pages), 0)

        def live_in(blk):
            return jnp.minimum(n_live - blk * block_pages, block_pages)

        def start_block(blk, slot):
            def one(p, carry):
                page = tables_ref[lane, blk * block_pages + p]
                pltpu.make_async_copy(c_hbm.at[layer, page, 0], c_buf.at[slot, p],
                                      sem.at[0, slot]).start()
                pltpu.make_async_copy(r_hbm.at[layer, page, 0], r_buf.at[slot, p],
                                      sem.at[1, slot]).start()
                return carry
            jax.lax.fori_loop(0, live_in(blk), one, None)

        def wait_block(blk, slot):
            def one(p, carry):
                pltpu.make_async_copy(c_hbm.at[layer, 0, 0], c_buf.at[slot, 0],
                                      sem.at[0, slot]).wait()
                pltpu.make_async_copy(r_hbm.at[layer, 0, 0], r_buf.at[slot, 0],
                                      sem.at[1, slot]).wait()
                return carry
            jax.lax.fori_loop(0, live_in(blk), one, None)

        @pl.when(lane == 0)
        def _finite():
            c_buf[...] = jnp.zeros_like(c_buf)
            r_buf[...] = jnp.zeros_like(r_buf)

        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, -1e30)
        l_s[...] = jnp.zeros_like(l_s)

        @pl.when(n_blocks > 0)
        def _first():
            start_block(0, 0)

        def step(blk, carry):
            slot = jax.lax.rem(blk, 2)

            @pl.when(blk + 1 < n_blocks)
            def _next():
                start_block(blk + 1, 1 - slot)

            wait_block(blk, slot)
            ckv = c_buf[slot].reshape(block_tokens, RANK)
            kr = r_buf[slot].reshape(block_tokens, 128)
            dot = functools.partial(jax.lax.dot_general,
                                    dimension_numbers=(((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = (dot(qc_ref[0], ckv) + dot(qr_ref[0], kr)) * (128 ** -0.5)
            k_pos = blk * block_tokens + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= p_lane, s, -1e30)
            m_prev, l_prev = m_s[:, :1], l_s[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_s[...] = acc_s[...] * alpha + jnp.dot(
                p.astype(ckv.dtype), ckv, preferred_element_type=jnp.float32)
            m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
            l_s[...] = jnp.broadcast_to(l_new, l_s.shape)
            return carry

        jax.lax.fori_loop(0, n_blocks, step, None)
        o_ref[0] = acc_s[...] / jnp.maximum(l_s[:, :1], 1e-30)

    lane_index = lambda s, *_: (s, 0, 0)  # noqa: E731
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(LANES,),
            in_specs=[pl.BlockSpec((1, H, RANK), lane_index),
                      pl.BlockSpec((1, H, 128), lane_index), hbm, hbm],
            out_specs=pl.BlockSpec((1, H, RANK), lane_index),
            scratch_shapes=[
                pltpu.VMEM((2, block_pages, PAGE, RANK), c_pages.dtype),
                pltpu.VMEM((2, block_pages, PAGE, 128), r_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((H, RANK), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((LANES, H, RANK), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(tables, pos, active.astype(jnp.int32), q[:, :, 0, :RANK],
      q[:, :, 0, RANK:], c_pages, r_pages)[:, :, None]


@ON_TPU
@pytest.mark.parametrize("live,ctx", [(4, 512), (8, 3072), (16, 3072), (32, 1024),
                                      (16, 7168), (32, 8000)])
def test_latent_decode_kernel_on_tpu(live, ctx):
    """The latent decode kernel at the cell's shapes (32 heads over one
    384-wide row, 16-token pages, an arena of 16384 pages x 6 layers read at a
    layer): parity with the gather + einsum reference on the live lanes,
    zeros on the others, ms and the share of the 640 B-a-token roofline
    printed; beside it the same attention with the row stored as two arrays
    (``_split_storage_kernel``) and the reference's time."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    q, arena, tables, pos, active = _latent_case(live, ctx)
    scale = 128 ** -0.5

    def kernel(q, arena, tables, pos, active):
        return attention_ops.paged_latent_decode_attention_kernel(
            q, arena, tables, pos, active, page_tokens=PAGE, value_width=RANK,
            sm_scale=scale, layer=3)

    def ref(q, arena, tables, pos, active):
        return attention_ops.paged_latent_attention_reference(
            q, arena, tables, pos, PAGE, RANK, scale, layer=3)

    c_pages = arena[..., :RANK]
    r_pages = arena[..., RANK:]

    def split(q, c_pages, r_pages, tables, pos, active):
        return _split_storage_kernel(q, c_pages, r_pages, tables, pos, active,
                                     layer=3)

    args = (q, arena, tables, pos, active)
    got = np.asarray(jax.jit(kernel)(*args))
    want = np.asarray(jax.jit(ref)(*args))
    err = float(np.max(np.abs(got[:live] - want[:live])))
    assert err < 2e-2, f"latent kernel diverges: max abs err {err}"
    assert not got[live:].any()
    two = np.asarray(jax.jit(split)(q, c_pages, r_pages, tables, pos, active))
    assert float(np.max(np.abs(two[:live] - want[:live]))) < 2e-2
    t_k = chained_device_time(kernel, args)
    t_s = chained_device_time(split, (q, c_pages, r_pages, tables, pos, active))
    t_r = chained_device_time(ref, args)
    tokens = live * (ctx + 1)
    least = max((tokens * 640 + live * H * (640 + 1024)) / 819e9,
                2 * H * 576 * tokens / 197e12)
    print(f"\n[latent_decode] live={live} ctx={ctx}: kernel {t_k*1e3:.3f} ms "
          f"({tokens * 640 / t_k / 1e9:.0f} GB/s of 640 B rows, "
          f"{100 * least / t_k:.1f} % of the roofline), split storage "
          f"{t_s*1e3:.3f} ms, reference {t_r*1e3:.3f} ms, max_abs_err {err:.4f}",
          flush=True)


@ON_TPU
@pytest.mark.parametrize("block", [256, 512, 1024, 2048])
def test_latent_block_tokens_on_tpu(monkeypatch, block):
    """The kernel's block size at the cell's mean shape (16 lanes x 3072
    tokens): ms printed for each, for ``LATENT_BLOCK_TOKENS``'s choice."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    monkeypatch.setattr(attention_ops, "LATENT_BLOCK_TOKENS", block)
    args = _latent_case(16, 3072)

    def kernel(q, arena, tables, pos, active):
        # the undecorated function: the jitted one would reuse the trace of
        # another block size (the constant is read while tracing)
        return attention_ops.paged_latent_decode_attention_kernel.__wrapped__(
            q, arena, tables, pos, active, page_tokens=PAGE, value_width=RANK,
            sm_scale=128 ** -0.5, layer=3)

    print(f"\n[latent_block] block_tokens={block}: "
          f"{chained_device_time(kernel, args)*1e3:.3f} ms", flush=True)
