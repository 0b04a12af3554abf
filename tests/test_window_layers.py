"""A decoder whose attention layers are of two kinds (window layers that keep
one ring of pages a lane beside global layers that keep every row, a rotary a
kind, a head width the hidden size does not give, renormalised gates) through
the program: the ``moe_lm`` family with ``layer_types`` against the plain
reference the benchmark keeps (``benchmark/families/mellum.py``), at a small
size on the CPU.

  (a) prefill + decode through BOTH arenas at prompts shorter than, equal to
      and five times the window (window 32, pages of 16: a ring of 3 pages),
      decoding through more than two whole turns of the ring, logits against
      the reference's full forward at every position;
  (b) the ring against the dense cached loop, which keeps every row and
      applies the mask: the same logits to 1e-5 (the two softmaxes sum over
      different lengths, 48 ring slots against every row, in float32);
  (c) the paged kernel in interpret mode with a first valid token at every
      offset of a page, and that it copies no page wholly before it;
  (d) the window flash kernel in interpret mode against
      ``attention_reference`` with the mask, at lengths that are and are not
      multiples of the window, in both of its forms;
  (e) a head width that is not ``d_model / n_heads`` (hidden 96, 4 heads of
      32) through ``apply``, prefill, decode and the dense cached loop;
  (f) each fault caught by name, orders above the tolerance;
  (g) what the ModelDef declares and what ``init_paged_cache`` builds: a
      window layer has ``lanes x R`` pages whatever the arena's page count;
  (h) through ``ContinuousGenerateEngine``: the engine answers what the solo
      decoder answers with lanes reused, the gauge reports both arenas, the
      ring says what a window call read, the counter what an admission did
      not store;
  (i) a fresh prefill goes through ``ops.attention.attention`` in every layer
      (the dispatch tally) and its program holds no ``(S, max_len)`` score
      block; OLMoE's config builds what it built;
  (j) what the family cannot do yet is refused by name.

THE TOLERANCE. Every comparison with the reference is of float32 models at
logits level, ``atol`` 1e-4 of logits whose spread is about 1: what is left is
the order of float32 sums. Each fault of (f) lands hundredths to whole tenths
away.
"""

import concurrent.futures as cf
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tfservingcache_tpu.models.generation as generation
import tfservingcache_tpu.ops.attention as att
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import (
    build,
    export_artifact,
    static_config,
    window_layers,
)
from tfservingcache_tpu.runtime.base import RuntimeError_
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import PrefillRows, TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId
from tfservingcache_tpu.utils.flight_recorder import RECORDER
from tfservingcache_tpu.utils.metrics import Metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _family():
    spec = importlib.util.spec_from_file_location(
        "bench_family_mellum",
        os.path.join(ROOT, "benchmark", "families", "mellum.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAMILY = _family()
S, F = "sliding_attention", "full_attention"
WINDOW, PT, LANES = 32, 16, 4
RING = 3                                     # 32 / 16 + 1 pages a lane
# hidden 96, 4 query / 2 KV heads of 32 (4 x 32 = 128, not the hidden size),
# one period s s s F, 8 experts of 48 (2 a token, gates renormalised)
PUBLISHED = {
    "hidden_size": 96, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "layer_types": [S, S, S, F, S, S, S, F],
    "mlp_layer_types": ["sparse"] * 8, "num_hidden_layers": 4,
    "sliding_window": WINDOW, "moe_intermediate_size": 48, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "vocab_size": 257,
    "tie_word_embeddings": False, "max_position_embeddings": 512,
    "rms_norm_eps": 1e-6, "attention_bias": False, "hidden_act": "silu",
    "torch_dtype": "float32",
    "rope_parameters": {
        F: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 16.0,
            "original_max_position_embeddings": 64, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        S: {"rope_type": "default", "rope_theta": 10000.0}},
}
MC = FAMILY.program_config(PUBLISHED)
PPS = MC["max_seq"] // PT


def _tree(seed=0, mc=MC):
    """Seeded weights in the benchmark's layout, every gain random too (a gain
    of one would hide a norm applied to the wrong tensor)."""
    rng = np.random.default_rng(seed)
    leaves = {name: (rng.standard_normal(shape) / np.sqrt(fan_in)).astype(np.float32)
              for name, (shape, fan_in) in FAMILY.leaf_shapes(mc).items()}
    tree = FAMILY.to_tree(mc, leaves)
    gain = lambda a: (1.0 + 0.2 * rng.standard_normal(a.shape)).astype(np.float32)  # noqa: E731
    tree["ln_f"] = gain(tree["ln_f"])
    for lp in tree["layers"]:
        lp["ln1"], lp["ln2"] = gain(lp["ln1"]), gain(lp["ln2"])
    return tree


def _bucket(n):
    b = 1
    while b < n:
        b *= 2
    return b


def _prefill(model, tree, prompt):
    ids = np.zeros((1, _bucket(len(prompt))), np.int32)
    ids[0, :len(prompt)] = prompt
    return generation._slot_prefill_jit(
        tree, ids, np.asarray([len(prompt)], np.int32), jax.random.PRNGKey(1),
        np.float32(0), np.int32(0), cfg_key=static_config(model),
        family="moe_lm")


def _admit(model, pk, pv, prompt_len, lane):
    """Both arenas with one request admitted into ``lane`` -> (cache dict,
    block tables)."""
    cfg = dict(static_config(model))
    cache = generation.init_paged_cache(cfg, LANES * PPS + 1, PT, lanes=LANES)
    tables = np.zeros((LANES, PPS), np.int32)
    tables[lane] = np.arange(1 + lane * PPS, 1 + (lane + 1) * PPS)
    rows = tuple(window_layers(model.layer_state))
    k, v, wk, wv = generation._window_paged_insert_jit(
        cache["k"], cache["v"], cache["wk"], cache["wv"], pk, pv, tables[lane],
        np.int32(lane), np.int32(prompt_len), page_tokens=PT,
        window_layers=rows, ring_pages=cache["wk"].shape[1] // LANES)
    return {"k": k, "v": v, "wk": wk, "wv": wv}, tables


def _decode(model, tree, cache, tables, lane, tok, pos, steps, kernel=False):
    """``steps`` greedy steps of ``lane`` through the paged arenas -> (the
    logits of each step, the tokens fed)."""
    cfg = dict(static_config(model))
    tokv, posv = np.zeros(LANES, np.int32), np.zeros(LANES, np.int32)
    active = np.zeros(LANES, bool)
    tokv[lane], posv[lane], active[lane] = tok, pos, True
    out, fed = [], []
    for _ in range(steps):
        logits, cache = generation._paged_forward_step(
            tree, jnp.asarray(tokv), cache, jnp.asarray(tables),
            jnp.asarray(posv), cfg, "moe_lm", PT, kernel=kernel,
            active=jnp.asarray(active))
        fed.append(int(tokv[lane]))
        out.append(np.asarray(logits[lane, 0]))
        tokv[lane] = int(np.argmax(out[-1]))
        posv[lane] += 1
    return out, fed


# -- (a) both arenas against the reference -------------------------------------

@pytest.mark.parametrize("prompt_len", [20, WINDOW, 5 * WINDOW],
                         ids=["shorter", "equal", "five_windows"])
def test_a_prefill_and_decode_through_both_arenas(prompt_len):
    """100 decode steps: the ring (48 tokens) turns more than twice."""
    model, tree = build("moe_lm", MC), _tree()
    prompt = np.random.default_rng(prompt_len).integers(1, 257, prompt_len)
    tok, pk, pv, last, _lane = _prefill(model, tree, prompt)
    ref = FAMILY.logits_many(MC, tree, [list(prompt)], 1)[0][0]
    np.testing.assert_allclose(np.asarray(last[0]), ref, atol=1e-4)
    cache, tables = _admit(model, pk, pv, prompt_len, lane=2)
    steps = 100
    got, fed = _decode(model, tree, cache, tables, 2, int(tok[0]), prompt_len,
                       steps)
    want = FAMILY.logits_many(MC, tree, [list(prompt) + fed], steps)[0]
    np.testing.assert_allclose(np.stack(got), want, atol=1e-4)


@pytest.mark.parametrize("kernel", [False, True], ids=["reference", "kernel"])
def test_a_a_head_of_64_packs_the_ring_as_it_packs_the_global_arena(
        monkeypatch, kernel):
    """Two KV heads of 64 are stored one 128-lane row in BOTH arenas
    (``init_paged_cache`` decides; PR 34): the admission packs a window
    layer's kept rows as it packs a global layer's, and the decode step reads
    them through the reference and through the kernel (its interpreter)."""
    monkeypatch.setattr(att, "PAGED_KERNEL_INTERPRET", kernel)
    mc = FAMILY.program_config(dict(PUBLISHED, head_dim=64))
    model, tree = build("moe_lm", mc), _tree(5, mc)
    plen, steps = 3 * WINDOW + 5, 40
    prompt = np.random.default_rng(6).integers(1, 257, plen)
    tok, pk, pv, _last, _ = _prefill(model, tree, prompt)
    cache, tables = _admit(model, pk, pv, plen, lane=3)
    assert cache["k"].shape == (1, LANES * PPS + 1, 1, PT, 128)
    assert cache["wk"].shape == (3, LANES * RING, 1, PT, 128)
    got, fed = _decode(model, tree, cache, tables, 3, int(tok[0]), plen, steps,
                       kernel=kernel)
    want = FAMILY.logits_many(mc, tree, [list(prompt) + fed], steps)[0]
    np.testing.assert_allclose(np.stack(got), want, atol=1e-4)


# -- (b) the ring against every row with the mask --------------------------------

def test_b_the_ring_answers_what_every_row_with_the_mask_answers():
    """The dense cached loop keeps every row of every layer and applies each
    layer's mask; the paged step reads a window layer's last rows out of a
    ring of three pages. Teacher-forced over 80 steps from a prompt of five
    windows: the same logits to 1e-5 (read: 3.3e-6; the softmax's sums run
    over 48 slots here and over every row there)."""
    model, tree = build("moe_lm", MC), _tree(1)
    cfg = dict(static_config(model))
    plen, steps = 5 * WINDOW, 80
    prompt = np.random.default_rng(3).integers(1, 257, plen)
    tok, pk, pv, _last, _ = _prefill(model, tree, prompt)
    cache, tables = _admit(model, pk, pv, plen, lane=1)
    got, fed = _decode(model, tree, cache, tables, 1, int(tok[0]), plen, steps)
    dense = generation.init_cache(cfg, 1, plen + steps)
    dense = {"k": dense["k"].at[:, :, :, :plen].set(pk[:, :, :, :plen]),
             "v": dense["v"].at[:, :, :, :plen].set(pv[:, :, :, :plen])}
    for j, t in enumerate(fed):
        logits, dense = generation._forward_cached_dyn(
            tree, jnp.asarray([[t]], jnp.int32), dense,
            jnp.asarray([plen + j], jnp.int32), cfg, "moe_lm")
        np.testing.assert_allclose(np.asarray(logits[0, 0]), got[j], atol=1e-5)


# -- (c) the paged kernel with a first valid token ------------------------------

def _arena(seed, lanes=3, pps=8, hkv=2, d=128, layers=2):
    key = jax.random.PRNGKey(seed)
    n = lanes * pps + 1
    k = jax.random.normal(key, (layers, n, hkv, PT, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 1), k.shape, jnp.float32)
    q = jax.random.normal(jax.random.fold_in(key, 2), (lanes, 4, 1, d))
    tables = jnp.asarray(np.arange(1, n).reshape(lanes, pps), jnp.int32)
    return q, k, v, tables


@pytest.mark.parametrize("offset", range(PT))
def test_c_kernel_with_a_first_valid_token_at_every_offset(offset):
    q, k, v, tables = _arena(offset)
    pos = jnp.asarray([100, 37 + offset, 127], jnp.int32)
    first = jnp.minimum(jnp.asarray([32 + offset, offset, 112 + offset]), pos)
    want = att.paged_decode_attention(q, k, v, tables, pos, PT, 1, first=first)
    got = att.paged_window_decode_attention_kernel(
        q, k, v, tables, pos, first, page_tokens=PT, interpret=True, layer=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # the bound is a bound: the answer differs from the one over every token
    whole = att.paged_decode_attention(q, k, v, tables, pos, PT, 1)
    assert float(jnp.max(jnp.abs(whole[0] - want[0]))) > 1e-3


def test_c_a_window_call_copies_no_page_wholly_before_the_window(monkeypatch):
    """Every page of a lane's ring that holds no token of ``(pos - window,
    pos]`` is POISONED with NaN: a call that copied one would multiply it into
    the value product (a masked score is a zero probability, and 0 x NaN is
    NaN). The view begins at the first valid token's page
    (``window_ring_view``) and the kernel's loop ends at ``pos``'s."""
    monkeypatch.setattr(att, "PAGED_KERNEL_INTERPRET", True)
    lanes, hkv, d = 4, 2, 128
    key = jax.random.PRNGKey(7)
    wk = jax.random.normal(key, (2, lanes * RING, hkv, PT, d), jnp.float32)
    wv = jax.random.normal(jax.random.fold_in(key, 1), wk.shape, jnp.float32)
    q = jax.random.normal(jax.random.fold_in(key, 2), (lanes, 4, 1, d))
    pos = jnp.asarray([5, 47, 63, 1000], jnp.int32)   # 1, 2, 2 and 3 pages read
    want = att.paged_window_attention(q, wk, wv, pos, PT, WINDOW, kernel=False,
                                      layer=1)
    poison = np.ones(lanes * RING, bool)
    for lane, p in enumerate(np.asarray(pos)):
        for page in range(max(0, p - WINDOW + 1) // PT, p // PT + 1):
            poison[lane * RING + page % RING] = False
    assert poison.sum() == 2 + 1 + 1 + 0
    nan = jnp.where(jnp.asarray(poison)[None, :, None, None, None], jnp.nan, 0.0)
    got = att.paged_window_attention(q, wk + nan, wv + nan, pos, PT, WINDOW,
                                     kernel=True, layer=1)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_c_the_ring_view_is_derived_from_the_position_alone():
    """Position p of lane s lives in page s R + (p // pt) % R; the table a
    window call reads is that ring rotated so that the oldest needed page
    comes first."""
    pos = jnp.asarray([0, 15, 31, 32, 47, 48, 1000], jnp.int32)
    tables, pos_v, first_v = att.window_ring_view(pos, WINDOW, PT, RING)
    for lane, p in enumerate(np.asarray(pos)):
        first = max(0, p - WINDOW + 1)
        page0 = first // PT
        want = [lane * RING + (page0 + j) % RING for j in range(RING)]
        assert list(np.asarray(tables[lane])) == want
        assert int(pos_v[lane]) == p - page0 * PT
        assert int(first_v[lane]) == first - page0 * PT
        # the page the step writes is in the view, where the view says
        assert want[int(pos_v[lane]) // PT] == lane * RING + (p // PT) % RING
    assert att.window_ring_pages(WINDOW, PT) == RING
    assert att.window_ring_pages(1024, 16) == 65


# -- (d) the window flash kernel -------------------------------------------------

@pytest.mark.parametrize("form", ["resident", "streamed"])
@pytest.mark.parametrize("s_len,window", [
    (256, 64), (512, 128), (300, 64), (640, 100), (384, 1000)],
    ids=["4_windows", "4_windows_128", "not_a_multiple", "odd_window",
         "window_longer_than_the_prompt"])
def test_d_window_flash_kernel_against_the_reference(monkeypatch, form, s_len,
                                                     window):
    if form == "streamed":
        monkeypatch.setattr(att, "KV_RESIDENT_LIMIT_BYTES", 0)
    key = jax.random.PRNGKey(s_len)
    q = jax.random.normal(key, (1, 4, s_len, 64), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, s_len, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, s_len, 64))
    want = att.attention_reference(q, k, v, True, window=window)
    got = att._flash_attention(q, k, v, True, 128, 128, True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # the reference's mask is the window's: i - j < window and j <= i
    i, j = np.arange(s_len)[:, None], np.arange(s_len)[None, :]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, 1)) / 8.0
    p = jax.nn.softmax(jnp.where((j <= i) & (i - j < window), scores, -jnp.inf))
    brute = jnp.einsum("bhqk,bhkd->bhqd", p, jnp.repeat(v, 2, 1))
    np.testing.assert_allclose(np.asarray(want), np.asarray(brute), atol=2e-6)


# -- (e) a head width of its own --------------------------------------------------

def test_e_a_head_width_the_hidden_size_does_not_give():
    """4 heads of 32 on hidden 96 through ``apply`` (the ``:predict`` path),
    whose window layers mask and whose rotary goes by kind too."""
    model, tree = build("moe_lm", MC), _tree(2)
    assert model.cache_row.width == 32 != MC["d_model"] // MC["n_heads"]
    assert tree["layers"][0]["attn"]["wq"].shape == (96, 128)
    ids = np.random.default_rng(4).integers(1, 257, (1, 3 * WINDOW))
    got = model.apply(tree, {"input_ids": jnp.asarray(ids)})["logits"][0]
    want = FAMILY.logits_many(MC, tree, [list(ids[0])], ids.shape[1])[0]
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)
    params = model.init(jax.random.PRNGKey(0))
    assert params["layers"][0]["attn"]["wo"].shape == (128, 96)


def test_e_the_solo_decoder_keeps_every_row_and_applies_the_mask():
    """``generation.generate`` (prefill through the flash gate, then the dense
    cached loop) emits the reference's greedy tokens past two windows."""
    model, tree = build("moe_lm", MC), _tree(5)
    prompt = np.random.default_rng(6).integers(1, 257, (1, 40))
    toks = np.asarray(generation.generate(model, tree, prompt, max_new_tokens=48))
    seq = list(prompt[0]) + list(toks[0][:-1])
    ref = FAMILY.logits_many(MC, tree, [seq], 48)[0]
    gap = ref.max(-1) - ref[np.arange(48), toks[0]]
    assert float(gap.max()) < 1e-4


# -- (f) each fault by name --------------------------------------------------------

def _faulty(fault):
    mc = dict(MC, rope_full=dict(MC["rope_full"]))
    if fault == "plain_frequencies_on_a_global_layer":
        mc.pop("rope_full")
    elif fault == "attention_factor_left_out":
        mc["rope_full"].pop("attention_factor")
    elif fault == "window_one_short":
        mc["sliding_window"] = WINDOW - 1
    elif fault == "window_one_long":
        mc["sliding_window"] = WINDOW + 1
    elif fault == "window_on_a_global_layer":
        mc["layer_types"] = [S] * 4
    elif fault == "no_window_at_all":
        mc["layer_types"] = [F] * 4
    elif fault == "gates_not_renormalised":
        mc["norm_topk_prob"] = False
    elif fault == "head_width_from_the_hidden_size":
        mc.pop("head_dim")
    return mc


@pytest.mark.parametrize("fault", [
    "plain_frequencies_on_a_global_layer", "yarn_on_a_window_layer",
    "attention_factor_left_out", "window_one_short", "window_one_long",
    "window_on_a_global_layer", "no_window_at_all", "gates_not_renormalised"])
def test_f_each_fault_lands_orders_above_the_tolerance(monkeypatch, fault):
    tree = _tree(8)
    ids = np.random.default_rng(9).integers(1, 257, (1, 4 * WINDOW))
    want = FAMILY.logits_many(MC, tree, [list(ids[0])], ids.shape[1])[0]
    sound = build("moe_lm", MC).apply(tree, {"input_ids": jnp.asarray(ids)})
    np.testing.assert_allclose(np.asarray(sound["logits"][0]), want, atol=1e-4)
    if fault == "yarn_on_a_window_layer":
        import tfservingcache_tpu.models.transformer_lm as lm

        real = lm.rope_of
        monkeypatch.setattr(lm, "rope_of", lambda cfg, window=0: real(cfg, 0))
        model = build("moe_lm", dict(MC, aux_loss_weight=0.02))   # a fresh trace
    else:
        model = build("moe_lm", _faulty(fault))
    got = model.apply(tree, {"input_ids": jnp.asarray(ids)})["logits"][0]
    assert float(np.max(np.abs(np.asarray(got) - want))) > 1e-2, fault


def test_f_a_head_width_from_the_hidden_size_does_not_fit_the_weights():
    tree = _tree(8)
    model = build("moe_lm", _faulty("head_width_from_the_hidden_size"))
    with pytest.raises(TypeError):
        model.apply(tree, {"input_ids": jnp.ones((1, 8), jnp.int32)})


# -- (g) what is declared and what is built -----------------------------------------

def test_g_a_window_layer_has_lanes_times_ring_pages_whatever_the_arena():
    model = build("moe_lm", MC)
    assert window_layers(model.layer_state) == (0, 1, 2)
    assert [s.window for s in model.layer_state] == [WINDOW] * 3 + [0]
    key = dict(static_config(model))
    assert key["layer_state"] == model.layer_state
    # (lane state, dense cache layer, layer of ITS arena, window)
    assert generation._layer_slots(key) == [
        (False, 0, 0, WINDOW), (False, 1, 1, WINDOW), (False, 2, 2, WINDOW),
        (False, 3, 0, 0)]
    assert generation.window_rows(key) == (0, 1, 2)
    for n_pages in (17, 4097):
        cache = jax.eval_shape(lambda: generation.init_paged_cache(
            key, n_pages, PT, lanes=LANES))
        assert cache["k"].shape == (1, n_pages, 2, PT, 32)        # 1 global layer
        assert cache["wk"].shape == cache["wv"].shape == (
            3, LANES * RING, 2, PT, 32)
    # no int8 form, no mesh, and the lane count is needed
    for bad in (dict(arena_dtype="int8", lanes=LANES), dict(lanes=0)):
        with pytest.raises(ValueError, match="window layers"):
            generation.init_paged_cache(key, 17, PT, **bad)


def test_g_the_benchmark_configuration_at_its_published_widths():
    """8 of Mellum2's 28 layers (two periods s s s F): 7.59 GB of weights; 2
    global layers x 16385 pages and 6 window layers x 32 lanes x 65 pages,
    1.48 GB, where all 8 layers in one arena would be 4.29 GB."""
    import json

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2-12b-a2.5b-instruct.json")) as f:
        config = json.load(f)
    mc = FAMILY.program_config(config)
    model = build("moe_lm", mc)
    assert window_layers(model.layer_state) == (0, 1, 2, 4, 5, 6)
    assert mc["n_layers"] == 8
    assert model.cache_row.width == 128 and mc["d_model"] // mc["n_heads"] == 72
    assert 7.58e9 < FAMILY.param_bytes(mc) < 7.60e9
    arena = jax.eval_shape(lambda: generation.init_paged_cache(
        dict(static_config(model)), 16385, 16, lanes=32))
    assert arena["k"].shape == (2, 16385, 4, 16, 128)
    assert arena["wk"].shape == (6, 32 * 65, 4, 16, 128)
    nbytes = 2 * 2 * (arena["k"].size + arena["wk"].size)
    assert 1.48e9 < nbytes < 1.49e9
    assert 4.29e9 < 2 * 2 * 8 * arena["k"].size // 2 < 4.30e9
    # a window and a global layer's rotary differ, and YaRN's blend is the
    # reference's own
    import tfservingcache_tpu.models.transformer_lm as lm

    plain, one, _ = lm.rope_of(mc, 1024)
    freqs, factor, _ = lm.rope_of(mc, 0)
    assert plain is None and one == 1.0 and factor == 1.2772588722239782
    want, _ = FAMILY.rope_frequencies(mc, F)
    np.testing.assert_allclose(freqs, want, rtol=1e-6)
    assert freqs[0] == pytest.approx(1.0) and freqs[-1] < 1e-5 / 8


def test_g_olmoe_builds_what_it_built():
    """A config with none of the new keys: no ``layer_state`` in the programs'
    key, one arena, no window operand, the attention gate never asked."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmoe-1b-7b-0125.json")) as f:
        import json
        config = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "bench_family_olmoe", os.path.join(ROOT, "benchmark", "families", "olmoe.py"))
    olmoe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(olmoe)
    mc = olmoe.program_config(dict(config, **config["rehearsal"]))
    model = build("moe_lm", mc)
    key = dict(static_config(model))
    assert "layer_state" not in key and not window_layers(model.layer_state)
    assert set(key) == set(model.config) | {"cache_row"}
    cache = jax.eval_shape(lambda: generation.init_paged_cache(key, 9, PT))
    assert set(cache) == {"k", "v"}
    before = dict(att.dispatch_tally())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    generation._slot_prefill_jit.lower(
        params, np.zeros((1, 32), np.int32), np.asarray([20], np.int32),
        jax.random.PRNGKey(0), np.float32(0), np.int32(0),
        cfg_key=static_config(model), family="moe_lm")
    after = att.dispatch_tally()
    assert all(after.get(k, 0) == before.get(k, 0) for k in after
               if k[0] in ("attention", "attention_window"))


# -- (h) through the engine -----------------------------------------------------------

def _load(tmp_path, name="windowed", metrics=None, **serving_kw):
    export_artifact("moe_lm", str(tmp_path), name=name, version=1,
                    config=MC, seed=0)
    rt = TPUModelRuntime(ServingConfig(platform="cpu", **serving_kw), metrics)
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


def test_h_engine_answers_as_the_solo_decoder_with_lanes_reused(tmp_path):
    """Six requests through two lanes, prompts on both sides of the window and
    answers that turn the ring: each answers what the solo decoder (every row
    kept, the mask applied) answers; the gauge reports both arenas, the ring
    what a window call read, the counter what an admission did not store."""
    metrics = Metrics()
    rt, mid = _load(tmp_path, metrics=metrics)
    rng = np.random.default_rng(12)
    lens = (5, 40, 1, 100, 33, 160)
    prompts = [rng.integers(1, MC["vocab_size"], n).astype(np.int32) for n in lens]
    new = 56
    try:
        solo = [np.asarray(rt.generate(mid, p[None], max_new_tokens=new, seed=1))[0]
                for p in prompts]
        eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4,
                                       page_tokens=PT, arena_pages=32)
        try:
            with cf.ThreadPoolExecutor(6) as pool:
                got = list(pool.map(
                    lambda p: eng.generate(mid, p[None], max_new_tokens=new)[0],
                    prompts))
            state = rt._slot_states[mid]
            state.check_page_conservation()
            assert state.k.shape[0] == 1                  # the global layer
            assert state.window[0].shape == (3, 2 * RING, 2, PT, 32)
            assert (state.window_tokens, state.ring_pages) == (WINDOW, RING)
            assert state.window_rows == (0, 1, 2)
            steps = RECORDER.snapshot(tail=4096)["models"][str(mid)]["steps"]
            read = [s["window_pages"] for s in steps if s["chunk"] > 0]
            assert read and 1.0 <= min(read) and max(read) <= RING
            assert max(read) > 2.0                        # a long lane reads 2-3
            label = metrics.model_label(mid.name, mid.version)
            ring = 2 * state.window[0].nbytes
            assert metrics.kv_arena_bytes.labels(label, "window")._value.get() == ring
            assert metrics.kv_arena_bytes.labels(
                label, "global")._value.get() == 2 * state.k.nbytes
            # rows older than a ring of 48 tokens, three window layers
            dropped = 3 * sum(max(0, n - RING * PT) for n in lens)
            assert metrics.gen_window_rows_dropped.labels(
                label)._value.get() == dropped
        finally:
            eng.close()
    finally:
        rt.close()
    for want, have in zip(solo, got):
        np.testing.assert_array_equal(have, want)


def test_h_an_admission_carries_its_prompts_length(tmp_path):
    rt, mid = _load(tmp_path, name="windowed_admit")
    try:
        state = rt.slot_decode_state(mid, 2, page_tokens=PT, arena_pages=16)
        assert state.reserve_pages(1, 80)
        tok, pk, pv, hit = rt.slot_prefill(mid, np.arange(1, 60), 0.0, 0, seed=1)
        assert isinstance(pk, PrefillRows) and pk.lane is None and not hit
        assert pk.prompt_len == 59 and pk.k.shape[0] == 4     # all row layers
        rt.slot_admit(state, 1, pk, pv)
        ring = np.asarray(state.window[0])
        assert ring[:, RING:].any() and not ring[:, :RING].any()
        # position p lives in the lane's page (p // 16) % 3 at offset p % 16:
        # the last 48 positions, 11..58
        for p in (11, 30, 47, 48, 58):
            np.testing.assert_array_equal(
                ring[0, RING + (p // PT) % RING, :, p % PT],
                np.asarray(pk.k)[0, 0, :, p])
        # an admission that lost its length is refused, not answered wrongly
        with pytest.raises(RuntimeError_, match="prompt's length"):
            rt.slot_admit(state, 0, pk.k, pv)
    finally:
        rt.close()


# -- (i) a fresh prefill builds no score block over the cache's length ------------------

def test_i_a_fresh_prefill_goes_through_the_attention_gate_in_every_layer():
    model = build("moe_lm", MC)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    s_pad = 256
    before = dict(att.dispatch_tally())
    generation._slot_prefill_jit.clear_cache()
    lowered = generation._slot_prefill_jit.lower(
        params, np.zeros((1, s_pad), np.int32), np.asarray([200], np.int32),
        jax.random.PRNGKey(0), np.float32(0), np.int32(0),
        cfg_key=static_config(model), family="moe_lm")
    after = att.dispatch_tally()
    grew = lambda gate: sum(  # noqa: E731
        after.get(k, 0) - before.get(k, 0) for k in after if k[0] == gate)
    assert grew("attention_window") == 3 and grew("attention") == 1
    # compiled for the chip the gate takes the kernels, which hold no score
    # block; on the CPU the reference's is (S, S) of the tokens at hand, never
    # (S, max_len) of the cache, and one position goes through the head
    jaxpr = jax.make_jaxpr(lambda *a: generation._slot_prefill_jit(
        *a, cfg_key=static_config(model), family="moe_lm"))(
        params, np.zeros((1, s_pad), np.int32), np.asarray([200], np.int32),
        jax.random.PRNGKey(0), np.float32(0), np.int32(0))
    text = str(jaxpr)
    assert f"{s_pad},{MC['vocab_size']}]" not in text       # no (S, V) logits
    del lowered


# -- (j) refused by name -----------------------------------------------------------------

REFUSALS = ["int8_arena", "shared_prefix", "conversation_kv", "spec_draft_model",
            "chunked_prefill", "draft_model", "mesh", "park_lane"]


@pytest.mark.parametrize("what", REFUSALS)
def test_j_what_cannot_turn_a_ring_is_refused_by_name(tmp_path, monkeypatch, what):
    knobs = {"conversation_kv": dict(conversation_kv_bytes=1 << 20),
             "spec_draft_model": dict(spec_draft_model="draft"),
             "chunked_prefill": dict(prefill_chunk_tokens=8)}.get(what, {})
    rt, mid = _load(tmp_path, name=f"windowed_{what}", **knobs)
    ids = np.ones((1, 4), np.int32)
    refused = lambda pattern: pytest.raises(  # noqa: E731
        RuntimeError_, match=f"window layers.*{pattern}")
    try:
        if what == "int8_arena":
            with refused("int8 arena"):
                rt.slot_decode_state(mid, 4, arena_dtype="int8")
        elif what == "shared_prefix":
            with refused("kv_share_prefix_bytes"):
                rt.slot_decode_state(mid, 4, share_prefix_bytes=1 << 20)
        elif what == "conversation_kv":
            with refused("conversation_kv_bytes"):
                rt.slot_decode_state(mid, 4)
        elif what == "spec_draft_model":
            with refused("spec_draft_model"):
                rt.slot_decode_state(mid, 4)
        elif what == "chunked_prefill":
            with refused("prefill_chunk_tokens"):
                rt.slot_decode_state(mid, 4)
        elif what == "park_lane":
            state = rt.slot_decode_state(mid, 4, page_tokens=PT)
            with refused("conversation park/resume"):
                rt.park_lane(state, 0, np.arange(1, 9))
            with refused("prefill_chunk_tokens"):
                rt.slot_prefill_chunk(mid, state, 0, np.arange(1, 9), 0, 8)
        elif what == "draft_model":
            export_artifact("transformer_lm", str(tmp_path), name="draft", version=1,
                            config={"vocab_size": MC["vocab_size"], "d_model": 32,
                                    "n_layers": 1, "n_heads": 2, "n_kv_heads": 2,
                                    "d_ff": 64, "max_seq": 64, "dtype": "float32"})
            draft = ModelId("draft", 1)
            rt.ensure_loaded(Model(identifier=draft,
                                   path=str(tmp_path / "draft" / "1")))
            with refused("draft_model"):
                rt.generate(mid, ids, max_new_tokens=2, seed=1,
                            draft_model_id=draft)
            state = rt.slot_decode_state(mid, 4)
            with refused("spec_draft_model"):
                rt.slot_attach_draft(state, draft)
        else:
            monkeypatch.setattr(rt, "mesh", object())
            with refused("mesh"):
                rt.generate(mid, ids, max_new_tokens=2, seed=1)
            with refused("mesh"):
                rt.slot_decode_state(mid, 4)
    finally:
        monkeypatch.undo()
        rt.close()


def test_j_a_verify_pass_over_a_ring_is_refused_at_trace_time():
    model = build("moe_lm", MC)
    cfg = dict(static_config(model))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: generation.init_paged_cache(
        cfg, 17, PT, lanes=LANES))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    with pytest.raises(ValueError, match="does not turn a window layer's ring"):
        jax.eval_shape(
            lambda p, c, t, tb, ps: generation._paged_verify_step(
                p, t, c, tb, ps, cfg, "moe_lm", PT),
            params, cache, i32(LANES, 3), i32(LANES, PPS), i32(LANES))
    # ... and a chunk of chunked prefill, which hands in no ring at all
    with pytest.raises(ValueError, match="does not turn a window layer's ring"):
        jax.eval_shape(
            lambda p, k, v, tb, t, st, n: generation._paged_prefill_chunk_jit(
                p, k, v, None, tb, t, st, n, cfg_key=static_config(model),
                family="moe_lm", page_tokens=PT),
            params, cache["k"], cache["v"], i32(1, PPS), i32(1, 8), i32(1),
            i32(1))


# -- hardware-gated rows (tools/tpu_kernel_check.py -k "window and on_tpu") --------------

ON_TPU = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)")
V5E_HBM, V5E_BF16 = 819e9, 197e12


@ON_TPU
@pytest.mark.parametrize("live", [1, 6, 16, 32])
def test_window_decode_kernel_on_tpu(live):
    """A window layer's decode call at the benchmark configuration's shape (32
    query heads over 4 KV heads of 128, pages of 16, a ring of 65 pages a lane,
    32 lanes of which ``live`` hold 600-8000 tokens): the kernel with a first
    valid token against the gather + einsum reference, its time against the
    least the chip could take for the pages it must read (2 KiB a token), and
    beside it a GLOBAL layer's call over the same lanes' whole contexts."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    lanes, hq, hkv, d, pt, window = 32, 32, 4, 128, 16, 1024
    ring = att.window_ring_pages(window, pt)
    rng = np.random.default_rng(live)
    pos = np.zeros(lanes, np.int32)
    pos[:live] = rng.integers(600, 8000, live)
    active = np.arange(lanes) < live
    key = jax.random.PRNGKey(live)
    shape = (2, lanes * ring, hkv, pt, d)
    wk = jax.random.normal(key, shape, jnp.bfloat16)
    wv = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 2), (lanes, hq, 1, d), jnp.bfloat16)
    pos_d, act = jnp.asarray(pos), jnp.asarray(active)

    def call(kernel):
        return lambda q, wk, wv, pos, act: att.paged_window_attention(
            q, wk, wv, pos, pt, window, kernel=kernel, active=act, layer=1)

    out = jax.jit(call(True))(q, wk, wv, pos_d, act)
    ref = jax.jit(call(False))(q, wk, wv, pos_d, act)
    assert not np.asarray(out)[~active].any()
    err = float(jnp.max(jnp.abs(out - ref)[act]))
    assert err < 3e-2, f"window decode kernel diverges: max abs err {err}"
    args = (q, wk, wv, pos_d, act)
    # (the reference is not timed: its gather does not depend on the query
    # the timing chain perturbs, so the chain hoists it and times an einsum)
    t_kern = chained_device_time(call(True), args)
    # the pages that hold each live lane's last min(t, window) tokens
    t = pos[:live] + 1
    kept = np.minimum(t, window)
    pages = (t - 1) // pt - (t - kept) // pt + 1
    need = int(pages.sum()) * pt * 2 * hkv * d * 2 + live * hq * d * 6
    # a global layer's call over the same lanes, every token of each
    pps = 512
    gk = jax.random.normal(key, (1, lanes * pps + 1, hkv, pt, d), jnp.bfloat16)
    tables = jnp.asarray(1 + np.arange(lanes * pps).reshape(lanes, pps), jnp.int32)
    t_glob = chained_device_time(
        lambda q, gk, tables, pos, act: att.paged_attention(
            q, gk, gk, tables, pos, pt, kernel=True, active=act),
        (q, gk, tables, pos_d, act))
    print(f"\n[window_decode] live={live}/32 tokens={int(t.sum())} "
          f"pages read {int(pages.sum())} (mean {pages.mean():.1f} a lane): "
          f"kernel {t_kern * 1e3:.3f} ms ({need / t_kern / 1e9:.0f} GB/s, "
          f"{100 * need / V5E_HBM / t_kern:.1f} % of the HBM roofline), "
          f"a global call over the same "
          f"lanes ({int((t // pt + 1).sum())} pages) {t_glob * 1e3:.3f} ms, "
          f"max_abs_err {err:.4f}", flush=True)


@ON_TPU
@pytest.mark.parametrize("s_len", [1024, 4096, 8192])
def test_window_flash_kernel_on_tpu(s_len):
    """A window layer's fresh prefill at the benchmark configuration's shape
    (32 query heads over 4 KV heads of 128, window 1024): the windowed flash
    kernel against ``attention_reference`` with the mask (a block of queries
    at a time: the whole score block is 8.6 GB at 8192), its time against the
    window's own FLOPs, and beside it the whole causal triangle's."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    hq, hkv, d, window = 32, 4, 128, 1024
    key = jax.random.PRNGKey(s_len)
    q = jax.random.normal(key, (1, hq, s_len, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, hkv, s_len, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, hkv, s_len, d), jnp.bfloat16)
    out = att.flash_window_attention(q, k, v, window=window)
    block = 512
    ref = jnp.concatenate([
        att.attention_reference(q[:, :, q0:q0 + block], k[:, :, :q0 + block],
                                v[:, :, :q0 + block], True, window=window)
        for q0 in range(0, s_len, block)], axis=2)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert err < 3e-2, f"window flash kernel diverges: max abs err {err}"
    t_win = chained_device_time(
        lambda q, k, v: att.flash_window_attention(q, k, v, window=window),
        (q, k, v))
    t_full = chained_device_time(
        lambda q, k, v: att.flash_attention(q, k, v, causal=True), (q, k, v))
    full = min(s_len, window)
    pairs = full * (full + 1) // 2 + (s_len - full) * window
    flops = 4 * hq * d * pairs
    print(f"\n[window_flash] S={s_len} window={window} "
          f"({att.flash_variant(s_len, d, 2)}): windowed {t_win * 1e3:.3f} ms "
          f"({flops / t_win / 1e12:.1f} TFLOP/s of the window's "
          f"{flops / 1e12:.3f} TFLOP, {100 * flops / V5E_BF16 / t_win:.1f} % of "
          f"the bf16 roofline), the whole causal triangle {t_full * 1e3:.3f} ms "
          f"({t_full / t_win:.2f}x), max_abs_err {err:.4f}", flush=True)
