"""A hybrid decoder (gated short convolutions beside grouped-query attention,
a dense SwiGLU in the leading layers and routed experts after) through the
program: the ``hybrid_lm`` family against the plain reference the benchmark
keeps (``benchmark/families/lfm2_moe.py``), at a small size on the CPU.

  (a) the family's ``apply`` logits against the reference, and that each of
      the mistakes the tolerance is there to catch lands orders above it;
  (b) prefill of a prompt whose length is NOT its bucket's, then 20 decode
      steps through the paged arena and the lane state, logits against the
      reference's full forward at every position; a prompt of one token;
  (c) the lane state by itself: a lane that served one request and admits
      another answers as a fresh lane, an inactive lane's state is bit for
      bit what it was after a chunk, two strangers in one step answer what
      each answers alone;
  (d) what a ModelDef declares: the arena has a layer a layer WITH pages (3 of
      14 at the benchmark's configuration, 0.81 GB at 8192 pages), and the
      three families of one kind declare what they declared;
  (e) through ``ContinuousGenerateEngine``: the engine answers what the solo
      decoder (the dense cache and its lane state) answers, with lanes reused;
  (f) what the family cannot do yet is refused by name;
  (g) the three accepted families' decode chunk is traced as the parent
      traced it: the operand a lane state would take is an empty pytree.

THE TOLERANCE. Every comparison with the reference is of float32 models at
logits level, ``atol`` 1e-4 of logits whose spread is about 1: what is left
is the order of float32 sums. A dropped convolution tap, a state read at the
bucket's end, softmax for sigmoid, a selection without its bias,
whole-projection QK-norm for per-head, a gate normalised without its 1e-6
each land hundredths to whole tenths away (asserted in (a), (b)).
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tfservingcache_tpu.models.generation as generation
import tfservingcache_tpu.models.hybrid_lm as hybrid
import tfservingcache_tpu.ops.attention as att
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import (
    CacheRow,
    LaneState,
    build,
    export_artifact,
    lane_layers,
    static_config,
)
from tfservingcache_tpu.ops import moe
from tfservingcache_tpu.runtime.base import RuntimeError_
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import PrefillRows, TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId
from tfservingcache_tpu.utils.flight_recorder import RECORDER
from tfservingcache_tpu.utils.tracing import TRACER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _family():
    spec = importlib.util.spec_from_file_location(
        "bench_family_lfm2_moe",
        os.path.join(ROOT, "benchmark", "families", "lfm2_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAMILY = _family()
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv"]
# hidden 64, 4 query / 2 KV heads of 16, 6 layers of the published pattern's
# beginning (c c A c c c), 2 dense layers of 96, then 8 experts of 32, 2 a token
PUBLISHED = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1.0, "conv_L_cache": 3, "conv_bias": False,
    "layer_types": TYPES, "num_dense_layers": 2, "num_hidden_layers": 6,
    "vocab_size": 97, "norm_eps": 1e-5, "rope_theta": 1000000,
    "max_position_embeddings": 64, "torch_dtype": "float32",
    "assumed": {"head_dim": {"value": 16}, "gate_norm_eps": {"value": 1e-6}},
}
MC = FAMILY.program_config(PUBLISHED)
PT = 8
LANES = 4
N_ATTN = TYPES[:6].count("full_attention")      # 1 of the 6 layers attends
N_CONV = 6 - N_ATTN


def _tree(seed=0, mc=MC):
    """Seeded weights in the benchmark's layout, every gain random too (a gain
    of one would hide a norm applied to the wrong tensor) and the selection
    bias large enough to change a selection in three."""
    rng = np.random.default_rng(seed)
    leaves = {name: (rng.standard_normal(shape) / np.sqrt(fan_in)).astype(np.float32)
              for name, (shape, fan_in) in FAMILY.leaf_shapes(mc).items()}
    tree = FAMILY.to_tree(mc, leaves)
    gain = lambda a: (1.0 + 0.2 * rng.standard_normal(a.shape)).astype(np.float32)  # noqa: E731
    tree["ln_f"] = gain(tree["ln_f"])
    for lp in tree["layers"]:
        lp["ln1"], lp["ln2"] = gain(lp["ln1"]), gain(lp["ln2"])
        if "attn" in lp:
            lp["attn"]["q_norm"] = gain(lp["attn"]["q_norm"])
            lp["attn"]["k_norm"] = gain(lp["attn"]["k_norm"])
        if "moe" in lp:
            lp["moe"]["bias"] = (5 * lp["moe"]["bias"]).astype(np.float32)
    return tree


def _apply(mc, tree, ids):
    out = build("hybrid_lm", mc).apply(
        jax.tree_util.tree_map(jnp.asarray, tree), {"input_ids": np.asarray(ids)[None]})
    return np.asarray(out["logits"])[0]


def _reference(tree, seq, mc=MC):
    return FAMILY.logits_many(mc, tree, [list(map(int, seq))], last=len(seq))[0]


# -- (a) the full forward ------------------------------------------------------

def _drop_tap(monkeypatch, tree):
    for lp in tree["layers"]:
        if "conv" in lp:
            lp["conv"]["w"] = lp["conv"]["w"].copy()
            lp["conv"]["w"][:, 0] = 0.0
    return MC, tree


def _softmax_scores(monkeypatch, tree):
    return dict(MC, route_score="softmax"), tree


def _no_bias(monkeypatch, tree):
    for lp in tree["layers"]:
        if "moe" in lp:
            lp["moe"] = {k: v for k, v in lp["moe"].items() if k != "bias"}
    return MC, tree


def _whole_projection_norm(monkeypatch, tree):
    for lp in tree["layers"]:
        if "attn" in lp:
            for side, n in (("q_norm", 4), ("k_norm", 2)):
                lp["attn"][side] = np.tile(lp["attn"][side], n)
    return MC, tree


MISTAKES = {"dropped_conv_tap": _drop_tap, "softmax_for_sigmoid": _softmax_scores,
            "selection_without_bias": _no_bias,
            "whole_projection_qk_norm": _whole_projection_norm}


@pytest.mark.parametrize("mistake", [None, *MISTAKES])
def test_a_full_forward_equals_the_reference(monkeypatch, mistake):
    tree = _tree(1)
    ids = np.random.default_rng(2).integers(1, MC["vocab_size"], 29)
    want = _reference(tree, ids)
    assert want.std() > 0.5
    if mistake is None:
        np.testing.assert_allclose(_apply(MC, tree, ids), want, atol=1e-4, rtol=0)
        return
    mc, bad_tree = MISTAKES[mistake](monkeypatch, tree)
    assert np.max(np.abs(_apply(mc, bad_tree, ids) - want)) > 1e-2, mistake


def test_a_gate_denominator_term_is_the_models_and_defaults_to_the_bare_sum():
    """``route`` divides by the sum of the chosen scores plus ``norm_eps``;
    0 (every other family) is the bare sum, traced as it was."""
    x = jnp.asarray(np.random.default_rng(3).standard_normal((5, 16)), jnp.float32)
    router = jnp.asarray(np.random.default_rng(4).standard_normal((16, 8)) - 3.0,
                         jnp.float32) * 4.0
    bare, idx, probs = moe.route(x, router, 2, True, "sigmoid")
    with_eps, idx2, _ = moe.route(x, router, 2, True, "sigmoid", norm_eps=1e-6)
    np.testing.assert_array_equal(idx, idx2)
    chosen = np.take_along_axis(np.asarray(probs), np.asarray(idx), -1)
    np.testing.assert_allclose(
        with_eps, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(bare).sum(-1), 1.0, rtol=1e-6)
    assert np.max(np.abs(np.asarray(bare) - np.asarray(with_eps))) > 1e-5
    same = lambda **kw: str(jax.make_jaxpr(  # noqa: E731
        lambda x: moe.route(x, router, 2, True, "sigmoid", **kw))(x))
    assert same() == same(norm_eps=0.0) != same(norm_eps=1e-6)


# -- (b) prefill, then decode through the arena and the lane state ------------

def _prefill(dev, prompt, p_pad=16, mc=MC):
    model = build("hybrid_lm", mc)
    ids = np.zeros((1, p_pad), np.int32)
    ids[0, :len(prompt)] = prompt
    tok, pk, pv, last, lane = generation._slot_prefill_jit(
        dev, ids, np.asarray([len(prompt)], np.int32), jax.random.PRNGKey(0),
        np.float32(0.0), np.int32(0), cfg_key=static_config(model),
        family="hybrid_lm")
    return int(tok[0]), pk, pv, np.asarray(last)[0], lane


def _paged_setup(dev, prompt, lane=1, pages=24, mc=MC):
    """Prefill ``prompt`` and admit it into lane ``lane`` of a fresh arena and
    a fresh lane-state array -> (cfg, cache with ``lane``, tables, pos, first
    token, the last prompt position's logits). ``MC64`` (2 KV heads of 64)
    gets the packed arena: one 128-wide row a token for the pair."""
    model = build("hybrid_lm", mc)
    cfg = dict(static_config(model))
    head = mc["d_model"] // mc["n_heads"]
    tok, pk, pv, last, state = _prefill(dev, prompt, mc=mc)
    assert pk.shape == pv.shape == (N_ATTN, 1, 2, 16, head)
    assert state.shape == (N_CONV, 1, 2, mc["d_model"])
    cache = generation.init_paged_cache(cfg, pages, PT, row=model.cache_row)
    assert cache["k"].shape == (
        (N_ATTN, pages, 1, PT, 128) if head == 64 else (N_ATTN, pages, 2, PT, 16))
    pps = mc["max_seq"] // PT
    tables = np.zeros((LANES, pps), np.int32)
    tables[lane, :5] = 1 + 5 * lane + np.arange(5)       # 40 tokens a lane
    k, v, _ = generation._paged_insert_jit(
        cache["k"], cache["v"], None, pk, pv, tables[lane], np.int32(0),
        page_tokens=PT)
    lanes = generation._lane_insert_jit(
        generation.init_lane_state(cfg, LANES), state, np.int32(lane))
    pos = np.zeros((LANES,), np.int32)
    pos[lane] = len(prompt)
    return cfg, {"k": k, "v": v, "lane": lanes}, tables, pos, tok, last


@pytest.mark.parametrize("prompt_len", [11, 1, 2],
                         ids=["off_bucket", "one_token", "two_tokens"])
def test_b_prefill_then_paged_decode_matches_the_reference_at_every_position(
        prompt_len):
    tree = _tree(2)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, MC["vocab_size"], prompt_len)
    forced = rng.integers(1, MC["vocab_size"], 20)        # teacher-forced tail
    seq = np.concatenate([prompt, forced])
    want = _reference(tree, seq)
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    lane = 1
    cfg, cache, tables, pos, _tok, last = _paged_setup(dev, prompt, lane=lane)
    np.testing.assert_allclose(last, want[prompt_len - 1], atol=1e-4, rtol=0)
    active = np.arange(LANES) == lane
    step = jax.jit(lambda cache, tok, pos: generation._paged_forward_step(
        dev, tok, cache, tables, pos, cfg, "hybrid_lm", PT, active=active))
    tok = np.zeros((LANES,), np.int32)
    for j, t in enumerate(forced):
        tok[lane] = t
        logits, cache = step(cache, tok, pos)
        np.testing.assert_allclose(np.asarray(logits)[lane, 0],
                                   want[prompt_len + j], atol=1e-4, rtol=0)
        pos[lane] += 1
    # the lanes nobody read kept the zeros they were built with
    others = np.asarray(cache["lane"])[:, ~active]
    assert not others.any()


# the same model with 2 KV heads of 64: its arena packs them into one row
MC64 = FAMILY.program_config(dict(
    PUBLISHED, hidden_size=256,
    assumed={"head_dim": {"value": 64}, "gate_norm_eps": {"value": 1e-6}}))


@pytest.fixture
def interpret_kernel(monkeypatch):
    generation._paged_decode_chunk_jit.clear_cache()
    monkeypatch.setattr(att, "PAGED_KERNEL_INTERPRET", True)
    yield
    generation._paged_decode_chunk_jit.clear_cache()


@pytest.mark.parametrize("kernel", [False, True], ids=["reference", "kernel"])
def test_b_head_64_prefill_then_decode_over_the_packed_arena_matches_the_reference(
        kernel, interpret_kernel):
    """(b) again at a head of 64: the arena stores the two KV heads in one
    128-wide row (``init_paged_cache``), the insert and the step's write pack
    their rows, and 20 decode steps read them back through the gather + einsum
    reference (it unpacks what it gathered) and through the decode kernel on
    the packed pages (its interpreter), each 1e-4 from the float32 reference
    at every position."""
    tree = _tree(2, MC64)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, MC64["vocab_size"], 11)
    forced = rng.integers(1, MC64["vocab_size"], 20)
    want = _reference(tree, np.concatenate([prompt, forced]), MC64)
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    lane = 1
    cfg, cache, tables, pos, _tok, last = _paged_setup(dev, prompt, lane=lane,
                                                       mc=MC64)
    np.testing.assert_allclose(last, want[len(prompt) - 1], atol=1e-4, rtol=0)
    active = np.arange(LANES) == lane
    gate = ("paged_attention",
            *(("kernel", "interpret") if kernel else ("reference", "kernel=False")))
    before = att.dispatch_tally().get(gate, 0)
    step = jax.jit(lambda cache, tok, pos: generation._paged_forward_step(
        dev, tok, cache, tables, pos, cfg, "hybrid_lm", PT, active=active,
        kernel=kernel))
    tok = np.zeros((LANES,), np.int32)
    for j, t in enumerate(forced):
        tok[lane] = t
        logits, cache = step(cache, tok, pos)
        np.testing.assert_allclose(np.asarray(logits)[lane, 0],
                                   want[len(prompt) + j], atol=1e-4, rtol=0)
        pos[lane] += 1
    assert cache["k"].shape[-1] == 128
    assert att.dispatch_tally().get(gate, 0) == before + N_ATTN


def test_b_head_64_decode_chunk_through_the_kernel_emits_the_references_tokens(
        interpret_kernel):
    tree = _tree(4, MC64)
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    prompt = np.random.default_rng(5).integers(1, MC64["vocab_size"], 13)
    lane = 2
    cfg, cache, tables, pos, first, _ = _paged_setup(dev, prompt, lane=lane,
                                                     mc=MC64)
    active = np.arange(LANES) == lane
    tok = np.zeros((LANES,), np.int32)
    tok[lane] = first
    k, _, _, _, _, toks, _, _, _ = generation._paged_decode_chunk_jit(
        dev, cache["k"], cache["v"], None, tables, tok, pos, active,
        np.uint32(1),
        np.zeros((LANES,), np.float32), np.zeros((LANES,), np.int32),
        cache["lane"], cfg_key=tuple(sorted(cfg.items())), family="hybrid_lm",
        chunk=4, page_tokens=PT, kernel=True)
    assert k.shape == cache["k"].shape == (N_ATTN, 24, 1, PT, 128)
    chain = [first]
    for _ in range(4):
        ref = FAMILY.logits_many(MC64, tree, [prompt.tolist() + chain], last=1)[0][0]
        chain.append(int(np.argmax(ref)))
    assert np.asarray(toks)[lane].tolist() == chain[1:]


def test_b_a_state_read_at_the_buckets_end_is_caught():
    """The mistake the prefill's ``real_len`` is there against: the state
    after the pad tokens of a bucket is another state, and the first decode
    step after it lands tenths away."""
    tree = _tree(2)
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    prompt = np.random.default_rng(3).integers(1, MC["vocab_size"], 11)
    _, _, _, _, good = _prefill(dev, prompt)
    cfg = dict(static_config(build("hybrid_lm", MC)))
    ids = np.zeros((1, 16), np.int32)
    ids[0, :11] = prompt
    _, cache = generation._forward_cached_dyn(
        dev, ids, generation.init_cache(cfg, 1, 16), jnp.zeros((1,), jnp.int32),
        cfg, "hybrid_lm", fresh=True)                      # no real_len
    assert np.max(np.abs(np.asarray(cache["lane"]) - np.asarray(good))) > 1e-2


def test_b_decode_chunk_program_greedy_tokens_and_stats():
    tree = _tree(4)
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    prompt = np.random.default_rng(5).integers(1, MC["vocab_size"], 13)
    lane = 2
    cfg, cache, tables, pos, first, _ = _paged_setup(dev, prompt, lane=lane)
    active = np.arange(LANES) == lane
    tok = np.zeros((LANES,), np.int32)
    tok[lane] = first
    k, v, scales, _, _, toks, stats, lanes, _ = generation._paged_decode_chunk_jit(
        dev, cache["k"], cache["v"], None, tables, tok, pos, active, np.uint32(1),
        np.zeros((LANES,), np.float32), np.zeros((LANES,), np.int32),
        cache["lane"], cfg_key=tuple(sorted(cfg.items())), family="hybrid_lm",
        chunk=4, page_tokens=PT)
    assert scales is None and k.shape == cache["k"].shape
    chain = [first]
    for _ in range(4):
        ref = FAMILY.logits_many(MC, tree, [prompt.tolist() + chain], last=1)[0][0]
        chain.append(int(np.argmax(ref)))
    assert np.asarray(toks)[lane].tolist() == chain[1:]
    hit, rows_max, local = np.asarray(stats)
    # one live row, 2 assignments a layer, a mean over the 4 layers WITH experts
    assert hit == 2.0 and rows_max == 1.0 and local == 2.0
    assert lanes.shape == (N_CONV, LANES, 2, 64)


# -- (c) the lane state by itself ----------------------------------------------

def _chunk(dev, cfg, cache, tables, tok, pos, active, chunk=4):
    k, v, _, tok, pos, toks, _, lanes, _ = generation._paged_decode_chunk_jit(
        dev, cache["k"], cache["v"], None, tables, tok, pos, active, np.uint32(7),
        np.zeros((LANES,), np.float32), np.zeros((LANES,), np.int32),
        cache["lane"], cfg_key=tuple(sorted(cfg.items())), family="hybrid_lm",
        chunk=chunk, page_tokens=PT)
    return {"k": k, "v": v, "lane": lanes}, np.asarray(tok), np.asarray(pos), \
        np.asarray(toks)


def test_c_a_reused_lane_answers_as_a_fresh_lane():
    dev = jax.tree_util.tree_map(jnp.asarray, _tree(6))
    rng = np.random.default_rng(7)
    first_prompt = rng.integers(1, MC["vocab_size"], 9)
    second_prompt = rng.integers(1, MC["vocab_size"], 12)
    lane = 1
    active = np.arange(LANES) == lane

    def serve(cache, tables, prompt):
        """Admit ``prompt`` into ``lane`` of this cache and decode 8 tokens."""
        tok0, pk, pv, _, state = _prefill(dev, prompt)
        k, v, _ = generation._paged_insert_jit(
            cache["k"], cache["v"], None, pk, pv, tables[lane], np.int32(0),
            page_tokens=PT)
        lanes = generation._lane_insert_jit(cache["lane"], state, np.int32(lane))
        tok = np.zeros((LANES,), np.int32)
        tok[lane] = tok0
        pos = np.zeros((LANES,), np.int32)
        pos[lane] = len(prompt)
        cache, tok, pos, a = _chunk(dev, cfg, {"k": k, "v": v, "lane": lanes},
                                    tables, tok, pos, active)
        cache, tok, pos, b = _chunk(dev, cfg, cache, tables, tok, pos, active)
        return cache, np.concatenate([a[lane], b[lane]])

    cfg, used, tables, *_ = _paged_setup(dev, first_prompt, lane=lane)
    used, _ = serve(used, tables, first_prompt)
    assert np.asarray(used["lane"])[:, lane].any()          # a state was left
    _, after_reuse = serve(used, tables, second_prompt)
    _, fresh, tables2, *_ = _paged_setup(dev, second_prompt, lane=lane)
    _, on_fresh = serve(fresh, tables2, second_prompt)
    np.testing.assert_array_equal(after_reuse, on_fresh)


def test_c_an_inactive_lanes_state_is_bit_identical_after_a_chunk():
    dev = jax.tree_util.tree_map(jnp.asarray, _tree(8))
    rng = np.random.default_rng(9)
    cfg, cache, tables, pos, first, _ = _paged_setup(
        dev, rng.integers(1, MC["vocab_size"], 10), lane=1)
    # lane 3 holds a retired request's state: anything but zeros
    junk = jnp.asarray(rng.standard_normal((N_CONV, 2, 64)), jnp.float32)
    cache["lane"] = cache["lane"].at[:, 3].set(junk)
    before = np.asarray(cache["lane"]).copy()
    tok = np.zeros((LANES,), np.int32)
    tok[1] = first
    active = np.arange(LANES) == 1
    after, *_ = _chunk(dev, cfg, cache, tables, tok, pos, active)
    after = np.asarray(after["lane"])
    np.testing.assert_array_equal(after[:, [0, 2, 3]], before[:, [0, 2, 3]])
    assert np.max(np.abs(after[:, 1] - before[:, 1])) > 1e-3


def test_c_two_strangers_in_one_step_answer_what_each_answers_alone():
    dev = jax.tree_util.tree_map(jnp.asarray, _tree(10))
    rng = np.random.default_rng(11)
    prompts = {0: rng.integers(1, MC["vocab_size"], 7),
               2: rng.integers(1, MC["vocab_size"], 14)}
    model = build("hybrid_lm", MC)
    cfg = dict(static_config(model))

    def run(lanes_in):
        cache = generation.init_paged_cache(cfg, 24, PT, row=model.cache_row)
        cache["lane"] = generation.init_lane_state(cfg, LANES)
        tables = np.zeros((LANES, MC["max_seq"] // PT), np.int32)
        tok = np.zeros((LANES,), np.int32)
        pos = np.zeros((LANES,), np.int32)
        for lane in lanes_in:
            tables[lane, :5] = 1 + 5 * lane + np.arange(5)
            tok[lane], pk, pv, _, state = _prefill(dev, prompts[lane])
            cache["k"], cache["v"], _ = generation._paged_insert_jit(
                cache["k"], cache["v"], None, pk, pv, tables[lane], np.int32(0),
                page_tokens=PT)
            cache["lane"] = generation._lane_insert_jit(
                cache["lane"], state, np.int32(lane))
            pos[lane] = len(prompts[lane])
        active = np.isin(np.arange(LANES), lanes_in)
        logits, _ = generation._paged_forward_step(
            dev, jnp.asarray(tok), cache, tables, jnp.asarray(pos), cfg,
            "hybrid_lm", PT, active=active)
        return np.asarray(logits)[:, 0]

    together = run([0, 2])
    for lane in (0, 2):
        np.testing.assert_allclose(together[lane], run([lane])[lane],
                                   atol=1e-5, rtol=0)


# -- (d) what the ModelDef declares --------------------------------------------

def test_d_the_benchmark_configurations_arena_has_a_layer_a_layer_with_pages():
    """LFM2-8B-A1B as the cell runs it: 14 layers, 3 with pages. The arena of
    8192 pages of 16 tokens is 3 x 8193 x 8 x 16 x 64 x 2 sides x 2 B = 0.81
    GB, not the 3.76 GB fourteen layers would take; the lane state is 2.9 MB.
    Its 8 KV heads of 64 are stored two a 128-lane row (PR 34): the same
    bytes. (At ISSUE 33's fallback of 10 layers: 2 layers with pages.)"""
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-8b-a1b.json")) as f:
        config = json.load(f)
    model = build("hybrid_lm", FAMILY.program_config(config))
    kinds = model.layer_state
    assert len(kinds) == 14
    assert lane_layers(kinds) == (0, 1, 3, 4, 5, 7, 8, 9, 11, 12, 13)
    assert set(kinds) == {CacheRow(2, 8, 64), LaneState(2, 2048)}
    cfg = dict(static_config(model))
    serving = config["server"]["serving"]
    pages = serving["kv_arena_pages"]
    arena = jax.eval_shape(lambda: generation.init_paged_cache(
        cfg, pages + 1, serving["kv_page_tokens"], row=model.cache_row))
    assert arena["k"].shape == arena["v"].shape == (3, 8193, 4, 16, 128)
    nbytes = sum(a.size * a.dtype.itemsize for a in arena.values())
    assert nbytes == 3 * 8193 * 8 * 16 * 64 * 2 * 2 and 0.80e9 < nbytes < 0.81e9
    lanes = jax.eval_shape(lambda: generation.init_lane_state(
        cfg, serving["generate_slots"]))
    assert lanes.shape == (11, 32, 2, 2048)
    assert lanes.size * lanes.dtype.itemsize == 2883584
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    held = sum(p.size for p in jax.tree_util.tree_leaves(params))
    assert 4.66e9 < held < 4.67e9                     # 9.33 GB in bf16
    # the issue's fallback of 10 layers: two periods, 2 layers with pages
    shorter = build("hybrid_lm", FAMILY.program_config(
        dict(config, num_hidden_layers=10)))
    arena = jax.eval_shape(lambda: generation.init_paged_cache(
        dict(static_config(shorter)), pages + 1, 16, row=shorter.cache_row))
    assert arena["k"].shape[0] == 2
    assert 0.53e9 < 2 * arena["k"].size * 2 < 0.54e9


@pytest.mark.parametrize("family", ["transformer_lm", "moe_lm", "mla_moe_lm"])
def test_d_families_of_one_kind_declare_what_they_declared(family):
    model = build(family, None)
    assert model.layer_state == (model.cache_row,) * model.config["n_layers"]
    assert not lane_layers(model.layer_state)
    key = dict(static_config(model))
    assert "layer_state" not in key and key["cache_row"] == model.cache_row
    assert generation.init_lane_state(key, 4) is None
    assert generation._layer_slots(key) == [
        (False, i, i, 0) for i in range(model.config["n_layers"])]


# -- (e) through the engine ----------------------------------------------------

def _load(tmp_path, name="hybrid", seed=0, **serving_kw):
    export_artifact("hybrid_lm", str(tmp_path), name=name, version=1,
                    config=MC, seed=seed)
    rt = TPUModelRuntime(ServingConfig(platform="cpu", **serving_kw), None)
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


def test_e_engine_answers_as_the_solo_decoder_with_lanes_reused(tmp_path):
    """Six requests through two lanes: every lane is reused, each request
    answers what the solo decoder (the dense cache with its lane state)
    answers, and the state's bytes, the layer counts and the admission's
    ``state_insert`` span are where an operator reads them."""
    rt, mid = _load(tmp_path)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, MC["vocab_size"], n).astype(np.int32)
               for n in (5, 16, 1, 9, 23, 2)]
    try:
        solo = [np.asarray(rt.generate(mid, p[None], max_new_tokens=9, seed=1))[0]
                for p in prompts]
        eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=4,
                                       page_tokens=PT, arena_pages=16)
        try:
            import concurrent.futures as cf
            with cf.ThreadPoolExecutor(6) as pool:
                got = list(pool.map(
                    lambda p: eng.generate(mid, p[None], max_new_tokens=9)[0],
                    prompts))
            state = rt._slot_states[mid]
            state.check_page_conservation()
            assert state.k.shape[0] == N_ATTN            # a layer a layer WITH pages
            assert state.lane_state.shape == (N_CONV, 2, 2, 64)
            assert state.moe_stats is not None
        finally:
            eng.close()
    finally:
        rt.close()
    for want, have in zip(solo, got):
        np.testing.assert_array_equal(have, want)


def test_e_admission_writes_the_lane_state_under_its_own_span(tmp_path):
    rt, mid = _load(tmp_path, name="hybrid_span")
    try:
        state = rt.slot_decode_state(mid, 2, page_tokens=PT, arena_pages=16)
        assert state.reserve_pages(1, 24)
        tok, pk, pv, hit = rt.slot_prefill(mid, np.arange(1, 8), 0.0, 0, seed=1)
        assert isinstance(pk, PrefillRows) and not hit
        assert not np.asarray(state.lane_state).any()
        with TRACER.span("admit") as root:
            rt.slot_admit(state, 1, pk, pv)
        names = [c.name for c in root.children]
        assert "state_insert" in names
        lanes = np.asarray(state.lane_state)
        assert lanes[:, 1].any() and not lanes[:, 0].any()
        np.testing.assert_array_equal(lanes[:, 1], np.asarray(pk.lane)[:, 0])
        # an admission that lost its state is refused, not answered wrongly
        with pytest.raises(RuntimeError_, match="without its lane state"):
            rt.slot_admit(state, 0, pk.k, pv)
    finally:
        rt.close()


# -- (f) refused by name -------------------------------------------------------

REFUSALS = ["int8_arena", "shared_prefix", "conversation_kv", "spec_draft_model",
            "chunked_prefill", "draft_model", "mesh", "park_lane"]


@pytest.mark.parametrize("what", REFUSALS)
def test_f_what_cannot_carry_the_lane_state_is_refused_by_name(
        tmp_path, monkeypatch, what):
    knobs = {"conversation_kv": dict(conversation_kv_bytes=1 << 20),
             "spec_draft_model": dict(spec_draft_model="draft"),
             "chunked_prefill": dict(prefill_chunk_tokens=8)}.get(what, {})
    rt, mid = _load(tmp_path, name=f"hybrid_{what}", **knobs)
    ids = np.ones((1, 4), np.int32)
    refused = lambda pattern: pytest.raises(  # noqa: E731
        RuntimeError_, match=f"lane-state layers.*{pattern}")
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


def test_f_a_verify_pass_over_lane_state_layers_is_refused_at_trace_time():
    model = build("hybrid_lm", MC)
    cfg = dict(static_config(model))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = generation.init_paged_cache(cfg, 8, PT, row=model.cache_row)
    cache["lane"] = generation.init_lane_state(cfg, 2)
    with pytest.raises(ValueError, match="does not carry a lane state"):
        jax.eval_shape(
            lambda p: generation._paged_verify_step(
                p, jnp.zeros((2, 4), jnp.int32), cache,
                jnp.zeros((2, 8), jnp.int32), jnp.zeros((2,), jnp.int32), cfg,
                "hybrid_lm", PT), params)


# -- (g) the accepted families' decode chunk is the parent's -------------------

ACCEPTED = {
    "dense": ("transformer_lm", {
        "vocab_size": 97, "d_model": 48, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 96, "max_seq": 64}),
    "expert": ("moe_lm", {
        "vocab_size": 97, "d_model": 64, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 4, "d_ff": 32, "n_experts": 8, "top_k": 2,
        "norm_topk_prob": True, "qk_norm": True, "tie_embeddings": False,
        "max_seq": 64, "rope_theta": 10000.0, "dtype": "float32"}),
    "latent": ("mla_moe_lm", None),
    # the shapes the cells run: heads of 128 (Mistral, OLMoE) and an odd
    # number of KV heads of 64 (SmolLM2): neither arena packs
    "head128": ("transformer_lm", {
        "vocab_size": 97, "d_model": 256, "n_layers": 2, "n_heads": 2,
        "n_kv_heads": 2, "d_ff": 96, "max_seq": 64}),
    "odd_head64": ("transformer_lm", {
        "vocab_size": 97, "d_model": 320, "n_layers": 2, "n_heads": 5,
        "n_kv_heads": 5, "d_ff": 96, "max_seq": 64}),
}


@pytest.mark.parametrize("which", sorted(ACCEPTED))
def test_g_accepted_families_decode_chunk_is_traced_as_before(which, monkeypatch):
    """The operand the lane state takes is an empty pytree for a family of one
    kind: its decode chunk has the parent's operands and results (the arena's
    sides donated, nothing beside them), no ``dynamic_update_slice`` a layer
    for a state, no gate denominator term, and its arena a layer a layer, of
    the parent's shape: a tile a KV head, ``row.width`` wide (PR 34 packs a
    two-sided head-64 row with an even number of heads, and none of these);
    the program is the one traced with the packing taken out."""
    family, config = ACCEPTED[which]
    model = build(family, config)
    cfg = dict(static_config(model))
    lanes, pps, chunk = 8, 4, 2
    cache = jax.eval_shape(lambda: generation.init_paged_cache(
        cfg, 40, 4, row=model.cache_row))
    assert cache["k"].shape[0] == cfg["n_layers"]
    row = model.cache_row
    assert cache["k"].shape == (cfg["n_layers"], 40, row.heads, 4, row.width)
    assert ("v" in cache) == (row.sides == 2)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    args = (params, cache["k"], cache.get("v"), None, i32(lanes, pps), i32(lanes),
            i32(lanes), jax.ShapeDtypeStruct((lanes,), jnp.bool_),
            jax.ShapeDtypeStruct((), jnp.uint32),
            jax.ShapeDtypeStruct((lanes,), jnp.float32), i32(lanes))
    static = dict(cfg_key=tuple(sorted(cfg.items())), family=family, chunk=chunk,
                  page_tokens=4, kernel=False)
    fn = lambda *a: generation._paged_decode_chunk_jit(*a, **static)  # noqa: E731
    traced = jax.make_jaxpr(fn)(*args)
    with_none = jax.make_jaxpr(
        lambda *a: generation._paged_decode_chunk_jit(*a, None, **static))(*args)
    assert str(traced) == str(with_none)
    n_in = len(jax.tree_util.tree_leaves(args))
    assert len(traced.jaxpr.invars) == n_in
    out = jax.eval_shape(fn, *args)
    assert out[-2] is None and len(out) == 9
    sides = [a for a in (cache["k"], cache.get("v")) if a is not None]
    n_out = len(sides) + 4 + (0 if out[6] is None else 1)   # + tok, pos, toks, counter
    assert len(traced.jaxpr.outvars) == n_out
    # the parent's program: what is traced when nothing can pack or unpack
    monkeypatch.setattr(generation, "pack_rows", lambda rows, arena: rows)
    monkeypatch.setattr(att, "unpack_pages", lambda pages, head_dim: pages)
    generation._paged_decode_chunk_jit.clear_cache()
    assert str(jax.make_jaxpr(fn)(*args)) == str(traced)
    generation._paged_decode_chunk_jit.clear_cache()
    text = str(traced)
    assert "1e-06" not in text
