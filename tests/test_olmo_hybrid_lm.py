"""Gated delta-rule linear attention beside full MHA (three linear layers to
one full layer) through the program: the ``olmo_hybrid_lm`` family against the
plain reference the benchmark keeps (``benchmark/families/olmo_hybrid.py``),
at a small size on the CPU with the published RATIOS (``d_k : d_v`` = 1 : 2, 3
linear to 1 full, MHA, 4 taps), two periods deep.

  (a) the family's ``apply`` logits against the reference, and each of the
      mistakes the tolerance is there to catch lands orders above it (beta
      without its factor 2, alpha applied after the update, a missing L2
      norm, a rotary where there is none, the norm before the mixer);
  (b) ``ops/delta_rule.py``: the chunked form is the step iterated, at ``T``
      not a multiple of 64 and with ``real_len`` < ``T``, state out equal; a
      row that took nothing keeps ``-0.0``;
  (c) prefill, then decode through the ENGINE's programs (``_slot_prefill_jit``,
      ``_paged_insert_jit``, ``_lane_insert_jit``, ``_paged_forward_step`` /
      ``_paged_decode_chunk_jit``), logits against the reference's full forward
      at every position; the state at ``real_len``, not at the bucket's end; a
      state kept in bf16 fails; an inactive lane's two parts bit for bit;
  (d) what the ModelDef declares, and the benchmark configuration's arithmetic;
  (e) through ``ContinuousGenerateEngine``: the engine answers what the solo
      decoder answers; the ring's ``state_lanes``;
  (f) what the family cannot do yet is refused by name;
  (g) the shared walk's three choices are taken only by a model that declares
      them: the fresh prefill attends among the tokens at hand from a GiB of
      scores up and answers the same, the accepted cells' buckets stay below.

THE TOLERANCE. Every comparison with the reference is of float32 models at
logits level, ``atol`` 1e-4 of logits whose spread is about 1. Two float32
computations of this block sit 2e-5 to 6e-5 apart at 8 layers on most seeds
(the RMSNorm over ONE head's 32 outputs multiplies rounding where that head's
output is small), so the seeds here are ones with room; at heads of 8 / 16
the floor itself passes 1e-4.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tfservingcache_tpu.models.generation as generation
import tfservingcache_tpu.models.olmo_hybrid_lm as olmo
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import (
    CacheRow,
    LaneState,
    build,
    export_artifact,
    lane_layers,
    static_config,
)
from tfservingcache_tpu.ops import delta_rule
from tfservingcache_tpu.runtime.base import RuntimeError_
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId
from tfservingcache_tpu.utils.flight_recorder import RECORDER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "olmo_hybrid_lm"


def _family(name="olmo_hybrid"):
    spec = importlib.util.spec_from_file_location(
        f"bench_family_{name}",
        os.path.join(ROOT, "benchmark", "families", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAMILY = _family()
L, F = "linear_attention", "full_attention"
# hidden 64, 4 heads of 16 (MHA), 4 linear heads of 16 / 32, 4 taps, two
# periods of L L L F, pages of 8
PUBLISHED = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 96, "num_hidden_layers": 8, "vocab_size": 97,
    "layer_types": [L, L, L, F] * 2, "linear_num_key_heads": 4,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 32, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rms_norm_eps": 1e-6,
    "rope_parameters": {"rope_theta": None}, "tie_word_embeddings": False,
    "max_position_embeddings": 256, "torch_dtype": "float32",
}
MC = FAMILY.program_config(PUBLISHED)
# built HERE, before any case patches the family's module
MODEL = build(NAME, MC)
PT = 8
LANES = 4
H, D_K, D_V, HEAD = 4, 16, 32, 16
WIDTH = H * (2 * D_K + D_V)
N_LINEAR, N_FULL = 6, 2


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
        lp["ln1_post"], lp["ln2_post"] = gain(lp["ln1_post"]), gain(lp["ln2_post"])
        if "gdn" in lp:
            lp["gdn"]["o_norm"] = gain(lp["gdn"]["o_norm"])
        else:
            lp["attn"]["q_norm"] = gain(lp["attn"]["q_norm"])
            lp["attn"]["k_norm"] = gain(lp["attn"]["k_norm"])
    return tree


def _apply(mc, tree, ids):
    out = build(NAME, mc).apply(
        jax.tree_util.tree_map(jnp.asarray, tree), {"input_ids": np.asarray(ids)[None]})
    return np.asarray(out["logits"])[0]


def _reference(tree, seq, mc=MC):
    return FAMILY.logits_many(mc, tree, [list(map(int, seq))], last=len(seq))[0]


# -- (a) the full forward ------------------------------------------------------

def _chunked_by(step):
    """``delta_chunked``'s signature over ``step`` iterated (a wrong step makes
    a wrong chunked form)."""
    def chunked(state, q, k, v, alpha, beta, real_len=None):
        outs = []
        for t in range(q.shape[1]):
            took = None if real_len is None else t < real_len
            o, state = step(state, q[:, t], k[:, t], v[:, t], alpha[:, t],
                            beta[:, t], took)
            outs.append(o)
        return jnp.stack(outs, 1), state
    return chunked


def _alpha_after_the_update(state, q, k, v, alpha, beta, took=None):
    """S <- alpha (S + k^T beta (v - k S)): the decay AFTER the write."""
    b, h, d_k = k.shape
    s4 = state.reshape(b, d_k, h, -1)
    k_col = k.transpose(0, 2, 1)[..., None]
    u = beta[..., None] * (v - jnp.sum(k_col * s4, axis=1))
    s4 = alpha[:, None, :, None] * (s4 + k_col * u[:, None])
    o = jnp.sum(q.transpose(0, 2, 1)[..., None] * s4, axis=1)
    return o, s4.reshape(state.shape)


MISTAKES = ["beta_without_its_factor_2", "alpha_after_the_update",
            "no_l2_norm", "a_rotary_where_there_is_none"]


@pytest.mark.parametrize("mistake", [None, *MISTAKES])
def test_a_full_forward_equals_the_reference(monkeypatch, mistake):
    tree = _tree(1)
    seq = np.random.default_rng(2).integers(1, MC["vocab_size"], 150)
    want = _reference(tree, seq)
    mc = MC
    if mistake == "beta_without_its_factor_2":
        mc = dict(MC, linear_allow_neg_eigval=False)
    elif mistake == "a_rotary_where_there_is_none":
        mc = dict(MC, rope_theta=10000.0)
    elif mistake == "alpha_after_the_update":
        monkeypatch.setattr(olmo, "delta_chunked",
                            _chunked_by(_alpha_after_the_update))
    elif mistake == "no_l2_norm":
        monkeypatch.setattr(olmo, "_l2norm", lambda x: x)
    got = _apply(mc, tree, seq)
    assert np.std(want) > 0.3
    err = float(np.max(np.abs(got - want)))
    if mistake is None:
        assert err < 1e-4, err
    else:
        # a hundred tolerances (keys of any length let the state grow without
        # bound: not a number is a failure too)
        assert not err < 1e-2, (mistake, err)


def test_a_a_rotary_base_in_the_file_is_applied_by_program_and_reference():
    """``rope_theta`` a number: both sides rotate, and agree."""
    published = dict(PUBLISHED, rope_parameters={"rope_theta": 500000.0})
    mc = FAMILY.program_config(published)
    assert mc["rope_theta"] == 500000.0
    tree = _tree(3, mc)
    seq = np.random.default_rng(4).integers(1, mc["vocab_size"], 40)
    np.testing.assert_allclose(_apply(mc, tree, seq), _reference(tree, seq, mc),
                               atol=1e-4, rtol=0)


# -- (b) the operator: two forms of one recurrence ------------------------------

def _rule_operands(rng, b, t):
    q, k = (rng.standard_normal((b, t, H, D_K)).astype(np.float32) for _ in "qk")
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(D_K)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((b, t, H, D_V)).astype(np.float32)
    alpha = rng.uniform(0.02, 0.999, (b, t, H)).astype(np.float32)
    beta = rng.uniform(0.0, 2.0, (b, t, H)).astype(np.float32)
    return q, k, v, alpha, beta


@pytest.mark.parametrize("t_len,real", [(150, (150, 97)), (64, (64, 1)),
                                        (193, (130, 0)), (5, (5, 3))],
                         ids=["150", "one_chunk", "193_a_row_takes_nothing", "5"])
def test_b_delta_chunked_is_delta_step_iterated(t_len, real):
    rng = np.random.default_rng(t_len)
    q, k, v, alpha, beta = _rule_operands(rng, 2, t_len)
    s0 = rng.standard_normal((2, D_K, H * D_V)).astype(np.float32)
    real = np.asarray(real, np.int32)
    o, s = delta_rule.delta_chunked(jnp.asarray(s0), q, k, v, alpha, beta,
                                    jnp.asarray(real))
    ss, outs = jnp.asarray(s0), []
    for i in range(t_len):
        oi, ss = delta_rule.delta_step(ss, q[:, i], k[:, i], v[:, i],
                                       alpha[:, i], beta[:, i],
                                       jnp.asarray(i < real))
        outs.append(oi)
    np.testing.assert_allclose(s, ss, atol=2e-5, rtol=1e-5)
    outs = np.stack(outs, 1)
    for row in range(2):
        np.testing.assert_allclose(np.asarray(o)[row, :real[row]],
                                   outs[row, :real[row]], atol=2e-5, rtol=1e-5)
    # without real_len every token counts
    _, s_all = delta_rule.delta_chunked(jnp.asarray(s0), q, k, v, alpha, beta)
    _, s_full = delta_rule.delta_chunked(
        jnp.asarray(s0), q, k, v, alpha, beta,
        jnp.full((2,), t_len, jnp.int32))
    np.testing.assert_allclose(s_all, s_full, atol=1e-6, rtol=0)


def test_b_a_row_that_took_nothing_keeps_its_state_bit_for_bit():
    rng = np.random.default_rng(5)
    q, k, v, alpha, beta = _rule_operands(rng, 2, 1)
    s0 = rng.standard_normal((2, D_K, H * D_V)).astype(np.float32)
    s0[1, 0, 0] = -0.0
    _, kept = delta_rule.delta_step(
        jnp.asarray(s0), q[:, 0], k[:, 0], v[:, 0], alpha[:, 0], beta[:, 0],
        jnp.asarray([True, False]))
    assert np.asarray(kept)[1].tobytes() == s0[1].tobytes()
    assert np.any(np.asarray(kept)[0] != s0[0])


# -- (c) prefill, then decode through the arena and the state -------------------

def _prefill(dev, prompt, p_pad, mc=MC):
    model = build(NAME, mc)
    ids = np.zeros((1, p_pad), np.int32)
    ids[0, :len(prompt)] = prompt
    tok, pk, pv, last, lane = generation._slot_prefill_jit(
        dev, ids, np.asarray([len(prompt)], np.int32), jax.random.PRNGKey(0),
        np.float32(0.0), np.int32(0), cfg_key=static_config(model), family=NAME)
    return int(tok[0]), pk, pv, np.asarray(last)[0], lane


def _admit(cfg, cache, tables, dev, prompt, p_pad, lane):
    """Prefill ``prompt`` and admit it into ``lane`` of ``cache``."""
    tok, pk, pv, last, state = _prefill(dev, prompt, p_pad)
    assert pk.shape == pv.shape == (N_FULL, 1, H, p_pad, HEAD)
    s, conv = state
    assert s.shape == (N_LINEAR, 1, D_K, H * D_V) and s.dtype == jnp.float32
    assert conv.shape == (N_LINEAR, 1, 3, WIDTH)
    pages = MC["max_seq"] // PT // 2                      # 128 tokens a lane
    tables[lane, :pages] = 1 + pages * lane + np.arange(pages)
    k, v, _ = generation._paged_insert_jit(
        cache["k"], cache["v"], None, pk, pv, tables[lane], np.int32(0),
        page_tokens=PT)
    lanes = generation._lane_insert_jit(cache["lane"], state, np.int32(lane))
    return {"k": k, "v": v, "lane": lanes}, tok, last


def _paged_setup(dev, prompt, p_pad, lane=1):
    cfg = dict(static_config(MODEL))
    pages = 1 + LANES * (MC["max_seq"] // PT // 2)
    cache = generation.init_paged_cache(cfg, pages, PT, row=MODEL.cache_row,
                                        lanes=LANES)
    assert cache["k"].shape == (N_FULL, pages, H, PT, HEAD)
    cache["lane"] = generation.init_lane_state(cfg, LANES)
    tables = np.zeros((LANES, MC["max_seq"] // PT), np.int32)
    cache, tok, last = _admit(cfg, cache, tables, dev, prompt, p_pad, lane)
    pos = np.zeros((LANES,), np.int32)
    pos[lane] = len(prompt)
    return cfg, cache, tables, pos, tok, last


@pytest.mark.parametrize("prompt_len,p_pad", [(11, 16), (16, 16), (70, 128), (1, 1)],
                         ids=["off_bucket", "fills_its_bucket",
                              "two_chunks_of_the_rule", "one_token"])
def test_c_prefill_then_paged_decode_matches_the_reference_at_every_position(
        prompt_len, p_pad):
    tree = _tree(0)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, MC["vocab_size"], prompt_len)
    forced = rng.integers(1, MC["vocab_size"], 20)
    want = _reference(tree, np.concatenate([prompt, forced]))
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    lane = 1
    cfg, cache, tables, pos, _tok, last = _paged_setup(dev, prompt, p_pad, lane)
    np.testing.assert_allclose(last, want[prompt_len - 1], atol=1e-4, rtol=0)
    active = np.arange(LANES) == lane
    step = jax.jit(lambda cache, tok, pos: generation._paged_forward_step(
        dev, tok, cache, tables, pos, cfg, NAME, PT, active=active))
    tok = np.zeros((LANES,), np.int32)
    for j, t in enumerate(forced):
        tok[lane] = t
        logits, cache = step(cache, tok, pos)
        np.testing.assert_allclose(np.asarray(logits)[lane, 0],
                                   want[prompt_len + j], atol=1e-4, rtol=0)
        pos[lane] += 1
    # the lanes nobody read kept the zeros they were built with, both parts
    for part in cache["lane"]:
        assert not np.asarray(part)[:, ~active].any()


def test_c_the_state_is_the_one_at_real_len_not_at_the_buckets_end():
    """A prompt of 11 in a bucket of 16: both parts of the state a prefill
    hands on are those after 11 tokens (the same prompt in a bucket of 128
    hands on the same), and 5 further real tokens move them."""
    dev = jax.tree_util.tree_map(jnp.asarray, _tree(4))
    prompt = np.random.default_rng(5).integers(1, MC["vocab_size"], 16)
    at11 = _prefill(dev, prompt[:11], 16)[4]
    again = _prefill(dev, prompt[:11], 128)[4]
    at16 = _prefill(dev, prompt, 16)[4]
    for a, b, c in zip(at11, again, at16):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
        assert np.max(np.abs(np.asarray(a) - np.asarray(c))) > 1e-2


def test_c_a_state_read_at_the_buckets_end_fails(monkeypatch):
    """The same prefill with the rule told nothing of ``real_len``: the first
    decode step's logits are hundreds of tolerances off."""
    tree = _tree(2)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, MC["vocab_size"], 11)
    want = _reference(tree, np.concatenate([prompt, [7]]))
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    whole = delta_rule.delta_chunked
    monkeypatch.setattr(
        olmo, "delta_chunked",
        lambda s, q, k, v, a, b, real_len=None: whole(s, q, k, v, a, b))
    generation._slot_prefill_jit.clear_cache()
    try:
        cfg, cache, tables, pos, _tok, _ = _paged_setup(dev, prompt, 16, lane=1)
        tok = np.zeros((LANES,), np.int32)
        tok[1] = 7
        logits, _ = generation._paged_forward_step(
            dev, tok, cache, tables, pos, cfg, NAME, PT,
            active=np.arange(LANES) == 1)
    finally:
        monkeypatch.undo()
        generation._slot_prefill_jit.clear_cache()
    assert np.max(np.abs(np.asarray(logits)[1, 0] - want[11])) > 1e-2


def test_c_a_matrix_state_kept_in_bf16_fails():
    """The state is float32 in the lane state (``LaneState.dtype``): the same
    decode with it rounded to bf16 between steps leaves the tolerance."""
    tree = _tree(2)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, MC["vocab_size"], 11)
    forced = rng.integers(1, MC["vocab_size"], 12)
    want = _reference(tree, np.concatenate([prompt, forced]))
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    cfg, cache, tables, pos, _tok, _ = _paged_setup(dev, prompt, 16, lane=1)
    assert cache["lane"][0].dtype == jnp.float32
    active = np.arange(LANES) == 1
    step = jax.jit(lambda cache, tok, pos: generation._paged_forward_step(
        dev, tok, cache, tables, pos, cfg, NAME, PT, active=active))
    tok = np.zeros((LANES,), np.int32)
    worst = 0.0
    for j, t in enumerate(forced):
        s, conv = cache["lane"]
        cache = {**cache, "lane": (
            s.astype(jnp.bfloat16).astype(jnp.float32), conv)}
        tok[1] = t
        logits, cache = step(cache, tok, pos)
        worst = max(worst, float(np.max(np.abs(
            np.asarray(logits)[1, 0] - want[11 + j]))))
        pos[1] += 1
    assert worst > 1e-3, worst


def test_c_the_norm_before_the_mixer_fails():
    """The shared walk norms what a layer GIVES where the layer holds
    ``ln1_post`` / ``ln2_post``: the same weights under ``ln1`` / ``ln2`` in
    the attention layers (the norm before, as every other family) answer
    something else."""
    tree = _tree(2)
    prompt = np.random.default_rng(3).integers(1, MC["vocab_size"], 16)
    want = _reference(tree, prompt)[-1]
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    np.testing.assert_allclose(_prefill(dev, prompt, 16)[3], want, atol=1e-4,
                               rtol=0)
    for lp in dev["layers"]:
        if "attn" in lp:
            lp["ln1"], lp["ln2"] = lp.pop("ln1_post"), lp.pop("ln2_post")
    assert np.max(np.abs(_prefill(dev, prompt, 16)[3] - want)) > 1e-2


def _chunk(dev, cfg, cache, tables, tok, pos, active, chunk=8):
    out = generation._paged_decode_chunk_jit(
        dev, cache["k"], cache["v"], None, tables, tok, pos, active,
        np.uint32(1), np.zeros((LANES,), np.float32),
        np.zeros((LANES,), np.int32), cache["lane"], None,
        cfg_key=tuple(sorted(cfg.items())), family=NAME, chunk=chunk,
        page_tokens=PT, kernel=False)
    k, v, _, tok, pos, toks, stats, lane, _counter = out
    assert stats is None
    return {"k": k, "v": v, "lane": lane}, np.asarray(toks), np.asarray(pos)


def test_c_an_inactive_lanes_state_and_conv_tail_are_bit_for_bit_after_a_chunk():
    """Two admitted lanes, one frozen for a chunk of 8: both parts of its
    state come back bit for bit, and the live lane emits the reference's
    greedy tokens."""
    tree = _tree(6)
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    rng = np.random.default_rng(7)
    p1, p2 = rng.integers(1, MC["vocab_size"], 13), rng.integers(1, MC["vocab_size"], 9)
    cfg, cache, tables, pos, first1, _ = _paged_setup(dev, p1, 16, lane=1)
    cache, _, _ = _admit(cfg, cache, tables, dev, p2, 16, lane=2)
    pos[2] = 9
    frozen = jax.tree_util.tree_map(np.asarray, cache)
    active = np.arange(LANES) == 1
    tok = np.zeros((LANES,), np.int32)
    tok[1] = first1
    after, toks, _ = _chunk(dev, cfg, cache, tables, tok, pos, active)
    for part, was in zip(after["lane"], frozen["lane"]):
        assert np.asarray(part)[:, 2].tobytes() == was[:, 2].tobytes()
        assert np.any(was[:, 2] != 0)
        assert np.any(np.asarray(part)[:, 1] != was[:, 1])
    chain = [int(first1)]
    for _ in range(8):
        ref = _reference(tree, np.concatenate([p1, chain]))
        chain.append(int(np.argmax(ref[-1])))
    np.testing.assert_array_equal(toks[1], chain[1:])


# -- (d) what the ModelDef declares --------------------------------------------

def test_d_the_declaration_says_what_each_layer_keeps():
    state = MODEL.layer_state
    assert lane_layers(state) == (0, 1, 2, 4, 5, 6)
    lane = state[0]
    assert isinstance(lane, LaneState) and lane.operator is olmo.gdn_layer
    assert lane.step is olmo.gdn_step
    assert lane.parts() == ((D_K, H * D_V, "float32"), (3, WIDTH, ""))
    assert state[3] == state[7] == CacheRow(2, H, HEAD) == MODEL.cache_row
    cfg = dict(static_config(MODEL))
    assert generation._row_layers(cfg) == N_FULL
    assert generation._window_of(cfg) == 0 and generation.shared_readers(cfg) == 0
    s, conv = generation.init_lane_state(cfg, LANES)
    assert s.shape == (N_LINEAR, LANES, D_K, H * D_V) and s.dtype == jnp.float32
    assert conv.shape == (N_LINEAR, LANES, 3, WIDTH)
    with pytest.raises(ValueError, match="layer_types must name"):
        build(NAME, dict(MC, layer_types=[L, "mamba"] * 4))


@pytest.mark.parametrize("live,lanes,loop,kernel", [
    (0, 16, 0, 0), (1, 16, 4, 1), (4, 16, 4, 4), (5, 16, 8, 5), (13, 16, 16, 13),
    (16, 16, 16, 16), (2, 3, 3, 2)], ids=lambda v: str(v))
@pytest.mark.parametrize("form", ["loop", "kernel"])
def test_d_the_state_write_follows_the_live_lanes(monkeypatch, form, live, lanes,
                                                  loop, kernel):
    """``state_write_lanes`` on the numpy mirror is what the device's step
    takes (``delta_rule.step_lanes_touched``, the step's own gate): where the
    gate is shut (the CPU: ``_step_loop``) the live lanes rounded up to whole
    trips of ``STEP_GROUP``, every lane of an engine no larger than a trip;
    where it is open (``delta_step_kernel``; here through its interpreter) the
    live lanes themselves; every slot for a model whose lane-state layers
    bring no ``step`` (the whole-array form), 0 for a model with no lane
    state."""
    if form == "kernel":
        monkeypatch.setattr(delta_rule, "DELTA_KERNEL_INTERPRET", True)
    wrote = loop if form == "loop" else kernel
    cfg = dict(static_config(MODEL))
    active = np.arange(lanes) % 2 == 0 if live == 2 else np.arange(lanes) < live
    assert active.sum() == live
    assert generation.state_write_lanes(active, cfg) == wrote
    whole = build("hybrid_lm", {"n_layers": 2, "layer_types": ["conv", "full_attention"],
                                "n_dense_layers": 2})
    assert generation.state_write_lanes(active, dict(static_config(whole))) == lanes
    assert generation.state_write_lanes(
        active, dict(static_config(build("transformer_lm", {})))) == 0


def test_d_the_step_in_place_is_the_operator_on_the_layers_slice():
    """``gdn_step`` on the whole arrays = ``gdn_layer`` on layer 1's slice:
    the residual delta of the lanes that took a token, their states after,
    and every other lane's and layer's state bit for bit."""
    cfg = dict(static_config(MODEL))
    tree = jax.tree_util.tree_map(jnp.asarray, _tree(3))
    rng = np.random.default_rng(4)
    s, conv = generation.init_lane_state(cfg, 6)
    s = jnp.asarray(rng.standard_normal(s.shape), s.dtype)
    conv = jnp.asarray(rng.standard_normal(conv.shape), conv.dtype)
    x = jnp.asarray(rng.standard_normal((6, 1, MC["d_model"])), jnp.float32)
    took = jnp.asarray([1, 0, 1, 1, 0, 1], jnp.int32)
    layer = tree["layers"][1]
    want, (s_want, conv_want), _ = olmo.gdn_layer(
        layer, x, (s[1], conv[1]), took, cfg)
    got, (s_got, conv_got), _ = olmo.gdn_step(
        layer, x, (s, conv), 1, took, None, cfg)
    live = np.asarray(took, bool)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(s_got[1], s_want, atol=1e-6, rtol=0)
    np.testing.assert_array_equal(conv_got[1], conv_want)
    for part, was in ((s_got, s), (conv_got, conv)):
        assert np.asarray(part)[1][~live].tobytes() == np.asarray(was)[1][~live].tobytes()
        keep = [i for i in range(part.shape[0]) if i != 1]
        assert np.asarray(part)[keep].tobytes() == np.asarray(was)[keep].tobytes()


def test_d_the_benchmark_configuration_two_periods_on_one_chip():
    """The arithmetic the configuration's file states: 8 layers = 6 linear +
    2 full (ISSUE 46's second depth: its peak-bytes reading decided), 2.44 G
    parameters = 4.87 GB, 30,720 B of K/V a token, 2,280,960 B of state a
    layer a lane, an arena of 90,112 tokens."""
    with open(os.path.join(ROOT, "benchmark", "configs", "olmo-hybrid-7b.json")) as f:
        config = json.load(f)
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "intermediate_size", "vocab_size", "linear_num_key_heads",
                "linear_num_value_heads", "linear_key_head_dim",
                "linear_value_head_dim", "linear_conv_kernel_dim",
                "rms_norm_eps", "layer_types"):
        assert config[key] == config["source_values"][key], key
    assert (config["hidden_size"], config["num_attention_heads"],
            config["intermediate_size"], config["vocab_size"]) == (
                3840, 30, 11008, 100352)
    mc = FAMILY.program_config(config)
    model = build(NAME, mc)
    kinds = mc["layer_types"]
    assert len(kinds) == 8 and kinds.count(L) == 6 and kinds[3::4] == [F] * 2
    assert mc["rope_theta"] is None
    assert abs(FAMILY.param_bytes(mc) / 1e9 - 4.87) < 0.01
    row = model.cache_row
    assert row == CacheRow(2, 30, 128)
    assert kinds.count(F) * row.sides * row.heads * row.width * 2 == 30720
    lane = model.layer_state[0]
    assert sum(r * w * (4 if d else 2) for r, w, d in lane.parts()) == 2280960
    srv = config["server"]["serving"]
    arena = srv["kv_arena_pages"] * srv["kv_page_tokens"] * 30720
    assert abs(arena / 1e9 - 2.77) < 0.01 and arena // 30720 == 90112
    assert config["max_position_embeddings"] == 16896


# -- (e) through the engine ----------------------------------------------------

def _load(tmp_path, name="olmohybrid", seed=0, metrics=None, **serving_kw):
    export_artifact(NAME, str(tmp_path), name=name, version=1, config=MC,
                    seed=seed)
    rt = TPUModelRuntime(ServingConfig(platform="cpu", **serving_kw), metrics)
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


def test_e_the_engine_answers_what_the_solo_decoder_answers(tmp_path):
    """Five requests through two lanes (every lane reused; one prompt of more
    than a chunk of the rule): each answers what the solo decoder (the dense
    cache with its two-part lane state) answers; the state's arrays, the
    ring's ``state_lanes`` and the gauge are where an operator reads them."""
    from tfservingcache_tpu.utils.metrics import Metrics

    metrics = Metrics()
    rt, mid = _load(tmp_path, metrics=metrics)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, MC["vocab_size"], n).astype(np.int32)
               for n in (70, 5, 19, 1, 26)]
    try:
        solo = [np.asarray(rt.generate(mid, p[None], max_new_tokens=20, seed=1))[0]
                for p in prompts]
        eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=8,
                                       page_tokens=PT, arena_pages=40)
        try:
            import concurrent.futures as cf
            with cf.ThreadPoolExecutor(5) as pool:
                got = list(pool.map(
                    lambda p: eng.generate(mid, p[None], max_new_tokens=20)[0],
                    prompts))
            state = rt._slot_states[mid]
            state.check_page_conservation()
            assert state.k.shape[0] == N_FULL and state.window is None
            s, conv = state.lane_state
            assert s.shape == (N_LINEAR, 2, D_K, H * D_V)
            assert conv.shape == (N_LINEAR, 2, 3, WIDTH)
            label = metrics.model_label(mid.name, mid.version)
            assert metrics.lane_state_bytes.labels(label)._value.get() == (
                s.nbytes + conv.nbytes)
            steps = RECORDER.snapshot()["models"][f"{mid.name}@{mid.version}"]["steps"]
            wrote = [st["state_lanes"] for st in steps if st["chunk"] > 0]
            # the whole-array form: a step writes every lane's slice
            assert wrote and set(wrote) == {2}
        finally:
            eng.close()
    finally:
        rt.close()
    for want, have in zip(solo, got):
        np.testing.assert_array_equal(have, want)


# -- (f) what the family cannot do yet is refused by name ----------------------

REFUSALS = ["int8_arena", "shared_prefix", "conversation_kv", "spec_draft_model",
            "chunked_prefill", "mesh", "park_lane"]


@pytest.mark.parametrize("what", REFUSALS)
def test_f_refused_by_name(tmp_path, monkeypatch, what):
    knobs = {"conversation_kv": dict(conversation_kv_bytes=1 << 20),
             "spec_draft_model": dict(spec_draft_model="draft"),
             "chunked_prefill": dict(prefill_chunk_tokens=8)}.get(what, {})
    rt, mid = _load(tmp_path, name=f"olmohybrid_{what}", **knobs)
    ids = np.ones((1, 4), np.int32)
    refused = lambda pattern: pytest.raises(  # noqa: E731
        RuntimeError_, match=(
            r"olmo_hybrid_lm \(lane-state layers\) does not support .*" + pattern))
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
        else:
            monkeypatch.setattr(rt, "mesh", object())
            with refused("mesh"):
                rt.generate(mid, ids, max_new_tokens=2, seed=1)
            with refused("mesh"):
                rt.slot_decode_state(mid, 4)
    finally:
        monkeypatch.undo()
        rt.close()


def test_f_a_forward_of_several_positions_over_the_arena_is_refused():
    cfg = dict(static_config(MODEL))
    dev = jax.tree_util.tree_map(jnp.asarray, _tree(0))
    cache = generation.init_paged_cache(cfg, 8, PT, row=MODEL.cache_row, lanes=LANES)
    cache["lane"] = generation.init_lane_state(cfg, LANES)
    with pytest.raises(ValueError, match="does not carry a lane state"):
        generation._paged_verify_step(
            dev, np.zeros((LANES, 4), np.int32), cache,
            np.zeros((LANES, 16), np.int32), np.zeros((LANES,), np.int32), cfg,
            NAME, PT)


# -- (g) the shared walk's choices ----------------------------------------------

def _kv(heads, max_len, batch=1):
    return {"k": jax.ShapeDtypeStruct((1, batch, heads, max_len, 128), jnp.bfloat16)}


@pytest.mark.parametrize("cell,heads,bucket,tokens_at_hand", [
    ("mistral7b-chat-steady", 32, 2048, False),
    ("olmoe-chat-steady", 16, 2048, False),
    ("lfm2-longgen-steady", 32, 1024, False),
    ("olmohybrid-longdoc-steady_2048", 30, 2048, False),
    ("olmohybrid-longdoc-steady_4096", 30, 4096, True),
    ("olmohybrid-longdoc-steady_16384", 30, 16384, True),
])
def test_g_the_fresh_prefill_attends_the_tokens_at_hand_from_a_gib_of_scores(
        cell, heads, bucket, tokens_at_hand):
    """The accepted cells' longest buckets stay on the dense form (their
    programs are the ones they were); this cell's long buckets do not build
    ``heads x S x S`` float32 scores, nor ``S x vocabulary`` logits."""
    cfg = {"n_layers": 1, "n_heads": heads, "n_kv_heads": heads, "d_model": 128 * heads}
    assert generation._attends_tokens_at_hand(
        cfg, _kv(heads, bucket), bucket) is tokens_at_hand


@pytest.mark.parametrize("family,mc", [
    (NAME, MC),
    ("transformer_lm", {"vocab_size": 64, "d_model": 32, "n_layers": 2,
                        "n_heads": 2, "n_kv_heads": 2, "d_ff": 48, "max_seq": 64,
                        "dtype": "float32"}),
], ids=[NAME, "transformer_lm"])
def test_g_the_tokens_at_hand_answer_what_the_dense_form_answers(
        monkeypatch, family, mc):
    """The same prefill on both sides of the gate: the last real position's
    logits, the rows and the state agree."""
    model = build(family, mc)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.zeros((1, 32), np.int32)
    ids[0, :21] = np.random.default_rng(1).integers(1, mc["vocab_size"], 21)

    def prefill():
        generation._slot_prefill_jit.clear_cache()
        return generation._slot_prefill_jit(
            params, ids, np.asarray([21], np.int32), jax.random.PRNGKey(0),
            np.float32(0.0), np.int32(0), cfg_key=static_config(model),
            family=family)

    dense = prefill()
    monkeypatch.setattr(generation, "_SCORE_BLOCK_BYTES", 0)
    try:
        at_hand = prefill()
    finally:
        monkeypatch.undo()
        generation._slot_prefill_jit.clear_cache()
    for a, b in zip(jax.tree_util.tree_leaves(dense),
                    jax.tree_util.tree_leaves(at_hand)):
        if a.ndim == 5 and a.shape[3] == 32:         # K/V rows: the real ones
            a, b = a[:, :, :, :21], b[:, :, :, :21]
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)


def test_g_an_accepted_family_keeps_its_rotary_and_its_norm_before():
    """``transformer_lm``'s decode step still rotates (cos and sin in its
    jaxpr) and norms before its mixers (2 a layer and the final one), and
    holds none of the leaves that switch the new choices on."""
    mc = {"vocab_size": 64, "d_model": 32, "n_layers": 2, "n_heads": 2,
          "n_kv_heads": 2, "d_ff": 48, "max_seq": 32, "dtype": "float32"}
    model = build("transformer_lm", mc)
    cfg = dict(static_config(model))
    assert cfg["rope_theta"] is not None and "qk_norm_eps" not in cfg
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert not any("ln1_post" in lp or "ln2_post" in lp for lp in params["layers"])
    cache = jax.eval_shape(lambda: generation.init_paged_cache(
        cfg, 6, 8, row=model.cache_row, lanes=2))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    text = str(jax.make_jaxpr(
        lambda p, c, tok, tables, pos: generation._paged_forward_step(
            p, tok, c, tables, pos, cfg, "transformer_lm", 8))(
        params, cache, i32(2), i32(2, 4), i32(2)))
    assert " cos " in text and " sin " in text
    assert text.count("rsqrt") == 2 * mc["n_layers"] + 1


# -- hardware-gated rows (tools/tpu_kernel_check.py -k "delta and on_tpu") ------

ON_TPU = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)")


@ON_TPU
@pytest.mark.parametrize("form", ["step", "chunked", "chunked_16384"])
def test_delta_rule_on_tpu(form):
    """Both forms at the benchmark's widths (30 heads of 96 / 192, bf16
    operands) against the step iterated in float32 on the host's arithmetic:
    ``delta_step`` over 16 lanes for 8 tokens and ``delta_step_live`` on a
    two-layer array against it; ``delta_chunked`` over one lane at a bucket of
    2048 (``real_len`` 1500) and of 16384 (``real_len`` 11800), as the chip
    takes it (``delta_chunk_kernel``, PR 47) AND in the block form it replaced
    there (the gate forced shut): the kernel's errors are held to the block
    form's, and both times are printed beside each other (``.chip_proof/
    bench_chunk.py``'s baseline: 3.97 ms at 2048, 17.28 at 8192, PR 46)."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    h, d_k, d_v = 30, 96, 192
    rng = np.random.default_rng(11)
    b, t, n_real = {"step": (16, 8, 8), "chunked": (1, 2048, 1500),
                    "chunked_16384": (1, 16384, 11800)}[form]
    real = np.full((b,), n_real, np.int32)
    q, k = (rng.standard_normal((b, t, h, d_k)).astype(np.float32) for _ in "qk")
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d_k)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((b, t, h, d_v)).astype(np.float32)
    alpha = rng.uniform(0.5, 0.999, (b, t, h)).astype(np.float32)
    beta = rng.uniform(0.0, 2.0, (b, t, h)).astype(np.float32)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731
    s0 = jnp.zeros((b, d_k, h * d_v), jnp.float32)
    step = jax.jit(delta_rule.delta_step)

    def iterated():
        s, outs = s0, []
        for i in range(n_real):
            o, s = step(s, bf(q[:, i]), bf(k[:, i]), bf(v[:, i]), alpha[:, i],
                        beta[:, i])
            outs.append(o)
        return jnp.stack(outs, 1), s

    want_o, want_s = iterated()
    scale = float(np.std(np.asarray(want_o)[0]))

    def errors(got_o, got_s):
        return (float(np.max(np.abs(np.asarray(got_o)[0, :n_real]
                                    - np.asarray(want_o)[0]))),
                float(np.max(np.abs(np.asarray(got_s) - np.asarray(want_s)))))

    if form == "step":
        # the live-lane form on a two-layer array: layer 1 advanced for the
        # lanes that took a token, every other slice bit for bit
        took = jnp.asarray(np.arange(b) % 3 != 1)
        full = jnp.stack([want_s + 1.0, want_s])
        i = t - 1
        o_want, s_want = step(want_s, bf(q[:, i]), bf(k[:, i]), bf(v[:, i]),
                              alpha[:, i], beta[:, i], took)
        o_got, after = jax.jit(delta_rule.delta_step_live, static_argnums=1)(
            full, 1, bf(q[:, i]), bf(k[:, i]), bf(v[:, i]), alpha[:, i],
            beta[:, i], took)
        live = np.asarray(took)
        assert np.asarray(after[0]).tobytes() == np.asarray(full[0]).tobytes()
        assert np.asarray(after[1])[~live].tobytes() == np.asarray(want_s)[~live].tobytes()
        np.testing.assert_allclose(np.asarray(after[1])[live],
                                   np.asarray(s_want)[live], atol=1e-5, rtol=0)
        np.testing.assert_allclose(np.asarray(o_got)[live],
                                   np.asarray(o_want)[live], atol=1e-5, rtol=0)
        assert np.all(np.isfinite(np.asarray(want_s)))
        print(f"delta_step: out err 0.000e+00 of std {scale:.3e}, state err 0.000e+00")
        return
    operands = (s0, bf(q), bf(k), bf(v), jnp.asarray(alpha), jnp.asarray(beta),
                jnp.asarray(real))
    kernel = lambda *a: delta_rule.delta_chunked(*a)  # noqa: E731

    def block_form(*a):
        refusal = delta_rule._kernel_refusal
        delta_rule._kernel_refusal = lambda *_: "the block form, for the comparison"
        try:
            return delta_rule.delta_chunked(*a)
        finally:
            delta_rule._kernel_refusal = refusal

    assert delta_rule._kernel_refusal(*operands[:1], *operands[2:4],
                                      delta_rule.CHUNK) is None
    found = {}
    for name, fn in (("kernel", kernel), ("block form", block_form)):
        err_o, err_s = errors(*jax.jit(fn)(*operands))
        # every output summed, and the chain carried through ``alpha``: a
        # chain that read one element of ``o`` would let the compiler drop
        # most of the block form's work, and one carried through the state
        # alone lets it lift everything the state does not touch (the decays,
        # the inverses, the solves) out of the timing loop
        ms = 1e3 * chained_device_time(
            lambda alpha, s, q, k, v, beta, real, fn=fn: sum(
                jnp.sum(x) for x in fn(s, q, k, v, alpha, beta, real)),
            (operands[4], *operands[:4], *operands[5:]), iters=4)
        found[name] = (err_o, err_s, ms)
        print(f"delta_chunked[{t}, real {n_real}] {name}: out err {err_o:.3e} of "
              f"std {scale:.3e}, state err {err_s:.3e}, {ms:.2f} ms = "
              f"{ms * 1e3 / t:.2f} us a bucket token a layer", flush=True)
    (k_o, k_s, k_ms), (b_o, b_s, b_ms) = found["kernel"], found["block form"]
    assert k_o < 0.05 * scale + 1e-3 and k_s < 0.05
    # no further from the step iterated than the form it replaced (a tenth
    # of room: the two sum in different orders)
    assert k_o <= 1.1 * b_o and k_s <= 1.1 * b_s, found
    # the pad chunks past real_len are skipped, the state never leaves VMEM
    assert k_ms < b_ms / 3, found


@ON_TPU
def test_delta_rule_on_tpu_step_kernel():
    """The one-token step at the benchmark's widths (30 heads of 96 x 192, a
    decay a head, 16 lanes, six layers) through ``delta_step_kernel`` (ISSUE
    52) beside today's loop at 1, 3, 4, 5 and 8 live lanes: errors against the
    float64 step and us a live lane a layer against the 5.4 the state's bytes
    allow (4,470,000 B a lane at 819 GB/s)."""
    from tests.test_delta_step_kernel import step_rows_on_tpu

    step_rows_on_tpu("30 heads of 96 x 192, a decay a head", 30, 96, 192, False,
                     lanes=16, layers=6, floor_us=5.4)
