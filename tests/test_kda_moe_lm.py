"""Kimi Delta Attention (a decay a CHANNEL) beside gated NoPE GQA, every layer
followed by a chip's share of the routed experts and a shared one, through the
program: the ``kda_moe_lm`` family against the plain reference the benchmark
keeps (``benchmark/families/kda_moe.py``), at a small size on the CPU with the
published RATIOS (one full layer then three linear, ``d_k = d_v``, GQA group
2, 4 taps, 8 of 16 routed experts held, 4 a token), two periods deep.

  (a) the family's ``apply`` logits against the reference, and each of the
      mistakes the tolerance is there to catch lands orders above it;
  (b) ``ops/delta_rule.py`` with a decay a channel: the chunked form is the
      step iterated (``T`` 64 / 200 / 2048 / 4160, ``real_len`` None / 0 /
      mid-chunk / ``T``, a nonzero incoming state, decays down to 1e-3 a step,
      where a naive ``exp(-G)`` overflows); a row that took nothing keeps its
      state bit for bit; the kernel's gate gives the decay its own kernel (ISSUE 50);
  (c) prefill, then decode through the ENGINE's programs (``_slot_prefill_jit``,
      ``_paged_insert_jit``, ``_lane_insert_jit``, ``_paged_forward_step`` /
      ``_paged_decode_chunk_jit``), logits against the reference's full forward
      at every position; an inactive lane's two parts bit for bit;
  (d) what the ModelDef declares, the in-place step against the operator, and
      the benchmark configuration's arithmetic;
  (e) through ``ContinuousGenerateEngine``: the engine answers what the solo
      decoder answers;
  (f) what the family cannot do yet is refused by name.

THE TOLERANCE. Every comparison with the reference is of float32 models at
logits level, ``atol`` 1e-4 of logits whose spread is about 1.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tfservingcache_tpu.models.generation as generation
import tfservingcache_tpu.models.kda_moe_lm as kda
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
from tfservingcache_tpu.ops.attention import dispatch_tally
from tfservingcache_tpu.runtime.base import RuntimeError_
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "kda_moe_lm"


def _family(name="kda_moe"):
    spec = importlib.util.spec_from_file_location(
        f"bench_family_{name}",
        os.path.join(ROOT, "benchmark", "families", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAMILY = _family()
L, F = "linear_attention", "full_attention"
# hidden 64, 4 query heads over 2 KV heads of 16, 4 linear heads of 16 / 16,
# 4 taps, gates of rank 16, two periods of F L L L, 8 of 16 experts of width
# 32 held (4 a token) and a shared one, pages of 8
PUBLISHED = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 8, "vocab_size": 97,
    "gqa_layers": [0, 4], "gqa_interval": 3, "use_rope": False,
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "moe_intermediate_size": 32, "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "first_k_dense_replace": 0,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "max_position_embeddings": 256, "torch_dtype": "float32",
    "source_values": {"n_routed_experts": 16},
    "assumed": {"kda_gate_rank": {"value": 16}, "expert_first": {"value": 0},
                "scoring_func": {"value": "sigmoid"}},
}
MC = FAMILY.program_config(PUBLISHED)
# built HERE, before any case patches the family's module
MODEL = build(NAME, MC)
PT = 8
LANES = 4
H, D_K, D_V, HEAD, N_KV = 4, 16, 16, 16, 2
WIDTH = H * (2 * D_K + D_V)
N_LINEAR, N_FULL = 6, 2


def _tree(seed=0, mc=MC):
    """Seeded weights in the benchmark's layout, every gain random too (a gain
    of one would hide a norm applied to the wrong tensor), the biases large
    enough to matter."""
    rng = np.random.default_rng(seed)
    leaves = {name: (rng.standard_normal(shape) / np.sqrt(fan_in)).astype(np.float32)
              for name, (shape, fan_in) in FAMILY.leaf_shapes(mc).items()}
    tree = FAMILY.to_tree(mc, leaves)
    gain = lambda a: (1.0 + 0.2 * rng.standard_normal(a.shape)).astype(np.float32)  # noqa: E731
    tree["ln_f"] = gain(tree["ln_f"])
    for lp in tree["layers"]:
        lp["ln1"], lp["ln2"] = gain(lp["ln1"]), gain(lp["ln2"])
        lp["moe"]["bias"] = lp["moe"]["bias"] * 10
        if "kda" in lp:
            lp["kda"]["o_norm"] = gain(lp["kda"]["o_norm"])
            lp["kda"]["b_g"] = lp["kda"]["b_g"] * 20
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
    """S <- diag(alpha) (S + k^T beta (v - k S)): the decay AFTER the write."""
    b, h, d_k = k.shape
    s4 = state.reshape(b, d_k, h, -1)
    k_col = k.transpose(0, 2, 1)[..., None]
    u = beta[..., None] * (v - jnp.sum(k_col * s4, axis=1))
    s4 = alpha.transpose(0, 2, 1)[..., None] * (s4 + k_col * u[:, None])
    o = jnp.sum(q.transpose(0, 2, 1)[..., None] * s4, axis=1)
    return o, s4.reshape(state.shape)


def _a_decay_a_head(state, q, k, v, alpha, beta, real_len=None):
    """The decay's mean over a head's channels, applied to the whole head."""
    mean = jnp.broadcast_to(jnp.mean(alpha, -1, keepdims=True), alpha.shape)
    return delta_rule.delta_chunked(state, q, k, v, mean, beta, real_len)


MISTAKES = ["the_decay_applied_a_head", "alpha_after_the_update",
            "beta_without_its_factor_2", "a_rotary_in_the_gqa_layer",
            "the_attention_gate_left_out", "silu_for_the_output_gates_sigmoid",
            "the_shared_expert_left_out"]


@pytest.mark.parametrize("mistake", [None, *MISTAKES])
def test_a_full_forward_equals_the_reference(monkeypatch, mistake):
    tree = _tree(1)
    seq = np.random.default_rng(2).integers(1, MC["vocab_size"], 150)
    want = _reference(tree, seq)
    mc = MC
    if mistake == "beta_without_its_factor_2":
        mc = dict(MC, linear_allow_neg_eigval=False)
    elif mistake == "a_rotary_in_the_gqa_layer":
        mc = dict(MC, rope_theta=10000.0)
    elif mistake == "alpha_after_the_update":
        monkeypatch.setattr(kda, "delta_chunked",
                            _chunked_by(_alpha_after_the_update))
    elif mistake == "the_decay_applied_a_head":
        monkeypatch.setattr(kda, "delta_chunked", _a_decay_a_head)
    elif mistake == "silu_for_the_output_gates_sigmoid":
        monkeypatch.setattr(kda, "_output_gate", jax.nn.silu)
    elif mistake == "the_attention_gate_left_out":
        for lp in tree["layers"]:
            lp.get("attn", {}).pop("w_gate", None)
    elif mistake == "the_shared_expert_left_out":
        for lp in tree["layers"]:
            del lp["moe"]["shared"]
    got = _apply(mc, tree, seq)
    assert np.std(want) > 0.3
    err = float(np.max(np.abs(got - want)))
    if mistake is None:
        assert err < 1e-4, err
    else:
        assert not err < 1e-2, (mistake, err)      # a hundred tolerances


@pytest.mark.parametrize("mistake", ["a_rotary_in_the_gqa_layer",
                                     "the_attention_gate_left_out"])
def test_a_the_cached_walk_reads_the_gate_and_the_rotary_too(mistake):
    """The same two mistakes through ``_attend_rows`` (the prefill program):
    the last position's logits are off by hundreds of tolerances."""
    tree = _tree(1)
    prompt = np.random.default_rng(2).integers(1, MC["vocab_size"], 16)
    want = _reference(tree, prompt)[-1]
    mc = MC
    if mistake == "a_rotary_in_the_gqa_layer":
        mc = dict(MC, rope_theta=10000.0)
    else:
        for lp in tree["layers"]:
            lp.get("attn", {}).pop("w_gate", None)
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    assert np.max(np.abs(_prefill(dev, prompt, 16, mc)[3] - want)) > 1e-2


# -- (b) the operator: two forms of one recurrence, a decay a channel -----------

def _rule_operands(rng, b, t, least=1e-3):
    q, k = (rng.standard_normal((b, t, H, D_K)).astype(np.float32) for _ in "qk")
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(D_K)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((b, t, H, D_V)).astype(np.float32)
    # log-uniform down to ``least`` a step: over a chunk of 64 the running
    # decay reaches e^-200 and beyond, and exp(-G) is no float32
    alpha = np.exp(rng.uniform(np.log(least), 0.0, (b, t, H, D_K))).astype(np.float32)
    beta = rng.uniform(0.0, 2.0, (b, t, H)).astype(np.float32)
    return q, k, v, alpha, beta


@jax.jit
def _iterated(s0, q, k, v, alpha, beta, real):
    """``delta_step`` over the tokens one after another (a scan of the step
    itself) -> (``o (B, T, H, d_v)``, the state after)."""
    def token(state, row):
        i, q_t, k_t, v_t, a_t, b_t = row
        o, state = delta_rule.delta_step(state, q_t, k_t, v_t, a_t, b_t, i < real)
        return state, o

    t_first = lambda a: jnp.moveaxis(jnp.asarray(a), 1, 0)      # noqa: E731
    state, o = jax.lax.scan(token, s0, (jnp.arange(q.shape[1]),
                                        *map(t_first, (q, k, v, alpha, beta))))
    return jnp.moveaxis(o, 0, 1), state


@pytest.mark.parametrize("t_len,real", [
    (64, None), (64, (64, 1)), (200, (200, 97)), (200, (130, 0)),
    (2048, (2048, 1000)), (4160, None), (4160, (0, 4100))],
    ids=["one_chunk", "one_chunk_a_row_of_one", "200_mid_chunk",
         "200_a_row_takes_nothing", "a_block", "two_blocks_and_a_tail",
         "4160_mid_block"])
def test_b_delta_chunked_with_a_decay_a_channel_is_delta_step_iterated(t_len, real):
    rng = np.random.default_rng(t_len)
    q, k, v, alpha, beta = _rule_operands(rng, 2, t_len)
    assert float(np.sum(np.log(alpha[0, :64, 0, 0]))) < -88     # exp(-G) overflows
    s0 = rng.standard_normal((2, D_K, H * D_V)).astype(np.float32)
    lens = None if real is None else jnp.asarray(real, jnp.int32)
    o, s = jax.jit(delta_rule.delta_chunked)(jnp.asarray(s0), q, k, v, alpha,
                                             beta, lens)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
    real = (t_len, t_len) if real is None else real
    outs, ss = _iterated(jnp.asarray(s0), q, k, v, alpha, beta,
                         jnp.asarray(real, jnp.int32))
    np.testing.assert_allclose(s, ss, atol=5e-5, rtol=1e-5)
    outs = np.asarray(outs)
    for row in range(2):
        np.testing.assert_allclose(np.asarray(o)[row, :real[row]],
                                   outs[row, :real[row]], atol=2e-5, rtol=1e-5)
        if real[row] == 0:      # nothing taken: the state is the one handed in
            np.testing.assert_array_equal(np.asarray(s)[row], s0[row])


def test_b_a_decay_a_channel_that_is_one_value_a_head_is_the_decay_a_head():
    """Both chunked forms and both steps on the same numbers."""
    rng = np.random.default_rng(11)
    q, k, v, alpha, beta = _rule_operands(rng, 2, 130, least=0.05)
    head = alpha[..., 0]
    wide = np.broadcast_to(head[..., None], alpha.shape)
    s0 = jnp.asarray(rng.standard_normal((2, D_K, H * D_V)), jnp.float32)
    lens = jnp.asarray([130, 77], jnp.int32)
    o1, s1 = delta_rule.delta_chunked(s0, q, k, v, head, beta, lens)
    o2, s2 = delta_rule.delta_chunked(s0, q, k, v, wide, beta, lens)
    np.testing.assert_allclose(s1, s2, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(o1)[1, :77], np.asarray(o2)[1, :77],
                               atol=2e-5, rtol=1e-5)
    a, sa = delta_rule.delta_step(s0, q[:, 0], k[:, 0], v[:, 0], head[:, 0], beta[:, 0])
    b, sb = delta_rule.delta_step(s0, q[:, 0], k[:, 0], v[:, 0], wide[:, 0], beta[:, 0])
    np.testing.assert_allclose(sa, sb, atol=1e-6, rtol=0)
    np.testing.assert_allclose(a, b, atol=1e-6, rtol=0)


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


def test_b_the_live_lane_step_takes_a_decay_a_channel():
    """``delta_step_live`` on a layer's slice of a whole array = ``delta_step``
    on the slice; lanes that took nothing, and the other layer, bit for bit."""
    rng = np.random.default_rng(6)
    lanes = 6
    q, k, v, alpha, beta = (a[:, 0] for a in _rule_operands(rng, lanes, 1))
    states = jnp.asarray(rng.standard_normal((2, lanes, D_K, H * D_V)), jnp.float32)
    took = np.asarray([1, 0, 1, 1, 0, 1], bool)
    o, after = delta_rule.delta_step_live(states, 1, q, k, v, alpha, beta,
                                          jnp.asarray(took))
    want_o, want = delta_rule.delta_step(states[1], q, k, v, alpha, beta,
                                         jnp.asarray(took))
    np.testing.assert_allclose(after[1], want, atol=1e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(o)[took], np.asarray(want_o)[took],
                               atol=1e-6, rtol=0)
    assert np.asarray(after)[0].tobytes() == np.asarray(states)[0].tobytes()
    assert (np.asarray(after)[1][~took].tobytes()
            == np.asarray(states)[1][~took].tobytes())


def test_b_the_kernels_gate_gives_a_decay_a_channel_its_kernel(monkeypatch):
    """Where every condition holds (the interpreter's flag, the kernel's
    widths) a decay a channel is no longer refused by name (ISSUE 50): it
    takes ``delta_channel_chunk_kernel``, the tally says which form ran, and
    it is the block form's result."""
    monkeypatch.setattr(delta_rule, "DELTA_KERNEL_INTERPRET", True)
    rng = np.random.default_rng(4)
    state = jnp.asarray(rng.standard_normal((1, 16, 2 * 64)), jnp.float32)
    q, k = (jnp.asarray(rng.standard_normal((1, 64, 2, 16)) / 4, jnp.float32)
            for _ in "qk")
    v = jnp.asarray(rng.standard_normal((1, 64, 2, 64)), jnp.float32)
    alpha = jnp.asarray(rng.uniform(0.05, 1.0, (1, 64, 2, 16)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (1, 64, 2)), jnp.float32)
    assert delta_rule._kernel_refusal(state, k, v, 64) is None
    assert delta_rule._kernel_refusal(state, k, v, 64, True) is None
    before = {key: n for key, n in dispatch_tally().items()
              if key[0] == "delta_chunked"}
    o, s = delta_rule.delta_chunked(state, q, k, v, alpha, beta)
    new = {key: n for key, n in dispatch_tally().items()
           if key[0] == "delta_chunked" and n != before.get(key)}
    assert len(new) == 1
    (key,) = new
    assert key[1:3] == ("kernel", "interpret")
    o_blk, s_blk = delta_rule._chunked_block_channel(
        delta_rule._heads(state, 2), q, k, v, alpha, beta, 64)
    np.testing.assert_allclose(o, o_blk, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(s, delta_rule._flat(s_blk), atol=2e-5, rtol=1e-5)


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
    assert pk.shape == pv.shape == (N_FULL, 1, N_KV, p_pad, HEAD)
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
    assert cache["k"].shape == (N_FULL, pages, N_KV, PT, HEAD)
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
    dev = jax.tree_util.tree_map(jnp.asarray, _tree(4))
    prompt = np.random.default_rng(5).integers(1, MC["vocab_size"], 16)
    at11 = _prefill(dev, prompt[:11], 16)[4]
    again = _prefill(dev, prompt[:11], 128)[4]
    at16 = _prefill(dev, prompt, 16)[4]
    for a, b, c in zip(at11, again, at16):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
        assert np.max(np.abs(np.asarray(a) - np.asarray(c))) > 1e-2


def _chunk(dev, cfg, cache, tables, tok, pos, active, chunk=8):
    out = generation._paged_decode_chunk_jit(
        dev, cache["k"], cache["v"], None, tables, tok, pos, active,
        np.uint32(1), np.zeros((LANES,), np.float32),
        np.zeros((LANES,), np.int32), cache["lane"], None,
        cfg_key=tuple(sorted(cfg.items())), family=NAME, chunk=chunk,
        page_tokens=PT, kernel=False)
    k, v, _, tok, pos, toks, stats, lane, _counter = out
    # an expert model: the chunk reports its expert layers' routing
    assert np.asarray(stats).shape[-1] == len(generation.MOE_STATS)
    return ({"k": k, "v": v, "lane": lane}, np.asarray(toks), np.asarray(pos),
            np.asarray(stats))


def test_c_an_inactive_lanes_state_and_conv_tail_are_bit_for_bit_after_a_chunk():
    """Two admitted lanes, one frozen for a chunk of 8: both parts of its
    state come back bit for bit, the live lane emits the reference's greedy
    tokens, and the chunk counts the assignments that landed on a held expert
    (at most the live lane's 4 a layer)."""
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
    after, toks, _, stats = _chunk(dev, cfg, cache, tables, tok, pos, active)
    for part, was in zip(after["lane"], frozen["lane"]):
        assert np.asarray(part)[:, 2].tobytes() == was[:, 2].tobytes()
        assert np.any(was[:, 2] != 0)
        assert np.any(np.asarray(part)[:, 1] != was[:, 1])
    local = stats.reshape(-1, len(generation.MOE_STATS))[
        :, generation.MOE_STATS.index("expert_rows_local")]
    assert local.max() <= MC["top_k"] and 0 < local.mean() < MC["top_k"]
    chain = [int(first1)]
    for _ in range(8):
        ref = _reference(tree, np.concatenate([p1, chain]))
        chain.append(int(np.argmax(ref[-1])))
    np.testing.assert_array_equal(toks[1], chain[1:])


def test_c_a_long_prefill_runs_the_mixer_and_the_experts_a_block_at_a_time(monkeypatch):
    """A bucket of 4096 (``real_rows.row_block`` cuts it in blocks of 512): the
    linear layer's mixer run block after block, the state carried, answers
    what the whole bucket at once answers for every real row, and hands on
    the same state; so do the routed experts once their rows pass
    ``EXPERT_ROWS_BYTES``; rows past the last real block come back zero."""
    import tfservingcache_tpu.models.moe_lm as moe_lm

    cfg = dict(static_config(MODEL))
    layer = jax.tree_util.tree_map(jnp.asarray, _tree(8))["layers"][1]
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((2, 4096, MC["d_model"])), jnp.float32)
    real = jnp.asarray([3000, 700], jnp.int32)
    state = (jnp.asarray(rng.standard_normal((2, D_K, H * D_V)), jnp.float32),
             jnp.asarray(rng.standard_normal((2, 3, WIDTH)), jnp.float32))
    got, (s_got, conv_got), _ = jax.jit(
        lambda x, state: kda.kda_layer(layer, x, state, real, cfg))(x, state)
    want, (s_want, conv_want) = jax.jit(
        lambda x, state: kda._kda_rows(layer, x, state, real, cfg))(x, state)
    for row, n in enumerate((3000, 700)):
        np.testing.assert_allclose(np.asarray(got)[row, :n],
                                   np.asarray(want)[row, :n], atol=2e-5, rtol=1e-5)
    assert not np.asarray(got)[:, 3072:].any()
    np.testing.assert_allclose(s_got, s_want, atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(conv_got, conv_want)

    whole, stats = moe_lm._moe_block(layer, x, cfg, jnp.float32, took=real)
    assert stats is not None
    monkeypatch.setattr(moe_lm, "EXPERT_ROWS_BYTES", 0)
    blocks, stats = moe_lm._moe_block(layer, x, cfg, jnp.float32, took=real)
    assert stats is None
    for row, n in enumerate((3000, 700)):
        np.testing.assert_allclose(np.asarray(blocks)[row, :n],
                                   np.asarray(whole)[row, :n], atol=1e-5, rtol=0)
    assert not np.asarray(blocks)[:, 3072:].any()


# -- (d) what the ModelDef declares --------------------------------------------

def test_d_the_declaration_says_what_each_layer_keeps():
    state = MODEL.layer_state
    assert lane_layers(state) == (1, 2, 3, 5, 6, 7)
    lane = state[1]
    assert isinstance(lane, LaneState) and lane.operator is kda.kda_layer
    assert lane.step is kda.kda_step
    assert lane.parts() == ((D_K, H * D_V, "float32"), (3, WIDTH, ""))
    assert state[0] == state[4] == CacheRow(2, N_KV, HEAD) == MODEL.cache_row
    cfg = dict(static_config(MODEL))
    assert generation._row_layers(cfg) == N_FULL
    assert generation._window_of(cfg) == 0 and generation.shared_readers(cfg) == 0
    s, conv = generation.init_lane_state(cfg, LANES)
    assert s.shape == (N_LINEAR, LANES, D_K, H * D_V) and s.dtype == jnp.float32
    assert conv.shape == (N_LINEAR, LANES, 3, WIDTH)
    # the step follows the live lanes, as Olmo-Hybrid's
    assert generation.state_write_lanes(np.arange(16) < 5, cfg) == 8
    with pytest.raises(ValueError, match="layer_types must name"):
        build(NAME, dict(MC, layer_types=[L, "mamba"] * 4))
    for key, value in (("use_rope", True), ("use_gqa_gate", False),
                       ("kda_use_full_proj", True), ("first_k_dense_replace", 1)):
        with pytest.raises(ValueError, match=key):
            FAMILY.program_config(dict(PUBLISHED, **{key: value}))


def test_d_the_step_in_place_is_the_operator_on_the_layers_slice():
    """``kda_step`` on the whole arrays = ``kda_layer`` on layer 1's slice:
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
    layer = tree["layers"][2]                   # the model's second lane layer
    want, (s_want, conv_want), _ = kda.kda_layer(
        layer, x, (s[1], conv[1]), took, cfg)
    got, (s_got, conv_got), _ = kda.kda_step(
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


def test_d_the_benchmark_configuration_one_period_of_an_ep8_chip():
    """The arithmetic the configuration's file states: 4 layers = 1 full + 3
    linear, 40 of 320 experts, an eighth of the vocabulary: 3.31 G parameters
    = 6.62 GB; 4,096 B of K/V a token; 4,341,760 B of state a layer a lane; an
    arena of 262,144 tokens."""
    with open(os.path.join(ROOT, "benchmark", "configs", "solar-open2-250b.json")) as f:
        config = json.load(f)
    source = config["source_values"]
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size",
               "max_position_embeddings"}
    assert set(config["reduced_why"]) == reduced
    for key, value in source.items():
        if key not in reduced:
            assert config[key] == value, key
    assert (source["num_hidden_layers"], source["n_routed_experts"],
            source["vocab_size"], source["max_position_embeddings"]) == (
                48, 320, 196608, 1048576)
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_intermediate_size"], config["num_experts_per_tok"]) == (
                4096, 64, 8, 128, 1280, 8)
    assert config["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    mc = FAMILY.program_config(config)
    model = build(NAME, mc)
    assert mc["layer_types"] == [F, L, L, L] and mc["rope_theta"] is None
    assert (mc["n_experts"], mc["n_experts_held"], mc["top_k"]) == (320, 40, 8)
    assert mc["vocab_size"] == 196608 // 8 and mc["max_seq"] == 16384 + 2048
    assert abs(FAMILY.param_bytes(mc) / 1e9 - 6.62) < 0.01
    row = model.cache_row
    assert row == CacheRow(2, 8, 128)
    assert row.sides * row.heads * row.width * 2 == 4096
    lane = model.layer_state[1]
    assert lane.parts() == ((128, 8192, "float32"), (3, 24576, ""))
    assert sum(r * w * (4 if d else 2) for r, w, d in lane.parts()) == 4341760
    srv = config["server"]["serving"]
    assert srv["kv_arena_pages"] * srv["kv_page_tokens"] == 262144


# -- (e) through the engine ----------------------------------------------------

def _load(tmp_path, name="solaropen2", seed=0, metrics=None, **serving_kw):
    export_artifact(NAME, str(tmp_path), name=name, version=1, config=MC,
                    seed=seed)
    rt = TPUModelRuntime(ServingConfig(platform="cpu", **serving_kw), metrics)
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


def test_e_the_engine_answers_what_the_solo_decoder_answers(tmp_path):
    """Five requests through two lanes (every lane reused; one prompt of more
    than a chunk of the rule): each answers what the solo decoder (the dense
    cache with its two-part lane state) answers."""
    rt, mid = _load(tmp_path)
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
    rt, mid = _load(tmp_path, name=f"solaropen2_{what}", **knobs)
    ids = np.ones((1, 4), np.int32)
    refused = lambda pattern: pytest.raises(  # noqa: E731
        RuntimeError_, match=(
            r"kda_moe_lm \(lane-state layers\) does not support .*" + pattern))
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


# -- the rule at the benchmark's widths on the chip -----------------------------

ON_TPU = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)")


@ON_TPU
@pytest.mark.parametrize("form", ["step", "chunked"])
def test_kda_rule_on_tpu(form):
    """Both forms with a decay a channel at the benchmark's widths (64 heads
    of 128 / 128, bf16 operands, decays log-uniform down to 0.05 a step)
    against the step iterated: ``delta_step_live`` on layer 1 of a three-layer
    array of 32 lanes of which 8 took a token (every other slice bit for bit;
    its time a live lane beside what the state's bytes allow), and
    ``delta_chunked`` over one lane at a bucket of 2048 (``real_len`` 1500)
    and of 8192 (6000), through the kernel the chip is given
    (``delta_channel_chunk_kernel``, ISSUE 50) and through the block form it is
    held to: largest errors and ms a layer side by side."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    h, d_k, d_v = 64, 128, 128
    rng = np.random.default_rng(12)
    b, t, n_real = {"step": (32, 1, 1), "chunked": (1, 2048, 1500)}[form]
    q, k = (rng.standard_normal((b, t, h, d_k)).astype(np.float32) for _ in "qk")
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d_k)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((b, t, h, d_v)).astype(np.float32)
    alpha = np.exp(rng.uniform(np.log(0.05), 0.0, (b, t, h, d_k))).astype(np.float32)
    beta = rng.uniform(0.0, 2.0, (b, t, h)).astype(np.float32)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731
    if form == "step":
        states = jnp.asarray(rng.standard_normal((3, b, d_k, h * d_v)), jnp.float32)
        took = jnp.asarray(np.arange(b) % 4 == 1)
        args = (bf(q[:, 0]), bf(k[:, 0]), bf(v[:, 0]), jnp.asarray(alpha[:, 0]),
                jnp.asarray(beta[:, 0]), took)
        o_want, s_want = jax.jit(delta_rule.delta_step)(states[1], *args)
        live_step = jax.jit(delta_rule.delta_step_live, static_argnums=1,
                            donate_argnums=0)
        o_got, after = live_step(states + 0.0, 1, *args)
        live = np.asarray(took)
        for layer in (0, 2):
            assert np.asarray(after[layer]).tobytes() == np.asarray(states[layer]).tobytes()
        assert np.asarray(after[1])[~live].tobytes() == np.asarray(states[1])[~live].tobytes()
        np.testing.assert_allclose(np.asarray(after[1])[live],
                                   np.asarray(s_want)[live], atol=1e-5, rtol=0)
        np.testing.assert_allclose(np.asarray(o_got)[live],
                                   np.asarray(o_want)[live], atol=1e-5, rtol=0)
        trips = 64

        @jax.jit
        def many(states, q, *rest):
            """``trips`` steps on the carried array, as a decode chunk makes
            them: each reads the one before it (``q`` moved by its output)."""
            def body(_, carry):
                states, moved = carry
                o, states = delta_rule.delta_step_live(
                    states, 1, q + moved.astype(q.dtype), *rest)
                return states, 1e-6 * o[1, 0, 0]
            return jax.lax.fori_loop(0, trips, body, (states, jnp.float32(0)))

        import time
        jax.block_until_ready(many(states, *args))
        t0 = time.perf_counter()
        jax.block_until_ready(many(states, *args))
        ms = 1e3 * (time.perf_counter() - t0) / trips
        lanes = int(live.sum())
        print(f"delta_step_live, a decay a channel: {lanes} live of {b} lanes "
              f"{ms * 1e3:.1f} us a layer = {ms * 1e3 / lanes:.1f} us a live lane "
              f"(8,503,552 B a lane at 819 GB/s: 10.4)", flush=True)
        return
    s0 = jnp.asarray(rng.standard_normal((b, d_k, h * d_v)), jnp.float32)
    real = jnp.asarray([n_real], jnp.int32)
    want_o, want_s = _iterated(s0, bf(q), bf(k), bf(v), jnp.asarray(alpha),
                               jnp.asarray(beta), real)
    operands = (s0, bf(q), bf(k), bf(v), jnp.asarray(alpha), jnp.asarray(beta), real)
    scale = float(np.std(np.asarray(want_o)[0, :n_real]))

    def timed(operands):
        alpha, rest = operands[4], (*operands[:4], *operands[5:])
        return 1e3 * chained_device_time(
            lambda alpha, s, q, k, v, beta, real: sum(
                jnp.sum(x) for x in delta_rule.delta_chunked(s, q, k, v, alpha, beta, real)),
            (alpha, *rest), iters=4)

    # the same numbers four times over, 6000 of 8192 real: the longer bucket
    long = (s0, *(jnp.concatenate([a] * 4, axis=1) for a in operands[1:6]),
            jnp.asarray([6000], jnp.int32))
    for name in ("kernel", "block form"):
        with pytest.MonkeyPatch.context() as m:
            if name == "block form":
                m.setattr(delta_rule, "_kernel_refusal", lambda *a: "the block form")
            kernels = lambda: sum(n for key, n in dispatch_tally().items()  # noqa: E731
                                  if key[:2] == ("delta_chunked", "kernel"))
            before = kernels()
            got_o, got_s = jax.jit(lambda *a: delta_rule.delta_chunked(*a))(*operands)
            assert (kernels() > before) == (name == "kernel"), dispatch_tally()
            err_o = float(np.max(np.abs(np.asarray(got_o)[0, :n_real]
                                        - np.asarray(want_o)[0, :n_real])))
            err_s = float(np.max(np.abs(np.asarray(got_s) - np.asarray(want_s))))
            ms, ms_long = timed(operands), timed(long)
        print(f"delta_chunked[{t}, real {n_real}], a decay a channel ({name}): "
              f"out err {err_o:.3e} of std {scale:.3e}, state err {err_s:.3e}, "
              f"{ms:.2f} ms = {ms * 1e3 / t:.2f} us a bucket token a layer; "
              f"[{4 * t}, real 6000] {ms_long:.2f} ms = "
              f"{ms_long * 1e3 / 6000:.2f} us a real token", flush=True)
        assert np.isfinite(np.asarray(got_o)).all()
        assert err_o < 0.05 * scale + 1e-3 and err_s < 0.05


@ON_TPU
def test_kda_rule_on_tpu_step_kernel():
    """The one-token step at the benchmark's widths (64 heads of 128 x 128, a
    decay a channel, 32 lanes, three layers) through ``delta_step_kernel``
    (ISSUE 52) beside today's loop at 1, 3, 4, 5 and 8 live lanes: errors
    against the float64 step and us a live lane a layer against the 10.4 the
    state's bytes allow (8,503,552 B a lane at 819 GB/s)."""
    from tests.test_delta_step_kernel import step_rows_on_tpu

    step_rows_on_tpu("64 heads of 128 x 128, a decay a channel", 64, 128, 128, True,
                     lanes=32, layers=3, floor_us=10.4)
