"""A decoder-hybrid-decoder (Mamba layers beside window attention, ONE
full-attention layer whose rows the cross-attention layers read, gated memory
units, differential attention) through the program: the ``sambay_lm`` family
against the plain reference the benchmark keeps
(``benchmark/families/sambay.py``), at a small size on the CPU with all five
layer kinds (8 layers: M W M W | M | F | G X).

  (a) the family's ``apply`` logits against the reference, and each of the
      mistakes the tolerance is there to catch lands orders above it (a
      dropped ``- lam`` term, a GMU fed the gated ``y``, a missing convolution
      bias, the GQA head map in place of the differential pairing);
  (b) prefill, then decode through the paged arena, the rings and the two-part
      lane state, logits against the reference's full forward at every
      position: prompts that do and do not fill their bucket, a prompt longer
      than the window; the state at ``real_len``, not at the bucket's end; a
      scan state kept in bf16 fails;
  (c) the lane state by itself: an inactive lane's two parts bit for bit
      after a chunk of 8, a reused lane answers as a fresh one;
  (d) what the ModelDef declares: the arena has ONE layer, the rings 3, the
      cross layers write no row, ``pages_used`` counts one layer; the
      benchmark configuration's arithmetic;
  (e) through ``ContinuousGenerateEngine``: the engine answers what the solo
      decoder answers, the ring's ``shared_pages`` and the gauge's two parts;
  (f) what the family cannot do yet is refused by name;
  (g) the accepted families build the programs they built.

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
import tfservingcache_tpu.models.sambay_lm as sambay
import tfservingcache_tpu.ops.attention as att
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import (
    CacheRow,
    LaneState,
    NoState,
    SharedRows,
    build,
    export_artifact,
    lane_layers,
    static_config,
    window_layers,
)
from tfservingcache_tpu.ops import ssm
from tfservingcache_tpu.runtime.base import RuntimeError_
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId
from tfservingcache_tpu.utils.flight_recorder import RECORDER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _family(name="sambay"):
    spec = importlib.util.spec_from_file_location(
        f"bench_family_{name}",
        os.path.join(ROOT, "benchmark", "families", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAMILY = _family()
WINDOW = 24
# hidden 128, 8 query / 4 KV heads of 16 (two KV pairs, so the pairing's order
# shows), 8 layers = every kind, E = 256, N = 8, R = 8, window 24, pages of 8
PUBLISHED = {
    "hidden_size": 128, "num_attention_heads": 8, "num_key_value_heads": 4,
    "intermediate_size": 96, "num_hidden_layers": 8, "sliding_window": WINDOW,
    "vocab_size": 97, "layer_norm_eps": 1e-5, "tie_word_embeddings": True,
    "max_position_embeddings": 128, "torch_dtype": "float32",
    "assumed": {"mamba_expand": {"value": 2}, "mamba_d_state": {"value": 8},
                "mamba_d_conv": {"value": 4}, "mamba_dt_rank": {"value": 8}},
}
MC = FAMILY.program_config(PUBLISHED)
# built HERE, before any case patches the family's module: ``build`` keeps one
# ModelDef a config, and its declaration holds the operators it was built with
MODEL = build("sambay_lm", MC)
KINDS = sambay.layer_kinds(8)
PT = 8
LANES = 4
RING = att.window_ring_pages(WINDOW, PT)          # 24 / 8 + 1 = 4 pages a lane
HEAD = 16
E, N = 256, 8
N_MAMBA, N_WINDOW = KINDS.count(sambay.MAMBA), KINDS.count(sambay.WINDOW)


def _tree(seed=0, mc=MC):
    """Seeded weights in the benchmark's layout, every gain random too (a gain
    of one would hide a norm applied to the wrong tensor), ``d_skip`` and the
    final norm's bias random, the ``lam`` vectors large enough that ``lam``
    differs from ``lam0`` by tenths."""
    rng = np.random.default_rng(seed)
    leaves = {name: (rng.standard_normal(shape) / np.sqrt(fan_in)).astype(np.float32)
              for name, (shape, fan_in) in FAMILY.leaf_shapes(mc).items()}
    tree = FAMILY.to_tree(mc, leaves)
    gain = lambda a: (1.0 + 0.2 * rng.standard_normal(a.shape)).astype(np.float32)  # noqa: E731
    tree["ln_f"] = gain(tree["ln_f"])
    tree["ln_f_b"] = (0.1 * rng.standard_normal(tree["ln_f_b"].shape)).astype(np.float32)
    for lp in tree["layers"]:
        lp["ln1"], lp["ln2"] = gain(lp["ln1"]), gain(lp["ln2"])
        if "ssm" in lp:
            lp["ssm"]["d_skip"] = gain(lp["ssm"]["d_skip"])
            lp["ssm"]["conv_b"] = (3 * lp["ssm"]["conv_b"]).astype(np.float32)
        if "attn" in lp:
            lp["attn"]["sub_norm"] = gain(lp["attn"]["sub_norm"])
            for w in FAMILY.LAM:
                lp["attn"][w] = (3 * lp["attn"][w]).astype(np.float32)
    return tree


def _apply(mc, tree, ids):
    out = build("sambay_lm", mc).apply(
        jax.tree_util.tree_map(jnp.asarray, tree), {"input_ids": np.asarray(ids)[None]})
    return np.asarray(out["logits"])[0]


def _reference(tree, seq, mc=MC):
    return FAMILY.logits_many(mc, tree, [list(map(int, seq))], last=len(seq))[0]


# -- (a) the full forward ------------------------------------------------------

def _drop_lam(monkeypatch, tree):
    real = sambay.diff_finish
    monkeypatch.setattr(
        sambay, "diff_finish",
        lambda attn, terms, depth, dtype: real(
            attn, (terms[0], jnp.zeros_like(terms[1])), depth, dtype))


def _gmu_fed_gated_y(monkeypatch, tree):
    """The memory handed on is ``y * silu(z)``, the gated output, not ``y``."""
    real = sambay.mamba_layer

    def gated(layer, x, state, real_len, cfg):
        out, after, extras = real(layer, x, state, real_len, cfg)
        u = sambay._norm(layer, "ln1", x, cfg["norm_eps"])
        z = jnp.split(u @ layer["ssm"]["w_in"], 2, axis=-1)[1]
        return out, after, {"memory": extras["memory"] * jax.nn.silu(z)}

    monkeypatch.setattr(sambay, "mamba_layer", gated)


def _no_conv_bias(monkeypatch, tree):
    for lp in tree["layers"]:
        if "ssm" in lp:
            lp["ssm"]["conv_b"] = np.zeros_like(lp["ssm"]["conv_b"])


def _gqa_head_map(monkeypatch, tree):
    """Heads ``4j, 4j + 1`` against ``k(2j)`` and ``4j + 2, 4j + 3`` against
    ``k(2j + 1)``: the GQA map ``head // 2``, not the differential pairing."""
    def wrong(q):
        b, hq, t, d = q.shape
        own = jnp.eye(2, dtype=q.dtype)[:, None, None, :, None]
        qd = q.reshape(b, hq // 4, 2, 2, t, d)
        return (qd[..., None, :] * own).reshape(b, hq, t, 2 * d)

    monkeypatch.setattr(att, "diff_queries", wrong)


MISTAKES = {"dropped_lam_term": _drop_lam, "gmu_fed_the_gated_y": _gmu_fed_gated_y,
            "missing_conv_bias": _no_conv_bias,
            "gqa_map_for_the_pairing": _gqa_head_map}


@pytest.mark.parametrize("mistake", [None, *MISTAKES])
def test_a_full_forward_equals_the_reference(monkeypatch, mistake):
    tree = _tree(1)
    ids = np.random.default_rng(2).integers(1, MC["vocab_size"], 45)  # > window
    want = _reference(tree, ids)
    assert want.std() > 0.5
    if mistake is not None:
        MISTAKES[mistake](monkeypatch, tree)
    got = _apply(MC, tree, ids)
    if mistake is None:
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    else:
        assert np.max(np.abs(got - want)) > 1e-2, mistake


def test_a_the_pairing_is_q1_q2_q1_q2_over_a_kv_pair():
    """The named case that holds the order: over KV pair ``j`` the four query
    heads ``4j .. 4j + 3`` are ``q1, q2, q1, q2``; ``diff_queries`` hands
    grouped-query attention the first and third as ``[q | 0]`` (they read
    ``k(2j)``), then the second and fourth as ``[0 | q]``; ``diff_outputs``
    gives pair ``i = 2j + a`` its two terms back."""
    q = jnp.arange(8, dtype=jnp.float32).reshape(1, 8, 1, 1) + 1.0   # head h = h + 1
    padded = np.asarray(att.diff_queries(q))[0, :, 0]                # (8, 2)
    np.testing.assert_array_equal(
        padded, [[1, 0], [3, 0], [0, 2], [0, 4], [5, 0], [7, 0], [0, 6], [0, 8]])
    o1, o2 = att.diff_outputs(jnp.asarray(padded)[None, :, None, :])
    np.testing.assert_array_equal(np.asarray(o1)[0, :, 0, 0], [1, 3, 5, 7])  # q1 of pair i
    np.testing.assert_array_equal(np.asarray(o2)[0, :, 0, 1], [2, 4, 6, 8])  # q2 of pair i


# -- (b) prefill, then decode through the arena, the rings and the state ------

def _prefill(dev, prompt, p_pad, mc=MC):
    model = build("sambay_lm", mc)
    ids = np.zeros((1, p_pad), np.int32)
    ids[0, :len(prompt)] = prompt
    tok, pk, pv, last, lane = generation._slot_prefill_jit(
        dev, ids, np.asarray([len(prompt)], np.int32), jax.random.PRNGKey(0),
        np.float32(0.0), np.int32(0), cfg_key=static_config(model),
        family="sambay_lm")
    return int(tok[0]), pk, pv, np.asarray(last)[0], lane


def _paged_setup(dev, prompt, p_pad, lane=1, pages=40, mc=MC):
    """Prefill ``prompt`` and admit it into lane ``lane`` of a fresh arena,
    fresh rings and a fresh two-part lane state -> (cfg, cache, tables, pos,
    first token, the last prompt position's logits)."""
    model = build("sambay_lm", mc)
    cfg = dict(static_config(model))
    tok, pk, pv, last, state = _prefill(dev, prompt, p_pad, mc=mc)
    # the dense cache has a layer a layer with rows of its OWN: 3 window + 1 full
    assert pk.shape == pv.shape == (N_WINDOW + 1, 1, 2, p_pad, 2 * HEAD)
    conv, h = state
    assert conv.shape == (N_MAMBA, 1, 3, E) and conv.dtype == jnp.float32
    assert h.shape == (N_MAMBA, 1, N, E) and h.dtype == jnp.float32
    cache = generation.init_paged_cache(cfg, pages, PT, row=model.cache_row,
                                        lanes=LANES)
    assert cache["k"].shape == (1, pages, 2, PT, 2 * HEAD)         # ONE layer
    assert cache["wk"].shape == (N_WINDOW, LANES * RING, 2, PT, 2 * HEAD)
    pps = mc["max_seq"] // PT
    tables = np.zeros((LANES, pps), np.int32)
    tables[lane, :9] = 1 + 9 * lane + np.arange(9)         # 72 tokens a lane
    k, v, wk, wv = generation._window_paged_insert_jit(
        cache["k"], cache["v"], cache["wk"], cache["wv"], pk, pv, tables[lane],
        np.int32(lane), np.int32(len(prompt)), page_tokens=PT,
        window_layers=generation.window_rows(cfg), ring_pages=RING)
    lanes = generation._lane_insert_jit(
        generation.init_lane_state(cfg, LANES), state, np.int32(lane))
    pos = np.zeros((LANES,), np.int32)
    pos[lane] = len(prompt)
    return (cfg, {"k": k, "v": v, "wk": wk, "wv": wv, "lane": lanes}, tables,
            pos, tok, last)


@pytest.mark.parametrize("prompt_len,p_pad", [(11, 16), (16, 16), (37, 64), (1, 1)],
                         ids=["off_bucket", "fills_its_bucket",
                              "longer_than_the_window", "one_token"])
def test_b_prefill_then_paged_decode_matches_the_reference_at_every_position(
        prompt_len, p_pad):
    tree = _tree(2)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, MC["vocab_size"], prompt_len)
    forced = rng.integers(1, MC["vocab_size"], 28)        # past a window's turn
    want = _reference(tree, np.concatenate([prompt, forced]))
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    lane = 1
    cfg, cache, tables, pos, _tok, last = _paged_setup(dev, prompt, p_pad, lane=lane)
    np.testing.assert_allclose(last, want[prompt_len - 1], atol=1e-4, rtol=0)
    active = np.arange(LANES) == lane
    step = jax.jit(lambda cache, tok, pos: generation._paged_forward_step(
        dev, tok, cache, tables, pos, cfg, "sambay_lm", PT, active=active))
    tok = np.zeros((LANES,), np.int32)
    global_before = np.asarray(cache["k"])
    for j, t in enumerate(forced):
        tok[lane] = t
        logits, cache = step(cache, tok, pos)
        np.testing.assert_allclose(np.asarray(logits)[lane, 0],
                                   want[prompt_len + j], atol=1e-4, rtol=0)
        pos[lane] += 1
    # the lanes nobody read kept the zeros they were built with, both parts
    for part in cache["lane"]:
        assert not np.asarray(part)[:, ~active].any()
    # the global arena changed in the lane's own pages only (and the trash
    # page, where the lanes nobody reads write)
    changed = np.any(np.asarray(cache["k"]) != global_before, axis=(0, 2, 3, 4))
    assert set(np.flatnonzero(changed)) <= {0, *tables[lane][tables[lane] > 0]}


def test_b_the_state_is_the_one_at_real_len_not_at_the_buckets_end():
    """A prompt of 11 in a bucket of 16: both parts of the state a prefill
    hands on are those after 11 tokens (the same prompt in a bucket of 64
    hands on the same), and 5 further real tokens move them."""
    tree = _tree(4)
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    prompt = np.random.default_rng(5).integers(1, MC["vocab_size"], 16)
    at11 = _prefill(dev, prompt[:11], 16)[4]
    again = _prefill(dev, prompt[:11], 64)[4]
    at16 = _prefill(dev, prompt, 16)[4]
    for a, b, c in zip(at11, again, at16):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=0)
        assert np.max(np.abs(np.asarray(a) - np.asarray(c))) > 1e-2


def test_b_a_scan_state_kept_in_bf16_fails():
    """The scan state is float32 in the lane state (``LaneState.dtype``): the
    same decode with the state rounded to bf16 between steps is hundreds of
    tolerances away within a few steps."""
    tree = _tree(2)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, MC["vocab_size"], 11)
    forced = rng.integers(1, MC["vocab_size"], 12)
    want = _reference(tree, np.concatenate([prompt, forced]))
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    cfg, cache, tables, pos, _tok, _ = _paged_setup(dev, prompt, 16, lane=1)
    assert cache["lane"][1].dtype == jnp.float32
    active = np.arange(LANES) == 1
    step = jax.jit(lambda cache, tok, pos: generation._paged_forward_step(
        dev, tok, cache, tables, pos, cfg, "sambay_lm", PT, active=active))
    tok = np.zeros((LANES,), np.int32)
    worst = 0.0
    for j, t in enumerate(forced):
        conv, h = cache["lane"]
        cache = {**cache, "lane": (
            conv, h.astype(jnp.bfloat16).astype(jnp.float32))}
        tok[1] = t
        logits, cache = step(cache, tok, pos)
        worst = max(worst, float(np.max(np.abs(
            np.asarray(logits)[1, 0] - want[11 + j]))))
        pos[1] += 1
    assert worst > 1e-3, worst


# -- (c) the lane state by itself ----------------------------------------------

def _chunk(dev, cfg, cache, tables, tok, pos, active, chunk=8, kernel=False):
    out = generation._paged_decode_chunk_jit(
        dev, cache["k"], cache["v"], None, tables, tok, pos, active,
        np.uint32(1), np.zeros((LANES,), np.float32),
        np.zeros((LANES,), np.int32), cache["lane"], (cache["wk"], cache["wv"]),
        cfg_key=tuple(sorted(cfg.items())), family="sambay_lm", chunk=chunk,
        page_tokens=PT, kernel=kernel)
    k, v, _, tok, pos, toks, stats, lane, _counter, ring = out
    assert stats is None
    return {"k": k, "v": v, "wk": ring[0], "wv": ring[1], "lane": lane}, \
        np.asarray(toks), np.asarray(pos)


def test_c_an_inactive_lanes_two_part_state_is_bit_for_bit_after_a_chunk():
    """Two admitted lanes, one frozen for a chunk of 8: both parts of its
    state, its ring pages and its global pages come back bit for bit, and the
    live lane emits the reference's greedy tokens."""
    tree = _tree(6)
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    rng = np.random.default_rng(7)
    p1, p2 = rng.integers(1, MC["vocab_size"], 13), rng.integers(1, MC["vocab_size"], 9)
    cfg, cache, tables, pos, first1, _ = _paged_setup(dev, p1, 16, lane=1)
    # admit the second prompt into lane 2 of the same cache
    _, pk, pv, _, state = _prefill(dev, p2, 16)
    tables[2, :9] = 1 + 9 * 2 + np.arange(9)
    k, v, wk, wv = generation._window_paged_insert_jit(
        cache["k"], cache["v"], cache["wk"], cache["wv"], pk, pv, tables[2],
        np.int32(2), np.int32(9), page_tokens=PT,
        window_layers=generation.window_rows(cfg), ring_pages=RING)
    lanes = generation._lane_insert_jit(cache["lane"], state, np.int32(2))
    cache = {"k": k, "v": v, "wk": wk, "wv": wv, "lane": lanes}
    pos[2] = 9
    frozen = jax.tree_util.tree_map(np.asarray, cache)
    active = np.arange(LANES) == 1
    tok = np.zeros((LANES,), np.int32)
    tok[1] = first1
    after, toks, _ = _chunk(dev, cfg, cache, tables, tok, pos, active)
    for part, was in zip(after["lane"], frozen["lane"]):
        np.testing.assert_array_equal(np.asarray(part)[:, 2], was[:, 2])
        assert np.any(np.asarray(part)[:, 1] != was[:, 1])
    # its ring too, but for the ONE row at its frozen position (9: page 1 of
    # its ring, offset 1), where a lane nobody reads writes junk that its own
    # next real step overwrites before any query reads it
    ring2 = slice(2 * RING, 3 * RING)
    moved = np.any(np.asarray(after["wk"])[:, ring2] != frozen["wk"][:, ring2],
                   axis=(0, 2, 4))                                # (pages, pt)
    assert set(zip(*np.nonzero(moved))) <= {(1, 1)}
    own = tables[2][:2]
    moved = np.any(np.asarray(after["k"])[:, own] != frozen["k"][:, own],
                   axis=(0, 2, 4))
    assert set(zip(*np.nonzero(moved))) <= {(1, 1)}
    chain = [int(first1)]
    for _ in range(8):
        ref = _reference(tree, np.concatenate([p1, chain]))
        chain.append(int(np.argmax(ref[-1])))
    np.testing.assert_array_equal(toks[1], chain[1:])


def test_c_selective_step_and_scan_are_one_recurrence():
    """``ops/ssm.py``: the scan over T tokens is T steps; the state returned
    is the one after ``real_len``; a row that took nothing keeps ``-0.0``."""
    rng = np.random.default_rng(8)
    b, t, n, e = 2, 19, 4, 6
    h0 = rng.standard_normal((b, n, e)).astype(np.float32)
    h0[1, 0, 0] = -0.0
    dt = np.abs(rng.standard_normal((b, t, e))).astype(np.float32)
    x, bm, cm = (rng.standard_normal(s).astype(np.float32)
                 for s in ((b, t, e), (b, t, n), (b, t, n)))
    a = -np.exp(rng.standard_normal((n, e))).astype(np.float32)
    d = rng.standard_normal((e,)).astype(np.float32)
    real = np.asarray([19, 7], np.int32)
    y, h = ssm.selective_scan(jnp.asarray(h0), dt, x, a, bm, cm, d, real)
    hs, ys = jnp.asarray(h0), []
    for i in range(t):
        yi, hs = ssm.selective_step(hs, dt[:, i], x[:, i], a, bm[:, i], cm[:, i],
                                    d, jnp.asarray(i < real))
        ys.append(yi)
    np.testing.assert_allclose(h, hs, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y)[0], np.stack(ys, 1)[0], atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y)[1, :7], np.stack(ys, 1)[1, :7],
                               atol=1e-5, rtol=1e-5)
    _, kept = ssm.selective_step(jnp.asarray(h0), dt[:, 0], x[:, 0], a, bm[:, 0],
                                 cm[:, 0], d, jnp.asarray([True, False]))
    assert np.asarray(kept)[1].tobytes() == h0[1].tobytes()


# -- (d) what the ModelDef declares --------------------------------------------

def test_d_the_declaration_says_what_each_layer_keeps():
    model = build("sambay_lm", MC)
    kinds = model.layer_state
    row = CacheRow(2, 2, 2 * HEAD)
    lane = LaneState(3, E, beside=(LaneState(N, E, "float32"),))
    assert kinds == (lane, CacheRow(2, 2, 2 * HEAD, window=WINDOW), lane,
                     CacheRow(2, 2, 2 * HEAD, window=WINDOW), lane, row,
                     NoState(sambay.gmu_layer), SharedRows(5))
    assert kinds[0].operator is sambay.mamba_layer
    assert kinds[0].parts() == ((3, E, ""), (N, E, "float32"))
    assert lane_layers(kinds) == (0, 2, 4) and window_layers(kinds) == (1, 3)
    cfg = dict(static_config(model))
    # (lane state, dense cache layer, layer of ITS arena, window); the cross
    # layer has the full-attention layer's slot, the GMU none
    assert generation._layer_slots(cfg) == [
        (True, 0, 0, 0), (False, 0, 0, WINDOW), (True, 1, 1, 0),
        (False, 1, 1, WINDOW), (True, 2, 2, 0), (False, 2, 0, 0),
        (False, -1, -1, 0), (False, 2, 0, 0)]
    assert generation._row_layers(cfg) == 3 and generation.window_rows(cfg) == (0, 1)
    assert generation.shared_readers(cfg) == 2
    with pytest.raises(ValueError, match="no earlier layer with rows"):
        generation._layer_slots({**cfg, "layer_state": (SharedRows(1), row)})


def test_d_the_benchmark_configuration_one_global_layer_eight_rings():
    """Phi-4-mini-flash-reasoning as the cell runs it: 32 layers, ONE with
    pages that grow (0.67 GB at 8192 pages of 16 tokens), 8 rings of 33 pages
    a lane (0.69 GB), 9 two-part states (0.10 GB), 3.85 B parameters."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        config = json.load(f)
    mc = FAMILY.program_config(config)
    model = build("sambay_lm", mc)
    kinds = [type(k).__name__ + ("W" if getattr(k, "window", 0) else "")
             for k in model.layer_state]
    assert len(kinds) == 32
    assert [kinds.count(k) for k in (
        "LaneState", "CacheRowW", "CacheRow", "NoState", "SharedRows")] == [
            9, 8, 1, 7, 7]
    assert kinds[16:18] == ["LaneState", "CacheRow"]
    assert {k.layer for k in model.layer_state if isinstance(k, SharedRows)} == {17}
    cfg = dict(static_config(model))
    serving = config["server"]["serving"]
    lanes, pt = serving["generate_slots"], serving["kv_page_tokens"]
    arena = jax.eval_shape(lambda: generation.init_paged_cache(
        cfg, serving["kv_arena_pages"] + 1, pt, row=model.cache_row, lanes=lanes))
    assert arena["k"].shape == arena["v"].shape == (1, 8193, 10, 16, 128)
    assert arena["wk"].shape == arena["wv"].shape == (8, 32 * 33, 10, 16, 128)
    size = lambda a: a.size * a.dtype.itemsize  # noqa: E731
    assert 0.67e9 < size(arena["k"]) + size(arena["v"]) < 0.68e9
    assert 0.69e9 < size(arena["wk"]) + size(arena["wv"]) < 0.70e9
    conv, h = jax.eval_shape(lambda: generation.init_lane_state(cfg, lanes))
    assert conv.shape == (9, 32, 3, 5120) and conv.dtype == jnp.bfloat16
    assert h.shape == (9, 32, 16, 5120) and h.dtype == jnp.float32
    assert 0.10e9 < size(conv) + size(h) < 0.11e9
    assert generation.shared_readers(cfg) == 8
    assert 7.69e9 < FAMILY.param_bytes(mc) < 7.72e9
    per = lambda kind: sum(  # noqa: E731
        int(np.prod(s)) for name, (s, _) in FAMILY._layer_shapes(mc, kind).items()
        if not name.startswith(("mlp", "ln")))
    assert [round(per(k) / 1e6, 1) for k in (
        FAMILY.MAMBA, FAMILY.FULL, FAMILY.GMU, FAMILY.CROSS)] == [
            41.2, 19.7, 26.2, 13.1]


# -- (e) through the engine ----------------------------------------------------

def _load(tmp_path, name="sambay", seed=0, metrics=None, **serving_kw):
    export_artifact("sambay_lm", str(tmp_path), name=name, version=1,
                    config=MC, seed=seed)
    rt = TPUModelRuntime(ServingConfig(platform="cpu", **serving_kw), metrics)
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


def test_e_the_engine_answers_what_the_solo_decoder_answers(tmp_path):
    """Five requests through two lanes (every lane reused; one prompt longer
    than the window): each answers what the solo decoder (the dense cache with
    its two-part lane state) answers; the state's arrays, the ring's
    ``shared_pages`` and the gauge are where an operator reads them."""
    from tfservingcache_tpu.utils.metrics import Metrics

    metrics = Metrics()
    rt, mid = _load(tmp_path, metrics=metrics)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, MC["vocab_size"], n).astype(np.int32)
               for n in (37, 5, 19, 1, 26)]
    try:
        solo = [np.asarray(rt.generate(mid, p[None], max_new_tokens=20, seed=1))[0]
                for p in prompts]
        eng = ContinuousGenerateEngine(rt, slots=2, chunk_tokens=8,
                                       page_tokens=PT, arena_pages=24)
        try:
            import concurrent.futures as cf
            with cf.ThreadPoolExecutor(5) as pool:
                got = list(pool.map(
                    lambda p: eng.generate(mid, p[None], max_new_tokens=20)[0],
                    prompts))
            state = rt._slot_states[mid]
            state.check_page_conservation()
            assert state.k.shape[0] == 1 and state.shared_readers == 2
            assert len(state.window) == 2 and state.window[0].shape[0] == N_WINDOW
            conv, h = state.lane_state
            assert conv.shape == (N_MAMBA, 2, 3, E) and h.shape == (N_MAMBA, 2, N, E)
            label = metrics.model_label(mid.name, mid.version)
            assert metrics.lane_state_bytes.labels(label)._value.get() == (
                conv.nbytes + h.nbytes)
            steps = RECORDER.snapshot()["models"][f"{mid.name}@{mid.version}"]["steps"]
            read = [s["shared_pages"] for s in steps if s["chunk"] > 0]
            # two readers (the full layer and the one cross layer), each the
            # pages of positions 0..p: a page a reader at least, a table at most
            assert read and all(2.0 <= r <= 2.0 * state.pages_per_slot for r in read)
            # pages are counted once a token, not once a reader
            assert max(s["pages_used"] for s in steps) <= 2 * -(-(37 + 20) // PT)
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
    rt, mid = _load(tmp_path, name=f"sambay_{what}", **knobs)
    ids = np.ones((1, 4), np.int32)
    refused = lambda pattern: pytest.raises(  # noqa: E731
        RuntimeError_, match=(
            r"sambay_lm \(lane-state layers, window layers, layers that read "
            r"another layer's rows\) does not support .*" + pattern))
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
    model = build("sambay_lm", MC)
    cfg = dict(static_config(model))
    dev = jax.tree_util.tree_map(jnp.asarray, _tree(0))
    cache = generation.init_paged_cache(cfg, 8, PT, row=model.cache_row, lanes=LANES)
    cache["lane"] = generation.init_lane_state(cfg, LANES)
    with pytest.raises(ValueError, match="does not carry a lane state"):
        generation._paged_verify_step(
            dev, np.zeros((LANES, 4), np.int32), cache,
            np.zeros((LANES, 16), np.int32), np.zeros((LANES,), np.int32), cfg,
            "sambay_lm", PT)


# -- (g) the accepted families build the programs they built -------------------

def _decode_jaxpr(family, mc, lanes=2, pt=8, pages=6):
    model = build(family, mc)
    cfg = dict(static_config(model))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: generation.init_paged_cache(
        cfg, pages, pt, row=model.cache_row, lanes=lanes))
    lane = jax.eval_shape(lambda: generation.init_lane_state(cfg, lanes))
    ring = (cache["wk"], cache["wv"]) if "wk" in cache else None
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f = lambda p, k, v, tables, tok, pos, active, lane, ring: (  # noqa: E731
        generation._paged_decode_chunk_jit.__wrapped__(
            p, k, v, None, tables, tok, pos, active, jnp.uint32(1),
            jnp.zeros((lanes,), jnp.float32), jnp.zeros((lanes,), jnp.int32),
            lane, ring, cfg_key=tuple(sorted(cfg.items())), family=family,
            chunk=2, page_tokens=pt, kernel=False))
    return jax.make_jaxpr(f)(
        params, cache["k"], cache.get("v"), i32(lanes, 4), i32(lanes),
        i32(lanes), jax.ShapeDtypeStruct((lanes,), jnp.bool_), lane, ring)


def _count(jaxpr, names):
    """Primitive counts of a jaxpr, sub-jaxprs included."""
    from collections import Counter

    seen = Counter()

    def walk(jp):
        for eqn in jp.eqns:
            seen[eqn.primitive.name] += 1
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(jaxpr.jaxpr)
    return {n: seen[n] for n in names}


TINY = {"vocab_size": 64, "d_model": 32, "n_heads": 2, "n_kv_heads": 2,
        "max_seq": 32, "dtype": "float32"}
ACCEPTED = {
    # family, config, (exp, reduce_sum, logistic) a decode chunk's jaxpr holds:
    # none of them gained a LayerNorm, a scan state, a ``- lam`` or a gate
    "transformer_lm": ("transformer_lm", dict(TINY, n_layers=2, d_ff=48)),
    "olmoe": ("moe_lm", dict(TINY, n_layers=2, d_ff=16, n_experts=4, top_k=2)),
    "mellum2": ("moe_lm", dict(
        TINY, n_layers=2, d_ff=16, n_experts=4, top_k=2,
        layer_types=["sliding_attention", "full_attention"], sliding_window=8)),
    "hybrid_lm": ("hybrid_lm", dict(
        TINY, n_layers=2, layer_types=["conv", "full_attention"],
        n_dense_layers=1, d_ff_dense=48, d_ff=16, n_experts=4, top_k=2)),
}


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_g_the_accepted_families_build_the_programs_they_built(name):
    """What this PR's shared code added is taken only by a layer that
    declares or holds it: an accepted family's decode chunk has no LayerNorm
    (no ``ln1_b`` leaf: its norms are RMSNorms, one ``rsqrt`` a norm and no
    mean subtracted), no scanned state (its lane operand is None, or
    ``hybrid_lm``'s ONE array), no differential term, and its attention calls
    carry no ``sm_scale`` of their own. The norm count is the model's: two a
    layer and the final one, plus ``hybrid_lm``'s per-head QK-norms."""
    family, mc = ACCEPTED[name]
    model = build(family, mc)
    cfg = dict(static_config(model))
    lane = generation.init_lane_state(cfg, 2)
    assert lane is None or not isinstance(lane, tuple)
    assert generation.shared_readers(cfg) == 0
    assert not any("lam_q1" in layer.get("attn", {}) or "ln1_b" in layer
                   for layer in jax.eval_shape(
                       model.init, jax.random.PRNGKey(0))["layers"])
    jaxpr = _decode_jaxpr(family, mc)
    text = str(jaxpr)
    assert "softplus" not in text and "log1p" not in text
    norms = 2 * mc["n_layers"] + 1 + (2 if family == "hybrid_lm" else 0)
    assert _count(jaxpr, ["rsqrt"])["rsqrt"] == norms
    # the operator a lane-state layer brings is the family's own
    ops = [k.operator.__name__ for k in model.layer_state
           if isinstance(k, LaneState)]
    assert ops == (["conv_layer"] if family == "hybrid_lm" else [])


# -- hardware-gated rows (tools/tpu_kernel_check.py -k "sambay and on_tpu") -------------

ON_TPU = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)")


def _plain_differential(q, k_rows, v_rows, first, last):
    """float64 on the host: two dense softmaxes a pair over tokens
    ``first..last`` of one lane. ``q (Hq, D)``, rows ``(pairs, L, 2 D)`` ->
    the two terms ``(Hq / 2, 2 D)`` each, pair ``i`` in the model's order."""
    hq, d = q.shape
    q, k_rows, v_rows = (np.asarray(t, np.float64) for t in (q, k_rows, v_rows))
    terms = np.zeros((2, hq // 2, 2 * d))
    for i in range(hq // 2):
        j = i // 2
        for c in range(2):
            keys = k_rows[j, first:last + 1, c * d:(c + 1) * d]
            s = keys @ q[2 * i + c] / np.sqrt(d)
            p = np.exp(s - s.max())
            terms[c, i] = (p / p.sum()) @ v_rows[j, first:last + 1]
    return terms


@ON_TPU
@pytest.mark.parametrize("kind", ["global", "window"])
def test_sambay_differential_decode_kernels_on_tpu(kind):
    """The differential output of the paged decode kernels at the benchmark
    configuration's shape (40 query heads of 64 over 10 rows of a KV pair, 128
    lanes, pages of 16, 32 lanes of which 6 hold 300-3000 tokens): queries
    padded with zeros at ``sm_scale`` 1/8 through ``paged_attention`` (the ONE
    global layer) and ``paged_window_attention`` (a ring of 33 pages, window
    512), both softmax terms of every pair against two dense float64 softmaxes
    on the host: the largest error, and the call's time."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    lanes, hq, pairs, d, pt, window, live = 32, 40, 10, 64, 16, 512, 6
    rng = np.random.default_rng(41)
    pos = np.zeros(lanes, np.int32)
    pos[:live] = rng.integers(300, 3000, live)
    active = np.arange(lanes) < live
    key = jax.random.PRNGKey(41)
    q = jax.random.normal(key, (lanes, hq, 1, d), jnp.bfloat16)
    if kind == "global":
        pps = 256
        shape = (1, lanes * pps + 1, pairs, pt, 2 * d)
        tables = 1 + np.arange(lanes * pps).reshape(lanes, pps)
    else:
        ring = att.window_ring_pages(window, pt)
        shape = (8, lanes * ring, pairs, pt, 2 * d)
    k = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), shape, jnp.bfloat16)
    pos_d, act = jnp.asarray(pos), jnp.asarray(active)

    def call(q, k, v, pos, act):
        padded = att.diff_queries(q)
        if kind == "global":
            out = att.paged_attention(
                padded, k, v, jnp.asarray(tables, jnp.int32), pos, pt,
                kernel=True, active=act, layer=0, sm_scale=d ** -0.5)
        else:
            out = att.paged_window_attention(
                padded, k, v, pos, pt, window, kernel=True, active=act, layer=5,
                sm_scale=d ** -0.5)
        return jnp.stack(att.diff_outputs(out))           # (2, S, pairs x 2, 1, 2 D)

    got = np.asarray(jax.jit(call)(q, k, v, pos_d, act), np.float64)[:, :, :, 0]
    kh, vh, qh = (np.asarray(t.astype(jnp.float32)) for t in (k, v, q))
    worst = 0.0
    for lane in range(live):
        p = int(pos[lane])
        if kind == "global":
            pages = tables[lane]
            rows = lambda a: a[0, pages].transpose(1, 0, 2, 3).reshape(  # noqa: E731
                pairs, -1, 2 * d)
            first = 0
        else:
            # the lane's ring in position order: token t lives in page
            # (t // pt) % ring; lay the last window's tokens out by position
            first = max(0, p - window + 1)
            at = [(lane * ring + (t // pt) % ring, t % pt) for t in range(p + 1)]
            rows = lambda a: np.stack(  # noqa: E731
                [a[5, pg, :, off] if t >= first else np.zeros((pairs, 2 * d))
                 for t, (pg, off) in enumerate(at)], axis=1)
        want = _plain_differential(qh[lane, :, 0], rows(kh), rows(vh), first, p)
        worst = max(worst, float(np.max(np.abs(got[:, lane] - want))))
    assert worst < 3e-2, f"differential {kind} decode diverges: {worst}"
    ms = chained_device_time(call, (q, k, v, pos_d, act)) * 1e3
    print(f"\n[sambay_diff_decode] {kind}: {live}/32 lanes, "
          f"{int(pos[:live].sum()) + live} tokens, both terms of 20 pairs: "
          f"largest error against the float64 softmaxes {worst:.4f}, "
          f"{ms:.3f} ms a call", flush=True)


@ON_TPU
def test_sambay_selective_scan_on_tpu():
    """The selective scan at the benchmark configuration's widths (E 5120, N
    16): a prompt bucket of 1024 with 700 real tokens and the one-token step
    over 32 lanes, against the recurrence in float64 on the host (the first
    256 tokens and 64 channels of it: the channels are independent): the
    largest error, and the two calls' times."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    e, n, t_len, real = 5120, 16, 1024, 700
    rng = np.random.default_rng(7)
    dt = np.abs(rng.standard_normal((1, t_len, e))).astype(np.float32) * 0.5
    x, bm, cm = (rng.standard_normal(s).astype(np.float32)
                 for s in ((1, t_len, e), (1, t_len, n), (1, t_len, n)))
    a = -np.exp(rng.standard_normal((n, e))).astype(np.float32)
    d_skip = np.ones((e,), np.float32)
    h0 = jnp.zeros((1, n, e), jnp.float32)
    scan = jax.jit(ssm.selective_scan)
    y, h = scan(h0, dt, x, a, bm, cm, d_skip, np.asarray([real], np.int32))
    cut, rows = 64, 256
    hh = np.zeros((n, cut))
    want = np.zeros((rows, cut))
    for i in range(real):
        hh = (np.exp(dt[0, i, :cut].astype(np.float64) * a[:, :cut]) * hh
              + (dt[0, i, :cut] * x[0, i, :cut])[None].astype(np.float64)
              * bm[0, i][:, None])
        if i < rows:
            want[i] = hh.T @ cm[0, i] + x[0, i, :cut]
    err_y = float(np.max(np.abs(np.asarray(y)[0, :rows, :cut] - want)))
    err_h = float(np.max(np.abs(np.asarray(h)[0, :, :cut] - hh)))
    assert err_y < 1e-2 and err_h < 1e-2, (err_y, err_h)
    t_scan = chained_device_time(
        lambda h, dt, x: ssm.selective_scan(h, dt, x, a, bm, cm, d_skip)[0],
        (h0, jnp.asarray(dt), jnp.asarray(x)))
    lanes = 32
    hs = jnp.zeros((lanes, n, e), jnp.float32)
    t_step = chained_device_time(
        lambda h, dt, x: ssm.selective_step(
            h, dt, x, a, bm[0, :lanes], cm[0, :lanes], d_skip)[1],
        (hs, jnp.asarray(dt[0, :lanes]), jnp.asarray(x[0, :lanes])))
    print(f"\n[sambay_scan] E={e} N={n}: scan of {t_len} tokens ({real} real) "
          f"{t_scan * 1e3:.3f} ms ({t_scan / t_len * 1e6:.2f} us a token), the "
          f"step over {lanes} lanes {t_step * 1e3:.3f} ms; largest error "
          f"against float64 y {err_y:.5f}, state {err_h:.5f}", flush=True)
