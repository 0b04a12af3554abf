"""OLMoE through the program: the ``moe_lm`` family (a dropless top-k expert
layer, QK-norm, an untied head) against the plain reference the benchmark
keeps (``benchmark/families/olmoe.py``), at a small size on the CPU.

  (a) the family's ``apply`` logits against the reference;
  (b) prefill, then decode token by token through the paged cache, logits
      against the reference's full forward at every position, and the
      decode chunk program's greedy tokens and routing stats;
  (c) batch invariance, bit for bit: a row alone, beside three others, and
      in another lane;
  (d) no token dropped when every token routes to the same experts;
  (e) the grouped product (the Pallas kernel through its interpreter, and
      ``jax.lax.ragged_dot``) against a per-token loop, with empty experts
      and groups that cross a tile;
  (f) through ``ContinuousGenerateEngine``: two requests share decode steps
      and answer as they do alone, also under the int8 arena, chunked
      prefill, shared-prefix pages and in-engine speculation;
  (g) a dense model traces the jaxpr it traced before the q/k/v and output
      head helpers replaced the inline copies;
  (h) the grouped kernel's row tile: a row's product is its own whatever
      the tile (bit for bit at 128 / 256 / 512), the tile a call takes is a
      function of its shapes alone (the four expert cells' buckets over the
      router's width; a decode step keeps 128), the visits the kernel plans
      and the rows they multiply at the cells' shapes, and the dispatch
      tally's record of the tile.

Logits are compared, never sampled tokens, in float32 models wherever the
comparison is against the reference: every tolerance is then about the order
of float32 sums, 1e-4 of logits whose spread is about 1, and a router run in
bf16 (1e-3 of a probability), a renormalised gate or a missing QK-norm lands
orders above it. A near-tie between the k-th and the next expert is the one
place two correct float32 programs may choose differently; the weights are
seeded so that no such gap is under 1e-4 (asserted where the reference is
used). The hardware-gated rows at the end are run on the chip by
``tools/tpu_kernel_check.py`` (compile + parity asserted, times printed).
"""

import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tfservingcache_tpu.models.generation as generation
import tfservingcache_tpu.models.transformer_lm as dense_lm
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import build, export_artifact
from tfservingcache_tpu.ops import moe
from tfservingcache_tpu.ops.attention import dispatch_tally
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId
from tfservingcache_tpu.utils.flight_recorder import RECORDER, STEP_FIELDS, FlightRecorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _family():
    """The benchmark's olmoe family file (config mapping, leaves, reference)."""
    spec = importlib.util.spec_from_file_location(
        "bench_family_olmoe", os.path.join(ROOT, "benchmark", "families", "olmoe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAMILY = _family()
# hidden 64, 4 heads of 16, 8 experts of width 32, 2 a token, 2 layers
PUBLISHED = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 16, "intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": False, "vocab_size": 97,
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "max_position_embeddings": 64, "num_hidden_layers": 2,
    "torch_dtype": "float32",
}
MC = FAMILY.program_config(PUBLISHED)
PT = 8


def _tree(seed=0, mc=MC):
    """Seeded weights in the benchmark's layout, every gain random too (a
    gain of one would hide a norm applied to the wrong tensor)."""
    rng = np.random.default_rng(seed)
    stacked = {name: (rng.standard_normal(shape) / np.sqrt(fan_in)).astype(np.float32)
               for name, (shape, fan_in) in FAMILY.leaf_shapes(mc).items()}
    tree = FAMILY.to_tree(mc, stacked)
    gain = lambda a: (1.0 + 0.2 * rng.standard_normal(a.shape)).astype(np.float32)  # noqa: E731
    tree["ln_f"] = gain(tree["ln_f"])
    for lp in tree["layers"]:
        lp["ln1"], lp["ln2"] = gain(lp["ln1"]), gain(lp["ln2"])
        lp["attn"]["q_norm"] = gain(lp["attn"]["q_norm"])
        lp["attn"]["k_norm"] = gain(lp["attn"]["k_norm"])
    return tree


def _no_near_tie(tree, ids, mc=MC):
    """The reference's own router gaps: the k-th and (k+1)-th probability of
    every token of every layer differ by more than 1e-4."""
    attend, gates, add_experts, _ = FAMILY._fns(
        mc["n_heads"], mc["n_kv_heads"], mc["rope_theta"], mc["top_k"], False)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tree["embed"][np.asarray(ids)], jnp.float32)
        for lp in tree["layers"]:
            h = attend(x, lp["attn"], lp["ln1"])
            z, weight = gates(h, lp["ln2"], lp["moe"]["router"])
            p = np.sort(np.asarray(jax.nn.softmax(
                z @ jnp.asarray(lp["moe"]["router"], jnp.float32), -1)), -1)
            assert np.min(p[:, -mc["top_k"]] - p[:, -mc["top_k"] - 1]) > 1e-4
            x = add_experts(h, z, weight, lp["moe"]["w1"], lp["moe"]["w3"],
                            lp["moe"]["w2"])


def test_a_apply_logits_match_the_reference():
    model = build("moe_lm", MC)
    tree = _tree(0)
    ids = np.random.default_rng(11).integers(1, MC["vocab_size"], 23)
    _no_near_tie(tree, ids)
    got = np.asarray(model.apply(tree, {"input_ids": ids[None]})["logits"])[0]
    want = FAMILY.logits_many(MC, tree, [ids.tolist()], last=len(ids))[0]
    assert want.std() > 0.5
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    # what the tolerance is for: a renormalised gate is another model
    other = build("moe_lm", dict(MC, norm_topk_prob=True))
    bad = np.asarray(other.apply(tree, {"input_ids": ids[None]})["logits"])[0]
    assert np.max(np.abs(bad - want)) > 1e-2
    # ... and so is one without the QK-norm or with the head tied
    for drop in ("q_norm", "lm_head"):
        cut = dict(tree)
        if drop == "lm_head":
            cut.pop("lm_head")
        else:
            cut["layers"] = [dict(lp, attn={k: v for k, v in lp["attn"].items()
                                            if k != "q_norm"})
                             for lp in tree["layers"]]
        bad = np.asarray(model.apply(cut, {"input_ids": ids[None]})["logits"])[0]
        assert np.max(np.abs(bad - want)) > 1e-2, drop


def _paged_setup(tree, prompt, lanes=4, lane=0, pages=24):
    """Prefill ``prompt`` and insert it into lane ``lane`` of a fresh paged
    arena -> (cache, tables, pos, first token, last prompt logits)."""
    cfg = build("moe_lm", MC).config
    cfg_key = tuple(sorted(cfg.items()))
    p_pad = 16
    ids = np.zeros((1, p_pad), np.int32)
    ids[0, :len(prompt)] = prompt
    tok, pk, pv, last, _lane = generation._slot_prefill_jit(
        tree, ids, np.asarray([len(prompt)], np.int32), jax.random.PRNGKey(0),
        np.float32(0.0), np.int32(0), cfg_key=cfg_key, family="moe_lm")
    cache = generation.init_paged_cache(cfg, pages, PT)
    pps = MC["max_seq"] // PT
    tables = np.zeros((lanes, pps), np.int32)
    tables[lane, :4] = 1 + 4 * lane + np.arange(4)       # 32 tokens a lane
    k, v, _ = generation._paged_insert_jit(
        cache["k"], cache["v"], None, pk, pv, tables[lane], np.int32(0),
        page_tokens=PT)
    pos = np.zeros((lanes,), np.int32)
    pos[lane] = len(prompt)
    return cfg, {"k": k, "v": v}, tables, pos, int(tok[0]), np.asarray(last)[0]


def test_b_prefill_then_paged_decode_matches_the_reference_at_every_position():
    tree = _tree(2)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, MC["vocab_size"], 11)
    forced = rng.integers(1, MC["vocab_size"], 9)         # teacher-forced tail
    seq = np.concatenate([prompt, forced])
    _no_near_tie(tree, seq)
    want = FAMILY.logits_many(MC, tree, [seq.tolist()], last=len(seq))[0]
    dev = jax.tree_util.tree_map(jnp.asarray, tree)
    cfg, cache, tables, pos, _tok, last = _paged_setup(dev, prompt)
    np.testing.assert_allclose(last, want[len(prompt) - 1], atol=1e-4, rtol=0)
    active = np.asarray([True, False, False, False])
    step = jax.jit(lambda cache, tok, pos: generation._paged_forward_step(
        dev, tok, cache, tables, pos, cfg, "moe_lm", PT, active=active))
    tok = np.zeros((4,), np.int32)
    for j, t in enumerate(forced):
        tok[0] = t
        logits, cache = step(cache, tok, pos)
        np.testing.assert_allclose(np.asarray(logits)[0, 0],
                                   want[len(prompt) + j], atol=1e-4, rtol=0)
        pos[0] += 1
    # the decode chunk program itself: greedy tokens are the argmax chain of
    # the reference, and the routing stats come back with them
    cfg, cache, tables, pos, first, _ = _paged_setup(dev, prompt)
    tok = np.zeros((4,), np.int32)
    tok[0] = first
    *_, toks, stats, _lane, _next = generation._paged_decode_chunk_jit(
        dev, cache["k"], cache["v"], None, tables, tok, pos, active, np.uint32(1),
        np.zeros((4,), np.float32), np.zeros((4,), np.int32),
        cfg_key=tuple(sorted(cfg.items())), family="moe_lm", chunk=4,
        page_tokens=PT, kernel=False)
    chain = [first]
    for _ in range(4):
        ref = FAMILY.logits_many(
            MC, tree, [prompt.tolist() + chain], last=1)[0][0]
        chain.append(int(np.argmax(ref)))
    assert np.asarray(toks)[0].tolist() == chain[1:]
    hit, rows_max, local = np.asarray(stats)
    # one live row, two experts, both held here (the chip holds every expert)
    assert hit == 2.0 and rows_max == 1.0 and local == 2.0


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_c_a_rows_logits_do_not_depend_on_the_rows_beside_it(dtype):
    """The same lane-count program, the same row: alone in lane 0, in lane 0
    beside three live strangers, and in lane 2. Bit-identical logits: the
    condition for sharing a decode step with strangers."""
    mc = dict(MC, dtype=dtype)
    tree = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, dtype) if a.ndim > 1 else jnp.asarray(a), _tree(4))
    cfg = build("moe_lm", mc).config
    rng = np.random.default_rng(5)
    lanes, pps = 4, MC["max_seq"] // PT
    arena = {w: jnp.asarray(rng.standard_normal(
        (cfg["n_layers"], 1 + lanes * pps, cfg["n_kv_heads"], PT, 16)), dtype)
        for w in ("k", "v")}
    tables = 1 + np.arange(lanes * pps, dtype=np.int32).reshape(lanes, pps)
    toks = rng.integers(1, MC["vocab_size"], lanes).astype(np.int32)
    pos = np.asarray([13, 20, 7, 30], np.int32)

    @jax.jit
    def step(tok, tables, pos, active):
        return generation._paged_forward_step(
            tree, tok, arena, tables, pos, cfg, "moe_lm", PT, active=active)[0]

    def row(lane, others_live):
        """lane 0's row (its token, its pages, its position) placed in ``lane``."""
        perm = list(range(lanes))
        perm[0], perm[lane] = perm[lane], perm[0]
        active = np.asarray([others_live] * lanes)
        active[lane] = True
        out = step(toks[perm], tables[perm], pos[perm], active)
        return np.asarray(out)[lane, 0]

    alone = row(0, False)
    assert np.isfinite(alone).all() and alone.std() > 0.1
    np.testing.assert_array_equal(row(0, True), alone)
    np.testing.assert_array_equal(row(2, True), alone)
    np.testing.assert_array_equal(row(3, False), alone)


def test_d_no_token_is_dropped_when_every_token_takes_the_same_experts():
    """A router of zeros gives every expert the same probability, and
    ``jax.lax.top_k`` then gives every token experts 0 and 1: 50 tokens, 100
    rows on two experts, six experts empty. Every token's answer is the sum
    of exactly those two experts at gate 1/8 each: none passed through."""
    t, d, ff, e, k = 50, 64, 32, 8, 2
    m = _experts(jax.random.PRNGKey(6), e, d, ff, jnp.float32)
    m["router"] = jnp.zeros_like(m["router"])
    x = jax.random.normal(jax.random.PRNGKey(7), (t, d))
    y, stats = jax.jit(lambda x: moe.moe_experts(x, m, k))(x)
    assert float(stats["experts_hit"]) == 2 and float(stats["expert_rows_max"]) == t
    want = sum((jax.nn.silu(x @ m["w1"][i]) * (x @ m["w3"][i])) @ m["w2"][i] / e
               for i in (0, 1))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5, rtol=0)
    assert float(jnp.min(jnp.max(jnp.abs(y), axis=-1))) > 1e-3   # no zero row


def _experts(key, e, d, ff, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    return {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) / np.sqrt(d),
        "w1": (jax.random.normal(ks[1], (e, d, ff)) / np.sqrt(d)).astype(dtype),
        "w3": (jax.random.normal(ks[2], (e, d, ff)) / np.sqrt(d)).astype(dtype),
        "w2": (jax.random.normal(ks[3], (e, ff, d)) / np.sqrt(ff)).astype(dtype),
    }


def _per_token_loop(x, m, top_k):
    """Each token against each of its own experts, one at a time."""
    gates, idx, _ = moe.route(x, m["router"], top_k)
    f32 = jnp.float32
    rows = []
    for i in range(x.shape[0]):
        out = jnp.zeros((x.shape[1],), f32)
        for r in range(top_k):
            e = int(idx[i, r])
            h = (jax.nn.silu(jnp.dot(x[i], m["w1"][e], preferred_element_type=f32))
                 * jnp.dot(x[i], m["w3"][e], preferred_element_type=f32))
            out = out + gates[i, r] * jnp.dot(
                h.astype(x.dtype), m["w2"][e], preferred_element_type=f32)
        rows.append(out)
    return jnp.stack(rows)


@pytest.fixture
def interpret_moe():
    moe.MOE_KERNEL_INTERPRET = True
    yield
    moe.MOE_KERNEL_INTERPRET = False


@pytest.mark.parametrize("path", ["ragged_dot", "kernel_interpret"])
def test_e_grouped_product_matches_per_token_loop(path, request):
    """37 tokens x 2 of 8 experts at width 128 / 256: 74 rows in one 128-row
    tile that every hit expert shares (each group crosses into the next
    one's tile), rigged so that experts 6 and 7 get no row at all. Both
    formulations must give what the per-token loop gives, to the rounding of
    the bf16 output (2^-8 of values of size about 0.5)."""
    if path == "kernel_interpret":
        request.getfixturevalue("interpret_moe")
    t, d, ff, e, k = 37, 128, 256, 8, 2
    m = _experts(jax.random.PRNGKey(0), e, d, ff)
    m["router"] = m["router"].at[:, 6:].set(-1e3 * jnp.abs(m["router"][:, 6:]) - 1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (t, d))).astype(jnp.bfloat16)
    before = dict(dispatch_tally())
    y, stats = jax.jit(lambda x: moe.moe_experts(x, m, k))(x)
    assert float(stats["experts_hit"]) <= 6 and float(stats["expert_rows_max"]) >= 74 / 6
    want = _per_token_loop(x, m, k)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(want),
                               atol=8e-3, rtol=0)
    key = ("moe_experts", "kernel", "interpret tm=128") if path == "kernel_interpret" \
        else ("moe_experts", "reference", "backend=cpu")
    assert dispatch_tally().get(key, 0) == before.get(key, 0) + 1


def test_e_kernel_rows_cross_tiles_and_masked_rows_hit_nothing(interpret_moe):
    """300 tokens x 2 = 600 rows over 128-row tiles (groups cross tile
    edges); a third of the rows masked: they are zero, the others are what
    they are without a mask, and fewer rows reach the experts."""
    t, d, ff, e, k = 300, 128, 128, 4, 2
    m = _experts(jax.random.PRNGKey(2), e, d, ff)
    x = jax.random.normal(jax.random.PRNGKey(3), (t, d)).astype(jnp.bfloat16)
    mask = jnp.arange(t) % 3 != 0
    full, s_full = jax.jit(lambda x: moe.moe_experts(x, m, k))(x)
    part, s_part = jax.jit(lambda x: moe.moe_experts(x, m, k, row_mask=mask))(x)
    assert not np.asarray(part)[~np.asarray(mask)].any()
    np.testing.assert_array_equal(np.asarray(part)[np.asarray(mask)],
                                  np.asarray(full)[np.asarray(mask)])
    assert float(s_part["expert_rows_max"]) < float(s_full["expert_rows_max"])
    want = _per_token_loop(x[:40], m, k)
    np.testing.assert_allclose(np.asarray(full[:40], np.float32),
                               np.asarray(want), atol=8e-3, rtol=0)


# -- (h) the row tile ------------------------------------------------------------

TILES = (128, 256, 512)
# the prompt buckets (powers of two) each expert cell's traffic fills
CELL_BUCKETS = {
    "olmoe-1b-7b-0125": (64, 128, 256, 512, 1024, 2048),
    "mistral-small-4-119b-2603": (512, 1024, 2048, 4096, 8192),
    "lfm2-8b-a1b": (64, 128, 256, 512, 1024),
    "mellum2-12b-a2.5b-instruct": (256, 512, 1024, 4096, 8192),
}


def _cell_experts(config):
    """(hidden, expert width, the router's width, experts held, top_k) of an
    expert cell, from ``benchmark/configs/<config>.json`` as it is run."""
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        c = json.load(f)
    held = c.get("num_experts", c.get("n_routed_experts"))
    width = c.get("source_values", {}).get("n_routed_experts", held)
    return (c["hidden_size"], c.get("moe_intermediate_size", c["intermediate_size"]),
            width, held, c["num_experts_per_tok"])


GROUPS = {
    "uniform": [96] * 8,
    "one_heavy_group": [1000, 1, 8, 3, 5, 2, 7, 4],
    "empty_groups": [0, 200, 0, 0, 313, 0, 90, 0],
    "rows_past_the_total": [40, 0, 130, 17, 0, 60, 3, 50],
}


@pytest.mark.parametrize("case", list(GROUPS))
def test_h_a_rows_product_does_not_depend_on_the_tile(case):
    """``moe_grouped_matmul`` (through its interpreter) at row tiles of 128,
    256 and 512: the SwiGLU product and the down product of every row under
    the groups' total are the same bytes at each, and what ``ragged_dot``
    gives to float32 rounding. k is not tiled, so a row's product is computed
    from that row alone whichever rows share its tile."""
    sizes = np.asarray(GROUPS[case], np.int32)
    total = int(sizes.sum())
    m = 1024 if case == "rows_past_the_total" else -(-total // 512) * 512
    d, ff = 128, 256
    w = _experts(jax.random.PRNGKey(5), len(sizes), d, ff, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (m, d), jnp.float32)
    gs = jnp.asarray(sizes)

    def both(tm):
        h = moe.moe_grouped_matmul(x, w["w1"], gs, w["w3"], tm=tm, interpret=True)
        y = moe.moe_grouped_matmul(h, w["w2"], gs, tm=tm, interpret=True)
        return np.asarray(h)[:total], np.asarray(y)[:total]

    got = {tm: both(tm) for tm in TILES}
    for tm in TILES[1:]:
        np.testing.assert_array_equal(got[tm][0], got[TILES[0]][0])
        np.testing.assert_array_equal(got[tm][1], got[TILES[0]][1])
    h = moe.grouped_matmul_reference(x, w["w1"], gs, w["w3"])
    y = moe.grouped_matmul_reference(h, w["w2"], gs)
    np.testing.assert_allclose(got[256][0], np.asarray(h)[:total], atol=1e-4, rtol=0)
    np.testing.assert_allclose(got[256][1], np.asarray(y)[:total], atol=1e-4, rtol=0)


@pytest.mark.parametrize("masked", [False, True], ids=["every_row", "row_mask"])
def test_h_a_prefill_sized_call_is_the_reference_and_itself_at_every_tile(
        interpret_moe, monkeypatch, masked):
    """700 tokens x 2 of 8 experts = 1400 assignments, more than a decode
    step's: the layer through the kernel equals the ``partitioned=True``
    (``ragged_dot``) path to float32 rounding, takes ``PREFILL_TM`` and says
    so in the dispatch tally, and is the same bytes with the tile forced to
    128, 256 and 512."""
    t, d, ff, e, k = 700, 128, 128, 8, 2
    m = _experts(jax.random.PRNGKey(8), e, d, ff, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(9), (t, d), jnp.float32)
    mask = jnp.arange(t) % 5 != 0 if masked else None
    before = dict(dispatch_tally())
    y, stats = moe.moe_experts(x, m, k, row_mask=mask)
    key = ("moe_experts", "kernel", f"interpret tm={moe.PREFILL_TM}")
    assert dispatch_tally().get(key, 0) == before.get(key, 0) + 1
    want, _ = moe.moe_experts(x, m, k, row_mask=mask, partitioned=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4, rtol=0)
    assert float(stats["expert_rows_local"]) == (int(mask.sum()) if masked else t) * k
    for tm in TILES:
        monkeypatch.setattr(moe, "row_tile", lambda a, e, tm=tm: tm)
        again, _ = moe.moe_experts(x, m, k, row_mask=mask)
        np.testing.assert_array_equal(np.asarray(again), np.asarray(y))


@pytest.mark.parametrize("config", list(CELL_BUCKETS))
def test_h_the_tile_is_a_function_of_the_calls_shapes(config):
    """Every prompt bucket of the four expert cells: a call of at most
    ``DECODE_ROWS`` assignments keeps the decode tile; so does one whose
    experts draw at most ``NARROW_ROWS`` rows each on average over the
    ROUTER's width; every larger one takes ``PREFILL_TM`` = 256, the tile at
    the chip's ridge: the largest bucket that stays narrow, cell by cell.
    Nothing but ``tokens x top_k`` and the router's width enters: a chip's
    share (32 of 128 experts held) takes the tile of the uncut layer."""
    _, _, width, held, top_k = _cell_experts(config)
    assert (moe.DECODE_TM, moe.PREFILL_TM, moe.NARROW_ROWS) == (128, 256, 64)
    tiles = {tokens: moe.row_tile(tokens * top_k, width)
             for tokens in CELL_BUCKETS[config]}
    narrow_to = {"olmoe-1b-7b-0125": 512, "mistral-small-4-119b-2603": 2048,
                 "lfm2-8b-a1b": 512, "mellum2-12b-a2.5b-instruct": 512}[config]
    assert tiles == {t: 128 if t <= narrow_to else 256 for t in CELL_BUCKETS[config]}
    if held != width:       # the held count alone would say 256 from 512 tokens on
        assert moe.row_tile(2048 * top_k, width) == 128
        assert moe.row_tile(2048 * top_k, held) == 256


@pytest.mark.parametrize("lanes,top_k", [(1, 8), (32, 4), (32, 8), (128, 8), (256, 4)])
def test_h_a_decode_step_keeps_its_tile(lanes, top_k):
    """No decode program changes: ``a <= 1024`` (32 lanes x 8, a verify pass
    of 128 rows x 8) takes ``DECODE_TM`` as it did, whatever the router's
    width (a test model's 4 experts, the cells' 32 to 128)."""
    assert lanes * top_k <= moe.DECODE_ROWS
    for width in (4, 8, 32, 64, 128):
        assert moe.row_tile(lanes * top_k, width) == moe.DECODE_TM == 128
    assert moe.row_tile(moe.DECODE_ROWS + 1, 8) == moe.PREFILL_TM


@pytest.mark.parametrize("config", list(CELL_BUCKETS))
def test_h_visits_and_the_rows_they_multiply_by_tile(config):
    """What the kernel's grid does at the cells' prefill shapes, counted from
    ``make_group_metadata`` (no clock): the assignments of a bucket drawn over
    the router's width, the held experts' groups kept. A visit multiplies a
    whole tile, so the rows multiplied fall with the tile wherever an expert
    draws fewer rows than the wider one (never by more than a tile an expert
    otherwise), while the visits grow by at most one an expert a halving. At
    the tile the layer takes the rows multiplied are under those at 512, the
    tile it took before, in every bucket; in units of one expert's weight read,
    a visit costing ``max(1, tm / 240)`` (240 rows: the v5e's ridge), 256 is
    never behind 512."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

    _, _, width, held, top_k = _cell_experts(config)
    rng = np.random.default_rng(width + top_k)
    for tokens in CELL_BUCKETS[config]:
        a = tokens * top_k
        if a <= moe.DECODE_ROWS:
            continue
        sizes = rng.multinomial(a, np.full(width, 1.0 / width))[:held].astype(np.int32)
        visits = {}
        for tm in TILES:
            *_, n = make_group_metadata(
                group_sizes=jnp.asarray(sizes), m=-(-a // tm) * tm, tm=tm,
                start_group=jnp.int32(0), num_nonzero_groups=held,
                visit_empty_groups=False)
            visits[tm] = int(n)
            assert held <= visits[tm] <= int(np.sum(-(-sizes // tm) + 1))
        multiplied = {tm: visits[tm] * tm for tm in TILES}
        for narrow, wide in ((128, 256), (256, 512)):
            assert visits[wide] <= visits[narrow] <= 2 * visits[wide] + held
            assert multiplied[narrow] <= multiplied[wide] + held * narrow
            if sizes.max() < wide:
                assert multiplied[narrow] < multiplied[wide], (config, tokens, visits)
        assert multiplied[moe.row_tile(a, width)] < multiplied[512], (config, tokens)
        assert visits[256] * max(1.0, 256 / 240) < visits[512] * 512 / 240


# -- (f) through the engine ------------------------------------------------------

ENGINE_CFG = dict(MC, dtype="float32")     # greedy parity between paths is robust


def _load(tmp_path, name="olmoe", family="moe_lm", config=ENGINE_CFG, seed=0,
          **serving_kw):
    export_artifact(family, str(tmp_path), name=name, version=1, config=config,
                    seed=seed)
    rt = TPUModelRuntime(ServingConfig(platform="cpu", **serving_kw), None)
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


def _ring(mid):
    return RECORDER.snapshot(tail=RECORDER.ring_entries)["models"].get(
        f"{mid.name}@{mid.version}", {"steps": []})["steps"]


@pytest.mark.parametrize("variant", ["plain", "int8_arena", "chunked_prefill",
                                     "shared_prefix", "speculation"])
def test_f_two_requests_share_decode_steps_and_answer_as_alone(tmp_path, variant):
    """Two requests in flight decode in the same steps (``active`` = 2 in the
    ring, where the expert layer's two fields are filled) and each answers
    what it answers alone. The same under what reaches the expert layer
    through ``_ffn_block`` without a line of its own: the int8 arena, chunked
    prefill (the verify step), shared-prefix pages and in-engine speculation
    with an expert target (a dense draft)."""
    knobs = {"int8_arena": dict(arena_dtype="int8"),
             "chunked_prefill": dict(prefill_chunk_tokens=8),
             "shared_prefix": dict(share_prefix_bytes=1 << 20),
             "speculation": dict(spec_draft_model="draft", spec_tokens=2)}.get(variant, {})
    rt, mid = _load(tmp_path, name=f"olmoe_{variant}")
    if variant == "speculation":
        export_artifact("transformer_lm", str(tmp_path), name="draft", version=1,
                        config={"vocab_size": MC["vocab_size"], "d_model": 32,
                                "n_layers": 1, "n_heads": 2, "n_kv_heads": 2,
                                "d_ff": 64, "max_seq": 64, "dtype": "float32"})
        rt.ensure_loaded(Model(identifier=ModelId("draft", 1),
                               path=str(tmp_path / "draft" / "1")))
    rng = np.random.default_rng(8)
    shared = rng.integers(1, MC["vocab_size"], 16)
    ids = np.stack([np.concatenate([shared, rng.integers(1, MC["vocab_size"], 5)])
                    for _ in range(2)]).astype(np.int32)
    eng = ContinuousGenerateEngine(rt, slots=4, chunk_tokens=4, page_tokens=PT,
                                   arena_pages=32, **knobs)
    try:
        both = eng.generate(mid, ids, max_new_tokens=9)
        steps = _ring(mid)
        alone = [eng.generate(mid, ids[r:r + 1], max_new_tokens=9)[0]
                 for r in range(2)]
        rt._slot_states[mid].check_page_conservation()
    finally:
        eng.close()
        rt.close()
    assert both.shape == (2, 9) and (both >= 0).all() and (both < MC["vocab_size"]).all()
    np.testing.assert_array_equal(both[0], alone[0])
    np.testing.assert_array_equal(both[1], alone[1])
    shared_steps = [s for s in steps if s["chunk"] > 0 and s["active"] == 2]
    assert shared_steps, steps
    if variant != "speculation":          # a spec round is the verify step
        for s in shared_steps:
            # 2 rows x 2 experts a token: between 2 and 4 experts a layer
            assert 2.0 <= s["experts_hit"] <= 4.0, s
            assert 1.0 <= s["expert_rows_max"] <= 2.0, s


def test_f_engine_answers_as_the_solo_path_and_a_dense_model_records_zero(tmp_path):
    """The engine's greedy tokens are the solo path's (``runtime.generate``:
    one request, the dense cache), and a dense model's ring entries carry the
    two routing fields as zero."""
    rt, mid = _load(tmp_path)
    dense_rt, dense_mid = _load(
        tmp_path, name="dense", family="transformer_lm",
        config={"vocab_size": 97, "d_model": 32, "n_layers": 1, "n_heads": 2,
                "n_kv_heads": 2, "d_ff": 64, "max_seq": 64, "dtype": "float32"})
    ids = np.random.default_rng(9).integers(1, 97, (1, 12)).astype(np.int32)
    try:
        solo = rt.generate(mid, ids, max_new_tokens=8, seed=1)
        eng = ContinuousGenerateEngine(rt, slots=4, chunk_tokens=4, page_tokens=PT,
                                       arena_pages=32)
        try:
            got = eng.generate(mid, ids, max_new_tokens=8)
        finally:
            eng.close()
        np.testing.assert_array_equal(got, np.asarray(solo))
        eng = ContinuousGenerateEngine(dense_rt, slots=4, chunk_tokens=4,
                                       page_tokens=PT, arena_pages=32)
        try:
            eng.generate(dense_mid, ids, max_new_tokens=8)
        finally:
            eng.close()
        steps = _ring(dense_mid)
        assert any(s["chunk"] > 0 for s in steps)
        assert all(s["experts_hit"] == 0 and s["expert_rows_max"] == 0 for s in steps)
        assert dense_rt._slot_states[dense_mid].moe_stats is None
    finally:
        rt.close()
        dense_rt.close()


def test_f_the_engine_and_the_solo_decoder_ask_the_modeldef_not_the_name():
    assert build("moe_lm", MC).engine_ready and build("transformer_lm").engine_ready
    assert not build("half_plus_two").engine_ready
    with pytest.raises(ValueError, match="engine_ready"):
        generation.generate(build("half_plus_two"), {}, np.ones((1, 3), np.int32))


def test_f_generate_of_an_expert_model_on_a_tpu_chip_group_is_refused_by_name(
        tmp_path, monkeypatch):
    """The grouped kernel is single-chip and the generate programs cannot see
    that they are partitioned: refused with a clear error, not a compile
    failure. (On the CPU a mesh takes ``jax.lax.ragged_dot`` and runs.)"""
    from tfservingcache_tpu.runtime.base import RuntimeError_

    rt, mid = _load(tmp_path)
    try:
        monkeypatch.setattr(rt, "mesh", object())
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError_, match="chip group"):
            rt.generate(mid, np.ones((1, 4), np.int32), max_new_tokens=2, seed=1)
        with pytest.raises(RuntimeError_, match="chip group"):
            rt.slot_decode_state(mid, 4)
    finally:
        monkeypatch.undo()
        rt.close()


# -- the ring's two fields --------------------------------------------------------

def test_ring_routing_fields_sit_at_the_end_and_older_dumps_render(tmp_path, capsys):
    """Appended, never inserted (the 19 older names keep their positions);
    a 19-field tuple from a dump written before them goes through the zip
    fallback and ``tools/engine_dump.py`` prints it without them."""
    assert STEP_FIELDS[19:22] == ("experts_hit", "expert_rows_max",
                                  "expert_rows_local")
    assert STEP_FIELDS[22:23] == ("write_lanes",)
    assert STEP_FIELDS[16:19] == ("prefill_ms", "chunk_ms", "emit_ms")
    fr = FlightRecorder(flight_dir=str(tmp_path))
    old = (time.time(), "continuous", 1.5, 8, 4, 1, 1, 3, 5, 2, 1, 2.0, 1, 1, 0, 0,
           0.25, 1.0, 0.125)
    assert len(old) == 19
    fr._ring("m@1").append(old)
    fr.record("m@1", "continuous", step_ms=2.5, chunk=8, active=4, admitted=0,
              retired=0, chunk_ms=2.0, experts_hit=25.25, expert_rows_max=2.5)
    steps = fr.snapshot()["models"]["m@1"]["steps"]
    assert "experts_hit" not in steps[0] and steps[0]["emit_ms"] == 0.125
    assert list(steps[1]) == list(STEP_FIELDS)
    assert (steps[1]["experts_hit"], steps[1]["expert_rows_max"]) == (25.25, 2.5)
    spec = importlib.util.spec_from_file_location(
        "engine_dump", os.path.join(ROOT, "tools", "engine_dump.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main([fr.dump("slo_breach", dedup_key=("slo", "old"))]) == 0
    out = capsys.readouterr().out
    assert "(prefill=0.25 chunk=1.00 emit=0.12 self=0.12) chunk=  8" in out
    assert "experts=25.2 rows_max=2.5" in out and out.count("experts=") == 1


def test_expert_layer_scopes_split_the_ffn_in_the_decode_chunk_and_in_apply():
    """``route`` and ``experts`` under ``layer/ffn``: what lets
    ``tools/trace_scopes.py`` split a step's ``ffn`` time three ways."""
    import re

    def scoped(lowered):
        locs = ["/" + name + "/" for name in
                re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))]
        return lambda path: any("/" + path + "/" in loc for loc in locs)

    model = build("moe_lm", ENGINE_CFG)
    cfg = model.config
    params = model.init(jax.random.PRNGKey(0))
    cache = generation.init_paged_cache(cfg, 8, 4)
    has = scoped(generation._paged_decode_chunk_jit.lower(
        params, cache["k"], cache["v"], None, np.zeros((2, 16), np.int32),
        np.zeros(2, np.int32), np.zeros(2, np.int32), np.ones(2, bool),
        np.uint32(1), np.zeros(2, np.float32),
        np.zeros(2, np.int32), cfg_key=tuple(sorted(cfg.items())),
        family="moe_lm", chunk=2, page_tokens=4, kernel=False))
    for path in ("layer/ffn/route", "layer/ffn/experts", "layer/attn", "lm_head"):
        assert has(path), path
    has = scoped(jax.jit(model.apply).lower(
        params, {"input_ids": np.zeros((1, 8), np.int32)}))
    for path in ("layer/ffn/route", "layer/ffn/experts", "layer/attn", "lm_head"):
        assert has(path), path


# -- (g) the helpers changed nothing for a dense model ----------------------------

def test_g_dense_model_traces_the_jaxpr_it_traced_before(monkeypatch):
    """``_paged_forward_step`` of a dense model (no ``q_norm``, no
    ``lm_head``) with the helpers, against the same function with the inline
    projections and head it had before them: equation for equation."""
    cfg = build("transformer_lm", {"vocab_size": 97, "d_model": 48, "n_layers": 2,
                                   "n_heads": 4, "n_kv_heads": 2, "d_ff": 96,
                                   "max_seq": 64}).config
    params = jax.eval_shape(build("transformer_lm", cfg).init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: generation.init_paged_cache(cfg, 9, PT))
    tables = jax.ShapeDtypeStruct((4, 8), jnp.int32)
    lane = jax.ShapeDtypeStruct((4,), jnp.int32)

    def trace():
        return str(jax.make_jaxpr(
            lambda p, tok, c, tb, pos: generation._paged_forward_step(
                p, tok, c, tb, pos, cfg, "transformer_lm", PT)
        )(params, lane, cache, tables, lane))

    now = trace()

    def inline_qkv(attn, h, n_heads, n_kv):
        b, s, d = h.shape
        hd = d // n_heads
        q = (h @ attn["wq"]).reshape(b, s, n_heads, hd).transpose(0, 2, 1, 3)
        k = (h @ attn["wk"]).reshape(b, s, n_kv, hd).transpose(0, 2, 1, 3)
        v = (h @ attn["wv"]).reshape(b, s, n_kv, hd).transpose(0, 2, 1, 3)
        return q, k, v

    def inline_head(params, x, dtype, eps=1e-5):
        x = dense_lm._rmsnorm(x, params["ln_f"], eps)
        return (x @ params["embed"].astype(dtype).T).astype(jnp.float32)

    monkeypatch.setattr(generation, "_qkv", inline_qkv)
    monkeypatch.setattr(generation, "_output_logits", inline_head)
    assert trace() == now
    assert "ragged_dot" not in now and "pallas_call" not in now   # no experts in it


# -- hardware-gated rows (tools/tpu_kernel_check.py) --------------------------

ON_TPU = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)",
)
D, FF, E = 2048, 1024, 64          # OLMoE-1B-7B's expert layer


@ON_TPU
@pytest.mark.parametrize("hit,rows", [(25, 1), (64, 1), (25, 4), (64, 4),
                                      (64, 48), (1, 0)])
def test_moe_grouped_matmul_on_tpu(hit, rows):
    """The grouped product alone at OLMoE's widths: ``hit`` of 64 experts
    with ``rows`` rows each (the others with none; row (1, 0): one expert,
    one row, 63 empty), SwiGLU product then down product, kernel against
    ``jax.lax.ragged_dot``: parity asserted, ms and the GB/s of the hit
    experts' weights printed."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    rows = max(rows, 1)
    m = _experts(jax.random.PRNGKey(hit), E, D, FF)
    sizes = np.zeros(E, np.int32)
    sizes[np.random.default_rng(hit).choice(E, hit, replace=False)] = rows
    total = int(sizes.sum())
    tm = moe.row_tile(total, E)
    padded = -(-total // tm) * tm
    x = jax.random.normal(jax.random.PRNGKey(7), (padded, D)).astype(jnp.bfloat16)
    gs = jnp.asarray(sizes)

    def kernel(x, w1, w3, w2, gs):
        h = moe.moe_grouped_matmul(x, w1, gs, w3, tm=tm, out_dtype=x.dtype)
        return moe.moe_grouped_matmul(h, w2, gs, tm=tm)

    def ref(x, w1, w3, w2, gs):
        h = moe.grouped_matmul_reference(x, w1, gs, w3, out_dtype=x.dtype)
        return moe.grouped_matmul_reference(h, w2, gs)

    args = (x, m["w1"], m["w3"], m["w2"], gs)
    got = np.asarray(jax.jit(kernel)(*args))[:total]
    want = np.asarray(jax.jit(ref)(*args))[:total]
    err = float(np.max(np.abs(got - want)))
    assert err < 3e-2, f"grouped kernel diverges: max abs err {err}"
    t_k = chained_device_time(kernel, args)
    t_r = chained_device_time(ref, args)
    gb = hit * 3 * D * FF * 2 / 1e9
    print(f"\n[moe_grouped_matmul] experts_hit={hit} rows_each={rows} rows={total} "
          f"tm={tm}: kernel {t_k*1e3:.3f} ms ({gb/t_k:.0f} GB/s of routed "
          f"weights), ragged_dot {t_r*1e3:.3f} ms, ratio {t_r/t_k:.2f}x, "
          f"max_abs_err {err:.4f}", flush=True)


@ON_TPU
@pytest.mark.parametrize("tokens,live", [(32, 4), (32, 8), (32, 32), (512, 512),
                                         (2048, 2048)])
def test_moe_layer_on_tpu(tokens, live):
    """The whole layer (route, sort, gather, two grouped products, combine)
    at OLMoE's widths, 8 experts a token: a decode step of 32 lanes with
    ``live`` of them active, and prefills of 512 and 2048 tokens. Kernel
    path against the ragged_dot path: parity on the live rows, ms printed,
    and the routing part alone."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    m = _experts(jax.random.PRNGKey(tokens + live), E, D, FF)
    x = jax.random.normal(jax.random.PRNGKey(11), (tokens, D)).astype(jnp.bfloat16)
    mask = jnp.arange(tokens) < live

    def layer(partitioned):
        def f(x, router, w1, w3, w2, mask):
            y, st = moe.moe_experts(
                x, {"router": router, "w1": w1, "w3": w3, "w2": w2}, 8,
                row_mask=mask, partitioned=partitioned)
            return y, st["experts_hit"]
        return f

    def routing(x, router, mask):
        gates, idx, _ = moe.route(x, router, 8)
        flat = jnp.where(mask[:, None], idx, E).reshape(-1)
        order = jnp.argsort(flat, stable=True)
        return gates[0, 0] + jnp.argsort(order)[0] + order[0]

    args = (x, m["router"], m["w1"], m["w3"], m["w2"], mask)
    (got, hit), (want, _) = jax.jit(layer(False))(*args), jax.jit(layer(True))(*args)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))
    assert err < 5e-2, f"expert layer diverges: max abs err {err}"
    assert not np.asarray(got)[live:].any()
    t_k = chained_device_time(layer(False), args)
    t_r = chained_device_time(layer(True), args)
    t_route = chained_device_time(routing, (x, m["router"], mask))
    gb = float(hit) * 3 * D * FF * 2 / 1e9
    print(f"\n[moe_layer] tokens={tokens} live={live} experts_hit={float(hit):.0f}: "
          f"kernel path {t_k*1e3:.3f} ms ({gb/t_k:.0f} GB/s of routed weights), "
          f"ragged_dot path {t_r*1e3:.3f} ms, ratio {t_r/t_k:.2f}x, route+sort "
          f"alone {t_route*1e3:.3f} ms, max_abs_err {err:.4f}", flush=True)


@ON_TPU
@pytest.mark.parametrize("config", list(CELL_BUCKETS))
def test_moe_prefill_tile_on_tpu(config, monkeypatch):
    """``moe_experts`` as a prefill at an expert cell's widths (read from its
    ``benchmark/configs`` file: hidden, expert width, the router's width, the
    experts held, top_k) at each prompt bucket whose call is larger than a
    decode step, with the row tile forced to 128, 256 and 512: ms a call and
    the GB/s of the hit experts' weights under a uniform router and under one
    whose selection bias sends EVERY token to the first held expert (one
    group of ``tokens`` rows beside the uniform rest); parity with the
    ``ragged_dot`` path asserted at every tile, and whether the tiles agree
    bit for bit printed. The row the layer itself takes is marked ``*``."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    d, ff, width, held, top_k = _cell_experts(config)
    share = (0, held) if held != width else None
    m = _experts(jax.random.PRNGKey(width), held, d, ff)
    router = jax.random.normal(jax.random.PRNGKey(3), (d, width), jnp.float32) / np.sqrt(d)
    biases = {"uniform": jnp.zeros((width,), jnp.float32),
              "one_heavy": jnp.zeros((width,), jnp.float32).at[0].set(10.0)}

    def layer(partitioned):
        def f(x, router, bias, w1, w3, w2):
            y, st = moe.moe_experts(
                x, {"router": router, "bias": bias, "w1": w1, "w3": w3, "w2": w2},
                top_k, held=share, partitioned=partitioned)
            return y, st["experts_hit"], st["expert_rows_max"]
        return f

    def routing(x, router, bias):
        gates, idx, _ = moe.route(x, router, top_k, bias=bias)
        order = jnp.argsort(idx.reshape(-1), stable=True)
        return gates[0, 0] + jnp.argsort(order)[0] + order[0]

    taken = moe.row_tile
    for tokens in CELL_BUCKETS[config]:
        a = tokens * top_k
        if a <= moe.DECODE_ROWS:
            continue
        x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, d)).astype(jnp.bfloat16)
        weights = (m["w1"], m["w3"], m["w2"])
        want = {name: np.asarray(jax.jit(layer(True))(x, router, b, *weights)[0],
                                 np.float32) for name, b in biases.items()}
        t_route = chained_device_time(routing, (x, router, biases["uniform"]))
        got = {}
        for tm in TILES:
            monkeypatch.setattr(moe, "row_tile", lambda a, e, tm=tm: tm)
            for name, b in biases.items():
                args = (x, router, b, *weights)
                y, hit, most = jax.jit(layer(False))(*args)
                y = np.asarray(y, np.float32)
                err = float(np.max(np.abs(y - want[name])))
                assert err < 5e-2, f"{config} {tokens} tm={tm} {name}: max abs err {err}"
                same = got.setdefault(name, y) is y or bool(np.array_equal(got[name], y))
                t_k = chained_device_time(layer(False), args, iters=8)
                gb = float(hit) * 3 * d * ff * 2 / 1e9
                print(f"\n[moe_prefill_tile] {config} tokens={tokens} a={a} "
                      f"a/E={a / width:.0f} {name} tm={tm}"
                      f"{'*' if tm == taken(a, width) else ''}: {t_k*1e3:.3f} ms a call "
                      f"({gb/t_k:.0f} GB/s of {float(hit):.0f} hit experts' weights, "
                      f"most rows {float(most):.0f}), route+sort alone "
                      f"{t_route*1e3:.3f} ms, max_abs_err {err:.4f}, "
                      f"bit_equal_to_tm128={same}", flush=True)
        monkeypatch.setattr(moe, "row_tile", taken)
