"""A decoder whose layers differ in their QUERY side (window layers of 9 query
heads beside global layers of 6 over the same 3 KV heads: GQA groups of 3 and
2 in the toy, 9 and 6 at Laguna-S-2.1's widths), an
output gate of one value a HEAD, a rotary a KIND (plain at a theta of their
own over the whole head in the window layers, YaRN over HALF the head in the
global ones), one dense layer, then a chip's share of the routed experts
(sigmoid scores, the top 3 renormalised and times 2.5) and a shared expert,
through the program's ``moe_lm`` against the plain reference the benchmark
keeps (``benchmark/families/laguna.py``), at a small size on the CPU.

  (a) ``:predict``'s forward (``apply``) against the reference;
  (b) prefill + decode through BOTH arenas (page boundaries, a ring gone
      round more than twice, positions past the toy's YaRN original of 64)
      against the reference's full forward, logits at every position;
  (c) the same model computed in bf16 FAILS the tolerance, so a lower
      precision than stated cannot hide inside it;
  (d) the gate a head, the half-turned rotary and the theta a kind each alone:
      the program with that one key wrong lands hundredths away;
  (e) the paged and the window decode kernels in interpret mode at GQA groups
      6 and 9 against the gather + einsum path;
  (f) what the ModelDef declares: one row shape in every layer (the arenas do
      not follow the query heads), the largest head count where one number is
      asked; a config without the new keys (OLMoE's, Mellum2's toys) lowers to
      the text it lowers to with the keys spelt out at their defaults (the
      text the parent commit lowers it to: compared once by hand, PR 53), with
      no new scope, and asks ``rope_of`` what it asked;
  (g) the family's file refuses a program that knows no head count a layer.

THE TOLERANCE. Every comparison with the reference is of float32 models at
logits level, ``atol`` 1e-4 of logits whose spread is about 1: what is left is
the order of float32 sums (read: 2e-6 to 1.5e-5). bf16 lands at 3e-2 or more
(c); each fault of (d) hundredths to tenths away.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tfservingcache_tpu.models.generation as generation
import tfservingcache_tpu.models.transformer_lm as lm
import tfservingcache_tpu.ops.attention as att
from tfservingcache_tpu.models import registry
from tfservingcache_tpu.models.registry import build, static_config, window_layers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARK = os.path.join(ROOT, "benchmark")


def _family(name):
    spec = importlib.util.spec_from_file_location(
        f"laguna_test_family_{name}",
        os.path.join(BENCHMARK, "families", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _merged(base: dict, over: dict) -> dict:
    return {**base, **{k: _merged(base[k], v) if isinstance(v, dict)
                       and isinstance(base.get(k), dict) else v
                       for k, v in over.items()}}


def _rehearsal(config_name):
    """A configuration's file at its ``rehearsal`` widths -> (family, file)."""
    with open(os.path.join(BENCHMARK, "configs", f"{config_name}.json")) as f:
        config = json.load(f)
    config = _merged(config, config["rehearsal"])
    return _family(config["family"]), config


FAMILY, PUBLISHED = _rehearsal("laguna-s-2.1")
# the configuration's own rehearsal toy (hidden 96; heads 6 / 9 over 3 KV
# heads of 32; window 32; 16 routed experts of which 4 are held, 3 a token;
# layer 0 dense; 5 layers F s s s F) in float32
MC = FAMILY.program_config(dict(PUBLISHED, torch_dtype="float32",
                                max_position_embeddings=512))
WINDOW, PT, LANES = MC["sliding_window"], 16, 4
PPS = MC["max_seq"] // PT
VOCAB = MC["vocab_size"]


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
    cfg = dict(static_config(model))
    cache = generation.init_paged_cache(cfg, LANES * PPS + 1, PT, lanes=LANES)
    tables = np.zeros((LANES, PPS), np.int32)
    tables[lane] = np.arange(1 + lane * PPS, 1 + (lane + 1) * PPS)
    k, v, wk, wv = generation._window_paged_insert_jit(
        cache["k"], cache["v"], cache["wk"], cache["wv"], pk, pv, tables[lane],
        np.int32(lane), np.int32(prompt_len), page_tokens=PT,
        window_layers=tuple(window_layers(model.layer_state)),
        ring_pages=cache["wk"].shape[1] // LANES)
    return {"k": k, "v": v, "wk": wk, "wv": wv}, tables


def _decode(model, tree, cache, tables, lane, tok, pos, steps, kernel=False):
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


# -- (a) :predict's forward ------------------------------------------------------

def test_a_predict_forward_against_the_reference():
    """150 positions: four windows and a half, past YaRN's original 64."""
    model, tree = build("moe_lm", MC), _tree()
    ids = np.random.default_rng(1).integers(1, VOCAB, (1, 150))
    got = model.apply(tree, {"input_ids": jnp.asarray(ids)})["logits"][0]
    want = FAMILY.logits_many(MC, tree, [list(ids[0])], ids.shape[1])[0]
    assert want.std() > 0.5
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)


# -- (b) both arenas against the reference ----------------------------------------

@pytest.mark.parametrize("prompt_len", [20, WINDOW, 5 * WINDOW],
                         ids=["shorter", "equal", "five_windows"])
def test_b_prefill_and_decode_through_both_arenas(prompt_len):
    """40 to 100 decode steps: page boundaries, from the short prompt the ring
    (48 tokens) filled and turned more than twice, positions to 220 where
    YaRN's original is 64."""
    model, tree = build("moe_lm", MC), _tree()
    prompt = np.random.default_rng(prompt_len).integers(1, VOCAB, prompt_len)
    tok, pk, pv, last, _lane = _prefill(model, tree, prompt)
    # one row shape in every layer, whatever its query heads
    assert pk.shape[0] == MC["n_layers"] and pk.shape[2] == MC["n_kv_heads"]
    ref = FAMILY.logits_many(MC, tree, [list(prompt)], 1)[0][0]
    np.testing.assert_allclose(np.asarray(last[0]), ref, atol=1e-4)
    cache, tables = _admit(model, pk, pv, prompt_len, lane=2)
    steps = {20: 100, WINDOW: 40, 5 * WINDOW: 60}[prompt_len]
    got, fed = _decode(model, tree, cache, tables, 2, int(tok[0]), prompt_len,
                       steps)
    want = FAMILY.logits_many(MC, tree, [list(prompt) + fed], steps)[0]
    np.testing.assert_allclose(np.stack(got), want, atol=1e-4)


# -- (c) a lower precision fails the tolerance -------------------------------------

def test_c_bf16_in_place_of_float32_fails_the_tolerance():
    tree = _tree(3)
    ids = np.random.default_rng(4).integers(1, VOCAB, (1, 4 * WINDOW))
    want = FAMILY.logits_many(MC, tree, [list(ids[0])], ids.shape[1])[0]
    low = build("moe_lm", dict(MC, dtype="bfloat16"))
    got = low.apply(tree, {"input_ids": jnp.asarray(ids)})["logits"][0]
    assert float(np.max(np.abs(np.asarray(got) - want))) > 1e-2


# -- (d) each new mechanism alone ----------------------------------------------------

# one config key wrong at a time
CONFIG_FAULTS = {
    "whole_head_turned_in_a_global_layer":
        lambda mc: mc["rope_full"].update(partial=1.0),
    "one_theta_for_both_kinds": lambda mc: mc.pop("rope_theta_window"),
    "routed_scale_left_out": lambda mc: mc.update(route_scale=1.0),
    "softmax_scores": lambda mc: mc.update(route_score="softmax"),
    "another_share_of_the_experts": lambda mc: mc.update(expert_first=4),
}


@pytest.mark.parametrize("fault", sorted(CONFIG_FAULTS))
def test_d_each_config_fault_lands_orders_above_the_tolerance(fault):
    tree = _tree(8)
    ids = np.random.default_rng(9).integers(1, VOCAB, (1, 4 * WINDOW))
    want = FAMILY.logits_many(MC, tree, [list(ids[0])], ids.shape[1])[0]
    mc = json.loads(json.dumps(MC))
    CONFIG_FAULTS[fault](mc)
    got = build("moe_lm", mc).apply(
        tree, {"input_ids": jnp.asarray(ids)})["logits"][0]
    assert float(np.max(np.abs(np.asarray(got) - want))) > 1e-2, fault


@pytest.mark.parametrize("fault", ["gate_left_out", "gate_a_column_of_ones",
                                   "shared_expert_left_out",
                                   "dense_layer_left_out"])
def test_d_each_leaf_fault_lands_orders_above_the_tolerance(fault):
    tree = _tree(8)
    ids = np.random.default_rng(9).integers(1, VOCAB, (1, 4 * WINDOW))
    want = FAMILY.logits_many(MC, tree, [list(ids[0])], ids.shape[1])[0]
    bad = jax.tree_util.tree_map(lambda a: a, tree)        # a copy of the dicts
    if fault == "gate_left_out":
        for lp in bad["layers"]:
            del lp["attn"]["w_gate"]
    elif fault == "gate_a_column_of_ones":
        # sigmoid(0) = 0.5 in every head: a gate that does not follow its head
        for lp in bad["layers"]:
            lp["attn"]["w_gate"] = np.zeros_like(lp["attn"]["w_gate"])
    elif fault == "shared_expert_left_out":
        for lp in bad["layers"]:
            lp.get("moe", {}).pop("shared", None)
    else:
        bad["layers"][0]["mlp"] = jax.tree_util.tree_map(
            np.zeros_like, bad["layers"][0]["mlp"])
    got = build("moe_lm", MC).apply(
        bad, {"input_ids": jnp.asarray(ids)})["logits"][0]
    assert float(np.max(np.abs(np.asarray(got) - want))) > 1e-2, fault


def test_d_the_gate_is_one_value_a_head_and_the_rotary_turns_half():
    """The two new shapes by hand, away from any model: ``head_gate`` gives one
    value a head laid out as the heads' outputs are, and a partial rotary
    passes the last columns bit for bit while the turned ones are the plain
    rotary's of a head that wide."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((2, 5, 12)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((12, 3)), jnp.float32)
    gate = lm.head_gate({"w_gate": w}, a, 3)
    assert gate.shape == (2, 3, 5, 1)
    np.testing.assert_allclose(
        np.asarray(gate[..., 0]),
        np.asarray(jax.nn.sigmoid(a @ w)).transpose(0, 2, 1), rtol=1e-6)
    column = lm.head_gate({"w_gate": jnp.tile(w, (1, 4))}, a, 3)
    assert column.shape == (2, 3, 5, 4)                # a value a head column

    x = jnp.asarray(rng.standard_normal((1, 2, 7, 32)), jnp.float32)
    pos = jnp.arange(7) + 100
    freqs = np.asarray(500000.0 ** (-np.arange(0, 16, 2) / 16), np.float32)
    half = lm._rope(x, pos, 1.0, (freqs, 1.5, 16))
    np.testing.assert_array_equal(np.asarray(half[..., 16:]), np.asarray(x[..., 16:]))
    np.testing.assert_allclose(
        np.asarray(half[..., :16]),
        np.asarray(lm._rope(x[..., :16], pos, 1.0, (freqs, 1.5, 0))), rtol=1e-6)
    per_example = generation._rope_per_example(x, pos[None], 1.0, (freqs, 1.5, 16))
    np.testing.assert_allclose(np.asarray(per_example), np.asarray(half), rtol=1e-6)
    # the kinds' rotaries, as the reference reckons them
    for kind, window in ((FAMILY.FULL, 0), (FAMILY.SLIDING, WINDOW)):
        freqs, factor, turned = lm.rope_of(MC, window)
        want, want_factor, want_turned = FAMILY.rope_frequencies(MC, kind)
        np.testing.assert_allclose(freqs, want, rtol=1e-6)
        assert factor == want_factor
        assert (turned or MC["head_dim"]) == want_turned
    assert lm.rope_of(MC, 0)[2] == 16 and lm.rope_of(MC, WINDOW)[2] == 0


# -- (e) the decode kernels at groups 6 and 9 -----------------------------------------

def _arena(seed, heads, lanes=3, pps=8, hkv=2, d=128, layers=2):
    key = jax.random.PRNGKey(seed)
    n = lanes * pps + 1
    k = jax.random.normal(key, (layers, n, hkv, PT, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 1), k.shape, jnp.float32)
    q = jax.random.normal(jax.random.fold_in(key, 2), (lanes, heads, 1, d))
    tables = jnp.asarray(np.arange(1, n).reshape(lanes, pps), jnp.int32)
    return q, k, v, tables


@pytest.mark.parametrize("group", [6, 9])
def test_e_paged_decode_kernel_at_the_group(group):
    q, k, v, tables = _arena(group, heads=2 * group)
    pos = jnp.asarray([100, 37, 127], jnp.int32)
    active = jnp.asarray([True, True, False])
    want = att.paged_decode_attention(q, k, v, tables, pos, PT, 1)
    got = att.paged_decode_attention_kernel(
        q, k, v, tables, pos, active=active, page_tokens=PT, interpret=True,
        layer=1)
    np.testing.assert_allclose(np.asarray(got[:2]), np.asarray(want[:2]),
                               atol=2e-5)
    assert not np.asarray(got[2]).any()                   # an inactive lane: zeros


@pytest.mark.parametrize("group", [6, 9])
def test_e_window_decode_kernel_at_the_group(group):
    q, k, v, tables = _arena(10 + group, heads=2 * group)
    pos = jnp.asarray([100, 37, 127], jnp.int32)
    first = jnp.asarray([69, 6, 96], jnp.int32)
    want = att.paged_decode_attention(q, k, v, tables, pos, PT, 1, first=first)
    got = att.paged_window_decode_attention_kernel(
        q, k, v, tables, pos, first, page_tokens=PT, interpret=True, layer=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("kernel", [False, True], ids=["reference", "kernel"])
def test_e_decode_steps_through_the_kernels_in_interpret_mode(monkeypatch, kernel):
    """The toy's heads are 32 wide, which the kernels' gate refuses off the
    interpreter; in interpret mode both kinds' decode calls run the kernel
    body at groups 2 and 3 of the toy and answer what the reference answers."""
    monkeypatch.setattr(att, "PAGED_KERNEL_INTERPRET", kernel)
    model, tree = build("moe_lm", MC), _tree(5)
    plen, steps = 3 * WINDOW + 5, 24
    prompt = np.random.default_rng(6).integers(1, VOCAB, plen)
    tok, pk, pv, _last, _ = _prefill(model, tree, prompt)
    cache, tables = _admit(model, pk, pv, plen, lane=3)
    got, fed = _decode(model, tree, cache, tables, 3, int(tok[0]), plen, steps,
                       kernel=kernel)
    want = FAMILY.logits_many(MC, tree, [list(prompt) + fed], steps)[0]
    np.testing.assert_allclose(np.stack(got), want, atol=1e-4)


# -- (f) what is declared; a config without the new keys ------------------------------

def test_f_one_row_shape_whatever_the_query_heads():
    model = build("moe_lm", MC)
    assert [registry.query_heads(MC, i) for i in range(5)] == [6, 9, 9, 9, 6]
    assert registry.query_heads(MC) == 9
    assert window_layers(model.layer_state) == (1, 2, 3)
    assert {(s.heads, s.width) for s in model.layer_state} == {(3, 32)}
    key = dict(static_config(model))
    cache = jax.eval_shape(lambda: generation.init_paged_cache(
        key, 17, PT, lanes=LANES))
    assert cache["k"].shape == (2, 17, 3, PT, 32)              # 2 global layers
    assert cache["wk"].shape == (3, LANES * 3, 3, PT, 32)      # 3 rings of 3 pages
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert params["layers"][0]["attn"]["wq"].shape == (96, 6 * 32)
    assert params["layers"][1]["attn"]["wq"].shape == (96, 9 * 32)
    assert params["layers"][1]["attn"]["w_gate"].shape == (96, 9)
    assert "mlp" in params["layers"][0] and "moe" not in params["layers"][0]
    assert params["layers"][1]["moe"]["w1"].shape == (4, 96, 32)   # 4 of 16 held
    assert params["layers"][1]["moe"]["router"].shape == (96, 16)
    assert set(params["layers"][1]["moe"]["shared"]) == {"w1", "w2", "w3"}
    # the weights the benchmark makes are the shapes the program's init makes
    tree = jax.tree_util.tree_map(lambda a: a.shape, _tree())
    assert tree == jax.tree_util.tree_map(lambda a: a.shape, params)


def _programs(mc):
    """The lowered text of the slot prefill and of the decode chunk."""
    model = build("moe_lm", mc)
    cfg_key = static_config(model)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    generation._slot_prefill_jit.clear_cache()
    generation._paged_decode_chunk_jit.clear_cache()
    prefill = generation._slot_prefill_jit.lower(
        params, np.zeros((1, 32), np.int32), np.asarray([20], np.int32),
        jax.random.PRNGKey(0), np.float32(0), np.int32(0), cfg_key=cfg_key,
        family="moe_lm")
    lanes, pages, pt = 2, 8, 4
    arena = generation.init_paged_cache(dict(cfg_key), pages, pt, lanes=lanes)
    ring = ((arena["wk"], arena["wv"]),) if "wk" in arena else ()
    chunk = generation._paged_decode_chunk_jit.lower(
        params, arena["k"], arena.get("v"), None,
        np.zeros((lanes, 4), np.int32), np.zeros(lanes, np.int32),
        np.zeros(lanes, np.int32), np.ones(lanes, bool), np.uint32(1),
        np.zeros(lanes, np.float32), np.zeros(lanes, np.int32), None, *ring,
        cfg_key=cfg_key, family="moe_lm", chunk=2, page_tokens=pt, kernel=False)
    predict = jax.jit(model.apply).lower(
        params, {"input_ids": np.zeros((1, 16), np.int32)})
    programs = (prefill, chunk, predict)
    return ([p.as_text() for p in programs],
            [p.as_text(debug_info=True) for p in programs])


@pytest.mark.parametrize("config_name", ["olmoe-1b-7b-0125",
                                         "mellum2-12b-a2.5b-instruct"])
def test_f_a_config_without_the_new_keys_lowers_to_what_it_lowers_to(config_name):
    """The programs of a config that states none of PR 53's keys are the ones
    the same config lowers to with every new key spelt out at its default (the
    heads a layer all ``n_heads``, no dense layer), operation for operation
    and scope for scope; they carry neither new scope; and ``rope_of`` answers
    what it answered (None: the plain frequencies computed in the trace)."""
    family, config = _rehearsal(config_name)
    mc = family.program_config(config)
    assert not {"n_heads_per_layer", "rope_theta_window", "attn_gate",
                "mlp_only_layers", "shared_width", "n_experts_held"} & set(mc)
    plain, scoped = _programs(mc)
    spelt, _ = _programs(dict(
        mc, n_heads_per_layer=[mc["n_heads"]] * mc["n_layers"],
        mlp_only_layers=[]))
    assert plain == spelt
    for text in scoped:
        assert "layer/attn" in text
        assert "/gate" not in text and "ffn/dense" not in text
    window = mc.get("sliding_window", 0)
    assert lm.rope_of(mc, window) == (None, 1.0, 0)
    if not mc.get("rope_full"):
        assert lm.rope_of(mc, 0) == (None, 1.0, 0)
    else:
        freqs, factor, turned = lm.rope_of(mc, 0)
        assert len(freqs) == mc["head_dim"] // 2 and turned == 0 and factor > 1
    generation._slot_prefill_jit.clear_cache()
    generation._paged_decode_chunk_jit.clear_cache()


# -- (g) a program that knows no head count a layer ------------------------------------

def test_g_the_family_refuses_a_program_without_heads_a_layer(monkeypatch):
    monkeypatch.delattr(registry, "query_heads")
    with pytest.raises(ValueError, match="knows no head count a layer"):
        FAMILY.program_config(PUBLISHED)


# -- hardware-gated rows (tools/tpu_kernel_check.py -k "laguna and on_tpu") ------------
#
# WHAT THEY LED TO (my chip run, PR 53; the rows are in PERF.md section 6 and
# beside this file's entry in tools/tpu_kernel_check.py): Mosaic takes both
# decode kernels and both flash kernels at groups 6 and 9 as they stand, every
# row within a fifth of group 8's share of its roofline (global decode 70.5-73.7
# % of the HBM peak at 4-16 live lanes in all three groups), so
# ``_paged_decode_call`` pads nothing. The last row (after review) holds the
# ROUTED half of the expert layer at the cell's shapes against a dense float32
# reference: errors 0.0023-0.0056 under its limit of 0.02 at six shapes.

ON_TPU = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)")
V5E_HBM, V5E_BF16 = 819e9, 197e12
HKV, HEAD, PAGE, LAGUNA_WINDOW = 8, 128, 16, 512


def _live_lanes(live, lanes=32, lo=2048, hi=17000):
    rng = np.random.default_rng(live)
    pos = np.zeros(lanes, np.int32)
    pos[:live] = rng.integers(lo, hi, live)
    return pos, np.arange(lanes) < live


@ON_TPU
@pytest.mark.parametrize("live", [1, 4, 16])
@pytest.mark.parametrize("hq", [48, 64, 72], ids=["group6", "group8", "group9"])
def test_laguna_global_decode_kernel_on_tpu(hq, live):
    """A GLOBAL layer's decode call at Laguna-S-2.1's shape (48 query heads
    over 8 KV heads of 128: group 6; 16-token pages; 32 lanes of which ``live``
    hold 2k-17k tokens) against the gather + einsum reference, its time
    against the least the chip could take for the live tokens' rows (4 KiB a
    token); beside it the same call at group 8 (64 heads: the accepted shape,
    whose share of the roofline the issue holds groups 6 and 9 to within a
    fifth of) and at group 9 (72 heads)."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    lanes, pps = 32, 1088
    pos, active = _live_lanes(live)
    key = jax.random.PRNGKey(live)
    n_pages = lanes * pps // 4 + 1                      # 1.1 GB a side
    gk = jax.random.normal(key, (1, n_pages, HKV, PAGE, HEAD), jnp.bfloat16)
    gv = jax.random.normal(jax.random.fold_in(key, 1), gk.shape, jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 2), (lanes, hq, 1, HEAD),
                          jnp.bfloat16)
    # every live lane its own pages (at most 16 live: 16 x 1088 < n_pages)
    tables = np.zeros((lanes, pps), np.int32)
    tables[:live] = 1 + np.arange(live * pps).reshape(live, pps) % (n_pages - 1)
    tables, pos_d, act = jnp.asarray(tables), jnp.asarray(pos), jnp.asarray(active)

    def call(kernel):
        return lambda q, gk, gv, tables, pos, act: att.paged_attention(
            q, gk, gv, tables, pos, PAGE, kernel=kernel, active=act)

    out = jax.jit(call(True))(q, gk, gv, tables, pos_d, act)
    ref = jax.jit(call(False))(q, gk, gv, tables, pos_d, act)
    err = float(jnp.max(jnp.abs(out - ref)[act]))
    assert err < 3e-2, f"decode kernel diverges at group {hq // HKV}: {err}"
    t_kern = chained_device_time(call(True), (q, gk, gv, tables, pos_d, act))
    tokens = int((pos[:live] + 1).sum())
    need = tokens * 2 * HKV * HEAD * 2 + live * hq * HEAD * 6
    print(f"\n[laguna_global_decode] group={hq // HKV} live={live}/32 "
          f"tokens={tokens}: kernel {t_kern * 1e3:.3f} ms "
          f"({need / t_kern / 1e9:.0f} GB/s, "
          f"{100 * need / V5E_HBM / t_kern:.1f} % of the HBM roofline), "
          f"max_abs_err {err:.4f}", flush=True)


@ON_TPU
@pytest.mark.parametrize("live", [1, 4, 16, 32])
@pytest.mark.parametrize("hq", [48, 64, 72], ids=["group6", "group8", "group9"])
def test_laguna_window_decode_kernel_on_tpu(hq, live):
    """A WINDOW layer's decode call at Laguna-S-2.1's shape (72 query heads
    over 8 KV heads of 128: group 9, the first group that spills over one
    8-row sublane tile without filling a second; window 512, a ring of 33
    pages a lane) against the reference and the pages it must read; beside it
    groups 6 and 8."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    lanes = 32
    ring = att.window_ring_pages(LAGUNA_WINDOW, PAGE)
    pos, active = _live_lanes(live)
    key = jax.random.PRNGKey(100 + live)
    shape = (2, lanes * ring, HKV, PAGE, HEAD)
    wk = jax.random.normal(key, shape, jnp.bfloat16)
    wv = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 2), (lanes, hq, 1, HEAD),
                          jnp.bfloat16)
    pos_d, act = jnp.asarray(pos), jnp.asarray(active)

    def call(kernel):
        return lambda q, wk, wv, pos, act: att.paged_window_attention(
            q, wk, wv, pos, PAGE, LAGUNA_WINDOW, kernel=kernel, active=act,
            layer=1)

    out = jax.jit(call(True))(q, wk, wv, pos_d, act)
    ref = jax.jit(call(False))(q, wk, wv, pos_d, act)
    assert not np.asarray(out)[~active].any()
    err = float(jnp.max(jnp.abs(out - ref)[act]))
    assert err < 3e-2, f"window decode kernel diverges at group {hq // HKV}: {err}"
    t_kern = chained_device_time(call(True), (q, wk, wv, pos_d, act))
    t = pos[:live] + 1
    kept = np.minimum(t, LAGUNA_WINDOW)
    pages = (t - 1) // PAGE - (t - kept) // PAGE + 1
    need = int(pages.sum()) * PAGE * 2 * HKV * HEAD * 2 + live * hq * HEAD * 6
    print(f"\n[laguna_window_decode] group={hq // HKV} live={live}/32 pages "
          f"read {int(pages.sum())}: kernel {t_kern * 1e3:.3f} ms "
          f"({need / t_kern / 1e9:.0f} GB/s, "
          f"{100 * need / V5E_HBM / t_kern:.1f} % of the HBM roofline), "
          f"max_abs_err {err:.4f}", flush=True)


@ON_TPU
@pytest.mark.parametrize("s_len", [2048, 8192, 16384])
@pytest.mark.parametrize("hq", [48, 72])
def test_laguna_flash_kernels_on_tpu(hq, s_len):
    """A fresh prefill's two attention calls at Laguna-S-2.1's shapes: the
    causal flash kernel (a global layer's, run here at 72 heads too) and the
    windowed one (window 512) against ``attention_reference`` a block of
    queries at a time, their times against their own FLOPs."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    key = jax.random.PRNGKey(s_len + hq)
    q = jax.random.normal(key, (1, hq, s_len, HEAD), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, HKV, s_len, HEAD),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), k.shape, jnp.bfloat16)
    block = 256
    rows = []
    for name, window, fn in (
            ("flash", 0, lambda q, k, v: att.flash_attention(q, k, v, causal=True)),
            ("flash_window", LAGUNA_WINDOW, lambda q, k, v: att.flash_window_attention(
                q, k, v, window=LAGUNA_WINDOW))):
        out = fn(q, k, v)
        err = 0.0
        for q0 in range(0, s_len, s_len // 4):          # four blocks of queries
            ref = att.attention_reference(
                q[:, :, q0:q0 + block], k[:, :, :q0 + block],
                v[:, :, :q0 + block], True, window=window)
            err = max(err, float(jnp.max(jnp.abs(
                out[:, :, q0:q0 + block].astype(jnp.float32)
                - ref.astype(jnp.float32)))))
        assert err < 3e-2, f"{name} diverges at {hq} heads: max abs err {err}"
        t = chained_device_time(fn, (q, k, v))
        full = min(s_len, window) if window else s_len
        pairs = full * (full + 1) // 2 + ((s_len - full) * window if window else 0)
        flops = 4 * hq * HEAD * pairs
        rows.append(f"{name} {t * 1e3:.3f} ms ({flops / t / 1e12:.1f} TFLOP/s, "
                    f"{100 * flops / V5E_BF16 / t:.1f} % of the bf16 roofline, "
                    f"max_abs_err {err:.4f})")
    print(f"\n[laguna_flash] heads={hq} S={s_len} "
          f"({att.flash_variant(s_len, HEAD, 2)}): " + "; ".join(rows), flush=True)


D_MODEL, D_EXPERT, ROUTER, HELD, TOP_K, ROUTE_SCALE = 3072, 1024, 256, 32, 10, 2.5


@ON_TPU
@pytest.mark.parametrize("tokens,live,first", [
    (32, 2, 0), (32, 4, 0), (32, 32, 0), (2048, 2048, 0), (8192, 6144, 0),
    (2048, 2048, 96)],
    ids=["step2_none_here", "step4", "step32", "prefill2048", "prefill6144",
         "share_from_96"])
def test_laguna_share_experts_on_tpu(tokens, live, first):
    """The routed half of an expert layer as THIS cell runs it (a router 256
    wide, sigmoid scores, the top 10 renormalised and times 2.5; 32 experts of
    3072 x 1024 held from ``first``; bf16): ``ops.moe.moe_experts`` through the
    grouped kernel against a reference that shares none of its code (float32
    at the highest precision, EVERY held expert applied to every row and
    weighted by the row's gate for it or by zero), at a decode step of 32
    lanes with ``live`` of them active (the first row's two lanes chose no
    expert held here: the layer's answer is then exactly zero) and at prefills
    of 2048 and 6144 real rows. The cell's own check cannot hold this term on the chip (random
    routers' coin flips drown it, ``configs/laguna-s-2.1.json``
    ``tolerance_why``): this row does, at the timed shapes. Asserted: the live
    rows within 2e-2 of the reference (bf16 rounding reads 4e-3), a masked row
    exactly zero, the three routing counters equal to the reference's counts;
    and that the limit SEES a fault: the same rows with no routed output, or
    through the share one place round, land ten limits or more away."""
    from tfservingcache_tpu.ops import moe
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    key = jax.random.split(jax.random.PRNGKey(tokens + live + first), 5)
    m = {"router": jax.random.normal(key[0], (D_MODEL, ROUTER)) / D_MODEL ** 0.5,
         "w1": (jax.random.normal(key[1], (HELD, D_MODEL, D_EXPERT))
                / D_MODEL ** 0.5).astype(jnp.bfloat16),
         "w3": (jax.random.normal(key[2], (HELD, D_MODEL, D_EXPERT))
                / D_MODEL ** 0.5).astype(jnp.bfloat16),
         "w2": (jax.random.normal(key[3], (HELD, D_EXPERT, D_MODEL))
                / D_EXPERT ** 0.5).astype(jnp.bfloat16)}
    x = jax.random.normal(key[4], (tokens, D_MODEL)).astype(jnp.bfloat16)
    mask = jnp.arange(tokens) < live

    def layer(x, m, mask):
        y, st = moe.moe_experts(
            x, m, TOP_K, norm_topk=True, row_mask=mask, score="sigmoid",
            route_scale=ROUTE_SCALE, held=(first, HELD))
        return y, jnp.stack([st[name] for name in generation.MOE_STATS])

    @jax.jit
    def plain(x, m, mask):
        with jax.default_matmul_precision("highest"):
            z = x.astype(jnp.float32)
            p = jax.nn.sigmoid(z @ m["router"])
            top, idx = jax.lax.top_k(p, TOP_K)
            top = ROUTE_SCALE * top / jnp.sum(top, -1, keepdims=True)
            weight = jnp.einsum("tk,tke->te", top, jax.nn.one_hot(idx, ROUTER))
            weight = jnp.where(mask[:, None], weight[:, first:first + HELD], 0.0)

            def add(y, w):
                w1, w3, w2, gate = w
                f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
                out = (jax.nn.silu(z @ f32(w1)) * (z @ f32(w3))) @ f32(w2)
                return y + gate[:, None] * out, None

            y, _ = jax.lax.scan(add, jnp.zeros_like(z),
                                (m["w1"], m["w3"], m["w2"], weight.T))
            return y, weight

    got, stats = jax.jit(layer)(x, m, mask)
    tally = sorted(why for (op, branch, why) in att.dispatch_tally()
                   if op == "moe_experts" and branch == "kernel")
    assert tally, "the grouped kernel was not taken"
    want, weight = plain(x, m, mask)
    got, want, weight = (np.asarray(a, np.float32) for a in (got, want, weight))
    err = float(np.max(np.abs(got - want)))
    assert err < 2e-2, f"share of experts diverges: max abs err {err}"
    assert not got[live:].any()
    chosen = weight > 0
    np.testing.assert_array_equal(
        np.asarray(stats), [chosen.any(0).sum(), chosen.sum(0).max(), chosen.sum()])
    # the faults the limit must see: no routed output; the share one place round
    rolled, _ = plain(x, {**m, **{w: jnp.roll(m[w], 1, 0) for w in ("w1", "w3", "w2")}},
                      mask)
    far_zero = float(np.max(np.abs(want)))
    far_roll = float(np.max(np.abs(np.asarray(rolled) - want)))
    assert not chosen.any() or min(far_zero, far_roll) > 10 * 2e-2, (
        far_zero, far_roll)
    t_k = chained_device_time(lambda *a: layer(*a)[0], (x, m, mask))
    hit = int(chosen.any(0).sum())
    gb = hit * 3 * D_MODEL * D_EXPERT * 2 / 1e9
    print(f"\n[laguna_share_experts] rows={tokens} live={live} held={first}.."
          f"{first + HELD} of {ROUTER}, top {TOP_K}: {int(chosen.sum())} routed rows "
          f"here ({chosen.sum() / max(live, 1):.2f} a live row), experts_hit={hit}, "
          f"rows_max={int(chosen.sum(0).max())}; kernel path {t_k * 1e3:.3f} ms "
          f"({gb / t_k:.0f} GB/s of the hit experts' weights); max_abs_err {err:.4f} "
          f"(limit 0.02; no routed output {far_zero:.3f} away, the share one "
          f"place round {far_roll:.3f}); kernel {tally}", flush=True)
