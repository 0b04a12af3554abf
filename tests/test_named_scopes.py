"""Named scopes on the device side (ISSUE 23): the step programs carry
``jax.named_scope`` names so a profiler capture says whose a ``copy`` or a
``fusion`` is. Trace-time only: this lowers the engine's programs at a tiny
size and looks for every name in the lowered text's locations.

Since PR 45 the decode chunk and the slot prefill walk a model's layers
through ONE body (``generation._walk_layers``) over two row stores, so both
programs are lowered for every cached family, at the rehearsal widths of its
cell's configuration, and held to the ``layer/...`` paths the readers of
``benchmark/layer_metrics``, ``benchmark/capture_scopes.py`` and
``tools/trace_scopes.py`` look for: a scope that moves makes a per-layer
metric ``null``, which nothing else off the chip notices."""

import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfservingcache_tpu.models import generation, real_rows, registry
from tfservingcache_tpu.models.transformer_lm import build

SCOPES = ("embed", "layer", "attn", "kv_read", "kv_write", "ffn", "lm_head",
          "sample")
TINY = {
    "vocab_size": 97, "d_model": 48, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 2, "d_ff": 96, "max_seq": 64, "dtype": "float32",
    "rope_theta": 10000.0,
}
BENCHMARK = os.path.join(os.path.dirname(__file__), "..", "benchmark")

# what each kind of row store alone carries, whatever the family: the paged
# arena is read where it lies, inside the attention call, and no layer's slice
# is ever taken out of it; the dense cache's layer is read before its write
KV_PAGED, KV_DENSE = {"layer/attn/kv_read"}, {"layer/kv_read"}
WINDOWED_PAGED = {"layer/attn/global/kv_read", "layer/attn/window/kv_read"}
EXPERTS = {"layer/ffn/route", "layer/ffn/experts"}
# family case -> (configuration file, paths BOTH programs carry, the decode
# chunk's alone, the slot prefill's alone)
FAMILIES = {
    "transformer_lm": (
        "mistral-7b-v0.3", {"layer/attn", "layer/kv_write", "layer/ffn"},
        KV_PAGED, KV_DENSE),
    "moe_lm": (
        "olmoe-1b-7b-0125",
        {"layer/attn", "layer/kv_write", "layer/ffn"} | EXPERTS,
        KV_PAGED, KV_DENSE),
    "mla_moe_lm": (
        "mistral-small-4-119b-2603",
        {"layer/attn", "layer/attn/q_lora", "layer/attn/kv_lora", "layer/ffn",
         "layer/ffn/shared"} | EXPERTS,
        # the one row a token is written between the two halves of the paged
        # layer's attention, and inside the dense one's
        KV_PAGED | {"layer/attn/absorb", "layer/kv_write"},
        {"layer/attn/kv_write"}),
    "hybrid_lm": (
        "lfm2-8b-a1b",
        # its leading dense layers lie beside expert layers: ``ffn/dense``
        {"layer/attn", "layer/conv", "layer/kv_write", "layer/ffn",
         "layer/ffn/dense"} | EXPERTS,
        KV_PAGED, KV_DENSE),
    "moe_lm-window": (
        "mellum2-12b-a2.5b-instruct",
        {"layer/attn", "layer/attn/global", "layer/attn/window",
         "layer/kv_write", "layer/ffn"} | EXPERTS,
        WINDOWED_PAGED, KV_DENSE),
    # query heads a layer, a gate a head under its kind's scope, one dense
    # layer among the expert ones, a share of the experts beside a shared one
    "moe_lm-heads": (
        "laguna-s-2.1",
        {"layer/attn", "layer/attn/global", "layer/attn/window",
         "layer/attn/global/gate", "layer/attn/window/gate", "layer/kv_write",
         "layer/ffn", "layer/ffn/dense", "layer/ffn/shared"} | EXPERTS,
        WINDOWED_PAGED, KV_DENSE),
    "sambay_lm": (
        "phi-4-mini-flash-reasoning",
        {"layer/attn", "layer/attn/global", "layer/attn/window",
         "layer/attn/cross", "layer/attn/global/diff", "layer/attn/window/diff",
         "layer/attn/cross/diff", "layer/ssm", "layer/gmu", "layer/kv_write",
         "layer/ffn"},
        WINDOWED_PAGED | {"layer/attn/cross/kv_read", "layer/ssm/step"},
        KV_DENSE | {"layer/ssm/scan"}),
    "olmo_hybrid_lm": (
        "olmo-hybrid-7b",
        {"layer/attn", "layer/gdn", "layer/gdn/proj", "layer/gdn/conv",
         "layer/gdn/gate", "layer/kv_write", "layer/ffn"},
        # the delta rule's one-token step in the decode chunk, its chunked
        # form over the prompt's bucket in the prefill
        KV_PAGED | {"layer/gdn/step"}, KV_DENSE | {"layer/gdn/chunk"}),
    "kda_moe_lm": (
        "solar-open2-250b",
        {"layer/attn", "layer/kda", "layer/kda/proj", "layer/kda/conv",
         "layer/kda/gate", "layer/kv_write", "layer/ffn", "layer/ffn/shared"}
        | EXPERTS,
        # as Olmo-Hybrid's: the rule's step in the chunk, its chunked form
        # (a decay a channel: the block form, no kernel yet) in the prefill
        KV_PAGED | {"layer/kda/step"}, KV_DENSE | {"layer/kda/chunk"}),
}
# every path a case names: a program must carry its own and none of the others
VOCABULARY = set().union(*(both | paged | dense
                           for _, both, paged, dense in FAMILIES.values()))


@pytest.fixture(scope="module")
def model():
    mdef = build(TINY)
    cfg = dict(mdef.config)
    params = mdef.init(jax.random.PRNGKey(0))
    return cfg, tuple(sorted(cfg.items())), params


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family_case(request):
    """-> (ModelDef at the rehearsal widths of the case's configuration, its
    static config, abstract params, the three path sets)."""
    name, *paths = FAMILIES[request.param]
    with open(os.path.join(BENCHMARK, "configs", f"{name}.json")) as f:
        config = json.load(f)
    config = _merged(config, config["rehearsal"])
    spec = importlib.util.spec_from_file_location(
        f"scopes_family_{config['family']}",
        os.path.join(BENCHMARK, "families", f"{config['family']}.py"))
    family = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(family)
    mdef = registry.build(family.PROGRAM_FAMILY, family.program_config(config))
    params = jax.eval_shape(mdef.init, jax.random.PRNGKey(0))
    return mdef, registry.static_config(mdef), params, paths


def _merged(base: dict, over: dict) -> dict:
    """``over`` laid over ``base``, dict by dict (``benchmark/run.py``'s)."""
    return {**base, **{k: _merged(base[k], v) if isinstance(v, dict)
                       and isinstance(base.get(k), dict) else v
                       for k, v in over.items()}}


def _locations(lowered) -> list:
    return re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))


def _scoped(lowered):
    """-> ``has(path)``: does some location of the lowered program carry
    ``path`` (``layer/attn``) as consecutive components of its scope path?"""
    locs = ["/" + name + "/" for name in _locations(lowered)]
    return lambda path: any("/" + path + "/" in loc for loc in locs)


def _layer_paths(lowered, through_loops: bool = False) -> set:
    """The paths of ``VOCABULARY`` that some operation of the program lies
    under, read FROM ``layer`` on (``layer/kv_read`` is not found inside
    ``layer/attn/kv_read``). ``through_loops``: a scope opened inside a loop's
    body counts under the scope the loop lies in (``layer/kda/while/body/proj``
    is ``layer/kda/proj``)."""
    found = set()
    for name in _locations(lowered):
        parts = [p for p in name.split("/")
                 if not (through_loops and p in ("while", "body", "closed_call"))]
        if "layer" in parts:
            at = parts.index("layer")
            found.update("/".join(parts[at:end])
                         for end in range(at + 2, len(parts)))
    return found & VOCABULARY


def _decode_chunk(mdef, cfg_key, params):
    lanes, pages, pt = 2, 8, 4
    arena = generation.init_paged_cache(dict(cfg_key), pages, pt, lanes=lanes)
    ring = ((arena["wk"], arena["wv"]),) if "wk" in arena else ()
    return generation._paged_decode_chunk_jit.lower(
        params, arena["k"], arena.get("v"), None,
        np.zeros((lanes, 4), np.int32), np.zeros(lanes, np.int32),
        np.zeros(lanes, np.int32), np.ones(lanes, bool),
        np.uint32(1),
        np.zeros(lanes, np.float32), np.zeros(lanes, np.int32),
        generation.init_lane_state(dict(cfg_key), lanes), *ring,
        cfg_key=cfg_key, family=mdef.family, chunk=2, page_tokens=pt,
        kernel=False,
    )


def _slot_prefill(mdef, cfg_key, params):
    return generation._slot_prefill_jit.lower(
        params, np.zeros((1, 8), np.int32), np.asarray([5], np.int32),
        jax.random.PRNGKey(2), np.float32(0.0), np.int32(0), cfg_key=cfg_key,
        family=mdef.family,
    )


def test_decode_chunk_program_carries_every_scope(family_case):
    mdef, cfg_key, params, (both, paged, _) = family_case
    lowered = _decode_chunk(mdef, cfg_key, params)
    has = _scoped(lowered)
    assert [s for s in SCOPES if not has(s)] == []
    assert _layer_paths(lowered) == both | paged
    # the reads of the arena (the reference's gather of the lanes' pages) and
    # its update (one scatter of the new rows a layer) are told apart inside a
    # layer; nothing re-stacks slices after the loop (PR 26: there are none)
    assert has("kv_read/gather") and has("layer/kv_write/scatter")


def test_slot_prefill_program_carries_every_scope(family_case):
    mdef, cfg_key, params, (both, _, dense) = family_case
    lowered = _slot_prefill(mdef, cfg_key, params)
    has = _scoped(lowered)
    # a latent row is read where it is written, inside the layer's attention
    assert [s for s in SCOPES if not has(s)] == (
        ["kv_read"] if mdef.family == "mla_moe_lm" else [])
    assert _layer_paths(lowered) == both | dense


# Since PR 48 a LONG slot prefill runs its token-wise stages over the row
# blocks that hold real rows (``models/real_rows.over_real_rows``): the scopes
# whose operations then lie in a loop's body, a family. The loop is INSIDE the
# stage's scope (``layer/ffn/while/body/...`` still runs through
# ``layer/ffn``), so every reader by scope finds what it found.
LOOPED = {
    "transformer_lm": {"layer/attn", "layer/ffn"},
    "moe_lm": {"layer/attn"},
    "mla_moe_lm": {"layer/attn", "layer/attn/q_lora", "layer/attn/kv_lora",
                   "layer/ffn/shared"},
    "hybrid_lm": {"layer/attn", "layer/ffn/dense"},
    "moe_lm-window": {"layer/attn", "layer/attn/global", "layer/attn/window"},
    "moe_lm-heads": {"layer/attn", "layer/attn/global", "layer/attn/window",
                     "layer/ffn/dense", "layer/ffn/shared"},
    "sambay_lm": {"layer/ffn"},
    "olmo_hybrid_lm": {"layer/attn", "layer/ffn", "layer/gdn/proj",
                       "layer/gdn/conv", "layer/gdn/gate"},
    # the WHOLE mixer of a linear layer a block of tokens at a time (one loop
    # under ``layer/kda``, its stages inside a trip)
    "kda_moe_lm": {"layer/attn", "layer/kda", "layer/ffn/shared"},
}


def _loop_scopes(lowered) -> set:
    """The paths, from ``layer`` on, under which a ``while`` opens."""
    found = set()
    for name in _locations(lowered):
        parts = name.split("/")
        if "layer" in parts:
            at = parts.index("layer")
            if "while" in parts[at:]:
                found.add("/".join(parts[at:parts.index("while", at)]))
    return found


def test_long_slot_prefill_loops_inside_its_stage_scopes(family_case,
                                                         monkeypatch, request):
    """The slot prefill at a bucket the loop engages in (the block floor
    dropped to 1 row for the 8-row bucket) carries the SAME ``layer/...``
    paths as the whole one, and its new ``while/body`` elements open inside
    the stages' scopes and nowhere else; at the bucket as it stands (every
    bucket under 4096) no loop is gained."""
    mdef, cfg_key, params, (both, _, dense) = family_case
    case = request.node.callspec.params["family_case"]
    generation._slot_prefill_jit.clear_cache()
    whole = _slot_prefill(mdef, cfg_key, params)
    monkeypatch.setattr(real_rows, "MIN_BLOCK_ROWS", 1)
    generation._slot_prefill_jit.clear_cache()
    looped = _slot_prefill(mdef, cfg_key, params)
    generation._slot_prefill_jit.clear_cache()
    assert _layer_paths(looped, True) == _layer_paths(whole) == both | dense
    assert _loop_scopes(looped) - _loop_scopes(whole) == LOOPED[case]
    # the decode chunk never loops a stage: one token a lane
    generation._paged_decode_chunk_jit.clear_cache()
    chunk = _decode_chunk(mdef, cfg_key, params)
    generation._paged_decode_chunk_jit.clear_cache()
    assert not {p for p in _loop_scopes(chunk)
                if p.split("/")[-1] in ("ffn", "proj", "conv", "gate",
                                        "shared", "q_lora", "kv_lora")}


def test_insert_and_predict_programs_are_named(model):
    cfg, cfg_key, params = model
    pt, pages = 4, 8
    cache = generation.init_paged_cache(cfg, pages, pt)
    n_kv, hd = cfg["n_kv_heads"], cfg["d_model"] // cfg["n_heads"]
    pk = jnp.zeros((cfg["n_layers"], 1, n_kv, 8, hd), cache["k"].dtype)
    has = _scoped(generation._paged_insert_jit.lower(
        cache["k"], cache["v"], None, pk, pk, np.zeros(4, np.int32),
        np.int32(0), page_tokens=pt,
    ))
    assert has("kv_write/scatter")
    # the :predict forward (models/transformer_lm.py)
    has = _scoped(jax.jit(build(TINY).apply).lower(
        params, {"input_ids": np.zeros((1, 8), np.int32)}))
    for path in ("embed", "layer/attn", "layer/ffn", "lm_head"):
        assert has(path), path


# -- the chunked delta rule's kernel in the prefill it serves (PR 47) ----------

def test_olmo_hybrid_prefill_for_the_chip_holds_the_rule_as_one_kernel(monkeypatch):
    """The cell's slot prefill at the published widths (8 layers, six of them
    linear; abstract weights) lowered FOR the TPU: every linear layer's
    chunked rule is ONE ``delta_chunk_kernel`` under ``layer/gdn/chunk``
    (``gdn_prefill_ms_per_ktok`` reads its time by that scope,
    ``gdn_chunk_roofline`` by the kernel's name), and nothing of the block
    form it replaced is left under that scope: no loop over chunks, no
    triangular solve, no transpose of anything as large as a lane's state."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with open(os.path.join(BENCHMARK, "configs", "olmo-hybrid-7b.json")) as f:
        config = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "scopes_family_olmo_hybrid_chip",
        os.path.join(BENCHMARK, "families", f"{config['family']}.py"))
    family = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(family)
    mdef = registry.build(family.PROGRAM_FAMILY, family.program_config(config))
    cfg = mdef.config
    linear = list(cfg["layer_types"]).count("linear_attention")
    assert linear == 6 and cfg["n_layers"] == 8
    state = cfg["linear_key_dim"] * cfg["linear_heads"] * cfg["linear_value_dim"]
    params = jax.eval_shape(mdef.init, jax.random.PRNGKey(0))
    sds = jax.ShapeDtypeStruct
    lowered = generation._slot_prefill_jit.trace(
        params, sds((1, 1024), jnp.int32), sds((1,), jnp.int32),
        jax.eval_shape(lambda: jax.random.PRNGKey(2)), sds((), jnp.float32),
        sds((), jnp.int32), cfg_key=registry.static_config(mdef),
        family=mdef.family).lower(lowering_platforms=("tpu",))
    text = lowered.as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', text, re.M))

    def scope_of(line):
        return names.get((re.findall(r"loc\((#loc\d+)\)\s*$", line) or [""])[0], "")

    # the kernel's call is a jit of its own (one trace and one lowering of its
    # body a program): every linear layer calls it under the scope, and the
    # function it calls holds the one custom call
    lines = text.splitlines()
    under = [ln for ln in lines if "layer/gdn/chunk" in scope_of(ln)]
    calls = [ln for ln in under if "call @delta_chunk_kernel" in ln]
    assert len(calls) == linear, len(calls)
    assert all(scope_of(ln).endswith("layer/gdn/chunk/jit(delta_chunk_kernel)")
               for ln in calls)
    first = next(i for i, ln in enumerate(lines)
                 if "func.func private @delta_chunk_kernel" in ln)
    callee = lines[first:next(i for i in range(first, len(lines))
                              if lines[i].startswith("  }"))]
    kernels = [ln for ln in callee if "tpu_custom_call" in ln]
    assert len(kernels) == 1 and 'kernel_name = "delta_chunk_kernel"' in kernels[0]
    assert not [ln for ln in under + callee if "stablehlo.while" in ln]
    assert "triangular_solve" not in text and "triangular-solve" not in text
    for ln in under + callee:
        if "stablehlo.transpose" in ln:
            shape = re.findall(r"tensor<([0-9x]+)x[a-z]", ln)[-1]
            assert np.prod([int(d) for d in shape.split("x")]) < state, ln
