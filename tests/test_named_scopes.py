"""Named scopes on the device side (ISSUE 23): the step programs carry
``jax.named_scope`` names so a profiler capture says whose a ``copy`` or a
``fusion`` is. Trace-time only: this lowers the engine's programs at a tiny
size and looks for every name in the lowered text's locations."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfservingcache_tpu.models import generation
from tfservingcache_tpu.models.transformer_lm import build

SCOPES = ("embed", "layer", "attn", "kv_read", "kv_write", "ffn", "lm_head",
          "sample")
TINY = {
    "vocab_size": 97, "d_model": 48, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 2, "d_ff": 96, "max_seq": 64, "dtype": "float32",
    "rope_theta": 10000.0,
}


@pytest.fixture(scope="module")
def model():
    mdef = build(TINY)
    cfg = dict(mdef.config)
    params = mdef.init(jax.random.PRNGKey(0))
    return cfg, tuple(sorted(cfg.items())), params


def _scoped(lowered):
    """-> ``has(path)``: does some location of the lowered program carry
    ``path`` (``layer/attn``) as consecutive components of its scope path?"""
    locs = ["/" + name + "/" for name in
            re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))]
    return lambda path: any("/" + path + "/" in loc for loc in locs)


def test_decode_chunk_program_carries_every_scope(model):
    cfg, cfg_key, params = model
    lanes, pages, pt = 2, 8, 4
    cache = generation.init_paged_cache(cfg, pages, pt)
    has = _scoped(generation._paged_decode_chunk_jit.lower(
        params, cache["k"], cache["v"], None,
        np.zeros((lanes, 4), np.int32), np.zeros(lanes, np.int32),
        np.zeros(lanes, np.int32), np.ones(lanes, bool),
        np.uint32(1),
        np.zeros(lanes, np.float32), np.zeros(lanes, np.int32),
        cfg_key=cfg_key, chunk=2, page_tokens=pt, kernel=False,
    ))
    assert [s for s in SCOPES if not has(s)] == []
    # the reads of the arena (the reference's gather of the lanes' pages) and
    # its update (one scatter of the new rows a layer) are told apart inside a
    # layer; nothing re-stacks slices after the loop (PR 26: there are none)
    for path in ("layer/attn/kv_read/gather", "layer/kv_write/scatter",
                 "layer/attn", "layer/ffn"):
        assert has(path), path
    assert not has("layer/kv_read")            # no layer's slice is taken out


def test_slot_prefill_program_carries_every_scope(model):
    cfg, cfg_key, params = model
    has = _scoped(generation._slot_prefill_jit.lower(
        params, np.zeros((1, 8), np.int32), np.asarray([5], np.int32),
        jax.random.PRNGKey(2), np.float32(0.0), np.int32(0), cfg_key=cfg_key,
    ))
    assert [s for s in SCOPES if not has(s)] == []


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
