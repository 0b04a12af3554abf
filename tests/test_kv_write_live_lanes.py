"""A decode step writes the KV rows of its live lanes only (S14).

``_paged_write_rows`` takes, in a decode chunk, the lanes of an order that
puts the chunk's live lanes first, ``_WRITE_GROUP`` a trip of a loop whose
trip count the device works out from the live count, inside the one program.
The change only REMOVES writes of inactive lanes:

- every page a live lane's table reaches, and the tokens, are bit-equal to the
  parent's (the same chunk with every lane's rows written: the parent's step,
  which is what ``_paged_write_rows`` still runs for a caller with no order);
- every other row of the arena (the trash page apart, where parked lanes
  collide) holds either what it held before the chunk or what the parent
  wrote there: nothing is added or altered;
- the host's count (``kv_write_lanes``: the ring's ``write_lanes``, the label
  of ``tpusc_gen_kv_write_steps_total``) is the device's for every live count;
- lanes coming and going never mint a second program;
- a caller with no ``active`` (a verify pass, a chunk of chunked prefill,
  speculation) writes every row as before (``tests/test_arena_in_place.py``
  holds the programs' structure: a loop a layer in the decode chunk, none in a
  prefill chunk).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tfservingcache_tpu.models.generation as generation
from tfservingcache_tpu.models.generation import (
    _paged_decode_chunk_jit,
    _WRITE_GROUP,
    _sample_per_row,
    _write_trips,
    kv_write_lanes,
)
from tfservingcache_tpu.models.registry import build, static_config

LANES, PT, PPS, N_PAGES, CHUNK = 32, 4, 8, 200, 3
FAMILIES = {
    "dense": ("transformer_lm", {
        "vocab_size": 97, "d_model": 48, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 96, "max_seq": PT * PPS, "dtype": "float32"}),
    "expert": ("moe_lm", {
        "vocab_size": 97, "d_model": 64, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 4, "d_ff": 32, "n_experts": 8, "top_k": 2,
        "norm_topk_prob": False, "qk_norm": True, "tie_embeddings": False,
        "max_seq": PT * PPS, "rope_theta": 10000.0, "dtype": "float32"}),
    "latent": ("mla_moe_lm", {"max_seq": PT * PPS, "dtype": "float32"}),
}
# (family, arena dtype): a latent (one-sided) arena has no int8 form
ARENAS = [("dense", ""), ("dense", "int8"), ("expert", ""), ("expert", "int8"),
          ("latent", "")]
LIVE_SETS = {
    "none": [], "1": [13], "3": [2, 17, 30], "4": [0, 9, 10, 31],
    "5": [1, 6, 7, 20, 28], "17": list(range(1, 32, 2)) + [4],
    "all": list(range(LANES)),
}
PARKED = 24     # inactive, but with pages of its own: a lane in chunked prefill


@functools.lru_cache(maxsize=None)
def _model(family):
    name, config = FAMILIES[family]
    md = build(name, config)
    return name, md, md.init(jax.random.PRNGKey(0))


def _arena(md, arena_dtype, seed=0):
    """An arena with something in every row, so that a row left unwritten is
    told from one written with zeros."""
    shapes = jax.eval_shape(lambda: generation.init_paged_cache(
        md.config, N_PAGES, PT, arena_dtype, row=md.cache_row))
    rng = np.random.default_rng(seed)
    cache = {}
    for key, a in shapes.items():
        if a.dtype == jnp.int8:
            cache[key] = jnp.asarray(rng.integers(-127, 128, a.shape, np.int8))
        else:
            cache[key] = jnp.asarray(rng.standard_normal(a.shape), a.dtype)
    return cache


def _lanes(live, parked=()):
    """Tables, positions and ``active`` for the live lanes (and for inactive
    lanes that hold pages): every lane's pages its own, page 0 the trash."""
    tables = np.zeros((LANES, PPS), np.int32)
    pos = np.zeros((LANES,), np.int32)
    active = np.zeros((LANES,), bool)
    nxt = 1
    for lane in sorted({*live, *parked}):
        n = 3 + lane % 3
        tables[lane, :n] = np.arange(nxt, nxt + n)
        nxt += n
        pos[lane] = 1 + (5 * lane) % (PT * n - CHUNK - 1)
        active[lane] = lane in live
    assert nxt <= N_PAGES
    return tables, pos, active


@functools.partial(jax.jit, static_argnames=("cfg_key", "family"))
def _parent_chunk(params, cache, tables, tok, pos, active, rngs, temperature,
                  top_k, *, cfg_key, family):
    """The parent's decode chunk: the program's scan with every lane's rows
    written (no order reaches ``_paged_write_rows``)."""
    cfg = dict(cfg_key)

    def step(carry, rng):
        cache, tok, pos = carry
        logits, cache = generation._paged_forward_step(
            params, tok, cache, tables, pos, cfg, family, PT, active=active)
        nxt = _sample_per_row(logits[:, 0], rng, temperature, top_k, active)
        nxt = jnp.where(active, nxt, tok)
        return (cache, nxt, pos + active.astype(jnp.int32)), nxt

    (cache, tok, pos), toks = jax.lax.scan(step, (cache, tok, pos), rngs)
    return cache, tok, pos, toks.T


def _both(family, arena_dtype, live, parked=()):
    name, md, params = _model(family)
    cache = _arena(md, arena_dtype)
    tables, pos, active = _lanes(live, parked)
    tok = jnp.asarray((7 * np.arange(LANES) + 3) % 90, jnp.int32)
    rngs = jax.random.split(jax.random.PRNGKey(5), CHUNK)
    temps = np.zeros((LANES,), np.float32)
    topks = np.zeros((LANES,), np.int32)
    key = static_config(md)
    want = _parent_chunk(params, dict(cache), tables, tok, pos, active, rngs,
                         temps, topks, cfg_key=key, family=name)
    before = {k: np.asarray(a) for k, a in cache.items()}
    scales = ({"k": cache["k_scale"], "v": cache["v_scale"]}
              if "k_scale" in cache else None)
    out = _paged_decode_chunk_jit(
        params, cache["k"], cache.get("v"), scales, tables, tok, pos, active,
        np.uint32(5), temps, topks, cfg_key=key, family=name, chunk=CHUNK,
        page_tokens=PT, kernel=False)
    got = generation._arena_cache(*out[:3])
    return before, want, (got, *out[3:6]), tables, active


@pytest.mark.parametrize("live", list(LIVE_SETS), ids=lambda s: f"live-{s}")
@pytest.mark.parametrize("family,arena_dtype", ARENAS,
                         ids=[f"{f}-{d or 'model'}" for f, d in ARENAS])
def test_live_lanes_rows_and_tokens_are_the_parents(family, arena_dtype, live):
    lanes = LIVE_SETS[live]
    parked = [PARKED] if PARKED not in lanes else []
    before, (w_cache, w_tok, w_pos, w_toks), (cache, tok, pos, toks), tables, active = (
        _both(family, arena_dtype, lanes, parked))
    assert set(cache) == set(w_cache)
    reached = np.unique(tables[active])
    reached = reached[reached > 0]
    assert len(reached) or not lanes
    for key in cache:
        got, want = np.asarray(cache[key]), np.asarray(w_cache[key])
        # every page a live lane's table reaches: the parent's bytes
        np.testing.assert_array_equal(got[:, reached], want[:, reached], key)
        # everywhere else but the trash page: the parent's write or none
        kept = (got == want) | (got == before[key])
        assert kept[:, 1:].all(), (key, np.argwhere(~kept[:, 1:])[:5])
    np.testing.assert_array_equal(np.asarray(toks)[active], np.asarray(w_toks)[active])
    np.testing.assert_array_equal(np.asarray(tok)[active], np.asarray(w_tok)[active])
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(w_pos))
    if lanes:
        # the live rows did land: their pages differ from what they held
        assert (np.asarray(cache["k"])[:, reached] != before["k"][:, reached]).any()


@pytest.mark.parametrize("lanes", [32, 17, 16, 8, 6, 5, 4, 2, 1])
def test_host_count_agrees_with_the_device(lanes):
    """``kv_write_lanes`` on the numpy mirror = the lanes the device's loop
    covers, from the same ``active``, for every live count: the trips it works
    out times the group, never more than the built width, never fewer than the
    live lanes and under one group more; an engine no wider than a group
    writes every lane."""

    @jax.jit
    def device(active):
        order, count = generation._live_lanes(active)
        return order, count, _write_trips(count)

    rng = np.random.default_rng(lanes)
    for count in range(lanes + 1):
        active = np.zeros((lanes,), bool)
        active[rng.permutation(lanes)[:count]] = True
        order, n, trips = device(active)
        assert int(n) == count
        # live lanes first, in lane order (stable), then the rest in theirs
        assert list(np.asarray(order)) == (
            list(np.flatnonzero(active)) + list(np.flatnonzero(~active)))
        wrote = kv_write_lanes(active)
        if lanes <= _WRITE_GROUP:
            assert wrote == lanes
            continue
        assert wrote == min(lanes, int(trips) * _WRITE_GROUP)
        assert count <= wrote < count + _WRITE_GROUP


def test_lanes_coming_and_going_mint_no_second_program(tmp_path):
    """One, three, five and six live lanes (one trip and two of an 8-lane
    engine's write) run ONE compiled ``_paged_decode_chunk_jit`` a chunk size;
    the ring's ``write_lanes`` and ``tpusc_gen_kv_write_steps_total{lanes}``
    say how many lanes each chunk's writes took."""
    from tfservingcache_tpu.config import ServingConfig
    from tfservingcache_tpu.models.registry import export_artifact
    from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
    from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
    from tfservingcache_tpu.types import Model, ModelId
    from tfservingcache_tpu.utils.flight_recorder import RECORDER
    from tfservingcache_tpu.utils.metrics import Metrics

    tiny = {"vocab_size": 97, "d_model": 48, "n_layers": 2, "n_heads": 4,
            "n_kv_heads": 2, "d_ff": 96, "max_seq": 64}
    export_artifact("transformer_lm", str(tmp_path), name="kvw", version=1,
                    config=tiny)
    metrics = Metrics()
    rt = TPUModelRuntime(ServingConfig(platform="cpu"), metrics=metrics)
    mid = ModelId("kvw", 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / "kvw" / "1")))
    eng = ContinuousGenerateEngine(rt, slots=8, chunk_tokens=4, metrics=metrics)

    def steps():
        snap = RECORDER.snapshot(tail=RECORDER.ring_entries)
        return [s for s in snap["models"][str(mid)]["steps"] if s["chunk"] > 0]

    try:
        ids = np.array([[5, 17, 40, 3]], np.int32)
        # 1 prefill token + two chunks of 4: only the chunk-4 program runs
        alone = eng.generate(mid, ids, max_new_tokens=9)
        before = _paged_decode_chunk_jit._cache_size()
        assert {s["write_lanes"] for s in steps()} == {4}
        for rows in (3, 5, 6, 1):
            out = eng.generate(mid, np.repeat(ids, rows, axis=0), max_new_tokens=9)
            assert (out == alone).all()
        assert _paged_decode_chunk_jit._cache_size() == before, (
            "a live count compiled a decode chunk of its own")
        ring = steps()
        assert {s["write_lanes"] for s in ring} == {4, 8}
        for s in ring:
            assert s["write_lanes"] == _WRITE_GROUP * -(-s["active"] // _WRITE_GROUP), s
        counted = {lanes: metrics.gen_kv_write_steps.labels(lanes)._value.get()
                   for lanes in ("4", "8")}
        assert counted == {
            lanes: sum(s["chunk"] for s in ring if str(s["write_lanes"]) == lanes)
            for lanes in counted}
    finally:
        eng.close()
        rt.close()


@pytest.mark.parametrize("family,arena_dtype", ARENAS,
                         ids=[f"{f}-{d or 'model'}" for f, d in ARENAS])
def test_a_caller_with_no_active_writes_every_row(family, arena_dtype):
    """A verify pass, a chunk of chunked prefill and speculation pass no
    order: every lane's T rows land where ``pages`` / ``off`` say."""
    _, md, _ = _model(family)
    cache = _arena(md, arena_dtype, seed=1)
    rng = np.random.default_rng(2)
    t_q, row = 3, md.cache_row or generation._cache_row(md.config)
    pages = rng.permutation(np.arange(1, N_PAGES))[:LANES * t_q].reshape(LANES, t_q)
    off = rng.integers(0, PT, (LANES, t_q))
    k_rows = rng.standard_normal((LANES, t_q, row.heads, row.width)).astype(np.float32)
    v_rows = None if row.sides == 1 else -k_rows
    new = generation._paged_write_rows(cache, 1, jnp.asarray(pages),
                                       jnp.asarray(off), k_rows, v_rows)
    assert set(new) == set(cache)
    for side, rows in (("k", k_rows), ("v", v_rows)):
        if rows is None:
            continue
        got = np.asarray(new[side])[1, pages, :, off].astype(np.float32)
        if arena_dtype == "int8":
            got = got * np.asarray(new[side + "_scale"])[1, pages, :, off][..., None]
            np.testing.assert_allclose(got, rows, atol=np.abs(rows).max() / 100)
        else:
            np.testing.assert_array_equal(got, rows)
        # the other layer is untouched
        np.testing.assert_array_equal(np.asarray(new[side])[0],
                                      np.asarray(cache[side])[0])
