"""The paged steps write their new KV rows into the arena in place (S10).

Structural, on the traced programs at a toy size: no equation of the decode
chunk or of a prefill chunk takes a layer's slice out of the 5-D arena, copies
one, or stacks slices back. Before PR 26 ``_paged_forward_step`` did all three
once a layer (``cache["k"][li]``, ``.at[page, :, off].set`` on the slice,
``jnp.stack``): eight passes over 268 MB to write 128 KB in the chat cells,
73 % of the chip's busy time. What the jaxpr may hold now:

- no ``slice`` / ``dynamic_slice`` / ``squeeze`` / ``concatenate`` /
  ``dynamic_update_slice`` / ``broadcast_in_dim`` whose result is a layer's
  slice of an arena buffer, or has as many elements as one or more;
- arena-shaped results only from ``scatter`` (``_paged_write_rows``), one a
  layer for each of ``k``, ``v`` and, on an int8 arena, the two scale buffers
  (the programs that carry the arena, ``pjit`` and ``scan``, pass it through).

The toy arena has far more pages than lanes x pages a lane, so what the CPU
reference legitimately gathers (the lanes' own pages) is small beside a layer.
"""

import functools

import jax
import jax.extend
import jax.numpy as jnp
import pytest

import tfservingcache_tpu.models.generation as generation
from tfservingcache_tpu.models.registry import build

LANES, PPS, PT, N_PAGES, CHUNK = 2, 4, 4, 256, 2
DENSE = ("transformer_lm", {
    "vocab_size": 97, "d_model": 48, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 2, "d_ff": 96, "max_seq": 64})
EXPERT = ("moe_lm", {
    "vocab_size": 97, "d_model": 64, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 4, "d_ff": 32, "n_experts": 8, "top_k": 2,
    "norm_topk_prob": False, "qk_norm": True, "tie_embeddings": False,
    "max_seq": 64, "rope_theta": 10000.0, "dtype": "float32"})
MOVERS = {"slice", "dynamic_slice", "squeeze", "concatenate",
          "dynamic_update_slice", "broadcast_in_dim"}


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            item = getattr(item, "jaxpr", item)          # a ClosedJaxpr's own
            if isinstance(item, jax.extend.core.Jaxpr):
                yield item


def _equations(jaxpr):
    """Every equation, with those of nested programs (pjit, scan, cond)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _equations(sub)


def _program(which, family, cfg, cache):
    """(the jitted program with its static arguments bound, abstract operands)"""
    params = jax.eval_shape(build(family, cfg).init, jax.random.PRNGKey(0))
    scales = ({"k": cache["k_scale"], "v": cache["v_scale"]}
              if "k_scale" in cache else None)
    static = dict(cfg_key=tuple(sorted(cfg.items())), family=family,
                  page_tokens=PT, kernel=False)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    lane = i32((LANES,))
    if which == "decode_chunk":
        fn = functools.partial(generation._paged_decode_chunk_jit,
                               chunk=CHUNK, **static)
        args = (params, cache["k"], cache["v"], scales, i32((LANES, PPS)),
                lane, lane, jax.ShapeDtypeStruct((LANES,), jnp.bool_),
                jax.ShapeDtypeStruct((CHUNK, 2), jnp.uint32),
                jax.ShapeDtypeStruct((LANES,), jnp.float32), lane)
    else:
        fn = functools.partial(generation._paged_prefill_chunk_jit, **static)
        args = (params, cache["k"], cache["v"], scales, i32((1, PPS)),
                i32((1, 8)), i32((1,)), i32((1,)))
    return fn, args


@pytest.mark.parametrize("which", ["decode_chunk", "prefill_chunk"])
@pytest.mark.parametrize("model", [DENSE, EXPERT], ids=["dense", "expert"])
@pytest.mark.parametrize("arena_dtype", ["", "int8"], ids=["bf16", "int8"])
def test_no_layer_slice_leaves_the_arena_and_none_is_stacked_back(
        arena_dtype, model, which):
    family, config = model
    cfg = build(family, config).config
    cache = jax.eval_shape(
        lambda: generation.init_paged_cache(cfg, N_PAGES, PT, arena_dtype))
    fn, args = _program(which, family, cfg, cache)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr

    buffers = {(b.shape, b.dtype) for b in cache.values()}
    layer_slices = {b.shape[1:] for b in cache.values()}
    layer_elems = cache["k"].size // cfg["n_layers"]
    assert LANES * PPS * 8 < N_PAGES          # the reference's gather is small
    scatters = {key: 0 for key in buffers}
    for eqn in _equations(jaxpr):
        name = eqn.primitive.name
        for out in eqn.outvars:
            aval = out.aval
            if not hasattr(aval, "shape"):
                continue
            if name in MOVERS:
                assert aval.size < layer_elems, (name, aval)
                assert aval.shape not in layer_slices, (name, aval)
                assert aval.shape[1:] not in layer_slices or aval.shape[0] != 1, (
                    name, aval)
            if (aval.shape, aval.dtype) in buffers:
                if name == "scatter":
                    scatters[(aval.shape, aval.dtype)] += 1
                else:
                    # only a program that carries the arena may hand it on
                    assert list(_sub_jaxprs(eqn)), (name, aval)
    # one scatter a layer for k and for v (the same shape and dtype), and on
    # an int8 arena as many again for their scales
    assert scatters == {key: 2 * cfg["n_layers"] for key in buffers}, scatters
    assert len(buffers) == (2 if arena_dtype == "int8" else 1)
