"""KV-cached autoregressive generation for the decoder-LM families
(transformer_lm, moe_lm — both share the attention/cache layout; the FFN
half is pluggable: dense silu-gate MLP vs routed expert block; mla_moe_lm
with its one-sided latent row; hybrid_lm, whose convolution layers keep a
fixed state a request beside the rows its attention layers keep; sambay_lm,
whose Mamba layers keep a two-part state a scan carries, whose cross-attention
layers read ONE full-attention layer's rows and whose gated memory units keep
nothing; olmo_hybrid_lm, whose linear-attention layers keep a float32 matrix
state a head, whose block norms what a mixer gives and which has no rotary).

What a layer keeps is the ModelDef's to say (``registry.static_config`` puts
``cache_row`` and, for a model of several kinds, ``layer_state`` into the
programs' config): a layer with a ``CacheRow`` has a layer of the cache or
the arena (``_layer_slots``: the arena has as many layers as the model has
such layers), a layer with a ``LaneState`` has a slice of the ``lane`` array
that rides in the same cache dict, ``(lane layers, lanes, rows, width)``. A
layer whose ``CacheRow`` has a ``window`` keeps one window of pages a lane:
in the dense cache it is a layer like any other (every row kept, the mask
applied), in the paged arena it has a layer of a SECOND arena beside the
global one, ``wk`` / ``wv`` ``(window layers, lanes x ring pages, heads,
page_tokens, width)``, in which a lane owns its ring of pages for life
(``_layer_slots``: a ``Slot``'s ``arena`` and ``window``). A ``LaneState`` of
several parts has an array a part, and ``lane`` is then the tuple of them. A
layer with ``SharedRows`` has no layer of any cache: its ``Slot`` is the one
of the layer whose rows it reads, and it writes nothing. A layer with
``NoState`` has no ``Slot`` to speak of. The layers that keep no rows bring
their operators in the declaration (``_lane_layer``; ``NoState.operator``):
nothing here knows a family's name.

Every cached forward walks the model's layers in ONE place (``_walk_layers``,
``_attend_rows``): a layer kind is written there once. Where a layer's rows
live, how new rows are written and how the queries attend them is a row
store's: ``_PagedRows`` (the arena through block tables: the decode step, the
verify pass, a prefill chunk; ``_paged_verify_step``) and ``_DenseRows`` (a
cache of ``max_len`` rows an example: the admission prefill and the solo
decoder; ``_forward_cached_dyn``).

No reference counterpart (the reference proxies opaque Predict calls —
SURVEY.md §5); generation is where a TPU-native LM server must not re-run
the full sequence per token. Design:

  - prefill: one full forward over the prompt that also WRITES each layer's
    K/V into a preallocated (B, n_kv, max_len, head_dim) cache — the prompt
    is processed at MXU-friendly width once;
  - decode: a ``lax.scan`` over new tokens, each step attending one query
    position against the cache — static shapes, one compiled program for
    the whole generation, no per-token Python dispatch;
  - sampling: greedy or temperature/top-k, PRNG threaded through the scan.

The whole generate (prefill + scan + sampling) is a single jittable
function: compile once per (batch, prompt-bucket, max_new_tokens) and reuse.
"""

from __future__ import annotations

import functools
import math
import warnings
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tfservingcache_tpu.models.mla_moe_lm import (
    absorbed_output,
    absorbed_query,
    dense_absorbed_attention,
    expanded_attention,
    latent_project,
    softmax_scale,
)
from tfservingcache_tpu.models.moe_lm import _moe_block
from tfservingcache_tpu.models.real_rows import over_real_rows
from tfservingcache_tpu.models.registry import (
    CacheRow,
    LaneState,
    NoState,
    SharedRows,
    kv_cache_row,
    query_heads,
    static_config,
)
from tfservingcache_tpu.models.sambay_lm import diff_finish, diff_project
from tfservingcache_tpu.models.transformer_lm import (
    _norm,
    _output_logits,
    _qkv,
    _rmsnorm,
    head_gate,
    rope_of,
)
from tfservingcache_tpu.ops.attention import (
    attention,
    diff_outputs,
    diff_queries,
    pack_rows,
    paged_attention,
    paged_attention_verify,
    paged_latent_attention,
    paged_window_attention,
    unpack_pages,
)
from tfservingcache_tpu.ops.delta_rule import step_lanes_touched

# The slot-decode jits donate their K/V buffers (in-place update on TPU);
# CPU/interpreter backends cannot honor donation and warn on EVERY dispatch
# — steady-state noise at chunk cadence on the test harness, carrying no
# action. The donation itself stays: it is the difference between rewriting
# and reallocating a (layers, S, n_kv, max_seq, hd) array per chunk on HBM.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)


def _cache_row(cfg) -> CacheRow:
    """The row a program's config carries: ``registry.static_config`` puts the
    ModelDef's ``cache_row`` under that key. A config made by hand with no row
    (tests of the K/V families) means the decoder-LM K/V row."""
    return cfg.get("cache_row") or kv_cache_row(cfg)


class Slot(NamedTuple):
    """What one layer of the model keeps, and where (``_layer_slots``)."""
    lane: bool    # a ``LaneState`` (no rows)
    index: int    # into the ``lane`` array, or into the DENSE cache's layers
    arena: int    # a row layer's index into ITS paged arena (``index`` again
                  # for a lane state): the ring arena's layers for a window
                  # layer, the global arena's for every other row layer
    window: int   # rows a lane keeps in the paged arena; 0 = every row


# a layer that keeps nothing and reads no cache (``NoState``)
_NO_SLOT = Slot(False, -1, -1, 0)


def _layer_kinds(cfg) -> tuple:
    """What each layer declares (``ModelDef.layer_state`` as ``static_config``
    carries it); None a layer for a config with no ``layer_state`` (every
    family of one kind: the K/V or latent row in every layer)."""
    return cfg.get("layer_state") or (None,) * int(cfg["n_layers"])


def _layer_slots(cfg) -> list[Slot]:
    """One ``Slot`` a layer of the model. A config with no ``layer_state``
    (every family of one kind) has a cache layer a model layer, in order, and
    the arena's index is the dense cache's. A layer with ``SharedRows`` gets
    the ``Slot`` of the layer whose rows it reads (an EARLIER layer: its rows
    are written before they are read), a layer with ``NoState`` ``_NO_SLOT``.
    THE reader of ``layer_state``: which layers keep a window is derived here
    and nowhere else in the programs."""
    kinds = _layer_kinds(cfg)
    out, rows, lanes, ring = [], 0, 0, 0
    for depth, kind in enumerate(kinds):
        if isinstance(kind, LaneState):
            out.append(Slot(True, lanes, lanes, 0))
            lanes += 1
            continue
        if isinstance(kind, NoState):
            out.append(_NO_SLOT)
            continue
        if isinstance(kind, SharedRows):
            if not (0 <= kind.layer < depth
                    and isinstance(kinds[kind.layer], CacheRow)):
                raise ValueError(
                    f"layer {depth} reads the rows of layer {kind.layer}, "
                    "which is no earlier layer with rows of its own")
            out.append(out[kind.layer])
            continue
        window = getattr(kind, "window", 0)
        out.append(Slot(False, rows, ring if window else rows - ring, window))
        rows += 1
        ring += bool(window)
    return out


def _own_rows(cfg) -> list[Slot]:
    """The ``Slot`` s of the layers that keep rows of their OWN, in order."""
    return [slot for slot, kind in zip(_layer_slots(cfg), _layer_kinds(cfg))
            if kind is None or isinstance(kind, CacheRow)]


def _window_of(cfg) -> int:
    """The window of the model's window layers, 0 for a model with none
    (one window a model: the ring arena has one shape)."""
    windows = {s.window for s in _own_rows(cfg)} - {0}
    if len(windows) > 1:
        raise ValueError(f"window layers of several windows: {windows}")
    return windows.pop() if windows else 0


def window_rows(cfg) -> tuple[int, ...]:
    """The window layers' indices among the model's ROW layers: where a
    prefill's K/V (the dense cache's layers) holds their rows."""
    return tuple(s.index for s in _own_rows(cfg) if s.window)


def _row_layers(cfg) -> int:
    """The model's layers that keep rows: the cache's and the arena's layers."""
    return len(_own_rows(cfg))


def shared_readers(cfg) -> int:
    """Layers whose decode call reads a GLOBAL arena layer that more than one
    layer reads (the layer that writes it and every ``SharedRows`` layer over
    it); 0 for a model in which every layer reads its own rows."""
    kinds = _layer_kinds(cfg)
    read = {k.layer for k in kinds if isinstance(k, SharedRows)}
    return len(read) + sum(isinstance(k, SharedRows) for k in kinds)


def shared_pages_read(pos, active, chunk: int, readers: int,
                      page_tokens: int) -> float:
    """Pages the ``readers`` decode calls over a shared global layer read a
    live lane a step, summed over the readers, mean over the live lanes and
    the ``chunk`` steps, worked out on the host from the ``pos`` / ``active``
    mirrors the chunk is dispatched with (the ring's ``shared_pages``): the
    pages that hold tokens ``0 .. p`` at each step's position ``p``, once a
    reader. 0.0 with no live lane."""
    pos = np.asarray(pos, np.int64)[np.asarray(active, bool)]
    if not pos.size:
        return 0.0
    p = pos[:, None] + np.arange(int(chunk))[None, :]
    return float(np.mean(p // page_tokens + 1)) * int(readers)


def _lane_layer(layer: dict, x, state, real_len, kind: LaneState, cfg):
    """A layer that keeps a lane state, its operator half, for the cached
    walk: the residual stream ``x`` BEFORE its norm and the layer's slice
    ``state`` (``(B, rows, width)``, or a tuple of such a part) -> (residual
    delta, the slice after ``real_len`` of the tokens at hand, what the layer
    hands on to the layers after it or None): the operator the layer's
    declaration brings (``hybrid_lm.conv_layer``, ``sambay_lm.mamba_layer``)."""
    return kind.operator(layer, x, state, real_len, cfg)


def _lane_slice(lane, li: int):
    """Layer ``li``'s slice of the lane state, an array or a tuple a part."""
    return jax.tree_util.tree_map(lambda a: a[li], lane)


def init_lane_state(cfg: dict, lanes: int):
    """Zeros ``(lane layers, lanes, rows, width)``: what the layers with a
    ``LaneState`` keep, a slice a lane (a request's beginning is zeros), in
    the part's dtype (the model's where it names none); a tuple of such
    arrays, one a part, for a state of several parts; None for a model with
    no such layer."""
    kinds = [k for k in cfg.get("layer_state") or ()
             if isinstance(k, LaneState)]
    if not kinds:
        return None
    if len(set(kinds)) != 1:
        raise ValueError(f"lane states of several shapes: {set(kinds)}")
    parts = tuple(
        jnp.zeros((len(kinds), lanes, rows, width),
                  jnp.dtype(dtype or cfg["dtype"]))
        for rows, width, dtype in kinds[0].parts())
    return parts[0] if len(parts) == 1 else parts


def _norm_eps(cfg) -> float:
    return cfg.get("rms_eps", cfg.get("norm_eps", 1e-5))


def _differential_qkv(attn: dict, a, cfg):
    """A differential layer's projections for the cached walk (a layer that
    holds ``lam_q1``): two softmaxes a head pair over rows that hold a pair of
    KV heads, no rotary -> (queries padded with zeros in grouped-query order,
    ``ops.attention.diff_queries``; the rows the layer keeps, ``(B, pairs, T,
    2 D)``, None for a layer that reads another's; the head's own
    ``sm_scale``)."""
    q, k, v = diff_project(attn, a, cfg["n_heads"], cfg["n_kv_heads"])
    return diff_queries(q), k, v, q.shape[-1] ** -0.5


def init_cache(cfg: dict, batch: int, max_len: int, mesh=None) -> dict:
    """Preallocated per-layer K/V buffers. bf16 storage halves HBM traffic;
    attention still accumulates in f32. ``mesh`` commits the buffers to
    KV-head shardings (parallel/sharding.kv_arena_shardings) so the slot
    jits compile partitioned programs from day one. A model with lane-state
    layers gets ``lane`` beside the sides, and cache layers for its other
    layers only."""
    row = _cache_row(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    shape = (_row_layers(cfg), batch, row.heads, max_len, row.width)
    cache = {side: jnp.zeros(shape, dtype) for side in "kv"[:row.sides]}
    lane = init_lane_state(cfg, batch)
    if lane is not None:
        cache["lane"] = lane
    if mesh is not None:
        from tfservingcache_tpu.parallel.sharding import shard_kv_arena

        cache = shard_kv_arena(cache, mesh)
    return cache


def _sample(logits, rng, temperature, top_k):
    """logits (B, V) -> token ids (B,): ``_sample_per_row`` with one
    ``temperature`` and one ``top_k`` for every row (the prefill's first
    token, the solo decoder's scan), so it pays what that sampler pays: an
    ``argmax`` alone where ``temperature <= 0``, the categorical draw where
    it is positive, the full-vocabulary sort only with a ``top_k`` inside
    (0, vocab).

    ``temperature`` and ``top_k`` are TRACED scalars, not compile-time
    constants: both arrive straight from the unauthenticated ``:generate``
    request body, and a static argname would mint (and cache forever) a fresh
    XLA compile of the whole prefill+scan program per novel value — a
    compile-DoS vector. One compiled program serves every sampling config:
    temperature<=0 selects greedy, top_k<=0 (or >= vocab) disables top-k
    filtering, both decided on the device (``lax.cond``), never by a static
    flag or a second program.
    """
    rows = logits.shape[:1]
    return _sample_per_row(
        logits, rng,
        jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), rows),
        jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), rows),
    )


def _decode_scan(params, cache, first_tok, start_pos, rng, temperature,
                 top_k, cfg, family, max_new_tokens: int):
    """The shared sampling scan: ``first_tok`` sits at ``start_pos`` (not
    yet in cache); emits max_new_tokens including it. The rng split
    structure is FIXED (one split per step) so the plain and from-cache
    paths draw identical streams for the same seed."""

    def step(carry, _):
        cache, tok, pos, rng = carry
        logits, cache = _forward_cached_dyn(
            params, tok[:, None], cache, pos, cfg, family
        )
        rng, sub = jax.random.split(rng)
        nxt = _sample(logits[:, 0], sub, temperature, top_k)
        return (cache, nxt, pos + 1, rng), tok

    (cache, _, _, _), toks = jax.lax.scan(
        step, (cache, first_tok, start_pos, rng), None, length=max_new_tokens
    )
    return jnp.transpose(toks, (1, 0)), cache  # (B, max_new_tokens)


@functools.partial(
    jax.jit,
    static_argnames=("cfg_key", "max_new_tokens", "family", "return_cache"),
)
def _generate_jit(
    params,
    input_ids,
    prompt_len,
    rng,
    temperature,
    top_k,
    *,
    cfg_key,
    max_new_tokens: int,
    family: str = "transformer_lm",
    return_cache: bool = False,
):
    cfg = dict(cfg_key)
    b, s_max = input_ids.shape
    max_len = s_max + max_new_tokens
    cache = init_cache(cfg, b, max_len)

    # prefill the (right-padded) prompt block — the start_pos = 0 case of the
    # per-example forward; padding positions write junk K/V but the per-step
    # mask keeps them invisible until overwritten
    last, cache = _prefill_fresh(params, input_ids, prompt_len, cache, cfg, family)
    rng, sub = jax.random.split(rng)
    tok = _sample(last, sub, temperature, top_k)

    toks, cache = _decode_scan(
        params, cache, tok, prompt_len, rng, temperature, top_k, cfg, family,
        max_new_tokens,
    )
    if return_cache:
        return toks, cache["k"], cache.get("v")
    return toks


@functools.partial(
    jax.jit,
    static_argnames=("cfg_key", "max_new_tokens", "family", "return_cache"),
)
def _generate_from_cache_jit(
    params,
    suffix_ids,          # (1, S_suffix_pad) — prompt tokens AFTER the prefix
    suffix_len,          # (1,) true suffix length
    cached_k,            # (layers, 1, n_kv, Lpad, head_dim)
    cached_v,
    cached_len,          # (1,) valid prefix rows (the rest is masked junk)
    rng,
    temperature,
    top_k,
    *,
    cfg_key,
    max_new_tokens: int,
    family: str = "transformer_lm",
    return_cache: bool = False,
):
    """Continue from a cached prompt-prefix KV: copy the prefix rows in,
    prefill ONLY the suffix, then the shared decode scan. Junk rows beyond
    ``cached_len`` (entry padding / stale tail) are overwritten by the
    suffix prefill and the per-step writes before any query can see them —
    the same argument that makes plain prefill's pad rows safe."""
    cfg = dict(cfg_key)
    b, s_pad = suffix_ids.shape
    l_pad = cached_k.shape[3]
    max_len = l_pad + s_pad + max_new_tokens
    cache = _with_prefix(init_cache(cfg, b, max_len), cached_k, cached_v)
    start = cached_len.astype(jnp.int32)                  # (1,)
    logits, cache = _forward_cached_dyn(
        params, suffix_ids, cache, start, cfg, family, real_len=suffix_len
    )
    last = jnp.take_along_axis(
        logits, (suffix_len - 1)[:, None, None], axis=1
    )[:, 0]
    rng, sub = jax.random.split(rng)
    tok = _sample(last, sub, temperature, top_k)

    toks, cache = _decode_scan(
        params, cache, tok, start + suffix_len, rng, temperature, top_k,
        cfg, family, max_new_tokens,
    )
    if return_cache:
        return toks, cache["k"], cache.get("v")
    return toks


def _with_prefix(cache: dict, cached_k, cached_v) -> dict:
    """``cache`` with a cached prefix's rows copied in from position 0, each
    side the cache has (a one-sided cache's ``cached_v`` is None)."""
    return {
        side: jax.lax.dynamic_update_slice(
            cache[side], rows.astype(cache[side].dtype), (0, 0, 0, 0, 0))
        for side, rows in (("k", cached_k), ("v", cached_v)) if side in cache
    }


# The dense prefill's score block, ``(B, heads, S, max_len)`` float32, from
# which a fresh forward attends among the tokens at hand instead (a GiB: 32
# heads at a 2048 bucket build half of it; 30 heads at 16384 would build 32 GB).
_SCORE_BLOCK_BYTES = 1 << 30


def _attends_tokens_at_hand(cfg, cache, s_len: int) -> bool:
    """Whether a FRESH forward of ``s_len`` tokens into ``cache`` attends among
    the tokens at hand through ``ops.attention.attention`` (the flash kernel
    where its gate admits) and projects ONE position through the head: a model
    with window layers always; a K/V model without them where the score block
    over the cache's length would reach ``_SCORE_BLOCK_BYTES``. Below that the
    programs are the ones they were."""
    if _window_of(cfg):
        return True
    _, batch, _, max_len, _ = cache["k"].shape
    return (batch * query_heads(cfg) * s_len * max_len * 4
            >= _SCORE_BLOCK_BYTES)


def _prefill_fresh(params, input_ids, prompt_len, cache, cfg, family):
    """The forward of whole (right-padded) prompts into a fresh cache, the
    start_pos = 0 case of ``_forward_cached_dyn`` -> (the last REAL prompt
    token's logits ``(B, V)`` f32, the cache). A row past ``prompt_len`` is
    not computed where that saves a block (``over_real_rows``: a long bucket's
    token-wise stages run the row blocks that hold real rows, and a pad row
    goes to no expert); what a pad position does write is junk or zeros the
    per-step mask keeps invisible until overwritten; a lane state is
    the one AT ``prompt_len``, which no pad token has touched. A latent family
    projects that one position through the head and no other (a long
    prompt's ``S_pad x V`` float32 logits are a gigabyte at 8192 x 32768), and
    so does a model with window layers (3.2 GB at 8192 x 98304) and any
    forward that attends among the tokens at hand (``_attends_tokens_at_hand``:
    6.6 GB at 16384 x 100352)."""
    b = input_ids.shape[0]
    one = _cache_row(cfg).sides == 1 or _attends_tokens_at_hand(
        cfg, cache, input_ids.shape[1])
    logits, cache = _forward_cached_dyn(
        params, input_ids, cache, jnp.zeros((b,), jnp.int32), cfg, family,
        fresh=True, logits_at=prompt_len - 1 if one else None,
        real_len=prompt_len,
    )
    if one:
        return logits[:, 0], cache
    return jnp.take_along_axis(
        logits, (prompt_len - 1)[:, None, None], axis=1)[:, 0], cache


def _sampling_lanes(temperature, top_k, vocab: int, active=None):
    """What a step's lanes ask of the sampler, as two (S,) masks: the rows
    that draw (exactly those the sampler's last select does not hand to
    ``argmax``) and, among them, the rows whose ``top_k`` filters. Operators
    only, so the device (traced arrays, ``_sample_per_row``) and the host
    (the engine's numpy mirrors, ``sample_path``) run the same lines.
    ``active`` leaves out lanes nobody reads: a retired lane keeps its last
    ``temperature`` / ``top_k`` until the next admission overwrites them."""
    samples = ~(temperature <= 0.0)
    if active is not None:
        samples = samples & active
    return samples, samples & (top_k > 0) & (top_k < vocab)


def sample_path(active, temps, topks, vocab: int) -> str:
    """The path ``_sample_per_row`` takes for these lanes, worked out on the
    host from the mirrors a chunk is dispatched with: ``"greedy"``,
    ``"sample"`` or ``"topk"`` (the ``tpusc_gen_sample_steps_total`` label)."""
    samples, wants_k = _sampling_lanes(
        np.asarray(temps), np.asarray(topks), vocab, np.asarray(active, bool))
    if not samples.any():
        return "greedy"
    return "topk" if wants_k.any() else "sample"


@jax.named_scope("sample")
def _sample_per_row(logits, rng, temperature, top_k, active=None):
    """Per-row sampling params: logits (S, V), temperature (S,) f32,
    top_k (S,) i32 -> token ids (S,). The continuous engine packs unrelated
    requests into one decode step, so each lane carries its own sampling
    config; the values stay TRACED for the same compile-DoS reason as
    ``_sample``.

    A step pays for what its lanes ask (``_sampling_lanes``; ``active``
    (S,) bool, where given, names the lanes somebody reads), chosen on the
    device inside the one program:

    - no lane draws (``greedy``): the ``argmax`` and nothing else;
    - some lane draws, none filters (``sample``): one categorical draw over
      all rows (matches the batched stream structure), no sort;
    - some drawing lane sets a ``top_k`` inside (0, vocab) (``topk``): the
      full-vocabulary sort for every row's threshold, then the draw.

    A row's token is the same on every path that may serve it: one lane
    that draws makes all S rows pay for the draw, as before."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    k = jnp.clip(top_k.astype(jnp.int32), 0, v)
    samples, wants_k = _sampling_lanes(temperature, k, v, active)

    def threshold_by_sort():
        sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(
            sorted_desc, jnp.clip(k - 1, 0, v - 1)[:, None], axis=-1
        )
        return jnp.where(((k > 0) & (k < v))[:, None], kth, -jnp.inf)

    def draw():
        thresh = jax.lax.cond(
            jnp.any(wants_k), threshold_by_sort,
            lambda: jnp.full((logits.shape[0], 1), -jnp.inf, logits.dtype),
        )
        filt = jnp.where(logits < thresh, -1e30, logits)
        temp = jnp.maximum(temperature.astype(jnp.float32), 1e-6)[:, None]
        sampled = jax.random.categorical(
            rng, filt / temp, axis=-1).astype(jnp.int32)
        return jnp.where(temperature <= 0.0, greedy, sampled)

    return jax.lax.cond(jnp.any(samples), draw, lambda: greedy)


@functools.partial(jax.jit, static_argnames=("cfg_key", "family"))
def _slot_prefill_jit(
    params,
    input_ids,           # (1, S_pad) right-padded prompt
    prompt_len,          # (1,)
    rng,
    temperature,         # scalar f32
    top_k,               # scalar i32
    *,
    cfg_key,
    family: str = "transformer_lm",
):
    """Prefill ONE prompt into a fresh (1, S_pad)-row cache and sample the
    request's first token — the admission half of the continuous engine.
    Returns (first_tok (1,), k, v, last_logits (1, V) f32, the lane state at
    ``prompt_len`` ``(lane layers, 1, rows, width)`` or None); the first
    token's own K/V is NOT yet in the cache (it sits at pos=prompt_len,
    written by the first decode-chunk step — the same convention as
    ``_decode_scan``'s first_tok). The last-position logits ride along so
    the shared-prefix index can cache them: an exact re-admission of the
    same prompt samples its first token from these under its own seed and
    skips prefill compute entirely."""
    cfg = dict(cfg_key)
    b, s_max = input_ids.shape
    last, cache = _prefill_fresh(
        params, input_ids, prompt_len, init_cache(cfg, b, s_max), cfg, family)
    _, sub = jax.random.split(rng)
    tok = _sample(last, sub, temperature, top_k)
    return tok, cache["k"], cache.get("v"), last, cache.get("lane")


@functools.partial(jax.jit, static_argnames=("cfg_key", "family"))
def _slot_prefill_from_cache_jit(
    params,
    suffix_ids,          # (1, S_suffix_pad)
    suffix_len,          # (1,)
    cached_k,            # (layers, 1, n_kv, Lpad, head_dim)
    cached_v,
    cached_len,          # (1,)
    rng,
    temperature,
    top_k,
    *,
    cfg_key,
    family: str = "transformer_lm",
):
    """Admission prefill continuing from a prefix-cache hit: copy the prefix
    rows, prefill only the suffix, sample the first token. Same junk-row
    safety argument as ``_generate_from_cache_jit``. Returns the
    last-position logits too (same contract as ``_slot_prefill_jit``)."""
    cfg = dict(cfg_key)
    b, s_pad = suffix_ids.shape
    l_pad = cached_k.shape[3]
    cache = _with_prefix(init_cache(cfg, b, l_pad + s_pad), cached_k, cached_v)
    start = cached_len.astype(jnp.int32)
    logits, cache = _forward_cached_dyn(
        params, suffix_ids, cache, start, cfg, family, real_len=suffix_len
    )
    last = jnp.take_along_axis(
        logits, (suffix_len - 1)[:, None, None], axis=1
    )[:, 0]
    _, sub = jax.random.split(rng)
    tok = _sample(last, sub, temperature, top_k)
    return tok, cache["k"], cache.get("v"), last


@jax.jit
def _sample_logits_jit(last, rng, temperature, top_k):
    """Sample one first token from CACHED last-position logits — the
    shared-prefix index's exact-hit path, replacing the whole prefill
    dispatch. The split-then-sample sequence is byte-identical to
    ``_slot_prefill_jit``'s tail, so an exact hit and a cold prefill of
    the same prompt produce the same token under the same seed."""
    _, sub = jax.random.split(rng)
    return _sample(last, sub, temperature, top_k)


def init_paged_cache(cfg: dict, n_pages: int, page_tokens: int,
                     arena_dtype: str = "", mesh=None,
                     row: CacheRow | None = None, lanes: int = 0) -> dict:
    """Preallocated paged KV arena shared by every lane of one model's
    continuous-decode state: fixed-size pages instead of per-lane
    ``max_seq`` rows, so HBM is sized by tokens in flight, not worst case.
    Page 0 is the TRASH page — never handed out by the free-list; retired
    and never-admitted lanes' block tables point at it so their frozen
    rewrites land somewhere no live lane ever gathers.

    ``arena_dtype="int8"`` (serving.kv_arena_dtype) stores the pages
    quantized with per-(page, head, token) f32 scales riding in a parallel
    ``k_scale``/``v_scale`` buffer — one scale per written KV row, so an
    append never requantizes resident rows (a true per-page scale would
    force a read-modify-write of the whole page on every decode step).
    Payload bytes halve vs bf16 (head_dim int8 + 4 scale bytes per row vs
    2*head_dim), which is where the extra admitted slots come from.

    ``mesh`` (ISSUE 20) commits the arena to KV-head shardings — each
    shard holds ``(layers, n_pages, n_kv/axis, page_tokens, hd)`` — with
    the int8 scale buffers sharded over the same KV-head axis (their dim
    2), matching the layout GSPMD picks for the decode programs so the
    arena-bytes accounting is stable from allocation onward. Block tables
    and the free-list stay
    host-side, so reserve/CoW/publish/census run unchanged on the sharded
    arena; every jit that donates the arena round-trips the committed
    layout, keeping donation effective.

    ``row`` (the family's ``ModelDef.cache_row``; a config with no row means
    the decoder-LM K/V row, ``_cache_row``) says what a page holds: ``sides``
    arrays of ``(heads, page_tokens, width)`` tiles. A latent family's arena
    is ONE side, ``k``, of one shared row a token; there is no ``v``. The
    arena's first axis counts the model's layers that keep rows: ``cfg``
    carries ``layer_state`` where some layers keep a lane state instead
    (``static_config``), and those have no layer here.

    **A K/V row 64 wide with an even number of heads is stored PACKED, two KV
    heads a 128-lane row**: ``(layers, n_pages, heads // 2, page_tokens,
    128)``, row ``[l, p, j, t] = [k(head 2j, t) | k(head 2j + 1, t)]``. The
    same bytes, pages and block tables; the shape a head-128 arena has, which
    the TPU keeps row-major and no program converts, and whose pages the
    paged decode kernel can copy out of HBM (a 64-wide row fills half a
    128-lane tile: the device stores such an arena with the PAGES minor, every
    program that addresses rows converts all of it, in and out, and the
    kernel's gate refuses it: PR 33's traces, PERF.md). Decided here, from
    the row's shape alone. An odd number of heads, an int8 arena (its scales
    are a row a head), a one-sided arena, a mesh (the arena is sharded over
    its KV heads there and the kernel is off) and every other width keep
    ``(heads, width)``.

    **A model with WINDOW layers** (a ``CacheRow`` with a ``window`` in its
    ``layer_state``) gets a second arena beside this one: ``wk`` / ``wv``
    ``(window layers, lanes x R, heads, page_tokens, width)`` with ``R =
    ops.attention.window_ring_pages(window, page_tokens)``, and ``k`` / ``v``
    hold its GLOBAL layers only. Lane ``s`` owns pages ``s R .. s R + R - 1``
    of every window layer for life and position ``p`` lives in its page ``(p
    // page_tokens) % R``: no free list, no reservation, no host table, and
    not one page more whatever a request's length. ``lanes`` is the state's
    lane count (needed for such a model only). No int8 form and no mesh."""
    from tfservingcache_tpu.ops.attention import window_ring_pages

    row = row or _cache_row(cfg)
    dtype = jnp.dtype(cfg["dtype"])
    heads, width = row.heads, row.width
    window = _window_of(cfg)
    if window and (arena_dtype == "int8" or mesh is not None or lanes < 1
                   or row.sides != 2):
        raise ValueError(
            "window layers keep a ring of pages a lane: no int8 form, no "
            f"mesh, K and V sides, and the lane count (got lanes={lanes})")
    if (row.sides == 2 and width == 64 and heads % 2 == 0
            and arena_dtype != "int8" and mesh is None):
        # THE place that decides a packed arena; every program learns it from
        # the array it is handed (``ops.attention.pack_rows``)
        heads, width = heads // 2, 128
    # a layer of the arena a model layer that HAS pages (``_layer_slots``);
    # a window layer's pages are the ring arena's
    n_window = len(window_rows(cfg))
    shape = (_row_layers(cfg) - n_window, n_pages, heads, page_tokens, width)
    if row.sides == 1:
        if arena_dtype == "int8":
            raise ValueError("a latent (one-sided) arena has no int8 form")
        return {"k": jnp.zeros(shape, jnp.dtype(arena_dtype or dtype))}
    if arena_dtype == "int8":
        sshape = shape[:-1]
        cache = {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32),
        }
    else:
        if arena_dtype:
            dtype = jnp.dtype(arena_dtype)
        cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        if window:
            ring = (n_window, lanes * window_ring_pages(window, page_tokens),
                    heads, page_tokens, width)
            cache["wk"] = jnp.zeros(ring, dtype)
            cache["wv"] = jnp.zeros(ring, dtype)
    if mesh is not None:
        from tfservingcache_tpu.parallel.sharding import shard_kv_arena

        cache = shard_kv_arena(cache, mesh)
    return cache


def _quantize_kv_rows(x):
    """Symmetric absmax int8 over the head_dim axis: ``x (..., hd)`` ->
    (int8 values, f32 scales ``(...)``) with ``x ≈ values * scales[..., None]``.
    Per-row scales keep quantization LOCAL to the written row — the
    incremental-append property the arena's write paths depend on."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


# Lanes a trip of a decode chunk's KV write (``_paged_write_rows``). Set on the
# chip (v5e, PR 32): a row of the scatter costs 95 ns and a trip of the loop
# about 5 us, i.e. 50 rows, two to three lanes of 16 to 32 rows (8 KV heads or
# 16, two sides). The engine runs 2 to 4 live lanes, which 4 writes in one
# trip; 32 live lanes take 8 trips a layer, 2.5 % of an OLMoE step at full load
# over one scatter of all rows (11.01 against 10.74 ms).
_WRITE_GROUP = 4


def _write_trips(count):
    """Trips of the write's loop for ``count`` live lanes. Operators only: the
    device (a traced scalar) and the host (``kv_write_lanes``) run this line."""
    return (count + _WRITE_GROUP - 1) // _WRITE_GROUP


def kv_write_lanes(active) -> int:
    """The lanes whose rows a decode chunk's KV write takes a step, worked out
    on the host from the ``active`` mirror the chunk is dispatched with (the
    ring's ``write_lanes``, the ``tpusc_gen_kv_write_steps_total`` label)."""
    active = np.asarray(active, bool)
    if active.size <= _WRITE_GROUP:
        return active.size
    return min(active.size, int(_write_trips(int(active.sum()))) * _WRITE_GROUP)


def state_write_lanes(active, cfg) -> int:
    """The lanes whose slice of the LANE STATE a decode step reads and writes,
    worked out on the host from the ``active`` mirror the chunk is dispatched
    with (the ring's ``state_lanes``). A model whose lane-state layers bring a
    ``LaneState.step`` advances what ``ops.delta_rule.step_lanes_touched``
    says: the live lanes where the step's kernel runs, whole trips of its
    loop where it does not; any other sets each layer's slice whole
    (``_PagedRows.keep_lane_state``: every lane, live or not: read and
    written, though it comes back bit for bit). 0 with no lane-state layer."""
    kinds = [k for k in _layer_kinds(cfg) if isinstance(k, LaneState)]
    if not kinds:
        return 0
    active = np.asarray(active, bool)
    if any(k.step is None for k in kinds):
        return int(active.size)
    return step_lanes_touched(
        int(active.sum()), int(active.size), kinds[0].dtype or cfg["dtype"],
        *(int(cfg[k]) for k in ("linear_heads", "linear_key_dim", "linear_value_dim")))


def window_pages_read(pos, active, chunk: int, window: int,
                      page_tokens: int) -> float:
    """Pages a window layer's decode call reads a live lane, mean over the
    live lanes and the ``chunk`` steps, worked out on the host from the
    ``pos`` / ``active`` mirrors the chunk is dispatched with (the ring's
    ``window_pages``): the pages that hold tokens ``max(0, p - window + 1) ..
    p`` at each step's position ``p``. 0.0 with no live lane."""
    pos = np.asarray(pos, np.int64)[np.asarray(active, bool)]
    if not pos.size:
        return 0.0
    p = pos[:, None] + np.arange(int(chunk))[None, :]
    first = np.maximum(p - (int(window) - 1), 0)
    return float(np.mean(p // page_tokens - first // page_tokens + 1))


def _live_lanes(active):
    """``(order, count)`` for ``_paged_write_rows``: the lanes, the live ones
    first (stable), and how many are live."""
    return (jnp.argsort(~active, stable=True).astype(jnp.int32),
            jnp.sum(active, dtype=jnp.int32))


def _paged_write_rows(cache, li: int, pages, off, k_rows, v_rows, live=None):
    """THE write into the paged arena, shared by every paged step: the new
    rows ``k_rows`` / ``v_rows`` ``(S, T, n_kv, hd)`` of layer ``li`` go to
    ``arena[li, pages, :, off]`` (``pages`` / ``off`` ``(S, T)``) as a scatter
    on the whole 5-D arena. The arena is donated to the chunk programs and
    carried by their scans, so the scatter updates it in place: a step moves
    its new rows, never a layer's slice (there is no ``cache["k"][li]`` and
    nothing is stacked back). ``li`` is a Python int (the layer loop is
    unrolled).

    The kv head is an INDEX of the scatter too, not a slice of its window:
    the update is one ``hd`` row a (lane, position, head), or, in a packed
    arena (``init_paged_cache``), one 128-wide row a (lane, position, head
    pair): half the rows, the same scatter. With the heads in
    the window (``.at[li, pages, :, off, :]``) the TPU compiler gives the
    scatter a layout of its own, heads next to ``hd``, and converts the WHOLE
    arena into it and back around every layer's write, because the paged
    kernels read the arena in the layout it is stored in (PR 26, compiled
    for the v5e: 20 arena-sized copies in the decode chunk; none this way).

    That scatter is sequential in its rows (95 ns each on a v5e), so a decode
    chunk hands down ``live`` (``_live_lanes`` of its frozen ``active``): the
    write then takes ``_WRITE_GROUP`` lanes of the order a trip of a loop
    whose trip count the device works out from the live count, and stops
    after the last live lane. The loop carries the arena as the chunk's scan
    does; each trip is the same scatter on fewer lanes. The slots of the last
    trip past the live count are inactive lanes, whose rows are the junk they
    always were: nothing is masked, every live lane's rows are written, and
    writes of inactive lanes are only left out. A caller that means every lane (a
    verify pass, a chunk of chunked prefill, speculation: no ``live``) gets
    the one scatter of all rows. (A ``lax.switch`` over whole-rung scatters
    is as fast for K/V arenas, but around a one-sided arena the v5e compiler
    copies the arena in and out of the conditional: PR 32.)

    An int8 arena (``k_scale`` present) quantizes each row here, with
    per-row scales, so resident rows are never requantized. Lanes parked on
    the trash page may collide: last-writer-wins junk that no live lane's
    block table can reach. A one-sided arena (latent rows) has no ``v``:
    ``v_rows`` is None. Returns the updated cache."""

    def write(cache, pages, off, k_rows, v_rows):
        # as the arena stores them: a packed arena's row is a PAIR of heads
        k_rows = pack_rows(k_rows, cache["k"])
        if v_rows is not None:
            v_rows = pack_rows(v_rows, cache["v"])
        heads = jnp.arange(k_rows.shape[2])[None, None, :]
        at = (li, pages[:, :, None], heads, off[:, :, None])     # (S, T, n_kv)
        if v_rows is None:
            return {**cache,
                    "k": cache["k"].at[at].set(k_rows.astype(cache["k"].dtype))}
        new = dict(cache)       # every side below; a ``lane`` state rides along
        if "k_scale" in cache:
            k_rows, k_s = _quantize_kv_rows(k_rows)
            v_rows, v_s = _quantize_kv_rows(v_rows)
            new["k_scale"] = cache["k_scale"].at[at].set(k_s)
            new["v_scale"] = cache["v_scale"].at[at].set(v_s)
        new["k"] = cache["k"].at[at].set(k_rows.astype(cache["k"].dtype))
        new["v"] = cache["v"].at[at].set(v_rows.astype(cache["v"].dtype))
        return new

    rows = (pages, off, k_rows, v_rows)
    with jax.named_scope("kv_write"):
        if live is None or k_rows.shape[0] <= _WRITE_GROUP:
            return write(cache, *rows)
        order, count = live

        def trip(i, cache):
            # past the end the slice is clamped: lanes written twice, alike
            take = jax.lax.dynamic_slice(
                order, (i * _WRITE_GROUP,), (_WRITE_GROUP,))
            return write(
                cache, *jax.tree_util.tree_map(lambda a: a[take], rows))

        return jax.lax.fori_loop(0, _write_trips(count), trip, cache)


def _paged_forward_step(params, tok, cache, tables, pos, cfg, family,
                        page_tokens: int, kernel: bool = False, active=None,
                        moe_stats: list | None = None, live=None):
    """One decode step (s_len=1 per lane, ``tok`` ``(S,)``) against the
    paged arena — the block-table counterpart of ``_forward_cached_dyn``,
    and ``_paged_verify_step``'s T = 1 case: that is where it is written."""
    return _paged_verify_step(
        params, tok[:, None], cache, tables, pos, cfg, family, page_tokens,
        kernel=kernel, active=active, moe_stats=moe_stats, live=live,
    )


def _paged_verify_step(params, toks, cache, tables, pos, cfg, family,
                       page_tokens: int, kernel: bool = False, active=None,
                       moe_stats: list | None = None, live=None):
    """One forward of T positions a lane against the paged arena: the decode
    step (T = 1), the verify pass of in-engine speculative decoding and a
    chunk of chunked prefill. Lane ``s``'s T tokens ``toks[s]`` sit at
    positions ``pos[s]..pos[s]+T-1``; each writes its K/V row at
    ``tables[lane, p // page_tokens]`` offset ``p % page_tokens`` (clipped
    to the last table slot: overshoot past a lane's reservation hits a
    zeroed table entry, i.e. the trash page) through ``_paged_write_rows``,
    in place on the arena, then the queries attend over the lane's pages of
    the arena where it lies: ``paged_attention`` at T = 1,
    ``paged_attention_verify`` (per-position causal masks, all T queries in
    ONE call) above it — the fused Pallas kernels when ``kernel`` and the
    backend/shape gate admit them, else the gather+einsum reference whose
    GQA/mask pipeline matches the dense path operation-for-operation, so
    greedy decode is token-for-token identical, and spec-on identical to
    spec-off. The attention's operand is the write's result, so the new
    rows are read after they are written by data dependency.

    An int8 arena (``cache["k_scale"]`` present) quantizes each new row at
    write time and attention dequantizes on the read side — rejected draft
    rows are quantization junk above the accepted prefix, masked until
    overwritten.

    ``active`` (T = 1 only: the chunk's frozen ``(S,)`` vector, default all
    lanes) goes to ``paged_attention`` as it is: the caller discards an
    inactive lane's token, so the kernel reads no page for it, and an expert
    layer routes it to no expert. Each expert layer's ``(experts_hit,
    expert_rows_max, expert_rows_local)`` is appended to ``moe_stats`` where
    the caller gives a list (a dense model appends nothing). ``live``
    (``_live_lanes(active)``, worked out once a chunk) lets the write follow
    the live lanes; without it every lane's rows are written.

    A latent family (one-sided arena) writes its ONE row a token and attends
    in the absorbed form, ``paged_latent_attention``: the fused kernel at
    T = 1, the gather + einsum reference above it.

    A layer that keeps a ``LaneState`` (``_layer_slots``) touches no page: its
    operator maps the lane's slice of ``cache["lane"]`` and the step's token
    to the slice after (``conv_operator``), and an inactive lane's slice
    stays bit for bit. Only T = 1 carries it: a verify pass or a prefill
    chunk of such a model is refused by name at trace time (the runtime
    refuses them before, ``_refuse_lane_state``)."""
    rows = _PagedRows(cache, tables, pos, toks.shape[1], cfg, family,
                      page_tokens, kernel, active, live)
    logits = _walk_layers(params, toks, rows, cfg, moe_stats=moe_stats)
    return logits, rows.cache


class _PagedRows:
    """Where ``_walk_layers`` keeps a layer's rows for ``_paged_verify_step``:
    in the paged arena, through the lanes' block tables (that docstring says
    what goes where). New rows go in through ``_paged_write_rows``, in place:
    the global arena at the pages the tables name, a window layer's into the
    lane's own ring; the queries attend the arena where it lies. A lane state
    is updated in place in ``cache["lane"]``. ``cache`` is the arena after
    the layers walked so far."""

    def __init__(self, cache, tables, pos, t_q: int, cfg, family,
                 page_tokens: int, kernel: bool, active, live):
        s_lanes = pos.shape[0]
        pps = tables.shape[1]
        positions = pos[:, None] + jnp.arange(t_q)[None, :]          # (S, T)
        pages = jnp.take_along_axis(
            tables, jnp.clip(positions // page_tokens, 0, pps - 1), axis=1
        )                                                            # (S, T)
        # past-the-table positions redirect to the trash page EXPLICITLY — the
        # clip alone would alias them onto the lane's own LAST slot, which is a
        # live reserved page when the lane's budget fills the whole table (a
        # draft scan near max_seq under spec headroom capping can get here)
        self.pages = jnp.where(positions // page_tokens >= pps, 0, pages)
        self.off = positions % page_tokens

        slots = _layer_slots(cfg)
        if t_q != 1 and any(s.lane for s in slots):
            raise ValueError(
                f"{family}: a forward of {t_q} positions a lane over the paged "
                "arena (a speculative verify pass, a prefill chunk) does not "
                "carry a lane state")
        windowed = any(s.window for s in slots)
        if windowed and (t_q != 1 or "wk" not in cache):
            raise ValueError(
                f"{family}: a forward of {t_q} positions a lane over the paged "
                "arena (a speculative verify pass, a prefill chunk) does not "
                "turn a window layer's ring")
        if windowed:
            # a window layer's write goes to the lane's own ring, wherever its
            # block table points: page (p // page_tokens) % R of the lane's R
            ring_pages = cache["wk"].shape[1] // s_lanes
            self.ring_at = (jnp.arange(s_lanes)[:, None] * ring_pages
                            + (positions // page_tokens) % ring_pages)
        # a lane nobody reads keeps its state: it takes 0 of the step's 1 token
        self.took = None if active is None else active.astype(jnp.int32)
        self.cache, self.tables, self.pos, self.positions = (
            cache, tables, pos, positions)
        self.t_q, self.cfg, self.page_tokens = t_q, cfg, page_tokens
        self.kernel, self.active, self.live = kernel, active, live

    # one token a lane on a donated carry: a ``LaneState.step`` may update it
    in_place = True

    def keep_lane_state(self, slot: Slot, after) -> None:
        self.cache = {**self.cache, "lane": jax.tree_util.tree_map(
            lambda a, n: a.at[slot.index].set(n.astype(a.dtype)),
            self.cache["lane"], after)}

    def write(self, slot: Slot, k, v) -> None:
        with jax.named_scope("attn"):    # the projection's: (S, T, heads, width)
            k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        if slot.window:
            ring = _paged_write_rows(
                {"k": self.cache["wk"], "v": self.cache["wv"]}, slot.arena,
                self.ring_at, self.off, k, v, self.live)
            self.cache = {**self.cache, "wk": ring["k"], "wv": ring["v"]}
        else:
            self.cache = _paged_write_rows(
                self.cache, slot.arena, self.pages, self.off, k, v, self.live)

    def attend(self, slot: Slot, q, scale):
        cache = self.cache
        if slot.window:
            return paged_window_attention(
                q, cache["wk"], cache["wv"], self.pos, self.page_tokens,
                slot.window, kernel=self.kernel, active=self.active,
                layer=slot.arena, sm_scale=scale)
        operands = (q, cache["k"], cache["v"], self.tables, self.pos,
                    self.page_tokens, cache.get("k_scale"),
                    cache.get("v_scale"))
        if self.t_q == 1:
            return paged_attention(*operands, kernel=self.kernel,
                                   active=self.active, layer=slot.arena,
                                   sm_scale=scale)
        return paged_attention_verify(*operands, kernel=self.kernel,
                                      layer=slot.arena)

    def latent_layer(self, attn, a, x, slot: Slot):
        """A latent layer's attention half: the ONE row a token written, then
        the absorbed form over the lane's pages."""
        cfg = self.cfg
        with jax.named_scope("attn"):
            q_n, q_r, k = latent_project(attn, a, self.positions, cfg)
            q = absorbed_query(attn, q_n, q_r, cfg)
            k = k[:, :, None]                                    # (S, T, 1, W)
        self.cache = _paged_write_rows(
            self.cache, slot.arena, self.pages, self.off, k, None, self.live)
        with jax.named_scope("attn"):
            out = paged_latent_attention(
                q, self.cache["k"], self.tables, self.pos, self.page_tokens,
                _cache_row(cfg).value_width, softmax_scale(cfg),
                kernel=self.kernel,
                active=self.active if self.t_q == 1 else None,
                layer=slot.arena)
            return x + absorbed_output(attn, out, cfg, x.dtype)


def _kind_scope(windowed: bool, kind: str = "global"):
    """The scope that tells a GLOBAL layer's attention from a window layer's
    (``layer/attn/global`` beside ``layer/attn/window``) in a model that has
    both; no scope at all in a model of one kind, whose programs keep the
    names they had."""
    import contextlib

    return jax.named_scope(kind) if windowed else contextlib.nullcontext()


@functools.partial(
    jax.jit,
    static_argnames=("cfg_key", "family", "page_tokens", "kernel"),
    donate_argnums=(1, 2, 3),
)
def _paged_prefill_chunk_jit(params, arena_k, arena_v, scales, table_row,
                             toks, start, real_len, *, cfg_key,
                             family="transformer_lm", page_tokens,
                             kernel=False):
    """One fixed-size prefill chunk written straight into a lane's reserved
    pages (chunked-prefill interleaving, serving.prefill_chunk_tokens).
    ``toks`` is (1, C) with C STATIC — the engine clamps the knob up to a
    pow2 and zero-pads the final chunk, so ONE compiled program serves
    every chunk of every prompt. ``start`` (1,) i32 is the absolute
    position of toks[:, 0]; ``real_len`` (1,) i32 counts the non-pad
    tokens in this chunk. Reuses the spec-decode verify step: K/V rows
    land at start..start+C-1 through the lane's block-table row (the
    trash-page redirect inside absorbs pad rows that run past the
    reservation), and per-position causal masks give each real query
    exact attention over every previously written chunk. Pad rows INSIDE
    the reservation hold junk at positions >= the prompt end — the same
    write-before-read argument as prefill padding makes them invisible:
    a query at pos p sees only rows <= p, and decode writes row p before
    any query attends to it. Returns the
    updated arena plus the last REAL token's logits (f32), which the
    final chunk feeds through the split-then-sample helper for a first
    token bit-identical in discipline to the monolithic prefill."""
    cfg = dict(cfg_key)
    cache = _arena_cache(arena_k, arena_v, scales)
    logits, cache = _paged_verify_step(
        params, toks, cache, table_row, start, cfg, family, page_tokens,
        kernel=kernel,
    )
    idx = jnp.clip(real_len - 1, 0, toks.shape[1] - 1)
    last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
    return (*_cache_arena(cache), last)


def _arena_cache(arena_k, arena_v, scales, lane_state=None,
                 window=None) -> dict:
    """The arena as the jits take it (``k``, ``v`` or None for a one-sided
    arena, the int8 arena's ``scales`` or None), the lane states beside
    it (None for a model whose layers all keep rows) and the window layers'
    ring arena ``(wk, wv)`` (None for a model with no window layer) -> the
    cache dict the steps carry."""
    cache = {"k": arena_k}
    if lane_state is not None:
        cache["lane"] = lane_state
    if window is not None:
        cache["wk"], cache["wv"] = window
    if arena_v is not None:
        cache["v"] = arena_v
    if scales is not None:
        cache["k_scale"] = scales["k"]
        cache["v_scale"] = scales["v"]
    return cache


def _cache_arena(cache: dict) -> tuple:
    """``_arena_cache``'s inverse -> (k, v | None, scales | None); a lane
    state is ``cache.get("lane")``, the ring arena ``_cache_window``."""
    scales = ({"k": cache["k_scale"], "v": cache["v_scale"]}
              if "k_scale" in cache else None)
    return cache["k"], cache.get("v"), scales


def _cache_window(cache: dict) -> tuple:
    """The window layers' ring arena of a cache dict, ``(wk, wv)``; ``()``
    (no output at all) for a model with no window layer."""
    return ((cache["wk"], cache["wv"]),) if "wk" in cache else ()


# ``fn`` over every side of an arena that exists: a one-sided (latent) arena's
# ``v`` is None, an empty pytree, and stays None
_each_side = jax.tree_util.tree_map


def _insert_rows(arena_k, arena_v, scales, pk, pv, table_row, base,
                 page_tokens: int):
    """``_paged_insert_jit``'s write (its docstring says what goes where),
    shared with ``_window_paged_insert_jit``'s global layers."""
    p_pad = pk.shape[3]
    n_pg = -(-p_pad // page_tokens)
    base = base.astype(jnp.int32)
    slot = jnp.arange(n_pg)
    pages = table_row[jnp.minimum(slot, table_row.shape[0] - 1)]
    # past the table's end or wholly under ``base``: the trash page
    pages = jnp.where((slot < table_row.shape[0])
                      & ((slot + 1) * page_tokens > base), pages, 0)
    # the EDGE pages hold rows that are not this insert's: the one ``base``
    # cuts and, where the bucket is no whole number of pages, the last. Each
    # is read, takes its new rows by a select and is written after the whole
    # pages, among which it goes to the trash page
    edges = [jnp.clip(base // page_tokens, 0, n_pg - 1)]
    if p_pad % page_tokens:
        edges.append(jnp.int32(n_pg - 1))
    edge = jnp.stack(edges)
    edge_pages = pages[edge]
    pages = jnp.concatenate([pages.at[edge].set(0), edge_pages])
    row = edge[:, None] * page_tokens + jnp.arange(page_tokens)   # (edges, pt)
    ours = ((row >= base) & (row < p_pad))[None, :, None, :]

    def page_form(rows, arena):
        # (layers, 1, n_kv, P_pad, hd) -> (layers, n_pg, heads, pt, width) as
        # ``arena`` stores a page (two heads a row where packed)
        rows = jnp.pad(rows[:, 0], (
            (0, 0), (0, 0), (0, n_pg * page_tokens - p_pad), (0, 0)))
        layers, n_kv, _, hd = rows.shape
        rows = rows.reshape(layers, n_kv, n_pg, page_tokens, hd)
        return pack_rows(rows.transpose(0, 2, 3, 1, 4), arena).transpose(
            0, 1, 3, 2, 4)

    def set_pages(buf, new):
        new = new.astype(buf.dtype)
        mine = ours if new.ndim == 4 else ours[..., None]   # a scale has no width
        kept = jnp.where(mine, new[:, edge], buf[:, edge_pages])
        return buf.at[:, pages].set(jnp.concatenate([new, kept], axis=1))

    kv, vv = _each_side(page_form, (pk, pv), (arena_k, arena_v))
    if scales is not None:
        kv, k_s = _quantize_kv_rows(kv)
        vv, v_s = _quantize_kv_rows(vv)
        scales = _each_side(set_pages, scales, {"k": k_s, "v": v_s})
    return (*_each_side(set_pages, (arena_k, arena_v), (kv, vv)), scales)


@functools.partial(
    jax.jit, donate_argnums=(0, 1, 2), static_argnames=("page_tokens",)
)
@jax.named_scope("kv_write")
def _paged_insert_jit(arena_k, arena_v, scales, pk, pv, table_row, base, *,
                      page_tokens):
    """Write one admitted request's prefill K/V (layers, 1, n_kv, P_pad, hd)
    into its reserved pages AS WHOLE PAGES: the rows are brought into the
    arena's own page form ``(layers, P_pad / page_tokens, heads as stored,
    page_tokens, stored width)`` (a transposition of the prompt's rows, two
    heads a row through ``pack_rows`` where the arena is packed, quantised
    first where it is int8: the scale buffers take the same form without the
    width) and page ``j`` of that form is set at ``table_row[j]``, in place on
    the donated arena: ONE scatter a buffer whose only index is the page, the
    form ``_pages_import_jit`` has. Logical row ``r`` so lies in page
    ``table_row[r // page_tokens]`` at offset ``r % page_tokens``, bit for bit
    where a write row by row put it. (A row was the unit until PR 42, with
    layers and heads in the scatter's window: the TPU compiler re-laid each
    side of the arena out for that scatter and back, four arena-sized copies
    an admission whatever the prompt's length.) ``table_row``
    is the lane's FULL (pages_per_slot,) block-table row — entries beyond
    the reservation are 0, so the pages of prefill-pad rows past the reserved
    budget (P_pad is a pow2 bucket and can overshoot it) land in the trash
    page, as do pages past the table's end.
    Junk pad rows inside the reservation are never visible: a query at pos
    p sees only rows <= p, and the decode step writes row p before
    attending. ``base`` (traced i32) is
    the shared-prefix boundary: rows < base belong to pages another lane /
    the prefix index owns READ-ONLY, so a page wholly under ``base`` is
    redirected to the trash page — prefill stops at the shared boundary and
    only private pages are written — and the ONE page ``base`` may cut keeps
    its rows under ``base``: that page is read, takes the rows from ``base``
    up by a select, and is written with the others. So is the last page where
    the bucket is no whole number of pages (a cached prefix + a small suffix
    bucket), whose rows past P_pad keep their bytes. base=0 is the plain
    unshared insert. One compile
    per P_pad bucket, same bound as the prefill itself (base is data, not
    a signature). ``scales`` is the int8 arena's {"k", "v"} per-row scale
    buffers (donated; None for an arena in the model's dtype): prefill rows are
    quantized here with the same per-row absmax discipline as the decode
    write, so a page is bit-identical whether filled by prefill or steps.
    A one-sided (latent) arena's ``arena_v`` / ``pv`` are None and stay so."""
    return _insert_rows(arena_k, arena_v, scales, pk, pv, table_row, base,
                        page_tokens)


@functools.partial(
    jax.jit, donate_argnums=(0, 1, 2, 3),
    static_argnames=("page_tokens", "window_layers", "ring_pages"))
@jax.named_scope("kv_write")
def _window_paged_insert_jit(arena_k, arena_v, wk, wv, pk, pv, table_row, lane,
                            prompt_len, *, page_tokens, window_layers,
                            ring_pages):
    """``_paged_insert_jit``'s sibling for a model with WINDOW layers: one
    admitted request's prefill K/V ``(row layers, 1, n_kv, P_pad, hd)``, all
    the model's row layers in order, into both arenas in one dispatch.

    The global layers' rows (every row layer not in the static
    ``window_layers``, indices into ``pk``) go to the lane's reserved pages
    exactly as ``_paged_insert_jit`` puts them (no shared-prefix boundary:
    such a model shares no prefix). Of each window layer's rows only the last
    ``min(prompt_len, R x page_tokens)`` are stored, in lane ``lane``'s ring:
    slot ``j`` of the ring's ``R x page_tokens`` holds the LAST prompt position
    ``r < prompt_len`` with ``r % (R x page_tokens) == j`` (position ``p``
    lives in page ``(p // page_tokens) % R``, offset ``p % page_tokens``), a
    gather of the kept rows and one contiguous update of the lane's R pages.
    Slots no prompt position maps to hold junk a decode step overwrites
    before any query reads it. ``prompt_len`` is the TRUE length: the pow2
    bucket's pad rows are never stored."""
    keep = jnp.asarray(
        [i for i in range(pk.shape[0]) if i not in window_layers], jnp.int32)
    arena_k, arena_v, _ = _insert_rows(
        arena_k, arena_v, None, pk[keep], pv[keep], table_row, jnp.int32(0),
        page_tokens)
    p_pad = pk.shape[3]
    ring = ring_pages * page_tokens
    slot = jnp.arange(ring)
    plen = prompt_len.astype(jnp.int32)
    # the last prompt position that lives in each slot of the ring
    src = slot + (jnp.maximum(plen - 1 - slot, 0) // ring) * ring
    src = jnp.clip(jnp.where(slot < plen, src, 0), 0, p_pad - 1)
    at = (0, lane.astype(jnp.int32) * ring_pages, 0, 0, 0)
    take = jnp.asarray(window_layers, jnp.int32)

    def lane_ring(rows, arena):
        # (window layers, n_kv, P_pad, hd) -> (window layers, R, heads, pt,
        # width) as the arena stores a row (two heads a row where packed)
        kept = pack_rows(rows[take, 0][:, :, src].transpose(0, 2, 1, 3), arena)
        wl, _, heads, width = kept.shape
        kept = kept.reshape(wl, ring_pages, page_tokens, heads, width)
        return jax.lax.dynamic_update_slice(
            arena, kept.transpose(0, 1, 3, 2, 4).astype(arena.dtype), at)

    return arena_k, arena_v, lane_ring(pk, wk), lane_ring(pv, wv)


@functools.partial(jax.jit, static_argnames=("width",))
@jax.named_scope("kv_read")
def _paged_gather_prefix_jit(arena_k, arena_v, scales, pages, *, width):
    """Gather ``n`` full shared-prefix pages into the dense
    (layers, 1, n_kv, n*page_tokens, hd) layout `_slot_prefill_from_cache_jit`
    expects as its cached prefix. Read-only on the arena (no donation — the
    shared pages stay live for every other referencing lane). One compile
    per distinct page count, bounded by pages_per_slot. An int8 arena
    (``scales`` not None) is dequantized here: the suffix prefill runs on
    dense f32 rows either way. ``width`` is the model's row (``CacheRow.width``):
    a packed arena's pages are unpacked to it after the gather."""
    # arena: (layers, n_pages, n_kv, page_tokens, hd); pages: (n,) i32
    k, v = _each_side(lambda a: unpack_pages(a[:, pages], width),
                      (arena_k, arena_v))           # (L, n, n_kv, pt, hd)
    if scales is not None:
        k = k.astype(jnp.float32) * scales["k"][:, pages][..., None]
        v = v.astype(jnp.float32) * scales["v"][:, pages][..., None]
    layers, n, n_kv, pt, hd = k.shape
    return _each_side(
        lambda a: a.swapaxes(1, 2).reshape(layers, n_kv, n * pt, hd)[:, None],
        (k, v))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _page_copy_jit(arena_k, arena_v, scales, src, dst):
    """Copy one arena page ``src`` -> ``dst`` in place (donated buffers, no
    arena-sized copy). This is the copy-on-write fast path: the host swaps
    the lane's block-table entry to ``dst`` afterwards and decrefs ``src``.
    ``src``/``dst`` are traced scalars, so every CoW event reuses the single
    compiled program — the decode-chunk program count is untouched. An int8
    arena's per-row scales (``scales`` {"k","v"}, donated) travel with the
    page bytes — a CoW'd or published page stays bit-identical."""
    arena_k, arena_v = _each_side(
        lambda a: a.at[:, dst].set(a[:, src]), (arena_k, arena_v))
    if scales is not None:
        scales = {
            "k": scales["k"].at[:, dst].set(scales["k"][:, src]),
            "v": scales["v"].at[:, dst].set(scales["v"][:, src]),
        }
    return arena_k, arena_v, scales


@jax.jit
def _pages_export_jit(arena_k, arena_v, scales, pages):
    """Gather ``n`` arena pages' RAW rows for conversation parking
    (cache/conversation_kv.py): page-layout (layers, n, n_kv, page_tokens,
    hd) in the arena dtype, plus the int8 arena's per-row scales
    (layers, n, n_kv, page_tokens) when present. Read-only on the arena —
    parking copies, it never steals — and deliberately NOT dequantized:
    the parked bytes must re-import bit-identical, and int8 + scales is
    half the host/disk footprint of dense rows. One compile per distinct
    page count, bounded by pages_per_slot."""
    k, v = _each_side(lambda a: a[:, pages], (arena_k, arena_v))
    if scales is None:
        return k, v, None
    return k, v, {"k": scales["k"][:, pages], "v": scales["v"][:, pages]}


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _pages_import_jit(arena_k, arena_v, scales, pages, pk, pv, pscales):
    """Scatter parked page payloads (the `_pages_export_jit` layout) back
    into freshly reserved arena pages — the resume half of the park cycle.
    Donated arena buffers, batched over all pages in one dispatch; the
    payload is already in the arena dtype so the set is a verbatim byte
    move and a park/resume round-trip leaves every page bit-identical to a
    lane that never retired. One compile per page count, same bound as the
    export."""
    arena_k = arena_k.at[:, pages].set(pk.astype(arena_k.dtype))
    if arena_v is not None:
        arena_v = arena_v.at[:, pages].set(pv.astype(arena_v.dtype))
    if scales is not None:
        scales = {
            "k": scales["k"].at[:, pages].set(pscales["k"]),
            "v": scales["v"].at[:, pages].set(pscales["v"]),
        }
    return arena_k, arena_v, scales


@functools.partial(
    jax.jit,
    static_argnames=("cfg_key", "family", "chunk", "page_tokens", "kernel"),
    donate_argnums=(1, 2, 3, 11, 12),
)
def _paged_decode_chunk_jit(
    params,
    arena_k,             # (layers, n_pages, n_kv, page_tokens, hd), or packed (init_paged_cache) — donated
    arena_v,
    scales,              # {"k","v"} int8 per-row scale buffers | None — donated
    tables,              # (S, pages_per_slot) i32 block tables
    tok,                 # (S,) last sampled token per lane
    pos,                 # (S,) i32 write position per lane
    active,              # (S,) bool — frozen for the whole chunk
    counter,             # () uint32 — the chunk's number in its state's key stream
    temperature,         # (S,) f32 per-lane
    top_k,               # (S,) i32 per-lane
    lane_state=None,     # (lane layers, S, rows, width) | None — donated
    window=None,         # (wk, wv) the window layers' ring arena | None — donated
    *,
    cfg_key,
    family: str = "transformer_lm",
    chunk: int,
    page_tokens: int,
    kernel: bool = False,
):
    """Advance every ACTIVE lane by ``chunk`` decode steps in one compiled
    program — the continuous engine's only steady-state dispatch. K/V live
    in the shared page arena and each lane reads through its block table.
    Inactive lanes ride along: their token/pos are frozen
    (``where(active, ...)``) and their writes, those the write does not leave
    out (``_paged_write_rows`` follows the live lanes), land on the trash
    page; the host ignores their emitted tokens. Admission/retirement happen on the
    host BETWEEN chunks; a row finishing mid-chunk keeps decoding from its
    own EOS until the chunk ends (the < chunk overshoot the wasted-steps
    counter measures). ``tables`` is traced, so recycling pages never
    mints a new program; compiled-program count stays one per chunk size
    (x2 for the ``kernel`` boolean — the serving.kv_paged_kernel gate).

    The small operands (``tables`` … ``top_k``) may be NumPy mirrors or device
    arrays the caller kept from an earlier chunk (``runtime.slot_decode_chunk``
    sends one only when its mirror changed; ``tok`` / ``pos`` are the last
    chunk's own outputs). The chunk's keys, one a step, are derived HERE from
    ``counter``: ``jax.random.split(jax.random.PRNGKey(counter), chunk)``, bit
    for bit what a host made of the same number, so no key program runs
    between two chunks; the last output is ``counter + 1``, the next chunk's
    operand, so the counter need not be sent either.

    The output after the tokens is the chunk's routing stats for a model
    with expert layers: float32 ``(experts_hit, expert_rows_max,
    expert_rows_local)``, each a mean over the chunk's steps and the layers
    that hold experts, computed by the program and fetched with the tokens;
    ``None`` (no output at all) for a dense model, whose program is therefore
    the one it was. The next is ``lane_state`` after the chunk (donated like
    the arena and carried by the same scan; an inactive lane's slice comes
    back bit for bit), ``None`` in and out for a model that keeps rows only;
    ``counter + 1`` follows it. A model with window layers takes their ring
    arena as ``window`` (donated and carried like the global arena; what a
    window call reads of it is worked out from ``pos`` inside the program, so
    it brings no operand of its own) and returns it LAST, one output more than
    every other model's program has."""
    cfg = dict(cfg_key)
    live = _live_lanes(active)       # once a chunk: ``active`` is frozen
    rngs = jax.random.split(jax.random.PRNGKey(counter), chunk)

    def step(carry, rng):
        cache, tok, pos = carry
        layer_stats: list = []
        logits, cache = _paged_forward_step(
            params, tok, cache, tables, pos, cfg, family,
            page_tokens, kernel=kernel, active=active, moe_stats=layer_stats,
            live=live,
        )
        nxt = _sample_per_row(logits[:, 0], rng, temperature, top_k, active)
        nxt = jnp.where(active, nxt, tok)
        pos = pos + active.astype(jnp.int32)
        # (3,): the step's mean over its expert layers; nothing for a dense model
        stats = jnp.mean(jnp.stack(layer_stats), axis=0) if layer_stats else None
        return (cache, nxt, pos), (nxt, stats)

    (cache, tok, pos), (toks, stats) = jax.lax.scan(
        step, (_arena_cache(arena_k, arena_v, scales, lane_state, window),
               tok, pos),
        rngs, length=chunk
    )
    if stats is not None:
        stats = jnp.mean(stats, axis=0)
    return (*_cache_arena(cache), tok, pos,
            jnp.transpose(toks, (1, 0)), stats,  # (S, chunk), (3,) | None
            cache.get("lane"), counter + 1, *_cache_window(cache))


@functools.partial(jax.jit, donate_argnums=(0,))
@jax.named_scope("state_insert")
def _lane_insert_jit(lane_state, new, lane):
    """An admitted request's lane state ``new (lane layers, 1, rows, width)``
    (``_slot_prefill_jit``'s last output) into slice ``lane`` of the state
    array, in place (donated); every part of a state of several parts in the
    one dispatch. ``lane`` is traced: one program a model."""
    return jax.tree_util.tree_map(
        lambda state, part: state.at[:, lane].set(
            part[:, 0].astype(state.dtype)), lane_state, new)


# what a decode chunk reports of its expert layers, in the order of its last
# output (``ops.moe.moe_experts``' stats; the ring's fields of the same names)
MOE_STATS = ("experts_hit", "expert_rows_max", "expert_rows_local")


def _ffn_block(layer: dict, x, cfg: dict, dtype, row_mask=None,
               moe_stats: list | None = None, took=None,
               beside_experts: bool = False):
    """The second half of a decoder layer (input is the residual stream
    BEFORE its norm; returns the residual delta), chosen by what the layer
    holds: ``moe`` = the routed expert layer, else the dense SwiGLU ``mlp``
    (normed by ``ln2`` before it, or by ``ln2_post`` after it in a layer that
    holds that leaf: the Olmo 2/3 family's reordered block).
    ``row_mask`` (one flag a row of ``x`` flattened) marks rows whose answer
    nobody reads: the expert layer routes them nowhere. ``took (B,)`` says how
    many of each example's rows are real (None = all): the dense MLP, and an
    expert layer's shared expert, run over the row blocks that hold them
    (``over_real_rows``). An expert layer's routing stats (``MOE_STATS``) are
    appended to ``moe_stats`` where the caller gives a list. A dense layer of
    a model that holds expert layers too (``beside_experts``) runs under
    ``layer/ffn/dense``, so that no reader by scope books it to the experts;
    a model that is dense throughout keeps ``layer/ffn``."""
    if "moe" in layer:
        y, stats = _moe_block(layer, x, cfg, dtype, row_mask=row_mask,
                              took=took)
        if moe_stats is not None:
            moe_stats.append(jnp.stack([stats[name] for name in MOE_STATS]))
        return y
    with jax.named_scope("ffn/dense" if beside_experts else "ffn"):
        after = "ln2_post" in layer      # a reordered block: the norm follows
        mlp = jax.tree_util.tree_map(lambda w: w.astype(dtype), layer["mlp"])

        def rows_mlp(x):
            h = x if after else _norm(layer, "ln2", x, _norm_eps(cfg))
            y = (jax.nn.silu(h @ mlp["w1"]) * (h @ mlp["w3"])) @ mlp["w2"]
            return _norm(layer, "ln2_post", y, _norm_eps(cfg)) if after else y

        return over_real_rows(rows_mlp, (x,), took)


def _walk_layers(params, ids, rows, cfg, logits_at=None,
                 moe_stats: list | None = None):
    """THE walk over a model's layers for a cached forward of the tokens
    ``ids (B, T)`` -> logits f32: every layer kind is written here, once.
    ``rows`` says where a layer's rows live, how new rows are written and how
    the queries attend them: ``_PagedRows`` (the arena, ``_paged_verify_step``)
    or ``_DenseRows`` (a cache of ``max_len`` rows an example,
    ``_forward_cached_dyn``). It also holds the tokens' ``positions``, how
    many of them each lane ``took``, which rows are ``active`` (None = all)
    and the ``cache`` a lane state is read from. A layer with a ``LaneState``
    or ``NoState`` brings its operator (a ``LaneState`` that also brings a
    ``step`` takes that in a store that is ``in_place``: the paged decode
    step, on the state arrays where they lie); a layer with rows, its own or
    another layer's, is ``_attend_rows``. ``logits_at (B,)`` projects that
    one position of each example through the head."""
    dtype = jnp.dtype(cfg["dtype"])
    handed: dict = {}     # what a layer's operator hands on to later layers
    beside_experts = any("moe" in layer for layer in params["layers"])

    with jax.named_scope("embed"):
        x = params["embed"][ids].astype(dtype)                   # (B, T, d)
    for depth, (layer, kind, slot) in enumerate(
            zip(params["layers"], _layer_kinds(cfg), _layer_slots(cfg))):
        with jax.named_scope("layer"):
            if slot.lane and rows.in_place and kind.step is not None:
                # the state arrays where they lie: the live lanes' slices
                out, lane, extras = kind.step(
                    layer, x, rows.cache["lane"], slot.index, rows.took,
                    rows.live, cfg)
                rows.cache = {**rows.cache, "lane": lane}
                handed.update(extras or {})
                x = x + out
            elif slot.lane:
                out, after, extras = _lane_layer(
                    layer, x, _lane_slice(rows.cache["lane"], slot.index),
                    rows.took, kind, cfg)
                rows.keep_lane_state(slot, after)
                handed.update(extras or {})
                x = x + out
            elif isinstance(kind, NoState):
                x = x + kind.operator(layer, x, handed, cfg)
            else:
                x = _attend_rows(layer, x, kind, slot, depth, rows, cfg)
            x = x + _ffn_block(layer, x, cfg, dtype, row_mask=rows.active,
                               moe_stats=moe_stats, took=rows.took,
                               beside_experts=beside_experts)
    if logits_at is not None:
        x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
    return _output_logits(params, x, dtype, _norm_eps(cfg))


def _attend_rows(layer: dict, x, kind, slot: Slot, depth: int, rows, cfg):
    """The attention half of layer ``depth``, a layer with rows -> the residual
    stream after it. The weights' cast and the norm (of what the layer takes,
    ``ln1``, or of what it gives, ``ln1_post``: the leaf it holds says
    which); then a latent row's
    projection, write and attention are the store's own (``latent_layer``);
    every other layer is projected here (differential, or rotary by the
    layer's kind), its rows written by the store (unless they are another
    layer's, ``SharedRows``: written when that one ran), its queries attended
    by the store, and the heads finished here (times ``sigmoid`` of a
    projection of the layer's normed input where ``attn`` holds ``w_gate``:
    an output gate). A config whose ``rope_theta``
    is None applies no rotary. The two token-wise halves written here (norm,
    projection and rotary before the store; ``wo`` and a following norm after
    it) run over the row blocks that hold the ``rows.took`` real rows
    (``over_real_rows``: a long prefill; any other forward traces them
    whole)."""
    b, t, _ = x.shape
    shared = isinstance(kind, SharedRows)

    def kind_scope():
        return _kind_scope(
            bool(_window_of(cfg)),
            "cross" if shared else "window" if slot.window else "global")

    with jax.named_scope("attn"):
        attn = jax.tree_util.tree_map(lambda w: w.astype(x.dtype), layer["attn"])
        after = "ln1_post" in layer      # a reordered block: the norm follows

    def normed(x):
        return x if after else _norm(layer, "ln1", x, _norm_eps(cfg))

    latent, differential = _cache_row(cfg).sides == 1, "lam_q1" in attn
    if latent or differential:    # their projections take the bucket's norm
        with jax.named_scope("attn"):
            a = normed(x)
    if latent:
        return rows.latent_layer(attn, a, x, slot)
    with jax.named_scope("attn"):
        if differential:
            q, k, v, scale = _differential_qkv(attn, a, cfg)
        else:
            # a QK-norm's own eps only where the model states one
            eps = (cfg["qk_norm_eps"],) if "qk_norm_eps" in cfg else ()
            rope, scale = rope_of(cfg, slot.window), None

            def project(x, positions):
                a = normed(x)
                q, k, v = _qkv(attn, a, query_heads(cfg, depth),
                               cfg["n_kv_heads"], *eps)
                if cfg["rope_theta"] is not None:     # None: no rotary at all
                    q = _rope_per_example(q, positions, cfg["rope_theta"], rope)
                    k = _rope_per_example(k, positions, cfg["rope_theta"], rope)
                if "w_gate" not in attn:
                    return q, k, v, None
                # an output gate a head column or a head (the leaf's width
                # says which), laid out as the heads' outputs are
                with kind_scope():
                    return q, k, v, head_gate(attn, a, q.shape[1])

            # the heads' rows lie along axis 2: (B, heads, T, width)
            q, k, v, gate = over_real_rows(
                project, (x, rows.positions), rows.took, out_axis=2)
    if not shared:
        rows.write(slot, k, v)
    with jax.named_scope("attn"), kind_scope():
        out = rows.attend(slot, q, scale).reshape(
            b, q.shape[1], t, q.shape[-1])
        if differential:
            return x + diff_finish(attn, diff_outputs(out), depth, x.dtype)

        def finish(out, gate=None):
            out = out.astype(x.dtype)
            if gate is not None:          # a layer that holds ``w_gate``
                out = out * gate
            out = out.transpose(0, 2, 1, 3)
            # heads x head width: the hidden size for most models
            out = out.reshape(b, out.shape[1], -1) @ attn["wo"]
            return (_norm(layer, "ln1_post", out, _norm_eps(cfg)) if after
                    else out)

        return x + over_real_rows(
            finish, (out,) if gate is None else (out, gate), rows.took,
            in_axis=2)


def _forward_cached_dyn(params, input_ids, cache, start_pos, cfg,
                        family: str = "transformer_lm", fresh: bool = False,
                        logits_at=None, real_len=None):
    """Like _forward_cached but with PER-EXAMPLE start positions (B,) —
    needed because prompts in one batch have different true lengths.
    ``fresh`` promises every start is 0 and the cache empty (a latent family
    then takes its expanded form; the K/V families compute the same either
    way). ``logits_at (B,)`` projects that one position of each example
    through the head -> logits ``(B, 1, V)``; None = every position.
    ``real_len (B,)`` is how many of the tokens at hand are real (None = all):
    a layer that keeps a lane state (``cache["lane"]``) leaves the state after
    that many, not after a right-padded prompt's pad tokens.

    A model with WINDOW layers keeps every row of every layer in this dense
    cache and applies each layer's mask (a window layer's query reads itself
    and the ``window - 1`` positions before it). Its ``fresh`` forward attends
    among the tokens at hand through ``ops.attention.attention`` in EVERY
    layer (the flash kernel where its gate admits, with the window's blocks
    skipped in a window layer), so no ``(S, max_len)`` score block is built: an
    8192-token prompt's would be 8.6 GB. So does the ``fresh`` forward of any
    other K/V model whose score block would reach a GiB
    (``_attends_tokens_at_hand``); below that the other K/V families' programs
    are the ones they were."""
    rows = _DenseRows(cache, start_pos, input_ids.shape[1], cfg, fresh,
                      real_len)
    logits = _walk_layers(params, input_ids, rows, cfg, logits_at=logits_at)
    return logits, rows.cache_after()


class _DenseRows:
    """Where ``_walk_layers`` keeps a layer's rows for ``_forward_cached_dyn``:
    in a dense cache ``(row layers, B, heads, max_len, width)``, each example's
    new rows written at its own ``start_pos`` and the queries attending the
    layer's whole length under a mask (that docstring says when the tokens at
    hand alone are attended). Nothing is updated in place: the layers' rows
    and lane states after the forward are gathered a layer and stacked at the
    end (``cache_after``). ``real_len (B,)`` (None = every token at hand is
    real) becomes ``took``, which the lane states and the token-wise stages
    follow, and ``active``, the rows somebody reads."""

    in_place = False   # a layer's state after the forward is stacked at the end
    live = None

    def __init__(self, cache, start_pos, s_len: int, cfg, fresh: bool,
                 real_len):
        self.positions = start_pos[:, None] + jnp.arange(s_len)[None, :]  # (B, S)
        self.cache, self.start_pos, self.took = cache, start_pos, real_len
        # one flag a row flattened (None = all): a right-padded prompt's pad
        # rows go to no expert, as the paged step's inactive lanes
        self.active = None if real_len is None else (
            jnp.arange(s_len)[None, :] < real_len[:, None]).reshape(-1)
        self.cfg, self.fresh = cfg, fresh
        self.flash = fresh and _attends_tokens_at_hand(cfg, cache, s_len)
        self.k, self.v, self.lane = [], [], []    # after the forward, a layer
        self.fresh_rows: dict = {}   # a row layer's K/V of the tokens at hand

    def keep_lane_state(self, slot: Slot, after) -> None:
        self.lane.append(after)

    def write(self, slot: Slot, k, v) -> None:
        cache = self.cache
        with jax.named_scope("kv_read"):
            k_layer, v_layer = cache["k"][slot.index], cache["v"][slot.index]

        # scatter each example's K/V row into its own cache offset
        def upd(cache_l, kv):
            def one(c, kv_b, p):
                return jax.lax.dynamic_update_slice(c, kv_b, (0, p, 0))
            return jax.vmap(one)(cache_l, kv, self.start_pos)

        with jax.named_scope("kv_write"):
            self.k.append(upd(k_layer, k.astype(cache["k"].dtype)))
            self.v.append(upd(v_layer, v.astype(cache["v"].dtype)))
        self.fresh_rows[slot.index] = (k, v)

    def attend(self, slot: Slot, q, scale):
        """Per-example visibility: key pos <= query pos. GQA grouped-K/V form:
        query heads fold into (kv_head, group) so the cache is read as-is,
        never repeated up to n_heads (the repeat would materialize group x
        cache bytes every step at exactly the scale GQA exists for)."""
        if self.flash:
            # the tokens at hand are all there is: no score block over the
            # cache's length, a window layer's blocks skipped
            k, v = self.fresh_rows[slot.index]
            return attention(q, k, v, causal=True, window=slot.window,
                             sm_scale=scale)
        k_cache, v_cache = self.k[slot.index], self.v[slot.index]
        b, _, s_len, d = q.shape
        heads = k_cache.shape[1]
        # dots read the caches in their stored dtype: upcasting K/V to f32 here
        # doubled the HBM bytes of the cache read EVERY decode step — the read
        # that dominates decode. Scores/softmax still accumulate f32 via
        # preferred_element_type (the flash-kernel recipe).
        qg = q.reshape(b, heads, q.shape[1] // heads, s_len, d)
        s = jnp.einsum("bkgqd,bkld->bkgql", qg, k_cache,
                       preferred_element_type=jnp.float32)
        s = s / math.sqrt(d) if scale is None else s * scale
        k_pos = jnp.arange(k_cache.shape[2])
        positions = self.positions
        mask = k_pos[None, None, :] <= positions[:, :, None]  # (B, S, max_len)
        if slot.window:
            mask &= positions[:, :, None] - k_pos[None, None, :] < slot.window
        s = jnp.where(mask[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgql,bkld->bkgqd", p.astype(v_cache.dtype), v_cache,
                          preferred_element_type=jnp.float32)

    def latent_layer(self, attn, a, x, slot: Slot):
        """A latent layer's attention half: the ONE row a token written at
        each example's ``start_pos``; then ``fresh`` (the cache holds nothing
        else) attends among the tokens at hand in the expanded form, through
        ``ops.attention.attention``; otherwise the absorbed form reads the
        layer's whole length."""
        cfg = self.cfg
        with jax.named_scope("attn"):
            rows_layer = self.cache["k"][slot.index]             # (B, 1, L, W)
            q_n, q_r, rows = latent_project(attn, a, self.positions, cfg,
                                            self.took)
            with jax.named_scope("kv_write"):
                rows_layer = jax.vmap(lambda c, new, p: jax.lax.dynamic_update_slice(
                    c, new[None], (0, p, 0))
                )(rows_layer, rows.astype(rows_layer.dtype), self.start_pos)
            self.k.append(rows_layer)
            if self.fresh:
                out = expanded_attention(attn, q_n, q_r, rows, cfg,
                                         took=self.took)
            else:
                out = dense_absorbed_attention(
                    absorbed_query(attn, q_n, q_r, cfg), rows_layer[:, 0],
                    self.positions, cfg)
                out = absorbed_output(attn, out, cfg, a.dtype)
        return x + out

    def cache_after(self) -> dict:
        with jax.named_scope("kv_write"):
            new_cache = {"k": jnp.stack(self.k)}
            if self.v:
                new_cache["v"] = jnp.stack(self.v)
        if self.lane:
            new_cache["lane"] = jax.tree_util.tree_map(
                lambda old, *parts: jnp.stack(parts).astype(old.dtype),
                self.cache["lane"], *self.lane)
        return new_cache


def _rope_per_example(x, positions, theta, rope=(None, 1.0, 0)):
    """Rotary embedding with per-example positions (B, S) over (B, H, S, D);
    ``rope`` is ``transformer_lm.rope_of``'s answer for the layer: the plain
    ``theta`` frequencies, or a kind's own with cos and sin times its factor,
    over the head's first ``turned`` columns where the kind turns a part."""
    d = x.shape[-1]
    freqs, factor, turned = rope
    if turned:    # a partial rotary: the leading columns turn, the rest pass
        head = _rope_per_example(x[..., :turned], positions, theta,
                                 (freqs, factor, 0))
        return jnp.concatenate([head, x[..., turned:]], axis=-1)
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(freqs)[None, None, :]  # (B,S,d/2)
    cos = jnp.cos(angles)[:, None]                                            # (B,1,S,d/2)
    sin = jnp.sin(angles)[:, None]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = x[..., 0::2], x[..., 1::2]
    rot = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rot.reshape(x.shape).astype(x.dtype)


def generate(  # static-bounded: cfg_key, max_new_tokens, return_cache -- cfg_key is per-model config; runtime callers pass pow2-bucketed max_new_tokens (next_bucket); return_cache is boolean
    model_def: Any,
    params: Any,
    input_ids,
    prompt_lengths=None,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: int = 0,
    rng=None,
    return_cache: bool = False,
) -> jax.Array:
    """Generate ``max_new_tokens`` per row of ``input_ids`` (B, S prompt,
    right-padded to a common S; ``prompt_lengths`` gives true lengths).

    Decoder-LM families sharing the transformer_lm attention/cache layout
    are supported (transformer_lm, moe_lm). Returns (B, max_new_tokens)
    int32 token ids; with ``return_cache`` also the final KV arrays (the
    prefix cache stores them for reuse).
    """
    if not model_def.engine_ready:
        raise ValueError(
            "generation supports the decoder-LM families (transformer_lm, "
            f"moe_lm: ModelDef.engine_ready), not {model_def.family!r}"
        )
    input_ids = jnp.asarray(input_ids, jnp.int32)
    b, s = input_ids.shape
    if prompt_lengths is None:
        prompt_lengths = jnp.full((b,), s, jnp.int32)
    else:
        prompt_lengths = jnp.asarray(prompt_lengths, jnp.int32)
    cfg = model_def.config
    if s + max_new_tokens > cfg["max_seq"]:
        raise ValueError(
            f"prompt {s} + max_new_tokens {max_new_tokens} exceeds max_seq {cfg['max_seq']}"
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    cfg_key = static_config(model_def)
    return _generate_jit(
        params,
        input_ids,
        prompt_lengths,
        rng,
        jnp.asarray(temperature, jnp.float32),
        jnp.asarray(top_k, jnp.int32),
        cfg_key=cfg_key,
        max_new_tokens=max_new_tokens,
        family=model_def.family,
        return_cache=return_cache,
    )


def generate_from_cache(  # static-bounded: cfg_key, max_new_tokens, return_cache -- cfg_key is per-model config; runtime callers pass pow2-bucketed max_new_tokens (next_bucket); return_cache is boolean
    model_def: Any,
    params: Any,
    suffix_ids,
    suffix_len: int,
    cached_k,
    cached_v,
    cached_len: int,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: int = 0,
    rng=None,
    return_cache: bool = False,
):
    """Continue a (B=1) generate from a cached prompt-prefix KV (the prefix
    cache's fast path — runtime/prefix_cache.py). ``suffix_ids`` (1, S') are
    the prompt tokens after the cached prefix, padded; ``cached_len`` is the
    number of valid rows in the padded ``cached_k/v``."""
    import jax

    if rng is None:
        rng = jax.random.PRNGKey(0)
    cfg = model_def.config
    cfg_key = static_config(model_def)
    return _generate_from_cache_jit(
        params,
        jnp.asarray(suffix_ids, jnp.int32),
        jnp.asarray([suffix_len], jnp.int32),
        cached_k,
        cached_v,
        jnp.asarray([cached_len], jnp.int32),
        rng,
        jnp.asarray(temperature, jnp.float32),
        jnp.asarray(top_k, jnp.int32),
        cfg_key=cfg_key,
        max_new_tokens=max_new_tokens,
        family=model_def.family,
        return_cache=return_cache,
    )
