"""Greedy speculative decoding: a small draft model proposes ``spec_tokens``
tokens per round, the target model verifies them in ONE chunked forward.

No reference counterpart (the reference proxies opaque Predict calls —
SURVEY.md §5). This is a TPU-shaped throughput feature: plain decode is one
MXU-starved (B, 1, D) matmul per token, serial in S; verification processes
``spec+1`` positions per target forward at MXU-friendly width, so accepted
drafts amortize the expensive model's weight reads over several tokens.

Exactness: at temperature 0 the emitted sequence matches the target
model's own greedy decode (tokens are only kept while they match the
target's argmax, and the first mismatch is replaced by the target's own
choice — the draft can change WHEN tokens are computed, never WHICH).
``tests/test_speculative.py`` asserts this token-for-token. Caveat: the
chunked verify forward and the width-1 decode forward are different matmul
shapes, so on accelerators a near-TIED argmax can round the other way —
the guarantee is "the target's greedy decode under the verify shapes",
bitwise on CPU/f32, argmax-tie-sensitive in bf16.

Cache discipline (the part that makes rollback free): a verify chunk always
starts exactly at the current accepted position, and attention masks reads
to ``k_pos <= query_pos`` — so K/V rows written for later-rejected tokens
are invisible until the next chunk overwrites them. "Rollback" is just not
advancing the position pointer (models/generation.py's mask, reused as-is).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from tfservingcache_tpu.models.generation import (
    _forward_cached_dyn,
    _paged_forward_step,
    _paged_verify_step,
    _sample_per_row,
    init_cache,
)
from tfservingcache_tpu.models.registry import static_config


def _greedy(logits) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _spec_decode_loop(params_t, params_d, cache_t, cache_d, first, prompt_len,
                      cfg_t, cfg_d, family_t, family_d, spec: int,
                      max_new_tokens: int):
    """The draft-propose / target-verify loop, shared by the plain and the
    cached-prefix entries (their caches differ only in how the TARGET
    prefill was produced; absolute positions are identical). Returns
    (out, rounds, cache_t, final_tok, final_idx) — final_tok is the last
    round's carry, final_idx its EMITTED index (n_done_old + a, unclamped):
    when the final round overshoots max_new_tokens the carry was never
    returned to the client and must NOT be written at the last completion
    position (see _writeback_final)."""
    b = first.shape[0]
    out = jnp.zeros((b, max_new_tokens), jnp.int32)
    out = out.at[:, 0].set(first)
    n_done = jnp.ones((b,), jnp.int32)
    rows = jnp.arange(b)[:, None]
    jrange = jnp.arange(spec + 1)
    final_idx0 = jnp.zeros((b,), jnp.int32)  # `first` sits at emitted idx 0

    def cond(carry):
        _, _, _, n_done, _, _, _ = carry
        return jnp.any(n_done < max_new_tokens)

    def body(carry):
        cache_t, cache_d, cur_tok, n_done, out, rounds, _ = carry
        # cur_tok is the accepted token AT position pos, not yet in either
        # cache (the same invariant as generation.py's scan step)
        pos = prompt_len + n_done - 1

        def draft_step(c, _):
            cache_d, tok, p = c
            logits, cache_d = _forward_cached_dyn(
                params_d, tok[:, None], cache_d, p, cfg_d, family_d
            )
            nxt = _greedy(logits[:, 0])
            return (cache_d, nxt, p + 1), nxt

        # spec+1 steps, not spec: the extra step forwards d_spec so its K/V
        # row lands in the draft cache. Without it a fully-accepted round
        # (a == spec) leaves a permanent never-written hole at pos+spec that
        # every later draft query attends to — silently decaying acceptance
        # (and the whole speedup) while the target keeps the output correct.
        (cache_d, _, _), d_toks = jax.lax.scan(
            draft_step, (cache_d, cur_tok, pos), None, length=spec + 1
        )
        d = jnp.transpose(d_toks[:spec], (1, 0))               # (B, spec)

        # one chunked target forward verifies all proposals: logits_j
        # predicts position pos+1+j
        chunk = jnp.concatenate([cur_tok[:, None], d], axis=1)  # (B, spec+1)
        logits_t, cache_t = _forward_cached_dyn(
            params_t, chunk, cache_t, pos, cfg_t, family_t
        )
        g = _greedy(logits_t)                                   # (B, spec+1)
        matches = (d == g[:, :spec]).astype(jnp.int32)
        a = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)       # (B,) 0..spec

        # emitted this round: d_1..d_a (== g_0..g_{a-1}) then g_a — always
        # a+1 target-greedy tokens
        g_at_a = jnp.take_along_axis(g, a[:, None], axis=1)[:, 0]
        d_pad = jnp.concatenate([d, jnp.zeros((b, 1), jnp.int32)], axis=1)
        e = jnp.where(
            jrange[None, :] < a[:, None], d_pad,
            jnp.where(jrange[None, :] == a[:, None], g_at_a[:, None], 0),
        )
        idx = n_done[:, None] + jrange[None, :]
        valid = (jrange[None, :] <= a[:, None]) & (idx < max_new_tokens)
        idx = jnp.where(valid, idx, max_new_tokens)             # OOB -> drop
        out = out.at[rows, idx].set(e, mode="drop")

        carry_idx = n_done + a  # g_at_a's emitted index, unclamped
        n_done = jnp.minimum(n_done + a + 1, max_new_tokens)
        return cache_t, cache_d, g_at_a, n_done, out, rounds + 1, carry_idx

    cache_t, _, final_tok, _, out, rounds, final_idx = jax.lax.while_loop(
        cond, body,
        (cache_t, cache_d, first, n_done, out, jnp.int32(0), final_idx0),
    )
    # rounds is a cheap health signal: a well-aligned draft should emit
    # ~spec+1 tokens per round; tests use it to catch acceptance decay that
    # exactness alone can't see (output stays correct regardless)
    return out, rounds, cache_t, final_tok, final_idx


def _writeback_final(params_t, cache_t, final_tok, final_idx, prompt_len,
                     cfg_t, family_t, max_new_tokens: int):
    """One (B, 1) target forward so the LAST completion position's K/V row
    is valid: rows are then correct for the whole prompt+completion. Every
    other emitted token was the input of some later verify chunk, so its
    row is already written; rejected tokens' rows were overwritten by the
    chunk that followed their rejection (the cache discipline in the module
    docstring).

    Overshoot case (final round clamped: final_idx > max_new-1): the carry
    was NEVER emitted, while the true last token out[:, max_new-1] was an
    ACCEPTED draft input of that chunk — its row is already correct.
    Writing the carry at prompt_len+max_new-1 would stomp it with a
    different token's K/V and poison the stored prefix entry, so the
    forward is aimed one slot PAST the persisted range instead (the slack
    rows every spec cache allocates; the row is junk nobody reads)."""
    overshoot = (final_idx > max_new_tokens - 1).astype(jnp.int32)
    pos = prompt_len + max_new_tokens - 1 + overshoot
    _, cache_t = _forward_cached_dyn(
        params_t, final_tok[:, None], cache_t, pos, cfg_t, family_t,
    )
    return cache_t


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg_t_key", "cfg_d_key", "max_new_tokens", "spec_tokens",
        "family_t", "family_d", "return_cache",
    ),
)
def _speculative_jit(
    params_t,
    params_d,
    input_ids,
    prompt_len,
    *,
    cfg_t_key,
    cfg_d_key,
    max_new_tokens: int,
    spec_tokens: int,
    family_t: str,
    family_d: str,
    return_cache: bool = False,
):
    cfg_t = dict(cfg_t_key)
    cfg_d = dict(cfg_d_key)
    b, s_max = input_ids.shape
    spec = spec_tokens
    # slack for chunk writes past the last emitted position (stale rows are
    # masked off and finished examples may keep writing while others drain)
    max_len = s_max + max_new_tokens + spec + 1
    cache_t = init_cache(cfg_t, b, max_len)
    cache_d = init_cache(cfg_d, b, max_len)

    zeros = jnp.zeros((b,), jnp.int32)
    logits_t, cache_t = _forward_cached_dyn(
        params_t, input_ids, cache_t, zeros, cfg_t, family_t
    )
    _, cache_d = _forward_cached_dyn(
        params_d, input_ids, cache_d, zeros, cfg_d, family_d
    )
    last = jnp.take_along_axis(
        logits_t, (prompt_len - 1)[:, None, None], axis=1
    )[:, 0]
    first = _greedy(last)

    out, rounds, cache_t, final_tok, final_idx = _spec_decode_loop(
        params_t, params_d, cache_t, cache_d, first, prompt_len,
        cfg_t, cfg_d, family_t, family_d, spec, max_new_tokens,
    )
    if return_cache:
        cache_t = _writeback_final(
            params_t, cache_t, final_tok, final_idx, prompt_len, cfg_t,
            family_t, max_new_tokens,
        )
        return out, rounds, cache_t["k"], cache_t["v"]
    return out, rounds


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg_t_key", "cfg_d_key", "max_new_tokens", "spec_tokens",
        "family_t", "family_d", "return_cache",
    ),
)
def _speculative_from_cache_jit(
    params_t,
    params_d,
    input_ids,          # (1, S_pad) FULL prompt — the draft prefills it all
    prompt_len,         # (1,)
    suffix_ids,         # (1, S_suffix_pad) prompt tokens AFTER the prefix
    suffix_len,         # (1,)
    cached_k,           # (layers, 1, n_kv, Lpad, head_dim) TARGET prefix K/V
    cached_v,
    cached_len,         # (1,) valid prefix rows; cached_len+suffix_len==prompt_len
    *,
    cfg_t_key,
    cfg_d_key,
    max_new_tokens: int,
    spec_tokens: int,
    family_t: str,
    family_d: str,
    return_cache: bool = True,
):
    """Speculative decoding whose TARGET prefill starts from cached prompt-
    prefix K/V (runtime/prefix_cache.py): turn N of a draft-assisted
    conversation pays target prefill only for its new tokens. The draft has
    no cached rows — it prefills the full prompt, which costs a fraction of
    the target prefill it replaces. Absolute positions are identical to the
    plain path, so the verify loop is shared and the output is the same
    greedy sequence."""
    cfg_t = dict(cfg_t_key)
    cfg_d = dict(cfg_d_key)
    b, s_max = input_ids.shape
    spec = spec_tokens
    _, s_pad = suffix_ids.shape
    l_pad = cached_k.shape[3]

    # target: copy prefix rows, prefill only the suffix
    cache_t = init_cache(cfg_t, b, l_pad + s_pad + max_new_tokens + spec + 1)
    cache_t = {
        "k": jax.lax.dynamic_update_slice(
            cache_t["k"], cached_k.astype(cache_t["k"].dtype), (0, 0, 0, 0, 0)
        ),
        "v": jax.lax.dynamic_update_slice(
            cache_t["v"], cached_v.astype(cache_t["v"].dtype), (0, 0, 0, 0, 0)
        ),
    }
    start = cached_len.astype(jnp.int32)
    logits_t, cache_t = _forward_cached_dyn(
        params_t, suffix_ids, cache_t, start, cfg_t, family_t
    )
    last = jnp.take_along_axis(
        logits_t, (suffix_len - 1)[:, None, None], axis=1
    )[:, 0]
    first = _greedy(last)

    # draft: full prefill (no draft rows are cached)
    cache_d = init_cache(cfg_d, b, s_max + max_new_tokens + spec + 1)
    _, cache_d = _forward_cached_dyn(
        params_d, input_ids, cache_d, jnp.zeros((b,), jnp.int32), cfg_d,
        family_d,
    )

    out, rounds, cache_t, final_tok, final_idx = _spec_decode_loop(
        params_t, params_d, cache_t, cache_d, first, prompt_len,
        cfg_t, cfg_d, family_t, family_d, spec, max_new_tokens,
    )
    if return_cache:
        cache_t = _writeback_final(
            params_t, cache_t, final_tok, final_idx, prompt_len, cfg_t,
            family_t, max_new_tokens,
        )
        return out, rounds, cache_t["k"], cache_t["v"]
    return out, rounds


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg_t_key", "cfg_d_key", "family_t", "family_d", "spec",
        "page_tokens", "kernel",
    ),
    donate_argnums=(2, 3, 4, 5, 6, 7),
)
def _paged_spec_round_jit(  # static-bounded: cfg_t_key, cfg_d_key, family_t, family_d, spec, page_tokens, kernel -- one value per (target, draft) model pair (config/family), spec is clamped to {1,2,4,8} at attach, page_tokens is ServingConfig kv_page_tokens, kernel is a boolean
    params_t,
    params_d,
    t_k,                 # target arena (layers, n_pages, n_kv, pt, hd) — donated
    t_v,
    t_scales,            # {"k","v"} int8 per-row scales | None — donated
    d_k,                 # draft arena — donated
    d_v,
    d_scales,
    t_tables,            # (S, pps_t) i32 target block tables
    d_tables,            # (S, pps_d) i32 draft block tables
    tok,                 # (S,) carry token per lane (at position pos, unwritten)
    pos,                 # (S,) i32 write position per lane
    active,              # (S,) bool — frozen for the whole round
    rng,                 # (2,) uint32 — one key per round
    temperature,         # (S,) f32 per-lane
    top_k,               # (S,) i32 per-lane
    *,
    cfg_t_key,
    cfg_d_key,
    family_t: str,
    family_d: str,
    spec: int,
    page_tokens: int,
    kernel: bool = False,
):
    """One speculative round for EVERY lane of the continuous engine: the
    draft proposes ``spec`` greedy tokens per lane (a spec+1-step paged
    scan over its own arena — the extra step writes d_spec's K/V row so
    full acceptance leaves no hole, same reasoning as ``_spec_decode_loop``),
    then ONE multi-position target forward verifies all spec+1 positions
    and each lane accepts a variable-length prefix.

    Per-row accept counts are TRACED data — ``accept`` comes back as an
    (S,) array and ``pos`` advances by it in-graph — so every acceptance
    pattern reuses this single program (the PR 3 per-row-sampling
    discipline; the executable-count guard test pins it). Non-greedy lanes
    (temperature > 0) degrade IN-GRAPH to 1-token decode: their accept
    count is forced to 0 and their emitted token is sampled from the
    verify pass's position-0 logits — exactly the token the plain chunk
    would have produced, under the same per-row sampling math.

    Rollback is the paged arena's mask discipline verbatim: rejected-
    suffix rows in both caches sit above the new ``pos`` and are
    overwritten write-before-read by the next round's first write at the
    carry position. Returns (t_k, t_v, t_scales, d_k, d_v, d_scales,
    tok', pos', toks (S, spec+1), accept (S,)) where lane ``s`` emits
    ``toks[s, :accept[s]]`` this round (accept = a+1 for active lanes,
    0 for frozen ones)."""
    cfg_t = dict(cfg_t_key)
    cfg_d = dict(cfg_d_key)

    cache_t = {"k": t_k, "v": t_v}
    if t_scales is not None:
        cache_t["k_scale"] = t_scales["k"]
        cache_t["v_scale"] = t_scales["v"]
    cache_d = {"k": d_k, "v": d_v}
    if d_scales is not None:
        cache_d["k_scale"] = d_scales["k"]
        cache_d["v_scale"] = d_scales["v"]

    def draft_step(c, _):
        cache_d, tk, p = c
        logits, cache_d = _paged_forward_step(
            params_d, tk, cache_d, d_tables, p, cfg_d, family_d,
            page_tokens, kernel=kernel,
        )
        nxt = _greedy(logits[:, 0])
        return (cache_d, nxt, p + 1), nxt

    (cache_d, _, _), d_toks = jax.lax.scan(
        draft_step, (cache_d, tok, pos), None, length=spec + 1
    )
    d = jnp.transpose(d_toks[:spec], (1, 0))                # (S, spec)

    # one multi-position target forward scores all spec+1 positions:
    # logits_t[:, j] predicts position pos+1+j
    chunk = jnp.concatenate([tok[:, None], d], axis=1)      # (S, spec+1)
    logits_t, cache_t = _paged_verify_step(
        params_t, chunk, cache_t, t_tables, pos, cfg_t, family_t,
        page_tokens, kernel=kernel,
    )
    g = _greedy(logits_t)                                   # (S, spec+1)
    matches = (d == g[:, :spec]).astype(jnp.int32)
    a = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)       # (S,) 0..spec

    # greedy rows emit g[:, :a+1] (for j < a, d_j == g_j so the target-
    # greedy rows ARE the emitted stream); non-greedy rows accept nothing
    # and emit one token sampled from the position-0 logits — identical
    # math to the plain chunk's _sample_per_row step
    greedy_row = temperature <= 0.0
    e0 = _sample_per_row(logits_t[:, 0], rng, temperature, top_k, active)
    a = jnp.where(greedy_row, a, 0)
    toks = g.at[:, 0].set(jnp.where(greedy_row, g[:, 0], e0))
    accept = jnp.where(active, a + 1, 0)                    # emitted count
    carry = jnp.take_along_axis(toks, a[:, None], axis=1)[:, 0]
    tok = jnp.where(active, carry, tok)
    pos = pos + accept

    t_scales = (
        {"k": cache_t["k_scale"], "v": cache_t["v_scale"]}
        if t_scales is not None else None
    )
    d_scales = (
        {"k": cache_d["k_scale"], "v": cache_d["v_scale"]}
        if d_scales is not None else None
    )
    return (cache_t["k"], cache_t["v"], t_scales,
            cache_d["k"], cache_d["v"], d_scales,
            tok, pos, toks, accept)


def speculative_generate(
    model_def_t: Any,
    params_t: Any,
    model_def_d: Any,
    params_d: Any,
    input_ids,
    prompt_lengths=None,
    max_new_tokens: int = 32,
    spec_tokens: int = 4,
    return_rounds: bool = False,
    return_cache: bool = False,
    cached_kv: tuple | None = None,
) -> jax.Array:
    """Greedy decode of the TARGET model, accelerated by the draft.

    Both models must share the decoder-LM cache layout (transformer_lm /
    moe_lm families) and the same vocabulary. Returns (B, max_new_tokens)
    int32 matching the target's own greedy decode token-for-token — exactly
    in exact arithmetic; on accelerators the chunked verify matmul and the
    width-1 decode matmul may tile/reassociate differently, so a near-tied
    argmax can break the other way (same caveat as any shape-dependent
    float reduction). ``return_rounds=True`` also returns the verify-round
    count — the acceptance-health signal tests use.

    ``return_cache=True`` (B=1) also returns the TARGET's post-decode K/V
    (rows valid for the whole prompt+completion — a final writeback forward
    covers the last carry), so the runtime can prime the prefix cache.
    ``cached_kv=(suffix_ids, suffix_len, k, v, cached_len)`` starts the
    target prefill from cached prefix rows instead of the full prompt (the
    draft still prefills the full ``input_ids``); the emitted sequence is
    the same greedy decode either way.
    """
    for md, role in ((model_def_t, "target"), (model_def_d, "draft")):
        if md.family not in ("transformer_lm", "moe_lm"):
            raise ValueError(
                f"speculative decoding supports transformer_lm/moe_lm "
                f"{role}s, not {md.family!r}"
            )
    if model_def_t.config["vocab_size"] != model_def_d.config["vocab_size"]:
        raise ValueError(
            "draft and target must share a vocabulary: "
            f"{model_def_d.config['vocab_size']} vs "
            f"{model_def_t.config['vocab_size']}"
        )
    if spec_tokens < 1:
        raise ValueError(f"spec_tokens must be >= 1, got {spec_tokens}")
    input_ids = jnp.asarray(input_ids, jnp.int32)
    b, s = input_ids.shape
    if prompt_lengths is None:
        prompt_lengths = jnp.full((b,), s, jnp.int32)
    else:
        prompt_lengths = jnp.asarray(prompt_lengths, jnp.int32)
    if s + max_new_tokens > model_def_t.config["max_seq"]:
        raise ValueError(
            f"prompt {s} + max_new_tokens {max_new_tokens} exceeds max_seq "
            f"{model_def_t.config['max_seq']}"
        )
    common = dict(
        cfg_t_key=static_config(model_def_t),
        cfg_d_key=static_config(model_def_d),
        max_new_tokens=max_new_tokens,
        spec_tokens=spec_tokens,
        family_t=model_def_t.family,
        family_d=model_def_d.family,
        return_cache=return_cache,
    )
    if cached_kv is not None:
        if b != 1:
            raise ValueError("cached-prefix speculative decoding is B=1 only")
        suffix_ids, suffix_len, ck, cv, cached_len = cached_kv
        res = _speculative_from_cache_jit(
            params_t, params_d, input_ids, prompt_lengths,
            jnp.asarray(suffix_ids, jnp.int32),
            jnp.asarray(suffix_len, jnp.int32).reshape(1),
            ck, cv, jnp.asarray(cached_len, jnp.int32).reshape(1),
            **common,
        )
    else:
        if return_cache and b != 1:
            raise ValueError("return_cache speculative decoding is B=1 only")
        res = _speculative_jit(
            params_t, params_d, input_ids, prompt_lengths, **common
        )
    if return_cache:
        out, rounds, k, v = res
        return (out, rounds, k, v) if return_rounds else (out, k, v)
    out, rounds = res
    return (out, rounds) if return_rounds else out
