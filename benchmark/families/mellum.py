"""The ``mellum`` family: Mellum-2-style mixture-of-experts decoders whose
attention layers are of two kinds, as the program's ``models/moe_lm`` runs
them: ``sliding_attention`` layers in which a query reads itself and the
``sliding_window - 1`` positions before it, ``full_attention`` layers that read
everything, a rotary a kind (plain frequencies in the window layers, YaRN's
blend with cos and sin scaled by ``attention_factor`` in the global ones), a
head width of its own (``head_dim`` x heads is not the hidden size), every
layer 64 experts of which a token takes 8 with the gates renormalised, an
untied output head.

What a family file holds is said in ``families/transformer_lm.py``; this one
differs where the architecture does:

* ``program_config`` maps the published keys (``layer_types``, of which the
  first ``num_hidden_layers`` are run; ``sliding_window``; ``head_dim``;
  ``rope_parameters`` by kind; ``moe_intermediate_size`` = ONE expert's width;
  ``num_experts``, ``num_experts_per_tok``, ``norm_topk_prob``;
  ``rms_norm_eps``) to ``moe_lm``'s config and refuses what the program does
  not compute (a dense layer, biases, another rotary than plain in the window
  layers and YaRN in the global ones). A program whose ``moe_lm`` knows no
  window layer (every commit before PR 39) cannot run this family:
  ``program_config`` says so at once, before any weight is made, so such a
  checkout exits non-zero in seconds;
* ``leaf_shapes``: ``attn/wq (layers, hidden, heads x head_dim)``, ``attn/wk``
  and ``attn/wv (layers, hidden, kv heads x head_dim)``, ``attn/wo (layers,
  heads x head_dim, hidden)``, the experts stacked ``(layers, experts, hidden,
  width)`` twice and ``(layers, experts, width, hidden)``, the router, the
  embedding and ``lm_head``. ``to_tree`` adds the gains (ones): ``ln1``,
  ``ln2``, ``ln_f``. There is no QK-norm.

The plain reference is the published block in float32 under
``jax.default_matmul_precision("highest")``, no cache, no kernels, no
batching, independent of the program's code (``eps`` 1e-6)::

    a   = RMSNorm(x; g_in)
    q   = a Wq  (heads x 128)     k = a Wk    v = a Wv  (kv heads x 128)
    f   = theta^(-2i/128)                                   in a sliding_attention layer
        = blend_i * theta^(-2i/128) / factor + (1 - blend_i) * theta^(-2i/128)
          with blend the linear ramp between the dimensions that turn
          beta_fast and beta_slow times in original_max positions, the same
          at every position; cos and sin times attention_factor   in a full_attention layer
    s_ij = rope_f(q_i) . rope_f(k_j) / sqrt(128)   for j <= i and, in a
          sliding_attention layer only, i - j < sliding_window
    h   = x + softmax_j(s) v  Wo                            (heads x 128 -> hidden)
    z   = RMSNorm(h; g_post)
    p   = softmax_E(z Wg)  (float32);   I = top_k(p);   g = p_I / sum(p_I)
    y   = h + sum_{i in I} g_i * ( silu(z Wgate_i) * (z Wup_i) ) Wdown_i
    out = RMSNorm(y_L; g_f) Whead                           (Whead is not the embedding)

Attention is computed a block of ``QUERY_BLOCK`` queries at a time against the
keys those queries may read (in a window layer: the block's own positions and
the ``sliding_window - 1`` before its first), so that 5,632 positions fit
beside the served model on the chip: 32 heads x 256 x 5,663 float32 scores are
186 MB where the whole score block would be 4 GB. The expert sum is taken the
way the program does not take it: every expert applied to every token and
weighted by ``g_i`` where the token chose it and by zero where it did not,
``EXPERT_GROUP`` experts' float32 weights on the device at a time.
Departures (listed in the configuration's file): interleaved rotary pairs, as
in ``transformer_lm.py``; no ``eos_id``; the multi-token-prediction head the
model's description mentions has no key in the published config and is left
out; ``intermediate_size`` is unused (no layer is dense).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import numpy as np

PROGRAM_FAMILY = "moe_lm"
ALIGN = 16          # the artifact format's leaf alignment (weights.py)
EXPERT_GROUP = 8    # experts whose float32 weights the reference holds at once
QUERY_BLOCK = 256   # queries the reference attends at once
SLIDING, FULL = "sliding_attention", "full_attention"


def program_config(config: dict) -> dict:
    from tfservingcache_tpu.models import registry

    if "window" not in {f.name for f in dataclasses.fields(registry.CacheRow)}:
        raise ValueError(
            "this program's moe_lm has no window layer: a cache row has no "
            "window, the head width is hidden / heads, one rotary a model "
            "(PR 39 adds them)")
    n = int(config["num_hidden_layers"])
    types = list(config["layer_types"])[:n]
    if len(types) != n or set(types) - {SLIDING, FULL}:
        raise ValueError(f"layer_types must give {n} layers of "
                         f"{[SLIDING, FULL]}, got {types}")
    if set(list(config.get("mlp_layer_types") or ["sparse"])[:n]) != {"sparse"}:
        raise ValueError("the program computes expert (sparse) layers only")
    for key, want in (("attention_bias", False), ("hidden_act", "silu")):
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: the program computes "
                             f"{want!r} only")
    rope = config["rope_parameters"]
    sliding, full = rope[SLIDING], rope[FULL]
    if sliding.get("rope_type", "default") != "default" or \
            full.get("rope_type") != "yarn":
        raise ValueError("the program computes plain rotary in the window "
                         "layers and YaRN in the global ones")
    if float(sliding["rope_theta"]) != float(full["rope_theta"]):
        raise ValueError("the program has one rope_theta a model")
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": n,
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "layer_types": types,
        "sliding_window": config["sliding_window"],
        "d_ff": config["moe_intermediate_size"],
        "n_experts": config["num_experts"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "qk_norm": False,
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "max_seq": config["max_position_embeddings"],
        "rope_theta": float(full["rope_theta"]),
        "rope_full": {
            "yarn": float(full["factor"]),
            "original_max": int(full["original_max_position_embeddings"]),
            "beta_fast": float(full["beta_fast"]),
            "beta_slow": float(full["beta_slow"]),
            "attention_factor": float(full["attention_factor"]),
        },
        "rms_eps": float(config["rms_norm_eps"]),
        "dtype": config["torch_dtype"],
    }


# -- the weights ------------------------------------------------------------

def leaf_shapes(mc: dict[str, Any]) -> dict[str, tuple[tuple[int, ...], int]]:
    """Stacked leaves -> (shape with the layer axis first, fan_in)."""
    d, v, ff, n, e = (mc["d_model"], mc["vocab_size"], mc["d_ff"],
                      mc["n_layers"], mc["n_experts"])
    q, kv = mc["n_heads"] * mc["head_dim"], mc["n_kv_heads"] * mc["head_dim"]
    shapes = {
        "embed": ((v, d), d),
        "attn/wq": ((n, d, q), d), "attn/wk": ((n, d, kv), d),
        "attn/wv": ((n, d, kv), d), "attn/wo": ((n, q, d), q),
        "moe/router": ((n, d, e), d),
        "moe/w1": ((n, e, d, ff), d), "moe/w3": ((n, e, d, ff), d),
        "moe/w2": ((n, e, ff, d), ff),
    }
    if not mc["tie_embeddings"]:
        shapes["lm_head"] = ((d, v), d)
    return shapes


def _gain_sizes(mc: dict[str, Any]) -> list[int]:
    """Lengths of every float32 gain ``to_tree`` adds."""
    d = mc["d_model"]
    return [d, d] * mc["n_layers"] + [d]


def param_bytes(mc: dict[str, Any]) -> int:
    """Bytes of one tenant's params.bin (bf16 matrices, f32 gains)."""
    import jax.numpy as jnp

    item = jnp.dtype(mc["dtype"]).itemsize
    shapes = leaf_shapes(mc)
    mats = sum(int(np.prod(s)) for s, _ in shapes.values())
    gains = _gain_sizes(mc)
    leaves = len(gains) + sum(s[0] if name.count("/") else 1
                              for name, (s, _) in shapes.items())
    return mats * item + sum(gains) * 4 + ALIGN * leaves


def to_tree(mc: dict[str, Any], stacked: dict[str, np.ndarray]) -> dict:
    """Host arrays -> the program's params pytree (views, no copy)."""
    ones = np.ones((mc["d_model"],), np.float32)
    layers = [{
        "attn": {w: stacked[f"attn/{w}"][i] for w in ("wq", "wk", "wv", "wo")},
        "moe": {w: stacked[f"moe/{w}"][i] for w in ("router", "w1", "w2", "w3")},
        "ln1": ones, "ln2": ones,
    } for i in range(mc["n_layers"])]
    tree = {"embed": stacked["embed"], "layers": layers, "ln_f": ones}
    if "lm_head" in stacked:
        tree["lm_head"] = stacked["lm_head"]
    return tree


# -- the plain reference ------------------------------------------------------

def rope_frequencies(mc: dict[str, Any], kind: str) -> tuple[np.ndarray, float]:
    """A layer kind's ``head_dim / 2`` rotary frequencies and what multiplies
    cos and sin: plain ``theta^(-2i/d)`` and 1 in a window layer, YaRN's blend
    and ``attention_factor`` in a global one."""
    d = int(mc["head_dim"])
    theta = float(mc["rope_theta"])
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if kind == SLIDING:
        return plain.astype(np.float32), 1.0
    y = mc["rope_full"]

    def dim_turning(turns: float) -> float:
        """The (fractional) dimension whose pair turns ``turns`` times within
        ``original_max`` positions."""
        return d * math.log(y["original_max"] / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_turning(y["beta_fast"])), 0)
    high = min(math.ceil(dim_turning(y["beta_slow"])), d - 1)
    blend = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    freqs = blend * plain / y["yarn"] + (1.0 - blend) * plain
    return freqs.astype(np.float32), float(y["attention_factor"])


def _rmsnorm(x, gain, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * gain


def _rope(x, freqs, factor: float):
    """(S, H, D) -> rotated, interleaved pairs, positions 0..S-1."""
    import jax.numpy as jnp

    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)


@functools.lru_cache(maxsize=8)
def _fns(n_heads: int, n_kv: int, hd: int, top_k: int, norm_topk: bool,
         eps: float):
    import jax
    import jax.numpy as jnp

    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    @jax.jit
    def project(x, attn, ln1, freqs, factor):
        s = x.shape[0]
        a = _rmsnorm(x, f32(ln1), eps)
        q = _rope((a @ f32(attn["wq"])).reshape(s, n_heads, hd), freqs, factor)
        k = _rope((a @ f32(attn["wk"])).reshape(s, n_kv, hd), freqs, factor)
        return q, k, (a @ f32(attn["wv"])).reshape(s, n_kv, hd)

    @functools.partial(jax.jit, static_argnames=("window",))
    def attend_block(q, k, v, q0, window):
        """A block of queries at positions ``q0..`` against the keys they may
        read, taken out of the whole sequence's ``k`` / ``v``: all of them in
        a global layer, in a window layer the ``window - 1`` positions before
        the block's first and the block's own (one shape a layer kind: ``q0``
        is traced and the slice's start is clamped, the mask decides)."""
        s_all, nq = k.shape[0], q.shape[0]
        span = min(s_all, window - 1 + nq) if window else s_all
        k0 = jnp.clip(q0 - (window - 1), 0, s_all - span) if window else 0
        k = jax.lax.dynamic_slice_in_dim(k, k0, span, 0)
        v = jax.lax.dynamic_slice_in_dim(v, k0, span, 0)
        g = n_heads // n_kv
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
        i = q0 + jnp.arange(nq)[:, None]
        j = k0 + jnp.arange(span)[None, :]
        seen = j <= i
        if window:
            seen &= i - j < window
        p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, v).reshape(nq, n_heads * hd)

    @jax.jit
    def add_output(x, out, wo):
        return x + out @ f32(wo)

    @jax.jit
    def gates(h, ln2, router):
        """-> (z, weight of every expert for every token: g_i where the token
        chose expert i, zero elsewhere)."""
        z = _rmsnorm(h, f32(ln2), eps)
        p = jax.nn.softmax(z @ f32(router), -1)
        top, idx = jax.lax.top_k(p, top_k)
        if norm_topk:
            top = top / jnp.sum(top, -1, keepdims=True)
        chosen = jax.nn.one_hot(idx, p.shape[-1], dtype=jnp.float32)   # (s, k, e)
        return z, jnp.einsum("sk,ske->se", top, chosen)

    @jax.jit
    def add_experts(y, z, weight, w1, w3, w2):
        for i in range(w1.shape[0]):
            out = (jax.nn.silu(z @ f32(w1[i])) * (z @ f32(w3[i]))) @ f32(w2[i])
            y = y + weight[:, i:i + 1] * out
        return y

    @jax.jit
    def head(x, ln_f, w):
        return _rmsnorm(x, f32(ln_f), eps) @ f32(w)

    return project, attend_block, add_output, gates, add_experts, head


def _attend(fns, x, attn, ln1, freqs, factor, window: int):
    """One layer's attention half over a whole sequence, a block of queries
    at a time against the keys that block may read."""
    import jax.numpy as jnp

    project, attend_block, add_output = fns[:3]
    q, k, v = project(x, attn, ln1, jnp.asarray(freqs), factor)
    outs = []
    for q0 in range(0, x.shape[0], QUERY_BLOCK):
        outs.append(attend_block(q[q0:q0 + QUERY_BLOCK], k, v, jnp.int32(q0),
                                 window=window))
    return add_output(x, jnp.concatenate(outs), attn["wo"])


def logits_many(mc: dict[str, Any], tree: dict, sequences,
                last: int = 1) -> list[np.ndarray]:
    """float32 logits ``(last, vocab)`` of the final ``last`` positions of
    each sequence; ``tree`` is the params pytree of host arrays. Layers are
    the outer loop and, inside a layer, groups of ``EXPERT_GROUP`` experts,
    so at most that many experts' float32 weights are on the device."""
    import jax
    import jax.numpy as jnp

    fns = _fns(int(mc["n_heads"]), int(mc["n_kv_heads"]), int(mc["head_dim"]),
               int(mc["top_k"]), bool(mc["norm_topk_prob"]),
               float(mc["rms_eps"]))
    gates, add_experts, head = fns[3:]
    n_exp = int(mc["n_experts"])
    with jax.default_matmul_precision("highest"):
        embed = tree["embed"]
        xs = [jnp.asarray(embed[np.asarray(ids, np.int32)]).astype(jnp.float32)
              for ids in sequences]
        for lp, kind in zip(tree["layers"], mc["layer_types"]):
            attn, ln1, ln2, router = jax.device_put(
                (lp["attn"], lp["ln1"], lp["ln2"], lp["moe"]["router"]))
            freqs, factor = rope_frequencies(mc, kind)
            window = int(mc["sliding_window"]) if kind == SLIDING else 0
            hs = [_attend(fns, x, attn, ln1, freqs, factor, window) for x in xs]
            routed = [gates(h, ln2, router) for h in hs]
            ys = hs
            for lo in range(0, n_exp, EXPERT_GROUP):
                hi = min(lo + EXPERT_GROUP, n_exp)
                w1, w3, w2 = jax.device_put(
                    tuple(lp["moe"][w][lo:hi] for w in ("w1", "w3", "w2")))
                ys = [add_experts(y, z, wt[:, lo:hi], w1, w3, w2)
                      for y, (z, wt) in zip(ys, routed)]
            xs = ys
        w = jnp.asarray(tree["lm_head"]) if "lm_head" in tree \
            else jnp.asarray(tree["embed"]).T
        ln_f = jnp.asarray(tree["ln_f"])
        return [np.asarray(head(x[-last:], ln_f, w)) for x in xs]
