"""The ``olmoe`` family: OLMoE-1B-7B-style mixture-of-experts decoders as
the program's ``models/moe_lm`` runs them (a dropless top-k expert layer,
QK-norm, an untied output head).

What a family file holds is said in ``families/transformer_lm.py``; this one
differs where the architecture does:

* ``program_config`` maps the published keys (``num_experts``,
  ``num_experts_per_tok``, ``intermediate_size`` = ONE expert's width,
  ``norm_topk_prob``, ``tie_word_embeddings``) to ``moe_lm``'s config and
  refuses what the program does not compute (``clip_qkv``, another
  ``rms_norm_eps`` than the program's 1e-5, rope scaling, biases). A program
  whose ``moe_lm`` is still the Switch capacity layer (every commit before
  PR 25) cannot run this family: ``program_config`` says so at once, before
  any weight is made, so such a checkout exits non-zero in seconds;
* ``leaf_shapes``: the experts stacked ``(layers, experts, hidden, width)``
  twice (``moe/w1`` the gate, ``moe/w3`` the up projection) and
  ``(layers, experts, width, hidden)`` (``moe/w2``), the router
  ``(layers, hidden, experts)``, and ``lm_head (hidden, vocab)``. All are
  made in the served dtype: the router is stored in bf16 like every float
  leaf of an artifact and promoted to float32 by the program and by the
  reference alike. ``to_tree`` adds the gains (ones): ``ln1``, ``ln2``,
  ``ln_f`` and the QK-norm gains ``attn/q_norm``, ``attn/k_norm``.

The plain reference is the published block in float32 under
``jax.default_matmul_precision("highest")``, no cache, no kernels, no
batching, independent of the program's code::

    a   = RMSNorm(x; g_in)
    q   = RMSNorm(a Wq; g_q)    k = RMSNorm(a Wk; g_k)    v = a Wv   # norms over ALL columns
    h   = x + softmax(causal(rope(q) rope(k)^T / sqrt(hd))) v  Wo
    z   = RMSNorm(h; g_post)
    p   = softmax_E(z Wg)  (float32);   I = top_k(p)   (jax.lax.top_k's ties)
    y   = h + sum_{i in I} p_i * ( silu(z Wgate_i) * (z Wup_i) ) Wdown_i   # p_i NOT renormalised
    out = RMSNorm(y_L; g_f) Whead                                          # Whead is not the embedding

The expert sum is taken the way the program does not take it: every expert
is applied to every token and weighted by ``p_i`` where the token chose it
and by zero where it did not, a few experts' float32 weights on the device
at a time. Departures (listed in each configuration's file): interleaved
rotary pairs, as in ``transformer_lm.py``; no ``eos_id``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import numpy as np

PROGRAM_FAMILY = "moe_lm"
ALIGN = 16          # the artifact format's leaf alignment (weights.py)
RMS_EPS = 1e-5
EXPERT_GROUP = 8    # experts whose float32 weights the reference holds at once


def program_config(config: dict) -> dict:
    from tfservingcache_tpu.models import registry

    if "engine_ready" not in {f.name for f in dataclasses.fields(registry.ModelDef)}:
        raise ValueError(
            "this program's moe_lm is the Switch top-1 capacity layer: it has "
            "no dropless top-k experts, QK-norm or untied head (PR 25 adds them)")
    if config["hidden_size"] // config["num_attention_heads"] != config["head_dim"]:
        raise ValueError("head_dim != hidden_size / num_attention_heads: "
                         "the program derives the head size from the two")
    for key, want in (("clip_qkv", None), ("rope_scaling", None),
                      ("attention_bias", False), ("hidden_act", "silu"),
                      ("rms_norm_eps", RMS_EPS)):
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: the program computes "
                             f"{want!r} only")
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "d_ff": config["intermediate_size"],
        "n_experts": config["num_experts"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "qk_norm": True,
        "tie_embeddings": bool(config["tie_word_embeddings"]),
        "max_seq": config["max_position_embeddings"],
        "rope_theta": float(config["rope_theta"]),
        "dtype": config["torch_dtype"],
    }


# -- the weights ------------------------------------------------------------

def leaf_shapes(mc: dict[str, Any]) -> dict[str, tuple[tuple[int, ...], int]]:
    """Stacked leaves -> (shape with the layer axis first, fan_in)."""
    d, v, ff, n, e = (mc["d_model"], mc["vocab_size"], mc["d_ff"],
                      mc["n_layers"], mc["n_experts"])
    hd = d // mc["n_heads"]
    q, kv = mc["n_heads"] * hd, mc["n_kv_heads"] * hd
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
    hd = d // mc["n_heads"]
    per_layer = [d, d, mc["n_heads"] * hd, mc["n_kv_heads"] * hd]
    return per_layer * mc["n_layers"] + [d]


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
    d, n = mc["d_model"], mc["n_layers"]
    hd = d // mc["n_heads"]
    ones = np.ones((d,), np.float32)
    layers = [{
        "attn": {**{w: stacked[f"attn/{w}"][i] for w in ("wq", "wk", "wv", "wo")},
                 "q_norm": np.ones((mc["n_heads"] * hd,), np.float32),
                 "k_norm": np.ones((mc["n_kv_heads"] * hd,), np.float32)},
        "moe": {w: stacked[f"moe/{w}"][i] for w in ("router", "w1", "w2", "w3")},
        "ln1": ones, "ln2": ones,
    } for i in range(n)]
    tree = {"embed": stacked["embed"], "layers": layers, "ln_f": ones}
    if "lm_head" in stacked:
        tree["lm_head"] = stacked["lm_head"]
    return tree


# -- the plain reference ------------------------------------------------------

def _rmsnorm(x, gain):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS)) * gain


def _rope(x, theta: float):
    """(S, H, D) -> rotated, interleaved pairs, positions 0..S-1."""
    import jax.numpy as jnp

    s, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)


@functools.lru_cache(maxsize=8)
def _fns(n_heads: int, n_kv: int, theta: float, top_k: int, norm_topk: bool):
    import jax
    import jax.numpy as jnp

    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    @jax.jit
    def attend(x, attn, ln1):
        s, d = x.shape
        hd = d // n_heads
        a = _rmsnorm(x, f32(ln1))
        q = _rmsnorm(a @ f32(attn["wq"]), f32(attn["q_norm"]))
        k = _rmsnorm(a @ f32(attn["wk"]), f32(attn["k_norm"]))
        q = _rope(q.reshape(s, n_heads, hd), theta)
        k = _rope(k.reshape(s, n_kv, hd), theta)
        v = (a @ f32(attn["wv"])).reshape(s, n_kv, hd)
        g = n_heads // n_kv
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
        causal = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return x + jnp.einsum("hqk,khd->qhd", p, v).reshape(s, d) @ f32(attn["wo"])

    @jax.jit
    def gates(h, ln2, router):
        """-> (z, weight of every expert for every token: p_i where the token
        chose expert i, zero elsewhere)."""
        z = _rmsnorm(h, f32(ln2))
        p = jax.nn.softmax(z @ f32(router), -1)
        top, idx = jax.lax.top_k(p, top_k)
        if norm_topk:
            top = top / jnp.sum(top, -1, keepdims=True)
        chosen = jax.nn.one_hot(idx, p.shape[-1], dtype=jnp.float32)   # (s, k, e)
        return z, jnp.einsum("sk,ske->se", top, chosen)

    @jax.jit
    def add_experts(y, z, weight, w1, w3, w2):
        """y + the weighted answers of the experts in this group, applied to
        every token (weight zero where a token did not choose one)."""
        for i in range(w1.shape[0]):
            out = (jax.nn.silu(z @ f32(w1[i])) * (z @ f32(w3[i]))) @ f32(w2[i])
            y = y + weight[:, i:i + 1] * out
        return y

    @jax.jit
    def head(x, ln_f, w):
        return _rmsnorm(x, f32(ln_f)) @ f32(w)

    return attend, gates, add_experts, head


def logits_many(mc: dict[str, Any], tree: dict, sequences,
                last: int = 1) -> list[np.ndarray]:
    """float32 logits ``(last, vocab)`` of the final ``last`` positions of
    each sequence; ``tree`` is the params pytree of host arrays. Layers are
    the outer loop and, inside a layer, groups of ``EXPERT_GROUP`` experts,
    so at most that many experts' float32 weights are on the device."""
    import jax
    import jax.numpy as jnp

    attend, gates, add_experts, head = _fns(
        int(mc["n_heads"]), int(mc["n_kv_heads"]), float(mc["rope_theta"]),
        int(mc["top_k"]), bool(mc["norm_topk_prob"]))
    n_exp = int(mc["n_experts"])
    with jax.default_matmul_precision("highest"):
        embed = tree["embed"]
        xs = [jnp.asarray(embed[np.asarray(ids, np.int32)]).astype(jnp.float32)
              for ids in sequences]
        for lp in tree["layers"]:
            attn, ln1, ln2, router = jax.device_put(
                (lp["attn"], lp["ln1"], lp["ln2"], lp["moe"]["router"]))
            hs = [attend(x, attn, ln1) for x in xs]
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
