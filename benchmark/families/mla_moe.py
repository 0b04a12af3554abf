"""The ``mla_moe`` family: latent-attention (MLA) decoders with a shared
expert beside routed ones, of the DeepSeek-V3 lineage, as the program's
``models/mla_moe_lm`` runs ONE CHIP'S SHARE of them (Mistral-Small-4-119B-2603
is the configuration: ``configs/mistral-small-4-119b-2603.json``).

What a family file holds is said in ``families/transformer_lm.py``; this one
differs where the architecture and the cut do:

* ``program_config`` maps the published keys to ``mla_moe_lm``'s config. The
  file's ``n_routed_experts`` is the number of experts HELD here and
  ``source_values.n_routed_experts`` the router's published width; the file's
  ``vocab_size`` is the slice of the vocabulary held here. It refuses what the
  program does not compute (expert groups, leading dense layers, biases, a
  window, tied embeddings, another rope type than yarn, ``mscale`` apart from
  ``mscale_all_dim``) and, at once and before any weight is made, a checkout
  whose program has no ``mla_moe_lm`` family (every commit before PR 31): such
  a checkout exits non-zero in seconds.
* ``leaf_shapes``: every matrix of a layer a leaf OF ITS OWN (``attn/wq_a/0``
  ...), not stacked over layers: ``weights.py`` makes all leaves in one jitted
  call, and a stacked ``(6, 32, 4096, 2048)`` expert leaf is 1.6 G elements
  whose float32 draw does not fit beside the rest. The router's selection
  bias is a leaf too, drawn small (std 0.02) so that it changes some
  selections and not all. ``to_tree`` adds the gains (ones).

The plain reference is the published block in float32 under
``jax.default_matmul_precision("highest")``: EXPANDED attention at every
position (never the absorbed form, never a cache), no kernels, no batching,
independent of the program's code. All norms are RMSNorm with the config's
``rms_norm_eps``::

    a              = norm(x; g_in)
    c_q            = norm(a W_qa; g_qa)
    [q_n | q_r]_h  = c_q W_qb
    [c_kv | k_r]   = a W_kva ;  c_kv = norm(c_kv; g_kva)
    [k_n | v]_h    = c_kv W_kvb
    s_h            = (q_n.k_n + rope(q_r).rope(k_r)) * scale * l4(p)
    h              = x + concat_h(softmax_causal(s_h) v_h) W_o
    z              = norm(h; g_post)
    g              = sigmoid(z W_g) ;  I = top_k(g + b) ;  w_i = g_i / sum_{j in I} g_j * routed_scaling_factor
    y              = h + SwiGLU_shared(z) + sum_{i in I, i held here} w_i SwiGLU_i(z)
    logits         = norm(y_L; g_f) W_head          # the held columns of the vocabulary

``rope`` uses YaRN's blended frequencies, ``scale = (nope + rope)^-0.5 m^2``
with ``m = 0.1 mscale_all_dim ln(factor) + 1``, ``l4(p) = 1 +
llama_4_scaling_beta ln(1 + floor(p / original_max_position_embeddings))``.
The expert sum is taken the way the program does not take it: every HELD
expert is applied to every token and weighted by ``w_i`` where the token chose
it and by zero where it did not, a few experts' float32 weights on the device
at a time; what the experts held elsewhere would add is left out, as in the
program. Attention runs in blocks of queries so that 7.7 k positions fit.
Departures are listed in the configuration's file.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import numpy as np

PROGRAM_FAMILY = "mla_moe_lm"
ALIGN = 16          # the artifact format's leaf alignment (weights.py)
EXPERT_GROUP = 4    # experts whose float32 weights the reference holds at once
QUERY_BLOCK = 512   # queries a block of the reference's attention
BIAS_STD = 0.02

ATTN = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
EXPERT = ("w1", "w2", "w3")


def program_config(config: dict) -> dict:
    from tfservingcache_tpu.models import registry

    if PROGRAM_FAMILY not in registry.families():
        raise ValueError(
            "this program has no mla_moe_lm family: no latent cache row, no "
            "shared expert, no held-experts argument (PR 31 adds them)")
    rope = config["rope_parameters"]
    for key, want in (("n_group", 1), ("topk_group", 1),
                      ("first_k_dense_replace", 0), ("attention_bias", False),
                      ("mlp_bias", False), ("hidden_act", "silu"),
                      ("sliding_window", None), ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: the program computes "
                             f"{want!r} only")
    if rope.get("rope_type", rope.get("type")) != "yarn":
        raise ValueError(f"rope type {rope.get('rope_type')!r}: yarn only")
    if rope["mscale"] != rope["mscale_all_dim"]:
        raise ValueError("mscale != mscale_all_dim: cos and sin would carry "
                         "a factor the program does not compute")
    if config["qk_nope_head_dim"] + config["qk_rope_head_dim"] != config["qk_head_dim"]:
        raise ValueError("qk_head_dim != qk_nope_head_dim + qk_rope_head_dim")
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "q_lora_rank": config["q_lora_rank"],
        "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope_head_dim": config["qk_nope_head_dim"],
        "qk_rope_head_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "d_ff": config["moe_intermediate_size"],
        "d_ff_shared": config["moe_intermediate_size"] * config["n_shared_experts"],
        "n_experts": config["source_values"]["n_routed_experts"],
        "n_experts_held": config["n_routed_experts"],
        "expert_first": int(config["assumed"]["expert_first"]["value"]),
        "top_k": config["num_experts_per_tok"],
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "route_score": config["assumed"]["scoring_func"]["value"],
        "route_scale": float(config["routed_scaling_factor"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "max_seq": config["max_position_embeddings"],
        "rope_theta": float(rope["rope_theta"]),
        "rope_factor": float(rope["factor"]),
        "rope_beta_fast": float(rope["beta_fast"]),
        "rope_beta_slow": float(rope["beta_slow"]),
        "rope_original_max": int(rope["original_max_position_embeddings"]),
        "rope_mscale": float(rope["mscale"]),
        "rope_mscale_all_dim": float(rope["mscale_all_dim"]),
        "llama4_beta": float(rope["llama_4_scaling_beta"]),
        "dtype": config["torch_dtype"],
    }


# -- the weights ------------------------------------------------------------

def _layer_shapes(mc: dict[str, Any]) -> dict[str, tuple[tuple[int, ...], int]]:
    d, h, ff, ffs = mc["d_model"], mc["n_heads"], mc["d_ff"], mc["d_ff_shared"]
    nope, rope, vd = mc["qk_nope_head_dim"], mc["qk_rope_head_dim"], mc["v_head_dim"]
    q_rank, rank, held = mc["q_lora_rank"], mc["kv_lora_rank"], mc["n_experts_held"]
    return {
        "attn/wq_a": ((d, q_rank), d),
        "attn/wq_b": ((q_rank, h * (nope + rope)), q_rank),
        "attn/wkv_a": ((d, rank + rope), d),
        "attn/wkv_b": ((rank, h * (nope + vd)), rank),
        "attn/wo": ((h * vd, d), h * vd),
        "moe/router": ((d, mc["n_experts"]), d),
        "moe/bias": ((mc["n_experts"],), round(1 / BIAS_STD ** 2)),
        "moe/w1": ((held, d, ff), d), "moe/w3": ((held, d, ff), d),
        "moe/w2": ((held, ff, d), ff),
        "moe/shared/w1": ((d, ffs), d), "moe/shared/w3": ((d, ffs), d),
        "moe/shared/w2": ((ffs, d), ffs),
    }


def leaf_shapes(mc: dict[str, Any]) -> dict[str, tuple[tuple[int, ...], int]]:
    """Leaves -> (shape, fan_in): ``<leaf>/<layer>`` for a layer's, then the
    embedding and the head."""
    shapes = {f"{name}/{i}": sf for i in range(mc["n_layers"])
              for name, sf in _layer_shapes(mc).items()}
    d, v = mc["d_model"], mc["vocab_size"]
    shapes["embed"] = ((v, d), d)
    shapes["lm_head"] = ((d, v), d)
    return shapes


def _gain_sizes(mc: dict[str, Any]) -> list[int]:
    """Lengths of every float32 gain ``to_tree`` adds."""
    d = mc["d_model"]
    return [d, d, mc["q_lora_rank"], mc["kv_lora_rank"]] * mc["n_layers"] + [d]


def param_bytes(mc: dict[str, Any]) -> int:
    """Bytes of one tenant's params.bin (bf16 matrices, f32 gains)."""
    import jax.numpy as jnp

    item = jnp.dtype(mc["dtype"]).itemsize
    shapes = leaf_shapes(mc)
    mats = sum(int(np.prod(s)) for s, _ in shapes.values())
    gains = _gain_sizes(mc)
    return mats * item + sum(gains) * 4 + ALIGN * (len(gains) + len(shapes))


def to_tree(mc: dict[str, Any], leaves: dict[str, np.ndarray]) -> dict:
    """Host arrays -> the program's params pytree (views, no copy)."""
    ones = lambda n: np.ones((n,), np.float32)  # noqa: E731
    d = mc["d_model"]
    layers = [{
        "attn": {**{w: leaves[f"attn/{w}/{i}"] for w in ATTN},
                 "q_a_norm": ones(mc["q_lora_rank"]),
                 "kv_a_norm": ones(mc["kv_lora_rank"])},
        "moe": {**{w: leaves[f"moe/{w}/{i}"]
                   for w in ("router", "bias") + EXPERT},
                "shared": {w: leaves[f"moe/shared/{w}/{i}"] for w in EXPERT}},
        "ln1": ones(d), "ln2": ones(d),
    } for i in range(mc["n_layers"])]
    return {"embed": leaves["embed"], "lm_head": leaves["lm_head"],
            "layers": layers, "ln_f": ones(d)}


# -- the plain reference ------------------------------------------------------

def yarn_inv_freq(mc: dict[str, Any]) -> np.ndarray:
    """YaRN's rotary frequencies over the rope columns (float64)."""
    d, base, factor = mc["qk_rope_head_dim"], mc["rope_theta"], mc["rope_factor"]
    plain = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def dim_of(turns: float) -> float:
        return d * math.log(mc["rope_original_max"] / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(mc["rope_beta_fast"])), 0)
    high = min(math.ceil(dim_of(mc["rope_beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp                       # 1 = extrapolate (plain), 0 = interpolate
    return plain / factor * (1 - keep) + plain * keep


def attention_scale(mc: dict[str, Any]) -> float:
    m = 0.1 * mc["rope_mscale_all_dim"] * math.log(mc["rope_factor"]) + 1.0
    return (mc["qk_nope_head_dim"] + mc["qk_rope_head_dim"]) ** -0.5 * m * m


@functools.lru_cache(maxsize=4)
def _fns(key: tuple):
    import jax
    import jax.numpy as jnp

    mc = dict(key)
    h, nope, rope_d, vd = (mc["n_heads"], mc["qk_nope_head_dim"],
                           mc["qk_rope_head_dim"], mc["v_head_dim"])
    rank, eps, top_k = mc["kv_lora_rank"], mc["rms_eps"], mc["top_k"]
    first, held = mc["expert_first"], mc["n_experts_held"]
    inv_freq = jnp.asarray(yarn_inv_freq(mc), jnp.float32)
    scale = attention_scale(mc)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    def norm(x, gain):
        return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * f32(gain)

    def rope(x, pos):
        """(S, ..., D) at positions (S,): interleaved pairs."""
        ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
        ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[1],))
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)

    @jax.jit
    def project(x, attn, ln1):
        """-> q (S, H, nope + rope), k (S, H, nope + rope), v (S, H, vd)."""
        s = x.shape[0]
        pos = jnp.arange(s)
        a = norm(x, ln1)
        q = (norm(a @ f32(attn["wq_a"]), attn["q_a_norm"]) @ f32(attn["wq_b"]))
        q = q.reshape(s, h, nope + rope_d)
        l4 = 1.0 + mc["llama4_beta"] * jnp.log1p(
            jnp.floor(pos / mc["rope_original_max"]))
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos)], -1)
        q = q * (scale * l4)[:, None, None]
        ckr = a @ f32(attn["wkv_a"])
        c_kv = norm(ckr[:, :rank], attn["kv_a_norm"])
        k_r = rope(ckr[:, rank:], pos)
        kv = (c_kv @ f32(attn["wkv_b"])).reshape(s, h, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (s, h, rope_d))], -1)
        return q, k, kv[..., nope:]

    @jax.jit
    def attend_block(q_blk, start, k, v):
        """Queries ``start..start + len(q_blk) - 1`` over all keys, causal."""
        scores = jnp.einsum("qhd,khd->hqk", q_blk, k)
        q_pos = start + jnp.arange(q_blk.shape[0])
        causal = jnp.arange(k.shape[0])[None, :] <= q_pos[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, v).reshape(q_blk.shape[0], h * vd)

    @jax.jit
    def residual(x, ctx, wo):
        return x + ctx @ f32(wo)

    @jax.jit
    def gates(hid, ln2, router, bias, shared):
        """-> (z, hid + the shared expert, the weight of every HELD expert
        for every token: w_i where the token chose it, zero elsewhere)."""
        z = norm(hid, ln2)
        g = jax.nn.sigmoid(z @ f32(router))
        _, idx = jax.lax.top_k(g + f32(bias), top_k)
        w = jnp.take_along_axis(g, idx, -1)
        if mc["norm_topk_prob"]:
            w = w / jnp.sum(w, -1, keepdims=True)
        w = w * mc["route_scale"]
        chosen = jax.nn.one_hot(idx, g.shape[-1], dtype=jnp.float32)   # (s, k, E)
        weight = jnp.einsum("sk,ske->se", w, chosen)[:, first:first + held]
        y = hid + (jax.nn.silu(z @ f32(shared["w1"])) * (z @ f32(shared["w3"]))) \
            @ f32(shared["w2"])
        return z, y, weight

    @jax.jit
    def add_experts(y, z, weight, w1, w3, w2):
        for i in range(w1.shape[0]):
            out = (jax.nn.silu(z @ f32(w1[i])) * (z @ f32(w3[i]))) @ f32(w2[i])
            y = y + weight[:, i:i + 1] * out
        return y

    @jax.jit
    def head(x, ln_f, w):
        return norm(x, ln_f) @ f32(w)

    return project, attend_block, residual, gates, add_experts, head


def logits_many(mc: dict[str, Any], tree: dict, sequences,
                last: int = 1) -> list[np.ndarray]:
    """float32 logits ``(last, vocab held)`` of the final ``last`` positions
    of each sequence; ``tree`` is the params pytree of host arrays. Layers are
    the outer loop and, inside a layer, groups of ``EXPERT_GROUP`` held
    experts, so at most that many experts' float32 weights are on the device;
    attention goes ``QUERY_BLOCK`` queries at a time."""
    import jax
    import jax.numpy as jnp

    project, attend_block, residual, gates, add_experts, head = _fns(
        tuple(sorted(mc.items())))
    held = int(mc["n_experts_held"])
    with jax.default_matmul_precision("highest"):
        embed = tree["embed"]
        xs = [jnp.asarray(embed[np.asarray(ids, np.int32)]).astype(jnp.float32)
              for ids in sequences]
        for lp in tree["layers"]:
            attn, ln1, ln2 = jax.device_put((lp["attn"], lp["ln1"], lp["ln2"]))
            moe = lp["moe"]
            router, bias, shared = jax.device_put(
                (moe["router"], moe["bias"], moe["shared"]))
            hs = []
            for x in xs:
                q, k, v = project(x, attn, ln1)
                ctx = jnp.concatenate([
                    attend_block(q[lo:lo + QUERY_BLOCK], lo, k, v)
                    for lo in range(0, x.shape[0], QUERY_BLOCK)])
                hs.append(residual(x, ctx, attn["wo"]))
            routed = [gates(hid, ln2, router, bias, shared) for hid in hs]
            ys = [y for _, y, _ in routed]
            for lo in range(0, held, EXPERT_GROUP):
                hi = min(lo + EXPERT_GROUP, held)
                w1, w3, w2 = jax.device_put(
                    tuple(moe[w][lo:hi] for w in ("w1", "w3", "w2")))
                ys = [add_experts(y, z, wt[:, lo:hi], w1, w3, w2)
                      for y, (z, _, wt) in zip(ys, routed)]
            xs = ys
        w, ln_f = jnp.asarray(tree["lm_head"]), jnp.asarray(tree["ln_f"])
        return [np.asarray(head(x[-last:], ln_f, w)) for x in xs]
