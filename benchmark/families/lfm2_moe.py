"""The ``lfm2_moe`` family: LFM2-style hybrid decoders as the program's
``models/hybrid_lm`` runs them (LFM2-8B-A1B is the configuration:
``configs/lfm2-8b-a1b.json``). Most layers are gated short convolutions, which
keep a FIXED state a request; one in four (by ``layer_types``) is grouped-query
attention with per-head QK-norm; the first ``num_dense_layers`` layers have a
dense SwiGLU and the rest routed experts.

What a family file holds is said in ``families/transformer_lm.py``; this one
differs where the architecture does:

* ``program_config`` maps the published keys to ``hybrid_lm``'s config. The
  file keeps the published ``layer_types`` WHOLE; the layers run are its first
  ``num_hidden_layers`` entries. It refuses what the program does not compute
  (a convolution bias, a router without its selection bias) and, at once and
  before any weight is made, a checkout whose program has no ``hybrid_lm``
  family (every commit before PR 33): such a checkout exits non-zero in
  seconds.
* ``leaf_shapes``: every matrix of a layer a leaf of its own
  (``conv/w_in/0`` ..., as ``mla_moe.py``: one layer's 32 experts are 117 M
  elements a leaf, a stack of twelve would not fit beside the rest while it is
  drawn), named by the operator a layer has (``conv`` or ``attn``) and the FFN
  it has (``mlp`` or ``moe``). The router's selection bias is a leaf too,
  drawn small (std 0.02). ``to_tree`` adds the gains (ones): ``ln1``, ``ln2``,
  ``ln_f`` and an attention layer's ``q_norm`` / ``k_norm`` of ONE head's
  length.

The plain reference is the published block in float32 under
``jax.default_matmul_precision("highest")``, whole sequences with no cache, no
state, no kernels, no batching, independent of the program's code.
``rms(x; g) = x * rsqrt(mean(x^2) + norm_eps) * g``; for layer ``l``::

    h  = x + Op_l(rms(x; g_op))         x' = h + FFN_l(rms(h; g_ffn))

    conv:       [B | C | X] = u W_in ;  z = B * X
                y_t = sum_{j<L} w[:, j] * z_{t-(L-1)+j}      (z_{<0} = 0)
                Op  = (C * y) W_out
    attention:  q = u W_q, k = u W_k, v = u W_v ; q, k normed PER HEAD over
                the head's columns (one gain a side, shared by its heads);
                rope over all columns; causal softmax at hd^-0.5; GQA; W_o
    dense FFN:  (silu(a W_1) * (a W_3)) W_2                  (l < num_dense_layers)
    experts:    s = sigmoid(a W_r) (float32) ; I = top_k(s + b)
                g_i = s_i / (sum_{j in I} s_j + 1e-6) * routed_scaling_factor
                sum_{i in I} g_i SwiGLU_i(a)                 (no shared expert)
    logits   =  rms(x_L; g_f) E^T                            (the head is the embedding)

The expert sum is taken the way the program does not take it: every expert is
applied to every token and weighted by ``g_i`` where the token chose it and by
zero where it did not, a few experts' float32 weights on the device at a time.
Departures (listed in the configuration's file): interleaved rotary pairs, as
in ``transformer_lm.py``; no ``eos_id``.
"""

from __future__ import annotations

import functools
from typing import Any

import numpy as np

PROGRAM_FAMILY = "hybrid_lm"
ALIGN = 16          # the artifact format's leaf alignment (weights.py)
EXPERT_GROUP = 8    # experts whose float32 weights the reference holds at once
BIAS_STD = 0.02

CONV = ("w_in", "w", "w_out")
ATTN = ("wq", "wk", "wv", "wo")
FFN = ("w1", "w2", "w3")


def program_config(config: dict) -> dict:
    from tfservingcache_tpu.models import registry

    if PROGRAM_FAMILY not in registry.families():
        raise ValueError(
            "this program has no hybrid_lm family: no layer kinds in a "
            "ModelDef, no lane state beside the paged arena (PR 33 adds them)")
    for key, want in (("conv_bias", False), ("use_expert_bias", True)):
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: the program computes "
                             f"{want!r} only")
    if config["hidden_size"] // config["num_attention_heads"] \
            != config["assumed"]["head_dim"]["value"]:
        raise ValueError("head_dim != hidden_size / num_attention_heads: "
                         "the program derives the head size from the two")
    n = int(config["num_hidden_layers"])
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": n,
        "layer_types": list(config["layer_types"][:n]),
        "conv_kernel": config["conv_L_cache"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "n_dense_layers": config["num_dense_layers"],
        "d_ff_dense": config["intermediate_size"],
        "d_ff": config["moe_intermediate_size"],
        "n_experts": config["num_experts"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "route_score": "sigmoid",
        "route_scale": float(config["routed_scaling_factor"]),
        "route_norm_eps": float(config["assumed"]["gate_norm_eps"]["value"]),
        "rms_eps": float(config["norm_eps"]),
        "max_seq": config["max_position_embeddings"],
        "rope_theta": float(config["rope_theta"]),
        "dtype": config["torch_dtype"],
    }


# -- the weights ------------------------------------------------------------

def _layer_shapes(mc: dict[str, Any], i: int) -> dict[str, tuple[tuple[int, ...], int]]:
    d, e, taps = mc["d_model"], mc["n_experts"], mc["conv_kernel"]
    hd = d // mc["n_heads"]
    q, kv = mc["n_heads"] * hd, mc["n_kv_heads"] * hd
    if mc["layer_types"][i] == "conv":
        shapes = {"conv/w_in": ((d, 3 * d), d), "conv/w": ((d, taps), taps),
                  "conv/w_out": ((d, d), d)}
    else:
        shapes = {"attn/wq": ((d, q), d), "attn/wk": ((d, kv), d),
                  "attn/wv": ((d, kv), d), "attn/wo": ((q, d), q)}
    if i < mc["n_dense_layers"]:
        ff = mc["d_ff_dense"]
        shapes.update({"mlp/w1": ((d, ff), d), "mlp/w3": ((d, ff), d),
                       "mlp/w2": ((ff, d), ff)})
    else:
        ff = mc["d_ff"]
        shapes.update({
            "moe/router": ((d, e), d),
            "moe/bias": ((e,), round(1 / BIAS_STD ** 2)),
            "moe/w1": ((e, d, ff), d), "moe/w3": ((e, d, ff), d),
            "moe/w2": ((e, ff, d), ff)})
    return shapes


def leaf_shapes(mc: dict[str, Any]) -> dict[str, tuple[tuple[int, ...], int]]:
    """Leaves -> (shape, fan_in): ``<leaf>/<layer>`` for a layer's, then the
    embedding (which is the head too)."""
    shapes = {f"{name}/{i}": sf for i in range(mc["n_layers"])
              for name, sf in _layer_shapes(mc, i).items()}
    shapes["embed"] = ((mc["vocab_size"], mc["d_model"]), mc["d_model"])
    return shapes


def _gain_sizes(mc: dict[str, Any]) -> list[int]:
    """Lengths of every float32 gain ``to_tree`` adds."""
    d = mc["d_model"]
    hd = d // mc["n_heads"]
    sizes = [d]
    for kind in mc["layer_types"]:
        sizes += [d, d] + ([hd, hd] if kind != "conv" else [])
    return sizes


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
    hd = d // mc["n_heads"]
    layers = []
    for i, kind in enumerate(mc["layer_types"]):
        layer: dict[str, Any] = {"ln1": ones(d), "ln2": ones(d)}
        if kind == "conv":
            layer["conv"] = {w: leaves[f"conv/{w}/{i}"] for w in CONV}
        else:
            layer["attn"] = {**{w: leaves[f"attn/{w}/{i}"] for w in ATTN},
                             "q_norm": ones(hd), "k_norm": ones(hd)}
        if i < mc["n_dense_layers"]:
            layer["mlp"] = {w: leaves[f"mlp/{w}/{i}"] for w in FFN}
        else:
            layer["moe"] = {w: leaves[f"moe/{w}/{i}"]
                            for w in ("router", "bias") + FFN}
        layers.append(layer)
    return {"embed": leaves["embed"], "layers": layers, "ln_f": ones(d)}


# -- the plain reference ------------------------------------------------------

@functools.lru_cache(maxsize=4)
def _fns(n_heads: int, n_kv: int, theta: float, top_k: int, norm_topk: bool,
         scale: float, gate_eps: float, eps: float):
    import jax
    import jax.numpy as jnp

    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    def rms(x, gain):
        return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * f32(gain)

    def rope(x):
        """(S, H, D) -> rotated, interleaved pairs, positions 0..S-1."""
        s, _, d = x.shape
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)

    @jax.jit
    def conv(x, op, ln1):
        s = x.shape[0]
        gate_in, gate_out, inner = jnp.split(rms(x, ln1) @ f32(op["w_in"]), 3, -1)
        z = gate_in * inner
        w = f32(op["w"])                                     # (d, L)
        taps = w.shape[1]
        padded = jnp.pad(z, ((taps - 1, 0), (0, 0)))
        y = sum(w[:, j] * padded[j:j + s] for j in range(taps))
        return x + (gate_out * y) @ f32(op["w_out"])

    @jax.jit
    def attend(x, attn, ln1):
        s, d = x.shape
        hd = d // n_heads
        a = rms(x, ln1)
        q = rms((a @ f32(attn["wq"])).reshape(s, n_heads, hd), attn["q_norm"])
        k = rms((a @ f32(attn["wk"])).reshape(s, n_kv, hd), attn["k_norm"])
        q, k = rope(q), rope(k)
        v = (a @ f32(attn["wv"])).reshape(s, n_kv, hd)
        g = n_heads // n_kv
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
        causal = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return x + jnp.einsum("hqk,khd->qhd", p, v).reshape(s, d) @ f32(attn["wo"])

    @jax.jit
    def dense(h, ln2, mlp):
        a = rms(h, ln2)
        return h + (jax.nn.silu(a @ f32(mlp["w1"])) * (a @ f32(mlp["w3"]))) \
            @ f32(mlp["w2"])

    @jax.jit
    def gates(h, ln2, router, bias):
        """-> (a, the weight of every expert for every token: g_i where the
        token chose expert i, zero elsewhere)."""
        a = rms(h, ln2)
        s = jax.nn.sigmoid(a @ f32(router))
        _, idx = jax.lax.top_k(s + f32(bias), top_k)
        g = jnp.take_along_axis(s, idx, -1)
        if norm_topk:
            g = g / (jnp.sum(g, -1, keepdims=True) + gate_eps)
        g = g * scale
        chosen = jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32)   # (s, k, e)
        return a, jnp.einsum("sk,ske->se", g, chosen)

    @jax.jit
    def add_experts(y, a, weight, w1, w3, w2):
        for i in range(w1.shape[0]):
            out = (jax.nn.silu(a @ f32(w1[i])) * (a @ f32(w3[i]))) @ f32(w2[i])
            y = y + weight[:, i:i + 1] * out
        return y

    @jax.jit
    def head(x, ln_f, embed):
        return rms(x, ln_f) @ f32(embed).T

    return conv, attend, dense, gates, add_experts, head


def logits_many(mc: dict[str, Any], tree: dict, sequences,
                last: int = 1) -> list[np.ndarray]:
    """float32 logits ``(last, vocab)`` of the final ``last`` positions of
    each sequence; ``tree`` is the params pytree of host arrays. Layers are
    the outer loop and, inside an expert layer, groups of ``EXPERT_GROUP``
    experts, so at most that many experts' float32 weights are on the
    device."""
    import jax
    import jax.numpy as jnp

    conv, attend, dense, gates, add_experts, head = _fns(
        int(mc["n_heads"]), int(mc["n_kv_heads"]), float(mc["rope_theta"]),
        int(mc["top_k"]), bool(mc["norm_topk_prob"]), float(mc["route_scale"]),
        float(mc["route_norm_eps"]), float(mc["rms_eps"]))
    n_exp = int(mc["n_experts"])
    with jax.default_matmul_precision("highest"):
        embed = tree["embed"]
        xs = [jnp.asarray(embed[np.asarray(ids, np.int32)]).astype(jnp.float32)
              for ids in sequences]
        for lp in tree["layers"]:
            ln1, ln2 = jax.device_put((lp["ln1"], lp["ln2"]))
            if "conv" in lp:
                op = jax.device_put(lp["conv"])
                hs = [conv(x, op, ln1) for x in xs]
            else:
                op = jax.device_put(lp["attn"])
                hs = [attend(x, op, ln1) for x in xs]
            del op
            if "mlp" in lp:
                mlp = jax.device_put(lp["mlp"])
                xs = [dense(h, ln2, mlp) for h in hs]
                del mlp
                continue
            moe = lp["moe"]
            router, bias = jax.device_put((moe["router"], moe["bias"]))
            routed = [gates(h, ln2, router, bias) for h in hs]
            ys = hs
            for lo in range(0, n_exp, EXPERT_GROUP):
                hi = min(lo + EXPERT_GROUP, n_exp)
                w1, w3, w2 = jax.device_put(
                    tuple(moe[w][lo:hi] for w in ("w1", "w3", "w2")))
                ys = [add_experts(y, a, wt[:, lo:hi], w1, w3, w2)
                      for y, (a, wt) in zip(ys, routed)]
            xs = ys
        ln_f, emb = jnp.asarray(tree["ln_f"]), jnp.asarray(embed)
        return [np.asarray(head(x[-last:], ln_f, emb)) for x in xs]
