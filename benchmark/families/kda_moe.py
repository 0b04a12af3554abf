"""The ``kda_moe`` family: decoders whose layers alternate Kimi Delta Attention
(arXiv:2510.26692: the gated delta rule with a decay a CHANNEL) with gated
NoPE grouped-query attention, every layer followed by routed experts and a
shared one, as the program's ``models/kda_moe_lm`` runs ONE CHIP'S SHARE of
them (Solar-Open2-250B is the configuration: ``configs/solar-open2-250b.json``).

What a family file holds is said in ``families/transformer_lm.py``; this one
differs where the architecture and the cut do:

* ``program_config`` maps the published keys to ``kda_moe_lm``'s config.
  ``gqa_layers`` names the full-attention layers and every other layer is
  linear (``layer_types`` is made from it). The file's ``n_routed_experts`` is
  the number of experts HELD here and ``source_values.n_routed_experts`` the
  router's published width; the file's ``vocab_size`` is the slice of the
  vocabulary held here. It refuses what the program does not compute (a
  rotary, full-attention layers without their gate, the gates' full
  projection, leading dense layers, tied embeddings, linear key heads fewer
  than value heads) and, at once and before any weight is made, a checkout
  whose program has no ``kda_moe_lm`` family (every commit before PR 49): such
  a checkout exits non-zero in seconds.
* ``leaf_shapes``: every matrix a leaf of its own, named by the mixer a layer
  has (``kda`` or ``attn``) and ``moe``. The decay's leaves are drawn as a
  trained layer's lie, as ``families/olmo_hybrid.py`` draws them and for its
  reasons: ``a_log`` (a head) at std 1 around ``A_LOG_MEAN``, ``dt_bias`` (a
  channel) at std 1 around ``DT_BIAS_MEAN`` (``to_tree`` adds the means), the
  decay's second matrix ``w_f2`` at a quarter of a projection's std, so that
  ``alpha = exp(-exp(a_log) softplus(a + dt_bias))`` spreads over (0, 1)
  across heads, channels and tokens with its median near 0.9. The router's
  selection bias and the output gate's bias are leaves too, drawn small (std
  ``BIAS_STD``). ``to_tree`` adds the gains (ones): ``ln1``, ``ln2``,
  ``ln_f``, ``o_norm``.

The plain reference is the published block in float32 under
``jax.default_matmul_precision("highest")``, whole sequences with no cache, no
state handed on, no chunks, no kernels, no batching, independent of the
program's code. ``rms(x; g)`` is an RMSNorm over the last axis at
``rms_norm_eps``; for layer ``l``::

    u = rms(x; g1) ;  h = x + Mix_l(u) ;  z = rms(h; g2)
    g = sigmoid(z W_r) ;  I = top_k(g + b) ;  w_i = g_i / sum_{j in I} g_j * routed_scaling_factor
    x' = h + SwiGLU_shared(z) + sum_{i in I, i held here} w_i SwiGLU_i(z)

    linear attention (H heads, d_k = d_v):
        [q' | k' | v'] = u W_qkv
        c_t = silu(sum_{j<4} w[:, j] [q' | k' | v']_{t-3+j})              (rows before 0 are 0)
        q_h = c^q_h / sqrt(|c^q_h|^2 + 1e-6) / sqrt(d_k) ;  k_h = c^k_h / sqrt(|c^k_h|^2 + 1e-6) ;  v_h = c^v_h
        a = (u W_f1) W_f2                                               (one value a channel: (H, d_k))
        alpha_h = exp(-exp(a_log_h) softplus(a_h + dt_bias_h)) ;  beta_h = 2 sigmoid(u w_b)_h
        S_h <- diag(alpha_h) S_h ;  S_h <- S_h + k_h^T (beta_h (v_h - k_h S_h)) ;  o_h = q_h S_h
                                                     (a lax.scan over t, S = 0 before, ONE token a trip)
        Mix = concat_h( rms(o_h; g_o) * sigmoid(((u W_g1) W_g2 + b_g)_h) ) W_o
    full attention (n query heads, n_kv KV heads of D, no rotary):
        q = u W_q ;  k = u W_k ;  v = u W_v
        o_i = softmax(q_i k_{i // group}^T / sqrt(D) + causal mask) v_{i // group}
        Mix = (concat_i(o_i) * sigmoid(u W_gate)) W_o
    logits = rms(x_n; g_f) W_head          # the held columns of the vocabulary

Attention runs by blocks of ``Q_BLOCK`` queries, one block at a time (each
block's scores against every key, under the causal mask), and the experts by
blocks of ``ROW_BLOCK`` rows, a group of ``EXPERT_GROUP`` held experts' float32
weights on the device at a time, so that 16,408 positions fit beside a serving
program: the same softmax, no running maximum, nothing approximated. The
expert sum is taken the way the program does not take it: every HELD expert is
applied to every token and weighted by ``w_i`` where the token chose it and by
zero where it did not; what the experts held elsewhere would add is left out,
as in the program. Departures are listed in the configuration's file.
"""

from __future__ import annotations

import functools
import json
from typing import Any

import numpy as np

PROGRAM_FAMILY = "kda_moe_lm"
ALIGN = 16          # the artifact format's leaf alignment (weights.py)
Q_BLOCK = 128       # queries a block of the reference's attention
ROW_BLOCK = 2048    # rows a block of the reference's experts
EXPERT_GROUP = 4    # experts whose float32 weights the reference holds at once
BIAS_STD = 0.02
A_LOG_MEAN, DT_BIAS_MEAN = 0.5, -3.0    # what ``to_tree`` adds to the draws

LINEAR, FULL = "linear_attention", "full_attention"
EXPERT = ("w1", "w2", "w3")


def program_config(config: dict) -> dict:
    from tfservingcache_tpu.models import registry

    if PROGRAM_FAMILY not in registry.families():
        raise ValueError(
            "this program has no kda_moe_lm family: no delta rule with a decay "
            "a channel, no gated NoPE attention (PR 49 adds them)")
    for key, want in (("use_rope", False), ("use_gqa_gate", True),
                      ("kda_use_full_proj", False), ("first_k_dense_replace", 0),
                      ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: the program computes "
                             f"{want!r} only")
    lin = config["linear_attn_config"]
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ValueError("the program's delta rule has a key head a value head")
    n = int(config["num_hidden_layers"])
    full = set(config["gqa_layers"])
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": n,
        "layer_types": [FULL if i in full else LINEAR for i in range(n)],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "linear_heads": lin["num_heads"],
        "linear_key_dim": lin["head_dim"],
        "linear_value_dim": lin["head_dim"],
        "linear_conv": lin["short_conv_kernel_size"],
        "linear_gate_rank": int(config["assumed"]["kda_gate_rank"]["value"]),
        "linear_allow_neg_eigval": bool(config["kda_allow_neg_eigval"]),
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
        "rope_theta": None,
        "max_seq": config["max_position_embeddings"],
        "dtype": config["torch_dtype"],
    }


# -- the weights ------------------------------------------------------------

def _layer_shapes(mc: dict[str, Any], kind: str) -> dict[str, tuple[tuple[int, ...], int]]:
    d, ff, ffs = mc["d_model"], mc["d_ff"], mc["d_ff_shared"]
    hd = mc["head_dim"]
    q, kv = mc["n_heads"] * hd, mc["n_kv_heads"] * hd
    h, d_k, d_v = mc["linear_heads"], mc["linear_key_dim"], mc["linear_value_dim"]
    width, rank, held = h * (2 * d_k + d_v), mc["linear_gate_rank"], mc["n_experts_held"]
    small = round(1 / BIAS_STD ** 2)
    shapes = {
        "moe/router": ((d, mc["n_experts"]), d),
        "moe/bias": ((mc["n_experts"],), small),
        "moe/w1": ((held, d, ff), d), "moe/w3": ((held, d, ff), d),
        "moe/w2": ((held, ff, d), ff),
        "moe/shared/w1": ((d, ffs), d), "moe/shared/w3": ((d, ffs), d),
        "moe/shared/w2": ((ffs, d), ffs),
    }
    if kind == LINEAR:
        shapes.update({
            "kda/w_qkv": ((d, width), d),
            "kda/w_f1": ((d, rank), d), "kda/w_f2": ((rank, h * d_k), 16 * rank),
            "kda/w_g1": ((d, rank), d), "kda/w_g2": ((rank, h * d_v), rank),
            "kda/b_g": ((h * d_v,), small),
            "kda/w_b": ((d, h), d),
            "kda/conv_w": ((width, mc["linear_conv"]), mc["linear_conv"]),
            "kda/a_log": ((h,), 1), "kda/dt_bias": ((h * d_k,), 1),
            "kda/w_o": ((h * d_v, d), h * d_v)})
    else:
        shapes.update({"attn/wq": ((d, q), d), "attn/wk": ((d, kv), d),
                       "attn/wv": ((d, kv), d), "attn/wo": ((q, d), q),
                       "attn/w_gate": ((d, q), d)})
    return shapes


def leaf_shapes(mc: dict[str, Any]) -> dict[str, tuple[tuple[int, ...], int]]:
    """Leaves -> (shape, fan_in): ``<leaf>/<layer>`` for a layer's, then the
    embedding and the head."""
    shapes = {f"{name}/{i}": sf
              for i, kind in enumerate(mc["layer_types"])
              for name, sf in _layer_shapes(mc, kind).items()}
    shapes["embed"] = ((mc["vocab_size"], mc["d_model"]), mc["d_model"])
    shapes["lm_head"] = ((mc["d_model"], mc["vocab_size"]), mc["d_model"])
    return shapes


def _gain_sizes(mc: dict[str, Any]) -> list[int]:
    """Lengths of every float32 gain ``to_tree`` adds."""
    d = mc["d_model"]
    sizes = [d]
    for kind in mc["layer_types"]:
        sizes += [d, d] + ([mc["linear_value_dim"]] if kind == LINEAR else [])
    return sizes


def param_bytes(mc: dict[str, Any]) -> int:
    """Bytes of one tenant's params.bin (drawn leaves in the model's dtype,
    float32 gains)."""
    import jax.numpy as jnp

    item = jnp.dtype(mc["dtype"]).itemsize
    shapes = leaf_shapes(mc)
    drawn = sum(int(np.prod(s)) for s, _ in shapes.values())
    gains = _gain_sizes(mc)
    return drawn * item + sum(gains) * 4 + ALIGN * (len(gains) + len(shapes))


def to_tree(mc: dict[str, Any], leaves: dict[str, np.ndarray]) -> dict:
    """Host arrays -> the program's params pytree (views, no copy but
    ``a_log`` and ``dt_bias``, which are moved to their means)."""
    ones = lambda n: np.ones((n,), np.float32)  # noqa: E731
    d = mc["d_model"]
    layers = []
    for i, kind in enumerate(mc["layer_types"]):
        layer: dict[str, Any] = {"ln1": ones(d), "ln2": ones(d)}
        for name in _layer_shapes(mc, kind):
            node = layer
            *groups, leaf = name.split("/")
            for g in groups:
                node = node.setdefault(g, {})
            node[leaf] = leaves[f"{name}/{i}"]
        if kind == LINEAR:
            for leaf, mean in (("a_log", A_LOG_MEAN), ("dt_bias", DT_BIAS_MEAN)):
                drawn = layer["kda"][leaf]
                layer["kda"][leaf] = (
                    drawn.astype(np.float32) + mean).astype(drawn.dtype)
            layer["kda"]["o_norm"] = ones(mc["linear_value_dim"])
        layers.append(layer)
    return {"embed": leaves["embed"], "lm_head": leaves["lm_head"],
            "layers": layers, "ln_f": ones(d)}


# -- the plain reference ------------------------------------------------------

@functools.lru_cache(maxsize=4)
def _fns(key: str):
    import jax
    import jax.numpy as jnp

    mc = json.loads(key)
    n_heads, n_kv, hd = mc["n_heads"], mc["n_kv_heads"], mc["head_dim"]
    lin_heads, d_k, d_v = (mc["linear_heads"], mc["linear_key_dim"],
                           mc["linear_value_dim"])
    eps, top_k = mc["rms_eps"], mc["top_k"]
    first, held = mc["expert_first"], mc["n_experts_held"]
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    def rms(x, gain):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(gain)

    @jax.jit
    def linear(x, op, g1):
        s = x.shape[0]
        u = rms(x, g1)
        w = f32(op["conv_w"])                                  # (width, taps)
        taps = w.shape[1]
        w_qkv = f32(op["w_qkv"])

        def part(lo, width):
            """``c`` over ``width`` columns from ``lo`` (q', k' or v'): a part
            at a time, so that beside a serving program no float32 array of
            all the columns of 16,408 positions exists."""
            cols = slice(lo, lo + width)
            padded = jnp.pad(u @ w_qkv[:, cols], ((taps - 1, 0), (0, 0)))
            return jax.nn.silu(sum(
                w[cols, j] * padded[j:j + s] for j in range(taps)))

        q = part(0, lin_heads * d_k).reshape(s, lin_heads, d_k)
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / jnp.sqrt(
            jnp.float32(d_k))
        k = part(lin_heads * d_k, lin_heads * d_k).reshape(s, lin_heads, d_k)
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        v = part(2 * lin_heads * d_k, lin_heads * d_v).reshape(s, lin_heads, d_v)
        a = ((u @ f32(op["w_f1"])) @ f32(op["w_f2"])).reshape(s, lin_heads, d_k)
        alpha = jnp.exp(-jnp.exp(f32(op["a_log"]))[:, None] * jax.nn.softplus(
            a + f32(op["dt_bias"]).reshape(lin_heads, d_k)))   # (S, H, d_k)
        beta = jax.nn.sigmoid(u @ f32(op["w_b"])) * (
            2.0 if mc["linear_allow_neg_eigval"] else 1.0)

        def token(state, row):
            q_t, k_t, v_t, a_t, b_t = row                      # (H, d), (H, d_k), (H,)
            state = a_t[:, :, None] * state                    # diag(alpha) S
            read = jnp.einsum("hk,hkv->hv", k_t, state)
            state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - read))[:, None, :]
            return state, jnp.einsum("hk,hkv->hv", q_t, state)

        _, o = jax.lax.scan(
            token, jnp.zeros((lin_heads, d_k, d_v), jnp.float32),
            (q, k, v, alpha, beta))
        del q, k, v, alpha
        z = ((u @ f32(op["w_g1"])) @ f32(op["w_g2"]) + f32(op["b_g"])).reshape(
            s, lin_heads, d_v)
        mix = (rms(o, op["o_norm"]) * jax.nn.sigmoid(z)).reshape(s, -1)
        return x + mix @ f32(op["w_o"])

    @jax.jit
    def attend(x, attn, g1):
        s = x.shape[0]
        u = rms(x, g1)
        group = n_heads // n_kv
        q = (u @ f32(attn["wq"])).reshape(s, n_kv, group, hd)
        k = (u @ f32(attn["wk"])).reshape(s, n_kv, hd)
        v = (u @ f32(attn["wv"])).reshape(s, n_kv, hd)
        # by blocks of queries, one at a time (``lax.map``), each against every
        # key under the causal mask: the scores of a block are all that exists
        blocks = -(-s // Q_BLOCK)
        q_pad = jnp.pad(q, ((0, blocks * Q_BLOCK - s), (0, 0), (0, 0), (0, 0)))
        key_at = jnp.arange(s)[None, :]

        def block(args):
            q_b, lo = args                                     # (Q_BLOCK, n_kv, group, hd)
            scores = jnp.einsum("qngd,knd->ngqk", q_b, k) / jnp.sqrt(jnp.float32(hd))
            mask = key_at <= (lo + jnp.arange(Q_BLOCK))[:, None]
            probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf), -1)
            return jnp.einsum("ngqk,knd->qngd", probs, v)

        out = jax.lax.map(block, (q_pad.reshape(blocks, Q_BLOCK, n_kv, group, hd),
                                  jnp.arange(blocks) * Q_BLOCK))
        out = out.reshape(blocks * Q_BLOCK, -1)[:s]
        return x + (out * jax.nn.sigmoid(u @ f32(attn["w_gate"]))) @ f32(attn["wo"])

    def by_rows(fn, rows):
        """``fn`` over blocks of ``ROW_BLOCK`` rows, one at a time."""
        s = rows.shape[0]
        blocks = -(-s // ROW_BLOCK)
        padded = jnp.pad(rows, ((0, blocks * ROW_BLOCK - s), (0, 0)))
        out = jax.lax.map(fn, padded.reshape(blocks, ROW_BLOCK, -1))
        return out.reshape(blocks * ROW_BLOCK, -1)[:s]

    @jax.jit
    def gates(hid, ln2, router, bias, shared):
        """-> (z, hid + the shared expert, the weight of every HELD expert
        for every token: w_i where the token chose it, zero elsewhere)."""
        z = rms(hid, ln2)
        g = jax.nn.sigmoid(z @ f32(router))
        _, idx = jax.lax.top_k(g + f32(bias), top_k)
        w = jnp.take_along_axis(g, idx, -1)
        if mc["norm_topk_prob"]:
            w = w / jnp.sum(w, -1, keepdims=True)
        w = w * mc["route_scale"]
        chosen = jax.nn.one_hot(idx, g.shape[-1], dtype=jnp.float32)   # (s, k, E)
        weight = jnp.einsum("sk,ske->se", w, chosen)[:, first:first + held]
        w1, w3, w2 = (f32(shared[w]) for w in ("w1", "w3", "w2"))
        y = hid + by_rows(lambda r: (jax.nn.silu(r @ w1) * (r @ w3)) @ w2, z)
        return z, y, weight

    @jax.jit
    def add_experts(y, z, weight, w1, w3, w2):
        for i in range(w1.shape[0]):
            a, b, c = f32(w1[i]), f32(w3[i]), f32(w2[i])
            out = by_rows(lambda r: (jax.nn.silu(r @ a) * (r @ b)) @ c, z)
            y = y + weight[:, i:i + 1] * out
        return y

    @jax.jit
    def head(x, ln_f, w):
        return rms(x, ln_f) @ f32(w)

    return linear, attend, gates, add_experts, head


def logits_many(mc: dict[str, Any], tree: dict, sequences,
                last: int = 1) -> list[np.ndarray]:
    """float32 logits ``(last, vocab held)`` of the final ``last`` positions
    of each sequence; ``tree`` is the params pytree of host arrays. Layers are
    the outer loop and, inside a layer, groups of ``EXPERT_GROUP`` held
    experts, so one mixer's and at most that many experts' float32 weights
    are on the device at a time."""
    import jax
    import jax.numpy as jnp

    linear, attend, gates, add_experts, head = _fns(
        json.dumps(mc, sort_keys=True))
    held = int(mc["n_experts_held"])
    with jax.default_matmul_precision("highest"):
        embed = tree["embed"]
        xs = [jnp.asarray(embed[np.asarray(ids, np.int32)]).astype(jnp.float32)
              for ids in sequences]
        for lp, kind in zip(tree["layers"], mc["layer_types"]):
            op = jax.device_put(lp["kda" if kind == LINEAR else "attn"])
            mix = linear if kind == LINEAR else attend
            hs = [mix(x, op, lp["ln1"]) for x in xs]
            del op, xs
            moe = lp["moe"]
            router, bias, shared = jax.device_put(
                (moe["router"], moe["bias"], moe["shared"]))
            routed = [gates(hid, lp["ln2"], router, bias, shared) for hid in hs]
            del hs
            ys = [y for _, y, _ in routed]
            for lo in range(0, held, EXPERT_GROUP):
                hi = min(lo + EXPERT_GROUP, held)
                w1, w3, w2 = jax.device_put(
                    tuple(moe[w][lo:hi] for w in ("w1", "w3", "w2")))
                ys = [add_experts(y, z, wt[:, lo:hi], w1, w3, w2)
                      for y, (z, _, wt) in zip(ys, routed)]
            xs = ys
            del routed, ys
        ln_f, w = jnp.asarray(tree["ln_f"]), jnp.asarray(tree["lm_head"])
        return [np.asarray(head(x[-last:], ln_f, w)) for x in xs]
