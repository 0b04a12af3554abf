"""The ``olmo_hybrid`` family: decoders whose layers alternate gated delta-rule
linear attention (Gated DeltaNet, arXiv:2412.06464) with full multi-head
attention, as the program's ``models/olmo_hybrid_lm`` runs them (Olmo-Hybrid-7B
is the configuration: ``configs/olmo-hybrid-7b.json``). ``layer_types`` names
each layer's kind. The block is the Olmo 2/3 family's reordered norm; the
attention layers have QK-norm over the whole projection and, with
``rope_parameters.rope_theta`` null, no rotary; the head is its own matrix.

What a family file holds is said in ``families/transformer_lm.py``; this one
differs where the architecture does:

* ``program_config`` maps the published keys to ``olmo_hybrid_lm``'s config.
  It refuses, at once and before any weight is made, a checkout whose program
  has no ``olmo_hybrid_lm`` family (every commit before PR 46): such a
  checkout exits non-zero in seconds.
* ``leaf_shapes``: every matrix a leaf, named by the mixer a layer has
  (``gdn`` or ``attn``). The decay's leaves are drawn as a trained layer's
  lie (the published initialiser's ranges: ``A = exp(a_log)`` of 0.2 to 12,
  ``softplus(dt_bias)`` of hundredths to tenths): ``a_log`` at std 1 around
  ``A_LOG_MEAN``, ``dt_bias`` at std 1 around ``DT_BIAS_MEAN`` (``to_tree``
  adds the means), ``w_a`` at a quarter of a projection's std, so that ``alpha
  = exp(-exp(a_log) softplus(a + dt_bias))`` spreads over (0, 1) across heads
  and tokens with its median near 0.9: a head that forgets within a few
  tokens beside heads that remember for hundreds. (Drawn around 0, ``a``
  swamps ``dt_bias`` and most heads forget everything at once; a head's
  output is then one token's ``(q . k) beta v``, often tiny, and the RMSNorm
  over it multiplies rounding by thousands: float32 against float32 read
  8e-3 at 8 layers.) ``to_tree``
  adds the gains (ones): ``ln1_post``, ``ln2_post``, ``ln_f``, ``q_norm``,
  ``k_norm``, ``o_norm``.

The plain reference is the published block in float32 under
``jax.default_matmul_precision("highest")``, whole sequences with no cache, no
state handed on, no chunks, no kernels, no batching, independent of the
program's code. ``rms(x; g)`` is an RMSNorm over the last axis at
``rms_norm_eps``; for layer ``l``::

    h = x + rms(Mix_l(x); g1)           x' = h + rms((silu(h W_1) * (h W_3)) W_2; g2)

    linear attention (H heads, d_k, d_v):
        [q' | k' | v'] = x W_qkv ;  a = x W_a ;  b = x W_b ;  z = x W_g
        c_t = silu(sum_{j<4} w[:, j] [q' | k' | v']_{t-3+j})              (rows before 0 are 0)
        q_h = c^q_h / sqrt(|c^q_h|^2 + 1e-6) / sqrt(d_k) ;  k_h = c^k_h / sqrt(|c^k_h|^2 + 1e-6) ;  v_h = c^v_h
        alpha_h = exp(-exp(a_log_h) softplus(a_h + dt_bias_h)) ;  beta_h = 2 sigmoid(b_h)
        S_h <- alpha_h S_h ;  S_h <- S_h + k_h^T (beta_h (v_h - k_h S_h)) ;  o_h = q_h S_h
                                                     (a lax.scan over t, S = 0 before, ONE token a trip)
        Mix = concat_h( rms(o_h; g_o) * silu(z_h) ) W_o
    full attention (n heads of D = hidden / n, MHA):
        q = rms(x W_q; g_q) ;  k = rms(x W_k; g_k) ;  v = x W_v            (the norm over the WHOLE projection)
        o_i = softmax(q_i k_i^T / sqrt(D) + causal mask) v_i ;  Mix = concat_i(o_i) W_o
    logits = rms(x_n; g_f) W_head

Attention runs by blocks of ``Q_BLOCK`` queries, one block at a time (each
block's scores against every key, under the causal mask), and the MLP by
blocks of ``ROW_BLOCK`` rows, so that 16384 positions of 30 heads fit beside
a serving program: the same softmax, no running maximum, nothing
approximated. Departures (listed in the
configuration's file): no ``eos_id``; ``W_qkv`` is one leaf where the
published checkpoint has three projections and three convolutions.
"""

from __future__ import annotations

import functools
from typing import Any

import numpy as np

PROGRAM_FAMILY = "olmo_hybrid_lm"
ALIGN = 16          # the artifact format's leaf alignment (weights.py)
Q_BLOCK = 256       # queries a block of the reference's attention
ROW_BLOCK = 2048    # rows a block of the reference's MLP
A_LOG_MEAN, DT_BIAS_MEAN = 0.5, -3.0    # what ``to_tree`` adds to the draws

LINEAR, FULL = "linear_attention", "full_attention"


def program_config(config: dict) -> dict:
    from tfservingcache_tpu.models import registry

    if PROGRAM_FAMILY not in registry.families():
        raise ValueError(
            "this program has no olmo_hybrid_lm family: no delta rule, no "
            "block whose norm follows its mixer (PR 46 adds them)")
    for key, want in (("tie_word_embeddings", False), ("attention_bias", False),
                      ("hidden_act", "silu")):
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: the program computes "
                             f"{want!r} only")
    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError("the program's delta rule has a key head a value head")
    types = list(config["layer_types"])[:int(config["num_hidden_layers"])]
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": int(config["num_hidden_layers"]),
        "layer_types": types,
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "d_ff": config["intermediate_size"],
        "linear_heads": config["linear_num_value_heads"],
        "linear_key_dim": config["linear_key_head_dim"],
        "linear_value_dim": config["linear_value_head_dim"],
        "linear_conv": config["linear_conv_kernel_dim"],
        "linear_allow_neg_eigval": bool(config["linear_allow_neg_eigval"]),
        "rms_eps": float(config["rms_norm_eps"]),
        "qk_norm_eps": float(config["rms_norm_eps"]),
        "rope_theta": (config.get("rope_parameters") or {}).get("rope_theta"),
        "max_seq": config["max_position_embeddings"],
        "dtype": config["torch_dtype"],
    }


# -- the weights ------------------------------------------------------------

def _sizes(mc: dict[str, Any]) -> tuple[int, int, int, int]:
    """``(H, d_k, d_v, the convolution's width 2 H d_k + H d_v)``."""
    h, d_k, d_v = mc["linear_heads"], mc["linear_key_dim"], mc["linear_value_dim"]
    return h, d_k, d_v, h * (2 * d_k + d_v)


def _layer_shapes(mc: dict[str, Any], kind: str) -> dict[str, tuple[tuple[int, ...], int]]:
    d, ff = mc["d_model"], mc["d_ff"]
    hd = d // mc["n_heads"]
    q, kv = mc["n_heads"] * hd, mc["n_kv_heads"] * hd
    h, _d_k, d_v, width = _sizes(mc)
    shapes = {"mlp/w1": ((d, ff), d), "mlp/w3": ((d, ff), d),
              "mlp/w2": ((ff, d), ff)}
    if kind == LINEAR:
        shapes.update({
            "gdn/w_qkv": ((d, width), d), "gdn/w_g": ((d, h * d_v), d),
            "gdn/w_a": ((d, h), 16 * d), "gdn/w_b": ((d, h), d),
            "gdn/conv_w": ((width, mc["linear_conv"]), mc["linear_conv"]),
            "gdn/a_log": ((h,), 1), "gdn/dt_bias": ((h,), 1),
            "gdn/w_o": ((h * d_v, d), h * d_v)})
    else:
        shapes.update({"attn/wq": ((d, q), d), "attn/wk": ((d, kv), d),
                       "attn/wv": ((d, kv), d), "attn/wo": ((q, d), q)})
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


def _const_sizes(mc: dict[str, Any]) -> list[int]:
    """Lengths of every float32 vector ``to_tree`` adds."""
    d = mc["d_model"]
    hd = d // mc["n_heads"]
    sizes = [d]
    for kind in mc["layer_types"]:
        sizes += [d, d]
        sizes += ([mc["linear_value_dim"]] if kind == LINEAR
                  else [mc["n_heads"] * hd, mc["n_kv_heads"] * hd])
    return sizes


def param_bytes(mc: dict[str, Any]) -> int:
    """Bytes of one tenant's params.bin (drawn leaves in the model's dtype,
    float32 gains)."""
    import jax.numpy as jnp

    item = jnp.dtype(mc["dtype"]).itemsize
    shapes = leaf_shapes(mc)
    drawn = sum(int(np.prod(s)) for s, _ in shapes.values())
    consts = _const_sizes(mc)
    return drawn * item + sum(consts) * 4 + ALIGN * (len(consts) + len(shapes))


def to_tree(mc: dict[str, Any], leaves: dict[str, np.ndarray]) -> dict:
    """Host arrays -> the program's params pytree (views, no copy but
    ``a_log`` and ``dt_bias``, which are moved to their means)."""
    ones = lambda n: np.ones((n,), np.float32)  # noqa: E731
    d = mc["d_model"]
    hd = d // mc["n_heads"]
    layers = []
    for i, kind in enumerate(mc["layer_types"]):
        layer: dict[str, Any] = {"ln1_post": ones(d), "ln2_post": ones(d)}
        for name in _layer_shapes(mc, kind):
            node = layer
            *groups, leaf = name.split("/")
            for g in groups:
                node = node.setdefault(g, {})
            node[leaf] = leaves[f"{name}/{i}"]
        if kind == LINEAR:
            for leaf, mean in (("a_log", A_LOG_MEAN), ("dt_bias", DT_BIAS_MEAN)):
                drawn = layer["gdn"][leaf]
                layer["gdn"][leaf] = (
                    drawn.astype(np.float32) + mean).astype(drawn.dtype)
            layer["gdn"]["o_norm"] = ones(mc["linear_value_dim"])
        else:
            layer["attn"]["q_norm"] = ones(mc["n_heads"] * hd)
            layer["attn"]["k_norm"] = ones(mc["n_kv_heads"] * hd)
        layers.append(layer)
    return {"embed": leaves["embed"], "lm_head": leaves["lm_head"],
            "layers": layers, "ln_f": ones(d)}


# -- the plain reference ------------------------------------------------------

@functools.lru_cache(maxsize=4)
def _fns(n_heads: int, lin_heads: int, d_k: int, d_v: int, eps: float,
         neg_eigval: bool, theta):
    import jax
    import jax.numpy as jnp

    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    def rms(x, gain):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(gain)

    @jax.jit
    def linear(x, op, g1):
        s = x.shape[0]
        w = f32(op["conv_w"])                                  # (width, taps)
        taps = w.shape[1]
        w_qkv = f32(op["w_qkv"])

        def part(first, width):
            """``c`` over ``width`` columns from ``first`` (q', k' or v'): a
            part at a time, so that beside a serving program no float32 array
            of all the columns of 16384 positions exists."""
            cols = slice(first, first + width)
            padded = jnp.pad(x @ w_qkv[:, cols], ((taps - 1, 0), (0, 0)))
            return jax.nn.silu(sum(
                w[cols, j] * padded[j:j + s] for j in range(taps)))

        q = part(0, lin_heads * d_k).reshape(s, lin_heads, d_k)
        k = part(lin_heads * d_k, lin_heads * d_k).reshape(s, lin_heads, d_k)
        v = part(2 * lin_heads * d_k, lin_heads * d_v).reshape(s, lin_heads, d_v)
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / jnp.sqrt(
            jnp.float32(d_k))
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        alpha = jnp.exp(-jnp.exp(f32(op["a_log"]))
                        * jax.nn.softplus(x @ f32(op["w_a"]) + f32(op["dt_bias"])))
        beta = jax.nn.sigmoid(x @ f32(op["w_b"])) * (2.0 if neg_eigval else 1.0)

        def token(state, row):
            q_t, k_t, v_t, a_t, b_t = row                      # (H, d), (H,)
            state = a_t[:, None, None] * state                 # (H, d_k, d_v)
            read = jnp.einsum("hk,hkv->hv", k_t, state)
            state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - read))[:, None, :]
            return state, jnp.einsum("hk,hkv->hv", q_t, state)

        _, o = jax.lax.scan(
            token, jnp.zeros((lin_heads, d_k, d_v), jnp.float32),
            (q, k, v, alpha, beta))
        z = (x @ f32(op["w_g"])).reshape(s, lin_heads, d_v)
        mix = (rms(o, op["o_norm"]) * jax.nn.silu(z)).reshape(s, -1) @ f32(op["w_o"])
        return x + rms(mix, g1)

    @jax.jit
    def attend(x, attn, g1):
        s, d = x.shape
        hd = d // n_heads
        q = rms(x @ f32(attn["wq"]), attn["q_norm"]).reshape(s, n_heads, hd)
        k = rms(x @ f32(attn["wk"]), attn["k_norm"]).reshape(s, n_heads, hd)
        v = (x @ f32(attn["wv"])).reshape(s, n_heads, hd)
        if theta is not None:
            freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
            angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
            cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]

            def rot(t):
                t1, t2 = t[..., 0::2], t[..., 1::2]
                return jnp.stack([t1 * cos - t2 * sin, t1 * sin + t2 * cos],
                                 -1).reshape(t.shape)

            q, k = rot(q), rot(k)
        # by blocks of queries, one at a time (``lax.map``), each against every
        # key under the causal mask: the scores of a block are all that exists
        blocks = -(-s // Q_BLOCK)
        q_pad = jnp.pad(q, ((0, blocks * Q_BLOCK - s), (0, 0), (0, 0)))
        key_at = jnp.arange(s)[None, :]

        def block(args):
            q_b, first = args                                  # (Q_BLOCK, n, hd)
            scores = jnp.einsum("qhd,khd->hqk", q_b, k) / jnp.sqrt(jnp.float32(hd))
            mask = key_at <= (first + jnp.arange(Q_BLOCK))[:, None]
            probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        out = jax.lax.map(block, (q_pad.reshape(blocks, Q_BLOCK, n_heads, hd),
                                  jnp.arange(blocks) * Q_BLOCK))
        mix = out.reshape(blocks * Q_BLOCK, -1)[:s] @ f32(attn["wo"])
        return x + rms(mix, g1)

    @jax.jit
    def ffn(h, g2, mlp):
        # by blocks of rows, one at a time: a token's MLP is its own
        s = h.shape[0]
        blocks = -(-s // ROW_BLOCK)
        rows = jnp.pad(h, ((0, blocks * ROW_BLOCK - s), (0, 0)))
        w1, w3, w2 = f32(mlp["w1"]), f32(mlp["w3"]), f32(mlp["w2"])
        y = jax.lax.map(lambda r: (jax.nn.silu(r @ w1) * (r @ w3)) @ w2,
                        rows.reshape(blocks, ROW_BLOCK, -1))
        return h + rms(y.reshape(blocks * ROW_BLOCK, -1)[:s], g2)

    @jax.jit
    def head(x, ln_f, w):
        return rms(x, ln_f) @ f32(w)

    return linear, attend, ffn, head


def logits_many(mc: dict[str, Any], tree: dict, sequences,
                last: int = 1) -> list[np.ndarray]:
    """float32 logits ``(last, vocab)`` of the final ``last`` positions of
    each sequence; ``tree`` is the params pytree of host arrays. Layers are
    the outer loop, so one layer's float32 weights are on the device at a
    time."""
    import jax
    import jax.numpy as jnp

    linear, attend, ffn, head = _fns(
        int(mc["n_heads"]), int(mc["linear_heads"]), int(mc["linear_key_dim"]),
        int(mc["linear_value_dim"]), float(mc["rms_eps"]),
        bool(mc["linear_allow_neg_eigval"]), mc["rope_theta"])
    with jax.default_matmul_precision("highest"):
        embed = tree["embed"]
        xs = [jnp.asarray(embed[np.asarray(ids, np.int32)]).astype(jnp.float32)
              for ids in sequences]
        for lp, kind in zip(tree["layers"], mc["layer_types"]):
            op = jax.device_put(lp["gdn" if kind == LINEAR else "attn"])
            mix = linear if kind == LINEAR else attend
            hs = [mix(x, op, lp["ln1_post"]) for x in xs]
            del op
            mlp = jax.device_put(lp["mlp"])
            xs = [ffn(h, lp["ln2_post"], mlp) for h in hs]
            del mlp
        ln_f, w = jnp.asarray(tree["ln_f"]), jnp.asarray(tree["lm_head"])
        return [np.asarray(head(x[-last:], ln_f, w)) for x in xs]
