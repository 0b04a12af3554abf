"""The ``sambay`` family: SambaY decoder-hybrid-decoders as the program's
``models/sambay_lm`` runs them (Phi-4-mini-flash-reasoning is the
configuration: ``configs/phi-4-mini-flash-reasoning.json``). A self-decoder of
Mamba layers alternating with window attention, one more Mamba layer and ONE
full-attention layer; then a cross-decoder of gated memory units (which re-read
that last Mamba layer's scan output of the same token) alternating with cross
attention (which reads the full-attention layer's keys and values). Attention
of all three kinds is differential. No positional encoding; the head is the
embedding.

What a family file holds is said in ``families/transformer_lm.py``; this one
differs where the architecture does:

* ``program_config`` maps the published keys (and the ``assumed`` Mamba sizes
  the published config has no key for) to ``sambay_lm``'s config. The layers'
  kinds follow from the depth by the family's rule (``layer_kinds``). It
  refuses, at once and before any weight is made, a checkout whose program has
  no ``sambay_lm`` family (every commit before PR 41): such a checkout exits
  non-zero in seconds.
* ``leaf_shapes``: every matrix and every vector of a layer a leaf of its own,
  named by the mixer a layer has (``ssm``, ``attn`` or ``gmu``). Vectors are
  drawn small so that the paths they sit on are exercised: LayerNorm biases,
  the convolution's bias and ``dt``'s bias at std 0.02-0.1, the four ``lam``
  vectors at std 0.1, ``a_log`` at std 1 (``A = -exp(a_log)`` around -1).
  ``to_tree`` adds the gains (ones): ``ln1``, ``ln2``, ``ln_f``, the
  sub-norm's; ``d_skip`` ones and the final norm's bias zeros.

The plain reference is the published block in float32 under
``jax.default_matmul_precision("highest")``, whole sequences with no cache, no
state handed on, no kernels, no batching, independent of the program's code.
``LN(x; g, b)`` is a LayerNorm over the last axis at ``layer_norm_eps``; for
layer ``l`` of ``n``, ``m = n / 2``::

    h  = x + Mix_l(LN(x; g1, b1))       x' = h + (silu(a W_1) * (a W_3)) W_2,  a = LN(h; g2, b2)

    Mamba (l < m even; l = m):
        [xs | z] = u W_in ;  xc_t = silu(sum_{j<4} w[:, j] xs_{t-3+j} + b_c)   (xs_{<0} = 0)
        [d | B | C] = xc W_x ;  dt = softplus(d W_dt + b_dt) ;  A = -exp(a_log)
        H_t = exp(dt_t A) * H_{t-1} + (dt_t * xc_t) B_t^T          (a lax.scan over t, H_{-1} = 0)
        y_t = H_t C_t + D * xc_t ;  Mix = (y * silu(z)) W_out ;  layer m hands on M = y
    gated memory unit (l >= m + 2 even):   Mix = (M * silu(u W_1)) W_2
    attention (window: l < m odd; full: l = m + 1; cross: l >= m + 2 odd):
        query heads in pairs (q1_i, q2_i) = (head 2i, head 2i + 1), KV pair j = i // 2:
        o_i = softmax(q1_i k(2j)^T / sqrt(D) + mask) V_j - lam softmax(q2_i k(2j+1)^T / sqrt(D) + mask) V_j
        V_j = [v(2j) | v(2j+1)] ;  o_i <- rms(o_i; g_sub) * (1 - lam0) ;  Mix = concat_i(o_i) W_o
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0 ;  lam0 = 0.8 - 0.6 exp(-0.3 l)
        mask causal, and i - j < sliding_window in a window layer;
        a cross layer's k, v are layer (m + 1)'s, its own are q and W_o
    logits = LN(x_n; g_f, b_f) E^T

Two dense softmaxes a pair, written as they are stated: nothing is padded with
zeros and no row holds two heads. The program's ``a_log`` leaf is ``(N, E)``
(its scan state's layout); the reference transposes it to the published ``(E,
N)``. Departures (listed in the configuration's file): no ``eos_id``.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import numpy as np

PROGRAM_FAMILY = "sambay_lm"
ALIGN = 16          # the artifact format's leaf alignment (weights.py)

MAMBA, WINDOW, FULL, GMU, CROSS = (
    "mamba", "sliding_attention", "full_attention", "gmu", "cross_attention")
LAM = ("lam_q1", "lam_k1", "lam_q2", "lam_k2")


def layer_kinds(n_layers: int) -> list[str]:
    """The family's rule, stated here on its own (the program has its copy)."""
    m = n_layers // 2
    return [(WINDOW if l % 2 else MAMBA) if l < m else
            MAMBA if l == m else FULL if l == m + 1 else
            (CROSS if l % 2 else GMU) for l in range(n_layers)]


def program_config(config: dict) -> dict:
    from tfservingcache_tpu.models import registry

    if PROGRAM_FAMILY not in registry.families():
        raise ValueError(
            "this program has no sambay_lm family: no scanned lane state, no "
            "layer that reads another layer's rows (PR 41 adds them)")
    assumed = config["assumed"]
    for key, want in (("tie_word_embeddings", True), ("mlp_bias", False),
                      ("lm_head_bias", False), ("hidden_act", "silu")):
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: the program computes "
                             f"{want!r} only")
    if config["num_attention_heads"] != 2 * config["num_key_value_heads"]:
        raise ValueError("differential attention pairs two query heads over "
                         "each KV head: num_attention_heads must be twice "
                         "num_key_value_heads")
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": int(config["num_hidden_layers"]),
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "d_ff": config["intermediate_size"],
        "sliding_window": config["sliding_window"],
        "layer_types": layer_kinds(int(config["num_hidden_layers"])),
        "ssm_expand": int(assumed["mamba_expand"]["value"]),
        "ssm_state": int(assumed["mamba_d_state"]["value"]),
        "ssm_conv": int(assumed["mamba_d_conv"]["value"]),
        "dt_rank": int(assumed["mamba_dt_rank"]["value"]),
        "norm_eps": float(config["layer_norm_eps"]),
        "max_seq": config["max_position_embeddings"],
        "dtype": config["torch_dtype"],
    }


# -- the weights ------------------------------------------------------------

def _std(std: float) -> int:
    """The fan-in at which ``weights.py``'s ``normal / sqrt(fan_in)`` has this
    standard deviation."""
    return round(1.0 / std ** 2)


def _layer_shapes(mc: dict[str, Any], kind: str) -> dict[str, tuple[tuple[int, ...], int]]:
    d, ff = mc["d_model"], mc["d_ff"]
    hd = d // mc["n_heads"]
    q, kv = mc["n_heads"] * hd, mc["n_kv_heads"] * hd
    e, n = mc["ssm_expand"] * d, mc["ssm_state"]
    r, taps = mc["dt_rank"], mc["ssm_conv"]
    shapes = {"ln1_b": ((d,), _std(0.02)), "ln2_b": ((d,), _std(0.02)),
              "mlp/w1": ((d, ff), d), "mlp/w3": ((d, ff), d),
              "mlp/w2": ((ff, d), ff)}
    if kind == MAMBA:
        shapes.update({
            "ssm/w_in": ((d, 2 * e), d), "ssm/conv_w": ((e, taps), taps),
            "ssm/conv_b": ((e,), _std(0.1)), "ssm/w_x": ((e, r + 2 * n), e),
            "ssm/w_dt": ((r, e), r), "ssm/dt_b": ((e,), _std(0.1)),
            "ssm/a_log": ((n, e), 1), "ssm/w_out": ((e, d), e)})
    elif kind == GMU:
        shapes.update({"gmu/w1": ((d, e), d), "gmu/w2": ((e, d), e)})
    else:
        shapes.update({"attn/wq": ((d, q), d), "attn/wo": ((q, d), q),
                       **{f"attn/{w}": ((hd,), _std(0.1)) for w in LAM}})
        if kind != CROSS:
            shapes.update({"attn/wk": ((d, kv), d), "attn/wv": ((d, kv), d)})
    return shapes


def leaf_shapes(mc: dict[str, Any]) -> dict[str, tuple[tuple[int, ...], int]]:
    """Leaves -> (shape, fan_in): ``<leaf>/<layer>`` for a layer's, then the
    embedding (which is the head too)."""
    shapes = {f"{name}/{i}": sf
              for i, kind in enumerate(layer_kinds(mc["n_layers"]))
              for name, sf in _layer_shapes(mc, kind).items()}
    shapes["embed"] = ((mc["vocab_size"], mc["d_model"]), mc["d_model"])
    return shapes


def _const_sizes(mc: dict[str, Any]) -> list[int]:
    """Lengths of every float32 vector ``to_tree`` adds."""
    d = mc["d_model"]
    e, hd = mc["ssm_expand"] * d, d // mc["n_heads"]
    sizes = [d, d]
    for kind in layer_kinds(mc["n_layers"]):
        sizes += [d, d]
        if kind == MAMBA:
            sizes.append(e)
        elif kind != GMU:
            sizes.append(2 * hd)
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
    """Host arrays -> the program's params pytree (views, no copy)."""
    ones = lambda n: np.ones((n,), np.float32)  # noqa: E731
    d = mc["d_model"]
    e, hd = mc["ssm_expand"] * d, d // mc["n_heads"]
    layers = []
    for i, kind in enumerate(layer_kinds(mc["n_layers"])):
        layer: dict[str, Any] = {"ln1": ones(d), "ln2": ones(d)}
        for name in _layer_shapes(mc, kind):
            node = layer
            *groups, leaf = name.split("/")
            for g in groups:
                node = node.setdefault(g, {})
            node[leaf] = leaves[f"{name}/{i}"]
        if kind == MAMBA:
            layer["ssm"]["d_skip"] = ones(e)
        elif kind != GMU:
            layer["attn"]["sub_norm"] = ones(2 * hd)
        layers.append(layer)
    return {"embed": leaves["embed"], "layers": layers, "ln_f": ones(d),
            "ln_f_b": np.zeros((d,), np.float32)}


# -- the plain reference ------------------------------------------------------

@functools.lru_cache(maxsize=4)
def _fns(n_heads: int, n_kv: int, window: int, eps: float):
    import jax
    import jax.numpy as jnp

    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    def ln(x, gain, bias):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * f32(gain) + f32(bias)

    @jax.jit
    def mamba(x, ssm, ln1, ln1_b):
        """-> (x + Mix, y): ``y`` is the scan's output, what layer m hands on."""
        s = x.shape[0]
        u = ln(x, ln1, ln1_b)
        xs, z = jnp.split(u @ f32(ssm["w_in"]), 2, -1)
        w = f32(ssm["conv_w"])                                # (E, taps)
        taps = w.shape[1]
        padded = jnp.pad(xs, ((taps - 1, 0), (0, 0)))
        xc = jax.nn.silu(sum(w[:, j] * padded[j:j + s] for j in range(taps))
                         + f32(ssm["conv_b"]))
        r = ssm["w_dt"].shape[0]
        n = ssm["a_log"].shape[0]
        dbc = xc @ f32(ssm["w_x"])
        d_in, b_in, c_in = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
        dt = jax.nn.softplus(d_in @ f32(ssm["w_dt"]) + f32(ssm["dt_b"]))
        a = -jnp.exp(f32(ssm["a_log"])).T                     # (E, N), as published

        def token(h, row):
            dt_t, xc_t, b_t, c_t = row
            h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * xc_t)[:, None] * b_t[None, :]
            return h, h @ c_t + f32(ssm["d_skip"]) * xc_t

        _, y = jax.lax.scan(token, jnp.zeros(a.shape, jnp.float32),
                            (dt, xc, b_in, c_in))
        return x + (y * jax.nn.silu(z)) @ f32(ssm["w_out"]), y

    @jax.jit
    def gmu(x, op, ln1, ln1_b, memory):
        u = ln(x, ln1, ln1_b)
        return x + (memory * jax.nn.silu(u @ f32(op["w1"]))) @ f32(op["w2"])

    def attend(x, attn, ln1, ln1_b, kv, lam0, windowed):
        """-> (x + Mix, (k, v)): ``kv`` is the full-attention layer's for a
        cross layer, None for a layer with keys and values of its own;
        ``lam0`` the layer's ``0.8 - 0.6 exp(-0.3 l)`` (an operand, so that the
        layers of one kind share one compiled function)."""
        s, d = x.shape
        hd = d // n_heads
        u = ln(x, ln1, ln1_b)
        q = (u @ f32(attn["wq"])).reshape(s, n_heads, hd)
        if kv is None:
            kv = ((u @ f32(attn["wk"])).reshape(s, n_kv, hd),
                  (u @ f32(attn["wv"])).reshape(s, n_kv, hd))
        k, v = kv
        i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        mask = j <= i
        if windowed:
            mask &= i - j < window
        lam = (jnp.exp(jnp.sum(f32(attn["lam_q1"]) * f32(attn["lam_k1"])))
               - jnp.exp(jnp.sum(f32(attn["lam_q2"]) * f32(attn["lam_k2"]))) + lam0)

        def probs(qh, kh):
            scores = qh @ kh.T / jnp.sqrt(jnp.float32(hd))
            return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)

        outs = []
        for pair in range(n_heads // 2):
            kv_pair = pair // 2
            values = jnp.concatenate(
                [v[:, 2 * kv_pair], v[:, 2 * kv_pair + 1]], -1)   # (S, 2 hd)
            o = (probs(q[:, 2 * pair], k[:, 2 * kv_pair]) @ values
                 - lam * probs(q[:, 2 * pair + 1], k[:, 2 * kv_pair + 1]) @ values)
            o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
            outs.append(o * f32(attn["sub_norm"]) * (1.0 - lam0))
        return x + jnp.concatenate(outs, -1) @ f32(attn["wo"]), kv

    attend = jax.jit(attend, static_argnames=("windowed",))

    @jax.jit
    def ffn(h, ln2, ln2_b, mlp):
        a = ln(h, ln2, ln2_b)
        return h + (jax.nn.silu(a @ f32(mlp["w1"])) * (a @ f32(mlp["w3"]))) \
            @ f32(mlp["w2"])

    @jax.jit
    def head(x, ln_f, ln_f_b, embed):
        return ln(x, ln_f, ln_f_b) @ f32(embed).T

    return mamba, gmu, attend, ffn, head


def logits_many(mc: dict[str, Any], tree: dict, sequences,
                last: int = 1) -> list[np.ndarray]:
    """float32 logits ``(last, vocab)`` of the final ``last`` positions of
    each sequence; ``tree`` is the params pytree of host arrays. Layers are
    the outer loop, so one layer's float32 weights are on the device at a
    time."""
    import jax
    import jax.numpy as jnp

    mamba, gmu, attend, ffn, head = _fns(
        int(mc["n_heads"]), int(mc["n_kv_heads"]), int(mc["sliding_window"]),
        float(mc["norm_eps"]))
    kinds = layer_kinds(int(mc["n_layers"]))
    with jax.default_matmul_precision("highest"):
        embed = tree["embed"]
        xs = [jnp.asarray(embed[np.asarray(ids, np.int32)]).astype(jnp.float32)
              for ids in sequences]
        memory = shared = [None] * len(xs)
        for depth, (lp, kind) in enumerate(zip(tree["layers"], kinds)):
            norms = jax.device_put((lp["ln1"], lp["ln1_b"]))
            if kind == MAMBA:
                op = jax.device_put(lp["ssm"])
                hs, memory = zip(*(mamba(x, op, *norms) for x in xs))
            elif kind == GMU:
                op = jax.device_put(lp["gmu"])
                hs = [gmu(x, op, *norms, m) for x, m in zip(xs, memory)]
            else:
                op = jax.device_put(lp["attn"])
                hs, rows = zip(*(
                    attend(x, op, *norms, kv if kind == CROSS else None,
                           jnp.float32(0.8 - 0.6 * math.exp(-0.3 * depth)),
                           windowed=kind == WINDOW)
                    for x, kv in zip(xs, shared)))
                if kind == FULL:
                    shared = rows
            del op
            mlp = jax.device_put(lp["mlp"])
            xs = [ffn(h, lp["ln2"], lp["ln2_b"], mlp) for h in hs]
            del mlp
        ln_f, ln_f_b, emb = (jnp.asarray(tree[k]) for k in ("ln_f", "ln_f_b", "embed"))
        return [np.asarray(head(x[-last:], ln_f, ln_f_b, emb)) for x in xs]
