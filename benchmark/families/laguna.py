"""The ``laguna`` family: Laguna-S-2.1-style mixture-of-experts decoders whose
layers differ in their QUERY side, as the program's ``models/moe_lm`` runs ONE
CHIP'S SHARE of them (``configs/laguna-s-2.1.json``): ``full_attention`` layers
of 48 query heads beside ``sliding_attention`` layers of 72 over the same 8 KV
heads of 128 (GQA groups of 6 and 9 in one model), an output gate of one value
a HEAD, a rotary a kind (window layers: plain, the whole head; global layers:
YaRN over the first ``partial_rotary_factor`` of the head, the rest unturned),
one leading dense SwiGLU layer, then routed experts (sigmoid scores, the top
10 renormalised and scaled by ``moe_routed_scaling_factor``) of which this chip
holds a share, and a shared expert every token takes.

What a family file holds is said in ``families/transformer_lm.py``; this one
differs where the architecture and the cut do:

* ``program_config`` maps the published keys (``layer_types``,
  ``mlp_layer_types``, ``num_attention_heads_per_layer``, of each the first
  ``num_hidden_layers`` entries; ``rope_parameters`` by kind; ``gating``; the
  expert keys) to ``moe_lm``'s config. The file's ``num_experts`` is the number
  of experts HELD here and ``source_values.num_experts`` the router's
  published width; the file's ``vocab_size`` is the slice of the vocabulary
  held here. It refuses what the program does not compute (biases, a gate
  that is not per-head, a window rotary that is not plain over the whole
  head, a global one that is not YaRN, a router with a soft cap or with its
  weight on the input, tied embeddings) and, AT ONCE and before any weight is
  made, a checkout whose ``moe_lm`` knows no head count a layer (every commit
  before PR 53): such a checkout exits non-zero in seconds.
* ``leaf_shapes``: the attention leaves in TWO stacks, one a kind, since their
  shapes differ (``attn_full/wq (global layers, hidden, 48 x 128)``,
  ``attn_window/wq (window layers, hidden, 72 x 128)``, ``wo`` and the gate
  ``w_gate (.., hidden, heads)`` likewise; ``wk`` / ``wv`` are the same shape
  in both), the dense layers' ``mlp``, the expert layers' router ``(hidden,
  256)``, HELD experts ``(layers, held, hidden, width)`` and shared expert;
  every matrix normal / sqrt(fan_in) but the routed experts' down projection,
  drawn at half of that (``ROUTED_DOWN_FAN`` says why). ``to_tree`` adds
  the gains (ones): ``ln1``, ``ln2``, ``ln_f``.

The plain reference is the block in float32 under
``jax.default_matmul_precision("highest")``, whole sequences, no cache, no
kernels, no batching, none of the program's code. ``H_l`` = 48 in a
``full_attention`` layer, 72 in a ``sliding_attention`` layer; ``eps`` 1e-6::

    a    = RMSNorm(x; g_in)
    q    = a Wq_l  (H_l x 128)     k = a Wk  (8 x 128)     v = a Wv  (8 x 128)
    gate = sigmoid(a Wg_l)         (H_l values, one a head)
    sliding_attention:  f_i = 10000^(-2i/128), i < 64: all 128 columns turn
    full_attention:     the first 64 columns turn, the last 64 pass unturned;
                        f_i = YaRN's blend of 500000^(-2i/64), i < 32 (factor
                        128, original 8192, beta_fast 32, beta_slow 1, the same
                        at every position); cos and sin times attention_factor
    s_ij = rope(q_i) . rope(k_j) / sqrt(128),  j <= i, and i - j < 512 in a
           sliding_attention layer
    h    = x + concat_h( gate_h * softmax_j(s_h) v ) Wo_l     (H_l x 128 -> 3072)
    z    = RMSNorm(h; g_post)
    dense layer:   y = h + ( silu(z W1) * (z W3) ) W2                (width 12288)
    expert layer:  p = sigmoid(z Wr)  (float32, 256);  I = top_10(p)
                   w = 2.5 * p_I / sum(p_I)
                   y = h + sum_{i in I, i held here} w_i E_i(z) + S(z)
                   E_i, S: SwiGLU of width 1024;  S the shared expert, ungated
    out  = RMSNorm(y_L; g_f) W_head                 (W_head is not the embedding)

Attention runs a block of ``Q_BLOCK`` queries at a time against the keys those
queries may read (a window layer: the block's own positions and the ``window -
1`` before its first), the MLPs by blocks of ``ROW_BLOCK`` rows, a group of
``EXPERT_GROUP`` held experts' float32 weights on the device at a time, so
that 16,407 positions fit beside the served model: the same softmax, nothing
approximated. The expert sum is taken the way the program does not take it:
every HELD expert applied to every token and weighted by ``w_i`` where the
token chose it and by zero where it did not; what the experts held elsewhere
would add is left out, as in the program. Departures and what is assumed are
listed in the configuration's file.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any

import numpy as np

PROGRAM_FAMILY = "moe_lm"
ALIGN = 16          # the artifact format's leaf alignment (weights.py)
Q_BLOCK = 128       # queries the reference attends at once
ROW_BLOCK = 2048    # rows a block of the reference's MLPs
EXPERT_GROUP = 4    # experts whose float32 weights the reference holds at once
# A routed expert's down projection is drawn at HALF a projection's std (its
# fan-in counted 4 times). With random weights a router decides nothing: its
# 10th and 11th of 256 scores lie 0.04 of a logit's std apart, so bf16 and
# float32 arithmetic choose different experts for many tokens, the choices
# compound down the layers, and at the published gate of a quarter an expert
# the check then measures those coin flips and not the arithmetic (at a full
# std the sound program read up to 1.33 reference-std over 52 sequences and
# the float8 reference down to 1.58: no limit between). At a QUARTER the flips
# fall to 0.18, but so does the term itself: a program whose routed experts
# give NOTHING read 0.23-0.56 over six seeds, under any limit the float8
# control allows. At a half the same fault reads 0.78-1.16 in every one of six
# seeds and experts one place round 0.90-1.42, against a sound program's 0.28
# at most on those seeds (my chip runs, PR 53: the cell's file has them all).
# Shapes, bytes, the router and every count a step takes are what they were.
ROUTED_DOWN_FAN = 4
SLIDING, FULL = "sliding_attention", "full_attention"
STACK = {FULL: "attn_full", SLIDING: "attn_window"}
SWIGLU = ("w1", "w2", "w3")


def program_config(config: dict) -> dict:
    from tfservingcache_tpu.models import registry

    if not hasattr(registry, "query_heads"):
        raise ValueError(
            "this program's moe_lm knows no head count a layer: one n_heads a "
            "model, one rope_theta, a whole-head rotary, no gate a head, no "
            "dense layer among expert layers (PR 53 adds them)")
    n = int(config["num_hidden_layers"])
    types = list(config["layer_types"])[:n]
    mlps = list(config["mlp_layer_types"])[:n]
    heads = [int(h) for h in config["num_attention_heads_per_layer"][:n]]
    if len(types) != n or set(types) - {SLIDING, FULL}:
        raise ValueError(f"layer_types must give {n} layers of "
                         f"{[SLIDING, FULL]}, got {types}")
    if len(mlps) != n or set(mlps) - {"dense", "sparse"} or len(heads) != n:
        raise ValueError(f"mlp_layer_types / num_attention_heads_per_layer "
                         f"must give {n} layers, got {mlps}, {heads}")
    dense = [i for i, kind in enumerate(mlps) if kind == "dense"]
    if dense != [i for i in config.get("mlp_only_layers", dense) if i < n]:
        raise ValueError("mlp_only_layers and mlp_layer_types disagree")
    for key, want in (("attention_bias", False), ("gating", "per-head"),
                      ("tie_word_embeddings", False),
                      ("moe_router_logit_softcapping", 0),
                      ("moe_apply_router_weight_on_input", False),
                      ("decoder_sparse_step", 1)):
        if config.get(key, want) != want:
            raise ValueError(f"{key}={config[key]!r}: the program computes "
                             f"{want!r} only")
    rope = config["rope_parameters"]
    sliding, full = rope[SLIDING], rope[FULL]
    if (sliding.get("rope_type", "default") != "default"
            or float(sliding.get("partial_rotary_factor", 1)) != 1.0
            or full.get("rope_type") != "yarn"):
        raise ValueError("the program computes plain rotary over the whole "
                         "head in the window layers and YaRN in the global ones")
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": n,
        "n_heads": config["num_attention_heads"],
        "n_heads_per_layer": heads,
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "layer_types": types,
        "sliding_window": config["sliding_window"],
        "attn_gate": "head",
        "mlp_only_layers": dense,
        "d_ff_dense": config["intermediate_size"],
        "d_ff": config["moe_intermediate_size"],
        "shared_width": config["shared_expert_intermediate_size"],
        "n_experts": config["source_values"]["num_experts"],
        "n_experts_held": config["num_experts"],
        "expert_first": int(config["assumed"]["expert_first"]["value"]),
        "top_k": config["num_experts_per_tok"],
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "route_score": config["assumed"]["scoring_func"]["value"],
        "route_scale": float(config["moe_routed_scaling_factor"]),
        "qk_norm": False,
        "tie_embeddings": False,
        "max_seq": config["max_position_embeddings"],
        "rope_theta": float(full["rope_theta"]),
        "rope_theta_window": float(sliding["rope_theta"]),
        "rope_full": {
            "yarn": float(full["factor"]),
            "original_max": int(full["original_max_position_embeddings"]),
            "beta_fast": float(full["beta_fast"]),
            "beta_slow": float(full["beta_slow"]),
            "attention_factor": float(full["attention_factor"]),
            "partial": float(full.get("partial_rotary_factor", 1.0)),
        },
        "rms_eps": float(config["rms_norm_eps"]),
        "dtype": config["torch_dtype"],
    }


# -- the weights ------------------------------------------------------------

def _layers_of(mc: dict[str, Any]) -> dict[str, list[int]]:
    """Which layers each stack of leaves holds, in order."""
    dense = set(mc["mlp_only_layers"])
    every = range(mc["n_layers"])
    return {
        "attn": list(every),
        STACK[FULL]: [i for i in every if mc["layer_types"][i] == FULL],
        STACK[SLIDING]: [i for i in every if mc["layer_types"][i] == SLIDING],
        "mlp": [i for i in every if i in dense],
        "moe": [i for i in every if i not in dense],
    }


def leaf_shapes(mc: dict[str, Any]) -> dict[str, tuple[tuple[int, ...], int]]:
    """Stacked leaves -> (shape with the stack's layers first, fan_in)."""
    d, v, hd = mc["d_model"], mc["vocab_size"], mc["head_dim"]
    ff, ffd, ffs = mc["d_ff"], mc["d_ff_dense"], mc["shared_width"]
    held, kv = mc["n_experts_held"], mc["n_kv_heads"] * hd
    count = {stack: len(layers) for stack, layers in _layers_of(mc).items()}
    n, nd, ns = count["attn"], count["mlp"], count["moe"]
    shapes = {
        "embed": ((v, d), d), "lm_head": ((d, v), d),
        "attn/wk": ((n, d, kv), d), "attn/wv": ((n, d, kv), d),
        "mlp/w1": ((nd, d, ffd), d), "mlp/w3": ((nd, d, ffd), d),
        "mlp/w2": ((nd, ffd, d), ffd),
        "moe/router": ((ns, d, mc["n_experts"]), d),
        "moe/w1": ((ns, held, d, ff), d), "moe/w3": ((ns, held, d, ff), d),
        "moe/w2": ((ns, held, ff, d), ff * ROUTED_DOWN_FAN),
        "moe/shared/w1": ((ns, d, ffs), d), "moe/shared/w3": ((ns, d, ffs), d),
        "moe/shared/w2": ((ns, ffs, d), ffs),
    }
    for kind, stack in STACK.items():
        layers = _layers_of(mc)[stack]
        if layers:
            h = mc["n_heads_per_layer"][layers[0]]
            shapes.update({
                f"{stack}/wq": ((len(layers), d, h * hd), d),
                f"{stack}/wo": ((len(layers), h * hd, d), h * hd),
                f"{stack}/w_gate": ((len(layers), d, h), d)})
    return {name: sf for name, sf in shapes.items() if 0 not in sf[0]}


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
    where = {stack: {layer: at for at, layer in enumerate(layers)}
             for stack, layers in _layers_of(mc).items()}
    layers = []
    for i, kind in enumerate(mc["layer_types"]):
        own = STACK[kind]
        layer: dict[str, Any] = {"ln1": ones, "ln2": ones, "attn": {
            **{w: stacked[f"attn/{w}"][i] for w in ("wk", "wv")},
            **{w: stacked[f"{own}/{w}"][where[own][i]]
               for w in ("wq", "wo", "w_gate")}}}
        if i in where["mlp"]:
            layer["mlp"] = {w: stacked[f"mlp/{w}"][where["mlp"][i]]
                            for w in SWIGLU}
        else:
            at = where["moe"][i]
            layer["moe"] = {
                **{w: stacked[f"moe/{w}"][at] for w in ("router", *SWIGLU)},
                "shared": {w: stacked[f"moe/shared/{w}"][at] for w in SWIGLU}}
        layers.append(layer)
    return {"embed": stacked["embed"], "lm_head": stacked["lm_head"],
            "layers": layers, "ln_f": ones}


# -- the plain reference ------------------------------------------------------

def rope_frequencies(mc: dict[str, Any], kind: str) -> tuple[np.ndarray, float, int]:
    """A layer kind's rotary -> (frequencies, what multiplies cos and sin,
    the head's leading columns that turn): plain ``theta_window^(-2i/d)`` over
    the whole head in a window layer; in a global one YaRN's blend over the
    first ``partial x head`` columns, ``attention_factor`` on cos and sin."""
    hd = int(mc["head_dim"])
    if kind == SLIDING:
        theta = float(mc["rope_theta_window"])
        plain = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
        return plain.astype(np.float32), 1.0, hd
    y = mc["rope_full"]
    d = int(hd * float(y["partial"]))
    theta = float(mc["rope_theta"])
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def dim_turning(turns: float) -> float:
        """The (fractional) dimension whose pair turns ``turns`` times within
        ``original_max`` positions."""
        return d * math.log(y["original_max"] / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_turning(y["beta_fast"])), 0)
    high = min(math.ceil(dim_turning(y["beta_slow"])), d - 1)
    blend = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    freqs = blend * plain / y["yarn"] + (1.0 - blend) * plain
    return freqs.astype(np.float32), float(y["attention_factor"]), d


@functools.lru_cache(maxsize=4)
def _fns(key: str):
    import jax
    import jax.numpy as jnp

    mc = json.loads(key)
    n_kv, hd, eps = mc["n_kv_heads"], mc["head_dim"], mc["rms_eps"]
    top_k, first, held = mc["top_k"], mc["expert_first"], mc["n_experts_held"]
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    def rms(x, gain):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(gain)

    def rope(x, freqs, factor, turned):
        """(S, H, D): the first ``turned`` columns rotated in interleaved
        pairs at positions 0..S-1, the rest as they are."""
        ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None, :]
        cos = (jnp.cos(ang) * factor)[:, None, :]
        sin = (jnp.sin(ang) * factor)[:, None, :]
        part = x[..., :turned]
        x1, x2 = part[..., 0::2], part[..., 1::2]
        rot = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
        return jnp.concatenate([rot.reshape(part.shape), x[..., turned:]], -1)

    def by_rows(fn, rows):
        """``fn`` over blocks of ``ROW_BLOCK`` rows, one at a time."""
        s = rows.shape[0]
        blocks = -(-s // ROW_BLOCK)
        padded = jnp.pad(rows, ((0, blocks * ROW_BLOCK - s), (0, 0)))
        out = jax.lax.map(fn, padded.reshape(blocks, ROW_BLOCK, -1))
        return out.reshape(blocks * ROW_BLOCK, -1)[:s]

    def swiglu(z, w):
        w1, w3, w2 = f32(w["w1"]), f32(w["w3"]), f32(w["w2"])
        return by_rows(lambda r: (jax.nn.silu(r @ w1) * (r @ w3)) @ w2, z)

    @functools.partial(jax.jit, static_argnames=("turned", "window"))
    def attend(x, attn, ln1, freqs, factor, turned, window):
        """One layer's attention half over a whole sequence. A block of
        ``Q_BLOCK`` queries at a time (``lax.map``: one block's scores are all
        that exists) against the keys the block may read: every key in a
        global layer, in a window layer the ``window - 1`` positions before
        the block's first and the block's own."""
        s = x.shape[0]
        a = rms(x, ln1)
        heads = attn["wq"].shape[1] // hd
        group = heads // n_kv
        q = rope((a @ f32(attn["wq"])).reshape(s, heads, hd), freqs, factor, turned)
        k = rope((a @ f32(attn["wk"])).reshape(s, n_kv, hd), freqs, factor, turned)
        v = (a @ f32(attn["wv"])).reshape(s, n_kv, hd)
        gate = jax.nn.sigmoid(a @ f32(attn["w_gate"]))             # (s, heads)
        blocks = -(-s // Q_BLOCK)
        q_pad = jnp.pad(q, ((0, blocks * Q_BLOCK - s), (0, 0), (0, 0)))
        span = min(s, window - 1 + Q_BLOCK) if window else s

        def block(args):
            q_b, q0 = args                                  # (Q_BLOCK, heads, hd)
            k0 = jnp.clip(q0 - (window - 1), 0, s - span) if window else 0
            k_b = jax.lax.dynamic_slice_in_dim(k, k0, span, 0)
            v_b = jax.lax.dynamic_slice_in_dim(v, k0, span, 0)
            scores = jnp.einsum(
                "qngd,knd->ngqk", q_b.reshape(Q_BLOCK, n_kv, group, hd), k_b
            ) / jnp.sqrt(jnp.float32(hd))
            i = q0 + jnp.arange(Q_BLOCK)[:, None]
            j = k0 + jnp.arange(span)[None, :]
            seen = j <= i
            if window:
                seen &= i - j < window
            probs = jax.nn.softmax(
                jnp.where(seen[None, None], scores, -jnp.inf), -1)
            return jnp.einsum("ngqk,knd->qngd", probs, v_b).reshape(
                Q_BLOCK, heads, hd)

        out = jax.lax.map(block, (q_pad.reshape(blocks, Q_BLOCK, heads, hd),
                                  jnp.arange(blocks) * Q_BLOCK))
        out = out.reshape(blocks * Q_BLOCK, heads, hd)[:s] * gate[:, :, None]
        return x + out.reshape(s, heads * hd) @ f32(attn["wo"])

    @jax.jit
    def dense(h, ln2, mlp):
        return h + swiglu(rms(h, ln2), mlp)

    @jax.jit
    def gates(h, ln2, router, shared):
        """-> (z, h + the shared expert, the weight of every HELD expert for
        every token: w_i where the token chose it, zero elsewhere)."""
        z = rms(h, ln2)
        p = jax.nn.sigmoid(z @ f32(router))
        top, idx = jax.lax.top_k(p, top_k)
        if mc["norm_topk_prob"]:
            top = top / jnp.sum(top, -1, keepdims=True)
        top = top * mc["route_scale"]
        chosen = jax.nn.one_hot(idx, p.shape[-1], dtype=jnp.float32)   # (s, k, E)
        weight = jnp.einsum("sk,ske->se", top, chosen)[:, first:first + held]
        return z, h + swiglu(z, shared), weight

    @jax.jit
    def add_experts(y, z, weight, w1, w3, w2):
        for i in range(w1.shape[0]):
            out = swiglu(z, {"w1": w1[i], "w3": w3[i], "w2": w2[i]})
            y = y + weight[:, i:i + 1] * out
        return y

    @jax.jit
    def head(x, ln_f, w):
        return rms(x, ln_f) @ f32(w)

    return attend, dense, gates, add_experts, head


def logits_many(mc: dict[str, Any], tree: dict, sequences,
                last: int = 1) -> list[np.ndarray]:
    """float32 logits ``(last, vocab held)`` of the final ``last`` positions
    of each sequence; ``tree`` is the params pytree of host arrays. Layers are
    the outer loop and, inside an expert layer, groups of ``EXPERT_GROUP``
    held experts, so one layer's attention and at most that many experts'
    float32 weights are on the device at a time."""
    import jax
    import jax.numpy as jnp

    attend, dense, gates, add_experts, head = _fns(json.dumps(mc, sort_keys=True))
    held = int(mc["n_experts_held"])
    with jax.default_matmul_precision("highest"):
        embed = tree["embed"]
        xs = [jnp.asarray(embed[np.asarray(ids, np.int32)]).astype(jnp.float32)
              for ids in sequences]
        for lp, kind in zip(tree["layers"], mc["layer_types"]):
            freqs, factor, turned = rope_frequencies(mc, kind)
            window = int(mc["sliding_window"]) if kind == SLIDING else 0
            attn = jax.device_put(lp["attn"])
            hs = [attend(x, attn, lp["ln1"], jnp.asarray(freqs), factor,
                         turned=turned, window=window) for x in xs]
            del attn, xs
            if "mlp" in lp:
                mlp = jax.device_put(lp["mlp"])
                xs = [dense(h, lp["ln2"], mlp) for h in hs]
                del mlp, hs
                continue
            moe = lp["moe"]
            router, shared = jax.device_put((moe["router"], moe["shared"]))
            routed = [gates(h, lp["ln2"], router, shared) for h in hs]
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
