"""sambay_lm — a decoder-hybrid-decoder LM (SambaY, arXiv:2507.06607): a
self-decoder of Mamba layers alternating with window attention, ONE
full-attention layer whose K/V rows are the only cache of the model that
grows, and a cross-decoder whose layers keep nothing: gated memory units that
re-read the last Mamba layer's scan output of the SAME token, alternating with
cross attention that reads the full-attention layer's rows. Attention of all
three kinds is differential (arXiv:2410.05258). No positional encoding.

No reference counterpart (the reference serves opaque SavedModels).
Phi-4-mini-flash-reasoning is its benchmark configuration. For layer ``l``,
``LN`` a LayerNorm with gain and bias::

    h  = x + Mix_l(LN(x; ln1, ln1_b))
    x' = h + (silu(a W_1) * (a W_3)) W_2,   a = LN(h; ln2, ln2_b)

With ``n`` layers and ``m = n / 2``, ``Mix_l`` is (``layer_kinds``): Mamba
where ``l < m`` is even and at ``l = m``; window attention where ``l < m`` is
odd; full attention at ``l = m + 1``; a gated memory unit where ``l >= m + 2``
is even; cross attention where it is odd.

* **Mamba** (``mamba_layer``; ``E = ssm_expand x d``, ``N = ssm_state``, ``R =
  dt_rank``): ``[xs | z] = u W_in``; ``xc = silu(conv(xs) + b_c)`` (depthwise,
  causal, ``ssm_conv`` taps); ``[d | B | C] = xc W_x``; ``dt = softplus(d W_dt
  + b_dt)``; ``A = -exp(a_log)``; the selective scan (``ops/ssm.py``) gives
  ``y``; ``Mix = (y * silu(z)) W_out``. A request keeps the last ``ssm_conv -
  1`` rows of ``xs`` in the model's dtype and the scan state ``H (N, E)`` in
  float32: a two-part ``registry.LaneState``. The layer hands ``y`` (before
  the gate) on to the layers after it as ``memory``.
* **Gated memory unit** (``gmu_layer``): ``Mix = (memory * silu(u W_1)) W_2``
  with ``memory`` the last Mamba layer's ``y`` for the SAME token: it keeps
  nothing (``registry.NoState``).
* **Differential attention** (window, full and cross alike; ``diff_project``,
  ``diff_finish``): query heads in pairs ``(q1, q2) = (head 2i, head 2i + 1)``
  over KV pairs ``j = i // 2``; ``o_i = softmax(q1 k(2j)^T / sqrt(D)) V -
  lam softmax(q2 k(2j+1)^T / sqrt(D)) V`` with ``V = [v(2j) | v(2j+1)]``;
  ``o_i <- rms(o_i; sub_norm) (1 - lam0)``; ``Mix = concat_i(o_i) W_o``;
  ``lam = exp(lam_q1 . lam_k1) - exp(lam_q2 . lam_k2) + lam0``, ``lam0 = 0.8 -
  0.6 exp(-0.3 l)``. The two softmaxes run through the attention functions
  every family uses, on 128-lane rows that hold a pair of KV heads
  (``ops.attention.diff_queries``); the combination is here, under scope
  ``diff``. A cross layer has ``wq`` and ``wo`` only and reads the rows of
  the full-attention layer (``registry.SharedRows``).

The head is the embedding. ``ModelDef.layer_state`` says what each layer keeps
and brings the operators of the layers that keep no rows;
``models/generation.py`` reads that and nothing of this family's config.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from tfservingcache_tpu.models.registry import (
    CacheRow,
    LaneState,
    ModelDef,
    NoState,
    SharedRows,
    TensorSpec,
    register,
)
from tfservingcache_tpu.models.transformer_lm import (
    _mlp_block,
    _norm,
    _output_logits,
    _rmsnorm,
)
from tfservingcache_tpu.ops.ssm import selective_scan, selective_step

MAMBA, WINDOW, FULL, GMU, CROSS = (
    "mamba", "sliding_attention", "full_attention", "gmu", "cross_attention")

DEFAULT_CONFIG: dict[str, Any] = {
    "vocab_size": 2048,
    "d_model": 256,
    "n_layers": 8,           # M W M W | M | F | G X
    "n_heads": 8,            # pairs of heads: a multiple of 4
    "n_kv_heads": 4,         # pairs too: even
    "d_ff": 512,
    "sliding_window": 64,
    # "layer_types": the kind of every layer; the family's rule where absent
    # (``layer_kinds``), and nothing else is accepted
    "ssm_expand": 2,         # E = ssm_expand x d_model
    "ssm_state": 16,         # N
    "ssm_conv": 4,           # taps: a Mamba layer keeps ssm_conv - 1 rows
    "dt_rank": 16,           # R
    "norm_eps": 1e-5,
    "max_seq": 1024,
    "dtype": "bfloat16",
}

SUB_NORM_EPS = 1e-5


def layer_kinds(n_layers: int) -> list[str]:
    """The kind of every layer of an ``n_layers`` model (the family's rule)."""
    m = n_layers // 2
    if n_layers < 4 or n_layers % 2:
        raise ValueError(f"n_layers={n_layers}: an even number, at least 4")
    kinds = []
    for l in range(n_layers):
        if l < m:
            kinds.append(WINDOW if l % 2 else MAMBA)
        elif l <= m + 1:
            kinds.append(MAMBA if l == m else FULL)
        else:
            kinds.append(CROSS if l % 2 else GMU)
    return kinds


def lambda_init(depth: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def _cast(tree: dict, dtype) -> dict:
    return jax.tree_util.tree_map(lambda w: w.astype(dtype), tree)


# -- the layers that keep no rows: operators the ModelDef declares ------------

@jax.named_scope("ssm")
def mamba_layer(layer: dict, x: jax.Array, state, real_len, cfg: dict):
    """A Mamba layer's mixer, the ``registry.LaneState`` operator: the
    residual stream ``x (B, T, d)`` before its norm and the lanes' state
    ``(conv (B, taps - 1, E), H (B, N, E) float32)`` (None = zeros: a
    request's beginning) -> (residual delta, the state after ``real_len (B,)``
    of the ``T`` tokens (None = all), ``{"memory": y}``). ``T = 1`` takes the
    one-token step, in which a row with ``real_len`` 0 keeps both parts bit
    for bit; a longer ``T`` the scan."""
    dtype = jnp.dtype(cfg["dtype"])
    ssm = _cast(layer["ssm"], dtype)
    b, t, _ = x.shape
    taps = ssm["conv_w"].shape[-1]
    n = ssm["a_log"].shape[0]
    u = _norm(layer, "ln1", x, cfg["norm_eps"])
    xs, z = jnp.split(u @ ssm["w_in"], 2, axis=-1)            # (B, T, E) each
    e = xs.shape[-1]
    if state is None:
        state = (jnp.zeros((b, taps - 1, e), dtype),
                 jnp.zeros((b, n, e), jnp.float32))
    conv, h = state
    rows = jnp.concatenate([conv.astype(dtype), xs], axis=1)  # (B, taps-1+T, E)
    w = ssm["conv_w"].astype(jnp.float32)                     # (E, taps)
    xc = sum(w[:, j] * rows[:, j:j + t].astype(jnp.float32) for j in range(taps))
    xc = jax.nn.silu(xc + ssm["conv_b"].astype(jnp.float32)).astype(dtype)
    d_in, b_in, c_in = jnp.split(
        xc @ ssm["w_x"], [ssm["w_dt"].shape[0], ssm["w_dt"].shape[0] + n], axis=-1)
    dt = jax.nn.softplus(
        (d_in @ ssm["w_dt"]).astype(jnp.float32) + ssm["dt_b"].astype(jnp.float32))
    # from the leaf as it is stored, not through the compute dtype again
    a = -jnp.exp(layer["ssm"]["a_log"].astype(jnp.float32))   # (N, E)
    if t == 1:
        y, h = selective_step(h, dt[:, 0], xc[:, 0], a, b_in[:, 0], c_in[:, 0],
                              ssm["d_skip"], real_len)
        y = y[:, None]
    else:
        y, h = selective_scan(h, dt, xc, a, b_in, c_in, ssm["d_skip"], real_len)
    y = y.astype(dtype)
    out = (y * jax.nn.silu(z)) @ ssm["w_out"]
    if real_len is None:
        conv = rows[:, t:]
    else:
        conv = jax.vmap(
            lambda r, k: jax.lax.dynamic_slice_in_dim(r, k, taps - 1, axis=0)
        )(rows, real_len.astype(jnp.int32))
    return out, (conv, h), {"memory": y}


@jax.named_scope("gmu")
def gmu_layer(layer: dict, x: jax.Array, handed: dict, cfg: dict) -> jax.Array:
    """A gated memory unit's mixer, the ``registry.NoState`` operator: the
    residual stream before its norm and what earlier layers of this forward
    handed on (``memory (B, T, E)``, the last Mamba layer's scan output for
    the same tokens) -> the residual delta."""
    dtype = jnp.dtype(cfg["dtype"])
    gmu = _cast(layer["gmu"], dtype)
    u = _norm(layer, "ln1", x, cfg["norm_eps"])
    return (handed["memory"].astype(dtype) * jax.nn.silu(u @ gmu["w1"])) @ gmu["w2"]


# -- differential attention: the family's half --------------------------------

def pair_row(cfg: dict, window: int = 0) -> CacheRow:
    """The row a token leaves in an attention layer: K and V of ``n_kv_heads /
    2`` PAIRS of heads, ``[head 2j | head 2j + 1]`` a row of twice the head's
    width (128 lanes at a head of 64: what a packed head-64 arena stores,
    ``generation.init_paged_cache``, declared here so that it holds at every
    head width), which is a differential pair's ``[k1 | k2]`` and its value."""
    hd = int(cfg["d_model"]) // int(cfg["n_heads"])
    return CacheRow(2, int(cfg["n_kv_heads"]) // 2, 2 * hd, window=int(window))


def diff_project(attn: dict, a: jax.Array, n_heads: int, n_kv: int):
    """The normed activations ``a (B, T, d)`` -> ``q (B, n_heads, T, D)`` in
    the model's head order and the rows the layer keeps, ``k``, ``v (B, n_kv /
    2, T, 2 D)`` (``pair_row``; a free reshape of the projection: heads ``2j``
    and ``2j + 1`` are neighbours); a cross layer (no ``wk``) gives ``k = v =
    None``."""
    b, t, _ = a.shape

    def heads(w, n):
        return (a @ attn[w]).reshape(b, t, n, -1).transpose(0, 2, 1, 3)

    if "wk" not in attn:
        return heads("wq", n_heads), None, None
    return heads("wq", n_heads), heads("wk", n_kv // 2), heads("wv", n_kv // 2)


@jax.named_scope("diff")
def diff_finish(attn: dict, terms, depth: int, dtype) -> jax.Array:
    """The two softmax terms ``(o1, o2)``, each ``(B, pairs, T, 2 D)``
    (``ops.attention.diff_outputs``), of layer ``depth`` -> the residual delta
    ``(B, T, d)``: ``o1 - lam o2``, the sub-norm over each pair's ``2 D``, ``x
    (1 - lam0)``, ``W_o``."""
    f32 = jnp.float32
    lam0 = lambda_init(depth)
    lam = (jnp.exp(jnp.sum(attn["lam_q1"].astype(f32) * attn["lam_k1"].astype(f32)))
           - jnp.exp(jnp.sum(attn["lam_q2"].astype(f32) * attn["lam_k2"].astype(f32)))
           + lam0)
    o = terms[0].astype(f32) - lam * terms[1].astype(f32)
    o = _rmsnorm(o, attn["sub_norm"], SUB_NORM_EPS) * (1.0 - lam0)
    b, pairs, t, w = o.shape
    return o.astype(dtype).transpose(0, 2, 1, 3).reshape(b, t, pairs * w) @ attn["wo"]


def _attention_layer(layer: dict, x, cfg, depth: int, window: int, rows):
    """One attention layer of the whole-sequence forward: ``rows`` is the
    full-attention layer's ``(k', v')`` for a cross layer, None for a layer
    with rows of its own -> (residual delta, this layer's ``(k', v')``)."""
    from tfservingcache_tpu.ops.attention import (
        attention,
        diff_outputs,
        diff_queries,
    )

    dtype = jnp.dtype(cfg["dtype"])
    attn = _cast(layer["attn"], dtype)
    a = _norm(layer, "ln1", x, cfg["norm_eps"])
    q, k, v = diff_project(attn, a, cfg["n_heads"], cfg["n_kv_heads"])
    if k is not None:
        rows = k, v
    out = attention(diff_queries(q), *rows, causal=True, window=window,
                    sm_scale=q.shape[-1] ** -0.5)
    return diff_finish(attn, diff_outputs(out), depth, dtype), rows


def _forward(params: dict, input_ids: jax.Array, cfg: dict) -> jax.Array:
    dtype = jnp.dtype(cfg["dtype"])
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(dtype)
    handed: dict = {}
    shared = None
    for depth, (layer, kind) in enumerate(
            zip(params["layers"], cfg["layer_types"])):
        with jax.named_scope("layer"):
            if kind == MAMBA:
                out, _, extras = mamba_layer(layer, x, None, None, cfg)
                handed.update(extras)
            elif kind == GMU:
                out = gmu_layer(layer, x, handed, cfg)
            else:
                with jax.named_scope("attn"):
                    out, rows = _attention_layer(
                        layer, x, cfg, depth,
                        cfg["sliding_window"] if kind == WINDOW else 0,
                        shared if kind == CROSS else None)
                if kind == FULL:
                    shared = rows
            x = x + out
            with jax.named_scope("ffn"):
                x = x + _mlp_block(_cast(layer["mlp"], dtype),
                                   _norm(layer, "ln2", x, cfg["norm_eps"]))
    return _output_logits(params, x, dtype, cfg["norm_eps"])


def layer_state_of(cfg: dict) -> tuple:
    """What each layer keeps of a request, by the family's rule."""
    e = int(cfg["ssm_expand"]) * int(cfg["d_model"])
    lane = LaneState(
        int(cfg["ssm_conv"]) - 1, e,
        beside=(LaneState(int(cfg["ssm_state"]), e, "float32"),),
        operator=mamba_layer)
    kinds = list(cfg["layer_types"])
    full = kinds.index(FULL)
    of = {MAMBA: lane, WINDOW: pair_row(cfg, cfg["sliding_window"]),
          FULL: pair_row(cfg), GMU: NoState(gmu_layer),
          CROSS: SharedRows(full)}
    return tuple(of[kind] for kind in kinds)


@register("sambay_lm", DEFAULT_CONFIG)
def build(config: dict) -> ModelDef:
    cfg = dict(config)
    if cfg["n_heads"] % 4 or cfg["n_kv_heads"] * 2 != cfg["n_heads"]:
        raise ValueError(
            "differential attention pairs query heads over pairs of KV heads: "
            f"n_heads a multiple of 4 and twice n_kv_heads, got "
            f"{cfg['n_heads']} / {cfg['n_kv_heads']}")
    kinds = layer_kinds(cfg["n_layers"])
    if list(cfg.setdefault("layer_types", kinds)) != kinds:
        raise ValueError(
            f"layer_types must be the family's rule for {cfg['n_layers']} "
            f"layers, {kinds}; got {cfg['layer_types']}")
    layer_state = layer_state_of(cfg)

    def apply(params, inputs):
        return {"logits": _forward(
            params, inputs["input_ids"].astype(jnp.int32), cfg)}

    def init(rng):
        d, v, ff = cfg["d_model"], cfg["vocab_size"], cfg["d_ff"]
        hd = d // cfg["n_heads"]
        q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
        e, n = cfg["ssm_expand"] * d, cfg["ssm_state"]
        r, taps = cfg["dt_rank"], cfg["ssm_conv"]
        keys = jax.random.split(rng, cfg["n_layers"] + 1)

        def dense(key, fan_in, shape):
            return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)

        layers = []
        for i, kind in enumerate(kinds):
            ks = jax.random.split(keys[i], 16)
            layer = {"ln1": jnp.ones((d,), jnp.float32),
                     "ln1_b": 0.02 * jax.random.normal(ks[12], (d,), jnp.float32),
                     "ln2": jnp.ones((d,), jnp.float32),
                     "ln2_b": 0.02 * jax.random.normal(ks[13], (d,), jnp.float32),
                     "mlp": {"w1": dense(ks[0], d, (d, ff)),
                             "w3": dense(ks[1], d, (d, ff)),
                             "w2": dense(ks[2], ff, (ff, d))}}
            if kind == MAMBA:
                layer["ssm"] = {
                    "w_in": dense(ks[3], d, (d, 2 * e)),
                    "conv_w": dense(ks[4], taps, (e, taps)),
                    "conv_b": 0.1 * jax.random.normal(ks[5], (e,), jnp.float32),
                    "w_x": dense(ks[6], e, (e, r + 2 * n)),
                    "w_dt": dense(ks[7], r, (r, e)),
                    "dt_b": 0.1 * jax.random.normal(ks[8], (e,), jnp.float32),
                    "a_log": jax.random.normal(ks[9], (n, e), jnp.float32),
                    "d_skip": jnp.ones((e,), jnp.float32),
                    "w_out": dense(ks[10], e, (e, d)),
                }
            elif kind == GMU:
                layer["gmu"] = {"w1": dense(ks[3], d, (d, e)),
                                "w2": dense(ks[4], e, (e, d))}
            else:
                layer["attn"] = {
                    "wq": dense(ks[3], d, (d, q)),
                    "wo": dense(ks[4], q, (q, d)),
                    **{f"lam_{s}": 0.1 * jax.random.normal(k, (hd,), jnp.float32)
                       for s, k in zip(("q1", "k1", "q2", "k2"), ks[5:9])},
                    "sub_norm": jnp.ones((2 * hd,), jnp.float32),
                }
                if kind != CROSS:
                    layer["attn"]["wk"] = dense(ks[9], d, (d, kv))
                    layer["attn"]["wv"] = dense(ks[10], d, (d, kv))
            layers.append(layer)
        return {
            "embed": dense(keys[-1], d, (v, d)),
            "layers": layers,
            "ln_f": jnp.ones((d,), jnp.float32),
            "ln_f_b": jnp.zeros((d,), jnp.float32),
        }

    def last_token_logits(outputs, dyn_sizes):
        # device-side slice at the last REAL position (seq is bucket-padded)
        logits = outputs["logits"]
        s = dyn_sizes.get("seq", logits.shape[1])
        b = dyn_sizes.get("batch", logits.shape[0])
        return logits[:b, s - 1, :]

    return ModelDef(
        family="sambay_lm",
        config=cfg,
        apply=apply,
        init=init,
        input_spec={"input_ids": TensorSpec("int32", ("batch", "seq"))},
        output_spec={"logits": TensorSpec("float32", ("batch", "seq", cfg["vocab_size"]))},
        # one chip holds the whole model: no partition rule, and generation on
        # a chip-group mesh is refused by name (``_refuse_lane_state``)
        partition_rules={r".*": (None,)},
        derived_outputs={
            "last_token_logits": (
                last_token_logits,
                TensorSpec("float32", ("batch", cfg["vocab_size"])),
            )
        },
        default_outputs=["last_token_logits"],
        store_param_dtype=cfg["dtype"],
        # a scan and a convolution over a lane's own rows, attention over a
        # lane's own pages: a row's answer is its own
        engine_ready=True,
        cache_row=pair_row(cfg),
        layer_state=layer_state,
    )
