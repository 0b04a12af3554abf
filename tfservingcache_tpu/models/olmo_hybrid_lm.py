"""olmo_hybrid_lm — a decoder LM whose layers are of two kinds: gated
delta-rule linear attention (Gated DeltaNet, arXiv:2412.06464), which keeps a
MATRIX state a head and nothing that grows, and full multi-head attention,
which keeps K/V rows. Olmo-Hybrid-7B is its benchmark configuration (three
linear layers to one full layer, eight times).

No reference counterpart (the reference serves opaque SavedModels). The block
is the Olmo 2/3 family's reordered norm: a layer norms what its mixer and its
MLP give, not what they take. For layer ``l``, ``rms`` an RMSNorm with a
learned gain at ``rms_eps``::

    h  = x + rms(Mix_l(x); ln1_post)        x' = h + rms(MLP(h); ln2_post)

* **Linear attention** (``gdn_layer``; ``H = linear_heads`` heads, ``d_k =
  linear_key_dim``, ``d_v = linear_value_dim``)::

      [q' | k' | v'] = x W_qkv        a = x W_a     b = x W_b     z = x W_g
      [q | k | v] = silu(causal depthwise convolution of ``linear_conv`` taps
                         over [q' | k' | v'], no bias)
      q_h = q_h / |q_h|_2 / sqrt(d_k)       k_h = k_h / |k_h|_2
      alpha_h = exp(-exp(a_log_h) softplus(a_h + dt_bias_h))      in (0, 1)
      beta_h  = 2 sigmoid(b_h)     (``linear_allow_neg_eigval``; else sigmoid)
      S_h <- alpha_h S_h ;  S_h <- S_h + k_h^T (beta_h (v_h - k_h S_h)) ;  o_h = q_h S_h
      Mix = concat_h( rms(o_h; o_norm) * silu(z_h) ) W_o

  ``ops/delta_rule.py`` holds the recurrence in its forms. A request keeps,
  a layer, the state ``S (d_k, H x d_v)`` in float32 and the last
  ``linear_conv - 1`` rows of ``[q' | k' | v']`` in the model's dtype: a
  two-part ``registry.LaneState``, whose ``step`` (``gdn_step``) advances a
  decode step's LIVE lanes on the state array where it lies.
* **Full attention**: transformer_lm's projections with QK-norm over the
  WHOLE query and key projection (``_qkv``: the gain is the projection's
  length), causal softmax at ``1 / sqrt(head)``, ``wo``. ``rope_theta`` None
  (the published file's ``null``) applies no rotary; a number applies the
  plain rotary of the other families.

The head is its own matrix (``lm_head``). ``layer_types`` in the config says
which layers are which; the ModelDef turns it into ``layer_state``, and that,
with what a layer's params hold (``ln1_post`` / ``ln2_post``: the norm follows)
and ``rope_theta``, is what ``models/generation.py`` reads.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from tfservingcache_tpu.models.real_rows import over_real_rows
from tfservingcache_tpu.models.registry import (
    LaneState,
    ModelDef,
    TensorSpec,
    kv_cache_row,
    register,
)
from tfservingcache_tpu.models.transformer_lm import (
    _mlp_block,
    _output_logits,
    _qkv,
    _rmsnorm,
    _rope,
)
from tfservingcache_tpu.ops.attention import attention
from tfservingcache_tpu.ops.delta_rule import (
    delta_chunked,
    delta_step,
    delta_step_live,
)

LINEAR, FULL = "linear_attention", "full_attention"

DEFAULT_CONFIG: dict[str, Any] = {
    "vocab_size": 2048,
    "d_model": 256,
    "n_layers": 4,
    "layer_types": [LINEAR, LINEAR, LINEAR, FULL],
    "n_heads": 4,            # full attention: MHA, heads of d_model / n_heads
    "n_kv_heads": 4,
    "d_ff": 512,
    "linear_heads": 4,       # H; the published ratio d_k : d_v = 1 : 2
    "linear_key_dim": 32,    # d_k
    "linear_value_dim": 64,  # d_v
    "linear_conv": 4,        # taps: a linear layer keeps linear_conv - 1 rows
    "linear_allow_neg_eigval": True,   # beta in (0, 2)
    "rms_eps": 1e-6,
    "qk_norm_eps": 1e-6,     # the QK-norm's, the published rms_norm_eps too
    "rope_theta": None,      # no rotary
    "max_seq": 1024,
    "dtype": "bfloat16",
}


L2_EPS = 1e-6      # beside a head's squared length (the published kernels')


def _cast(tree: dict, dtype) -> dict:
    return jax.tree_util.tree_map(lambda w: w.astype(dtype), tree)


def _l2norm(x: jax.Array) -> jax.Array:
    """Each head's vector (the last axis) at unit length, float32."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def conv_heads(rows: jax.Array, w: jax.Array, h: int, d_k: int, d_v: int,
               dtype):
    """The causal depthwise convolution and silu over ``rows (B, taps - 1 + T,
    2 H d_k + H d_v)``, the inputs ``[q' | k' | v']`` with their ``taps - 1``
    leading rows, under the float32 taps ``w (W, taps)`` -> ``q`` / ``k (B, T,
    H, d_k)`` L2-normalised a head (``q`` also scaled by ``1 / sqrt(d_k)``)
    and ``v (B, T, H, d_v)``, in ``dtype``."""
    f32 = jnp.float32
    b, taps = rows.shape[0], w.shape[-1]
    t = rows.shape[1] - (taps - 1)

    def mixed(first: int, width: int):
        """The taps and silu over ``width`` columns from ``first``: a part at
        a time, so that no float32 array of all ``W`` columns exists (0.75 GB
        at a 16384-token bucket of Olmo-Hybrid's 11,520)."""
        cols = slice(first, first + width)
        return jax.nn.silu(sum(
            w[cols, j] * rows[:, j:j + t, cols].astype(f32)
            for j in range(taps)))

    q = mixed(0, h * d_k).reshape(b, t, h, d_k)
    k = mixed(h * d_k, h * d_k).reshape(b, t, h, d_k)
    q = (_l2norm(q) * d_k ** -0.5).astype(dtype)
    k = _l2norm(k).astype(dtype)
    v = mixed(2 * h * d_k, h * d_v).astype(dtype).reshape(b, t, h, d_v)
    return q, k, v


def _mixer_inputs(layer: dict, x: jax.Array, conv, cfg: dict, took=None):
    """What the rule takes of the tokens ``x (B, T, d)`` and the lanes' last
    convolution inputs ``conv (B, taps - 1, W)``: ``(q, k, v, alpha, beta, z,
    rows)`` with ``q`` / ``k (B, T, H, d_k)`` normalised, ``v (B, T, H,
    d_v)``, ``alpha`` / ``beta (B, T, H)`` float32, ``z`` the output gate's
    projection and ``rows (B, taps - 1 + T, W)`` the convolution's inputs,
    whose tail is the state after. ``took (B,)`` says how many of each
    example's tokens are real (None = all): a long prefill projects and
    convolves the row blocks that hold them (``over_real_rows``; a block of
    the convolution begins ``taps - 1`` rows early)."""
    f32 = jnp.float32
    dtype = jnp.dtype(cfg["dtype"])
    gdn = _cast(layer["gdn"], dtype)
    b = x.shape[0]
    h, d_k, d_v = (int(cfg[key]) for key in (
        "linear_heads", "linear_key_dim", "linear_value_dim"))
    taps = gdn["conv_w"].shape[-1]
    with jax.named_scope("proj"):
        def project(x):
            qkv = x @ gdn["w_qkv"]                             # (B, T, 2 H d_k + H d_v)
            z = x @ gdn["w_g"]                                 # (B, T, H d_v)
            a_in = (x @ gdn["w_a"]).astype(f32)                # (B, T, H)
            b_in = (x @ gdn["w_b"]).astype(f32)
            return qkv, z, a_in, b_in

        qkv, z, a_in, b_in = over_real_rows(project, (x,), took)
    with jax.named_scope("conv"):
        rows = jnp.concatenate([conv.astype(dtype), qkv], axis=1)
        w = gdn["conv_w"].astype(f32)                          # (W, taps)

        q, k, v = over_real_rows(
            lambda rows: conv_heads(rows, w, h, d_k, d_v, dtype), (rows,), took,
            halo=taps - 1)
    with jax.named_scope("gate"):
        # from the leaves as they are stored, not through the compute dtype
        alpha = jnp.exp(-jnp.exp(layer["gdn"]["a_log"].astype(f32))
                        * jax.nn.softplus(a_in + layer["gdn"]["dt_bias"].astype(f32)))
        beta = jax.nn.sigmoid(b_in)
        if cfg.get("linear_allow_neg_eigval", True):
            beta = 2.0 * beta
    return q, k, v, alpha, beta, z, rows


def _mixer_output(layer: dict, o: jax.Array, z: jax.Array, cfg: dict,
                  took=None):
    """The rule's outputs ``o (B, T, H, d_v)`` float32 -> the residual delta:
    the per-head RMSNorm times ``silu(z)``, ``w_o``, the norm that follows
    the mixer; each over the row blocks that hold the ``took`` real tokens
    (None = all)."""
    dtype = jnp.dtype(cfg["dtype"])
    b = o.shape[0]
    with jax.named_scope("gate"):
        o_norm = layer["gdn"]["o_norm"].astype(jnp.float32)

        def gate(o, z):
            o = _rmsnorm(o, o_norm, cfg["rms_eps"]).astype(dtype)
            return o.reshape(b, o.shape[1], -1) * jax.nn.silu(z)

        o = over_real_rows(gate, (o, z), took)
    with jax.named_scope("proj"):
        w_o = layer["gdn"]["w_o"].astype(dtype)
        return over_real_rows(
            lambda o: _rmsnorm(o @ w_o, layer["ln1_post"], cfg["rms_eps"]),
            (o,), took)


def _conv_after(rows: jax.Array, t: int, real_len):
    """The last ``taps - 1`` convolution inputs after ``real_len (B,)`` of the
    ``t`` tokens at hand (None = all)."""
    keep = rows.shape[1] - t
    if real_len is None:
        return rows[:, t:]
    return jax.vmap(
        lambda r, n: jax.lax.dynamic_slice_in_dim(r, n, keep, axis=0)
    )(rows, real_len.astype(jnp.int32))


@jax.named_scope("gdn")
def gdn_layer(layer: dict, x: jax.Array, state, real_len, cfg: dict):
    """A linear-attention layer's mixer with its norm, the
    ``registry.LaneState`` operator: the residual stream ``x (B, T, d)`` and
    the lanes' state ``(S (B, d_k, H x d_v) float32, conv (B, taps - 1, 2 H
    d_k + H d_v))`` (None = zeros: a request's beginning) -> (residual delta,
    the state after ``real_len (B,)`` of the ``T`` tokens (None = all),
    nothing handed on). ``T = 1`` takes the one-token step, in which a row
    with ``real_len`` 0 keeps both parts bit for bit; a longer ``T`` the
    chunked form."""
    b, t, _ = x.shape
    if state is None:
        h, d_k, d_v = (int(cfg[key]) for key in (
            "linear_heads", "linear_key_dim", "linear_value_dim"))
        taps = layer["gdn"]["conv_w"].shape[-1]
        state = (jnp.zeros((b, d_k, h * d_v), jnp.float32),
                 jnp.zeros((b, taps - 1, h * (2 * d_k + d_v)),
                           jnp.dtype(cfg["dtype"])))
    s, conv = state
    q, k, v, alpha, beta, z, rows = _mixer_inputs(layer, x, conv, cfg,
                                                  real_len)
    if t == 1:
        o, s = delta_step(s, q[:, 0], k[:, 0], v[:, 0], alpha[:, 0], beta[:, 0],
                          real_len)
        o = o[:, None]
    else:
        o, s = delta_chunked(s, q, k, v, alpha, beta, real_len)
    return (_mixer_output(layer, o, z, cfg, real_len),
            (s, _conv_after(rows, t, real_len)), None)


@jax.named_scope("gdn")
def gdn_step(layer: dict, x: jax.Array, lane, index: int, took, live,
             cfg: dict):
    """``gdn_layer``'s one-token form on the model's WHOLE lane-state arrays
    ``lane = (S (lane layers, lanes, d_k, H x d_v) float32, conv (lane layers,
    lanes, taps - 1, W))``, the ``registry.LaneState.step``: layer ``index``'s
    matrix states are advanced where they lie, for the lanes that ``took`` a
    token and no other (``ops.delta_rule.delta_step_live``: 2.2 MB a lane a
    layer at the benchmark's widths, which setting the layer's slice whole
    would read and write for every lane); the convolution's tail is small (69
    KB a lane) and its slice is set whole, a lane that took nothing keeping
    its own."""
    states, convs = lane
    q, k, v, alpha, beta, z, rows = _mixer_inputs(layer, x, convs[index], cfg)
    o, states = delta_step_live(states, index, q[:, 0], k[:, 0], v[:, 0],
                                alpha[:, 0], beta[:, 0], took, live)
    convs = convs.at[index].set(_conv_after(rows, 1, took).astype(convs.dtype))
    return _mixer_output(layer, o[:, None], z, cfg), (states, convs), None


@jax.named_scope("attn")
def _attention_layer(layer: dict, x: jax.Array, cfg: dict) -> jax.Array:
    """One full-attention layer of the whole-sequence forward -> the residual
    delta."""
    b, s, _ = x.shape
    attn = _cast(layer["attn"], x.dtype)
    q, k, v = _qkv(attn, x, cfg["n_heads"], cfg["n_kv_heads"],
                   cfg["qk_norm_eps"])
    if cfg["rope_theta"] is not None:
        q = _rope(q, jnp.arange(s), cfg["rope_theta"])
        k = _rope(k, jnp.arange(s), cfg["rope_theta"])
    out = attention(q, k, v, causal=True)
    out = out.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, s, -1)
    return _rmsnorm(out @ attn["wo"], layer["ln1_post"], cfg["rms_eps"])


def _forward(params: dict, input_ids: jax.Array, cfg: dict) -> jax.Array:
    dtype = jnp.dtype(cfg["dtype"])
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(dtype)
    for layer, kind in zip(params["layers"], cfg["layer_types"]):
        with jax.named_scope("layer"):
            if kind == LINEAR:
                out, _, _ = gdn_layer(layer, x, None, None, cfg)
            else:
                out = _attention_layer(layer, x, cfg)
            x = x + out
            x = x + _rmsnorm(_mlp_block(_cast(layer["mlp"], dtype), x),
                             layer["ln2_post"], cfg["rms_eps"])
    return _output_logits(params, x, dtype, cfg["rms_eps"])


def layer_state_of(cfg: dict) -> tuple:
    """What each layer keeps of a request, from ``layer_types``: the K/V row
    for a full-attention layer; for a linear one the float32 matrix states of
    its heads, ``(d_k, H x d_v)``, beside the convolution's last rows."""
    h, d_k, d_v = (int(cfg[key]) for key in (
        "linear_heads", "linear_key_dim", "linear_value_dim"))
    lane = LaneState(
        d_k, h * d_v, "float32",
        beside=(LaneState(int(cfg["linear_conv"]) - 1, h * (2 * d_k + d_v)),),
        operator=gdn_layer, step=gdn_step)
    kinds = {LINEAR: lane, FULL: kv_cache_row(cfg)}
    types = list(cfg["layer_types"])
    if len(types) != int(cfg["n_layers"]) or set(types) - set(kinds):
        raise ValueError(
            f"layer_types must name {cfg['n_layers']} layers of "
            f"{sorted(kinds)}, got {types}")
    return tuple(kinds[t] for t in types)


@register("olmo_hybrid_lm", DEFAULT_CONFIG)
def build(config: dict) -> ModelDef:
    cfg = dict(config)
    cfg.setdefault("qk_norm_eps", cfg["rms_eps"])
    layer_state = layer_state_of(cfg)
    types = list(cfg["layer_types"])

    def apply(params, inputs):
        return {"logits": _forward(
            params, inputs["input_ids"].astype(jnp.int32), cfg)}

    def init(rng):
        d, v, ff = cfg["d_model"], cfg["vocab_size"], cfg["d_ff"]
        hd = d // cfg["n_heads"]
        q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
        h, d_k, d_v = (cfg["linear_heads"], cfg["linear_key_dim"],
                       cfg["linear_value_dim"])
        width, taps = h * (2 * d_k + d_v), cfg["linear_conv"]
        keys = jax.random.split(rng, cfg["n_layers"] + 2)

        def dense(key, fan_in, shape):
            return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)

        layers = []
        for i, kind in enumerate(types):
            ks = jax.random.split(keys[i], 12)
            layer = {"ln1_post": jnp.ones((d,), jnp.float32),
                     "ln2_post": jnp.ones((d,), jnp.float32),
                     "mlp": {"w1": dense(ks[0], d, (d, ff)),
                             "w3": dense(ks[1], d, (d, ff)),
                             "w2": dense(ks[2], ff, (ff, d))}}
            if kind == LINEAR:
                layer["gdn"] = {
                    "w_qkv": dense(ks[3], d, (d, width)),
                    "w_g": dense(ks[4], d, (d, h * d_v)),
                    "w_a": dense(ks[5], d, (d, h)),
                    "w_b": dense(ks[6], d, (d, h)),
                    "conv_w": dense(ks[7], taps, (width, taps)),
                    # alpha spread over (0, 1): exp(a_log) softplus(.) from
                    # hundredths to units
                    "a_log": jax.random.uniform(ks[8], (h,), jnp.float32, -3.0, 1.0),
                    "dt_bias": jax.random.normal(ks[9], (h,), jnp.float32),
                    "o_norm": jnp.ones((d_v,), jnp.float32),
                    "w_o": dense(ks[10], h * d_v, (h * d_v, d)),
                }
            else:
                layer["attn"] = {
                    "wq": dense(ks[3], d, (d, q)),
                    "wk": dense(ks[4], d, (d, kv)),
                    "wv": dense(ks[5], d, (d, kv)),
                    "wo": dense(ks[6], q, (q, d)),
                    "q_norm": jnp.ones((q,), jnp.float32),
                    "k_norm": jnp.ones((kv,), jnp.float32),
                }
            layers.append(layer)
        return {
            "embed": dense(keys[-1], d, (v, d)),
            "lm_head": dense(keys[-2], d, (d, v)),
            "layers": layers,
            "ln_f": jnp.ones((d,), jnp.float32),
        }

    def last_token_logits(outputs, dyn_sizes):
        # device-side slice at the last REAL position (seq is bucket-padded)
        logits = outputs["logits"]
        s = dyn_sizes.get("seq", logits.shape[1])
        b = dyn_sizes.get("batch", logits.shape[0])
        return logits[:b, s - 1, :]

    return ModelDef(
        family="olmo_hybrid_lm",
        config=cfg,
        apply=apply,
        init=init,
        input_spec={"input_ids": TensorSpec("int32", ("batch", "seq"))},
        output_spec={"logits": TensorSpec("float32", ("batch", "seq", cfg["vocab_size"]))},
        # one chip holds its layers whole: no partition rule, and generation
        # on a chip-group mesh is refused by name (``_refuse_lane_state``)
        partition_rules={r".*": (None,)},
        derived_outputs={
            "last_token_logits": (
                last_token_logits,
                TensorSpec("float32", ("batch", cfg["vocab_size"])),
            )
        },
        default_outputs=["last_token_logits"],
        store_param_dtype=cfg["dtype"],
        # a recurrence and a convolution over a lane's own rows, attention
        # over a lane's own pages: a row's answer is its own
        engine_ready=True,
        cache_row=kv_cache_row(cfg),
        layer_state=layer_state,
    )
