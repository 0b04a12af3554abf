"""kda_moe_lm — a decoder LM whose layers are of two kinds, Kimi Delta
Attention (arXiv:2510.26692: the gated delta rule with a decay a CHANNEL,
which keeps a matrix state a head and nothing that grows) and gated NoPE
grouped-query attention (which keeps K/V rows), each followed by an expert
layer with a shared expert. Solar-Open2-250B is its benchmark configuration
(one full layer, then three linear layers, twelve times; 320 routed experts
of which a chip holds a share).

No reference counterpart (the reference serves opaque SavedModels). The block
is pre-norm, ``rms`` an RMSNorm with a learned gain at ``rms_eps``::

    h  = x + Mix_l(rms(x; ln1))         x' = h + MoE_l(rms(h; ln2))

* **Linear attention** (``kda_layer``; ``H = linear_heads`` heads, ``d_k =
  linear_key_dim``, ``d_v = linear_value_dim``), ``u = rms(x; ln1)``::

      [q' | k' | v'] = u W_qkv
      [q | k | v] = silu(causal depthwise convolution of ``linear_conv`` taps
                         over [q' | k' | v'], no bias)
      q_h = q_h / |q_h|_2 / sqrt(d_k)       k_h = k_h / |k_h|_2
      a = (u W_f1) W_f2                      one value a CHANNEL: (H, d_k)
      alpha_h = exp(-exp(a_log_h) softplus(a_h + dt_bias_h))      in (0, 1)^d_k
      beta_h  = 2 sigmoid(u w_b)_h  (``linear_allow_neg_eigval``; else sigmoid)
      S_h <- diag(alpha_h) S_h ;  S_h <- S_h + k_h^T (beta_h (v_h - k_h S_h)) ;  o_h = q_h S_h
      Mix = concat_h( rms(o_h; o_norm) * sigmoid(((u W_g1) W_g2 + b_g)_h) ) W_o

  ``ops/delta_rule.py`` holds the recurrence in its forms, each of which takes
  a decay a channel beside Olmo-Hybrid's decay a head. A request keeps, a
  layer, the state ``S (d_k, H x d_v)`` in float32 and the last ``linear_conv
  - 1`` rows of ``[q' | k' | v']`` in the model's dtype: a two-part
  ``registry.LaneState``, whose ``step`` (``kda_step``) advances a decode
  step's LIVE lanes on the state array where it lies.
* **Full attention**: grouped-query softmax attention at ``1 / sqrt(head)``
  with no rotary (``rope_theta`` None) and an elementwise output gate, ``Mix =
  (o * sigmoid(u W_gate)) W_o``: the ``w_gate`` leaf in ``attn`` is what
  ``generation._attend_rows`` reads.
* **Expert layer**, every layer: ``moe_lm._moe_block`` (sigmoid scores, a
  selection bias, ``n_experts_held`` of ``n_experts`` held here, a shared
  expert every token takes).

The head is its own matrix (``lm_head``). ``layer_types`` in the config says
which layers are which; the ModelDef turns it into ``layer_state``, and that,
with what a layer's params hold, is what ``models/generation.py`` reads.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from tfservingcache_tpu.models.moe_lm import _moe_block
from tfservingcache_tpu.models.olmo_hybrid_lm import (
    FULL,
    LINEAR,
    _cast,
    _conv_after,
    conv_heads,
)
from tfservingcache_tpu.models.real_rows import (
    over_real_rows,
    real_blocks,
    row_block,
)
from tfservingcache_tpu.models.registry import (
    LaneState,
    ModelDef,
    TensorSpec,
    head_width,
    kv_cache_row,
    register,
)
from tfservingcache_tpu.models.transformer_lm import (
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

DEFAULT_CONFIG: dict[str, Any] = {
    "vocab_size": 2048,
    "d_model": 256,
    "n_layers": 4,
    "layer_types": [FULL, LINEAR, LINEAR, LINEAR],
    "n_heads": 8,            # full attention: GQA, heads of ``head_dim``
    "n_kv_heads": 2,
    "head_dim": 32,
    "linear_heads": 8,       # H; d_k = d_v as published
    "linear_key_dim": 32,
    "linear_value_dim": 32,
    "linear_conv": 4,        # taps: a linear layer keeps linear_conv - 1 rows
    "linear_gate_rank": 32,  # the rank of both low-rank gates
    "linear_allow_neg_eigval": True,   # beta in (0, 2)
    "d_ff": 64,              # ONE routed expert's width
    "d_ff_shared": 64,       # the shared expert's
    "n_experts": 16,
    "top_k": 4,
    "norm_topk_prob": True,
    "route_score": "sigmoid",
    "route_scale": 1.0,
    "rms_eps": 1e-5,
    "rope_theta": None,      # no rotary at all
    "max_seq": 1024,
    "dtype": "bfloat16",
}


def _mixer_inputs(layer: dict, x: jax.Array, conv, cfg: dict, took=None):
    """What the rule takes of the tokens ``x (B, T, d)`` (the residual stream
    before its norm) and the lanes' last convolution inputs ``conv (B, taps -
    1, W)``: ``(q, k, v, alpha, beta, z, rows)`` with ``q`` / ``k (B, T, H,
    d_k)`` normalised, ``v (B, T, H, d_v)``, ``alpha (B, T, H, d_k)`` and
    ``beta (B, T, H)`` float32, ``z (B, T, H x d_v)`` the output gate's
    projection and ``rows (B, taps - 1 + T, W)`` the convolution's inputs,
    whose tail is the state after. ``took (B,)`` says how many of each
    example's tokens are real (None = all): a long prefill norms, projects
    and convolves the row blocks that hold them (``over_real_rows``; a block
    of the convolution begins ``taps - 1`` rows early)."""
    f32 = jnp.float32
    dtype = jnp.dtype(cfg["dtype"])
    kda = _cast(layer["kda"], dtype)
    b = x.shape[0]
    h, d_k, d_v = (int(cfg[key]) for key in (
        "linear_heads", "linear_key_dim", "linear_value_dim"))
    taps = kda["conv_w"].shape[-1]
    with jax.named_scope("proj"):
        def project(x):
            u = _rmsnorm(x, layer["ln1"], cfg["rms_eps"])
            qkv = u @ kda["w_qkv"]                             # (B, T, 2 H d_k + H d_v)
            z = (u @ kda["w_g1"]) @ kda["w_g2"] + kda["b_g"]   # (B, T, H d_v)
            a_in = ((u @ kda["w_f1"]) @ kda["w_f2"]).astype(f32)   # (B, T, H d_k)
            b_in = (u @ kda["w_b"]).astype(f32)                # (B, T, H)
            return qkv, z, a_in, b_in

        qkv, z, a_in, b_in = over_real_rows(project, (x,), took)
    with jax.named_scope("conv"):
        rows = jnp.concatenate([conv.astype(dtype), qkv], axis=1)
        w = kda["conv_w"].astype(f32)                          # (W, taps)

        q, k, v = over_real_rows(
            lambda rows: conv_heads(rows, w, h, d_k, d_v, dtype), (rows,), took,
            halo=taps - 1)
    with jax.named_scope("gate"):
        # from the leaves as they are stored, not through the compute dtype
        rate = jnp.exp(layer["kda"]["a_log"].astype(f32))[:, None]    # (H, 1)
        bias = layer["kda"]["dt_bias"].astype(f32).reshape(h, d_k)
        alpha = jnp.exp(-rate * jax.nn.softplus(
            a_in.reshape(b, -1, h, d_k) + bias))
        beta = jax.nn.sigmoid(b_in)
        if cfg.get("linear_allow_neg_eigval", True):
            beta = 2.0 * beta
    return q, k, v, alpha, beta, z, rows


def _output_gate(z: jax.Array) -> jax.Array:
    """The linear layer's output gate: a sigmoid (Olmo-Hybrid's is a silu)."""
    return jax.nn.sigmoid(z)


def _mixer_output(layer: dict, o: jax.Array, z: jax.Array, cfg: dict,
                  took=None):
    """The rule's outputs ``o (B, T, H, d_v)`` float32 -> the residual delta:
    the per-head RMSNorm times ``sigmoid(z)``, then ``w_o``; each over the row
    blocks that hold the ``took`` real tokens (None = all)."""
    dtype = jnp.dtype(cfg["dtype"])
    b = o.shape[0]
    with jax.named_scope("gate"):
        o_norm = layer["kda"]["o_norm"].astype(jnp.float32)

        def gate(o, z):
            o = _rmsnorm(o, o_norm, cfg["rms_eps"]).astype(dtype)
            return o.reshape(b, o.shape[1], -1) * _output_gate(z)

        o = over_real_rows(gate, (o, z), took)
    with jax.named_scope("proj"):
        w_o = layer["kda"]["w_o"].astype(dtype)
        return over_real_rows(lambda o: o @ w_o, (o,), took)


def _kda_rows(layer: dict, x: jax.Array, state: tuple, real_len, cfg: dict,
              step: bool = False):
    """``kda_layer`` over the tokens ``x (B, T, d)`` at once, from the state
    ``(S, conv)`` -> (residual delta, the state after ``real_len``); ``step``:
    the one token a row through the one-token form."""
    t = x.shape[1]
    s, conv = state
    q, k, v, alpha, beta, z, rows = _mixer_inputs(layer, x, conv, cfg,
                                                  real_len)
    if step:
        o, s = delta_step(s, q[:, 0], k[:, 0], v[:, 0], alpha[:, 0], beta[:, 0],
                          real_len)
        o = o[:, None]
    else:
        o, s = delta_chunked(s, q, k, v, alpha, beta, real_len)
    return (_mixer_output(layer, o, z, cfg, real_len),
            (s, _conv_after(rows, t, real_len)))


@jax.named_scope("kda")
def kda_layer(layer: dict, x: jax.Array, state, real_len, cfg: dict):
    """A linear-attention layer's mixer under its norm, the
    ``registry.LaneState`` operator: the residual stream ``x (B, T, d)`` and
    the lanes' state ``(S (B, d_k, H x d_v) float32, conv (B, taps - 1, 2 H
    d_k + H d_v))`` (None = zeros: a request's beginning) -> (residual delta,
    the state after ``real_len (B,)`` of the ``T`` tokens (None = all),
    nothing handed on). ``T = 1`` takes the one-token step, in which a row
    with ``real_len`` 0 keeps both parts bit for bit; a longer ``T`` the
    chunked form. A long prefill (a bucket ``real_rows.row_block`` cuts) runs
    the WHOLE mixer a block of tokens at a time, the state carried from block
    to block, for the blocks that hold a real token: what a block needs in
    float32 (a decay a channel is 0.54 GB a layer at 16384 tokens, beside
    ``[q' | k' | v']`` of 0.8 GB twice) is then a block's, and rows past the
    last block come back zero, as ``over_real_rows`` leaves them."""
    b, t, _ = x.shape
    if state is None:
        h, d_k, d_v = (int(cfg[key]) for key in (
            "linear_heads", "linear_key_dim", "linear_value_dim"))
        taps = layer["kda"]["conv_w"].shape[-1]
        state = (jnp.zeros((b, d_k, h * d_v), jnp.float32),
                 jnp.zeros((b, taps - 1, h * (2 * d_k + d_v)),
                           jnp.dtype(cfg["dtype"])))
    block = row_block(t)
    if real_len is None or not block:
        return (*_kda_rows(layer, x, state, real_len, cfg, step=t == 1), None)
    real_len = real_len.astype(jnp.int32)

    def trip(i, carry):
        y, state = carry
        start = i * block
        out, state = _kda_rows(
            layer, jax.lax.dynamic_slice_in_dim(x, start, block, 1), state,
            jnp.clip(real_len - start, 0, block), cfg)
        return jax.lax.dynamic_update_slice_in_dim(y, out, start, 1), state

    y, state = jax.lax.fori_loop(
        0, real_blocks(jnp.max(real_len), block), trip, (jnp.zeros_like(x), state))
    return y, state, None


@jax.named_scope("kda")
def kda_step(layer: dict, x: jax.Array, lane, index: int, took, live,
             cfg: dict):
    """``kda_layer``'s one-token form on the model's WHOLE lane-state arrays
    ``lane = (S (lane layers, lanes, d_k, H x d_v) float32, conv (lane layers,
    lanes, taps - 1, W))``, the ``registry.LaneState.step``: layer ``index``'s
    matrix states are advanced where they lie, for the lanes that ``took`` a
    token and no other (``ops.delta_rule.delta_step_live``: 4.2 MB a lane a
    layer at the benchmark's widths); the convolution's tail is small (147 KB
    a lane) and its slice is set whole, a lane that took nothing keeping its
    own."""
    states, convs = lane
    q, k, v, alpha, beta, z, rows = _mixer_inputs(layer, x, convs[index], cfg)
    o, states = delta_step_live(states, index, q[:, 0], k[:, 0], v[:, 0],
                                alpha[:, 0], beta[:, 0], took, live)
    convs = convs.at[index].set(_conv_after(rows, 1, took).astype(convs.dtype))
    return _mixer_output(layer, o[:, None], z, cfg), (states, convs), None


@jax.named_scope("attn")
def _attention_layer(layer: dict, x: jax.Array, cfg: dict) -> jax.Array:
    """One full-attention layer of the whole-sequence forward -> the residual
    delta; the output gate and the rotary as ``generation._attend_rows`` reads
    them (a ``w_gate`` leaf; ``rope_theta`` a number)."""
    b, s, _ = x.shape
    attn = _cast(layer["attn"], x.dtype)
    u = _rmsnorm(x, layer["ln1"], cfg["rms_eps"])
    q, k, v = _qkv(attn, u, cfg["n_heads"], cfg["n_kv_heads"])
    if cfg["rope_theta"] is not None:
        q = _rope(q, jnp.arange(s), cfg["rope_theta"])
        k = _rope(k, jnp.arange(s), cfg["rope_theta"])
    out = attention(q, k, v, causal=True)
    out = out.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, s, -1)
    if "w_gate" in attn:
        out = out * jax.nn.sigmoid(u @ attn["w_gate"])
    return out @ attn["wo"]


def _forward(params: dict, input_ids: jax.Array, cfg: dict) -> jax.Array:
    dtype = jnp.dtype(cfg["dtype"])
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(dtype)
    for layer, kind in zip(params["layers"], cfg["layer_types"]):
        with jax.named_scope("layer"):
            if kind == LINEAR:
                out, _, _ = kda_layer(layer, x, None, None, cfg)
            else:
                out = _attention_layer(layer, x, cfg)
            x = x + out
            y, _stats = _moe_block(layer, x, cfg, dtype)
            x = x + y
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
        operator=kda_layer, step=kda_step)
    kinds = {LINEAR: lane, FULL: kv_cache_row(cfg)}
    types = list(cfg["layer_types"])
    if len(types) != int(cfg["n_layers"]) or set(types) - set(kinds):
        raise ValueError(
            f"layer_types must name {cfg['n_layers']} layers of "
            f"{sorted(kinds)}, got {types}")
    return tuple(kinds[t] for t in types)


@register("kda_moe_lm", DEFAULT_CONFIG)
def build(config: dict) -> ModelDef:
    cfg = dict(config)
    layer_state = layer_state_of(cfg)
    types = list(cfg["layer_types"])

    def apply(params, inputs):
        return {"logits": _forward(
            params, inputs["input_ids"].astype(jnp.int32), cfg)}

    def init(rng):
        d, v, ff = cfg["d_model"], cfg["vocab_size"], cfg["d_ff"]
        ffs = cfg.get("d_ff_shared", ff)
        hd = head_width(cfg)
        q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
        h, d_k, d_v = (cfg["linear_heads"], cfg["linear_key_dim"],
                       cfg["linear_value_dim"])
        width, taps = h * (2 * d_k + d_v), cfg["linear_conv"]
        rank = cfg["linear_gate_rank"]
        e, held = cfg["n_experts"], cfg.get("n_experts_held", cfg["n_experts"])
        keys = jax.random.split(rng, cfg["n_layers"] + 2)

        def dense(key, fan_in, shape):
            return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)

        layers = []
        for i, kind in enumerate(types):
            ks = jax.random.split(keys[i], 24)
            layer = {
                "ln1": jnp.ones((d,), jnp.float32),
                "ln2": jnp.ones((d,), jnp.float32),
                "moe": {
                    "router": dense(ks[0], d, (d, e)),
                    "bias": 0.02 * jax.random.normal(ks[1], (e,), jnp.float32),
                    "w1": dense(ks[2], d, (held, d, ff)),
                    "w3": dense(ks[3], d, (held, d, ff)),
                    "w2": dense(ks[4], ff, (held, ff, d)),
                    "shared": {"w1": dense(ks[5], d, (d, ffs)),
                               "w3": dense(ks[6], d, (d, ffs)),
                               "w2": dense(ks[7], ffs, (ffs, d))},
                }}
            if kind == LINEAR:
                layer["kda"] = {
                    "w_qkv": dense(ks[8], d, (d, width)),
                    "w_f1": dense(ks[9], d, (d, rank)),
                    "w_f2": dense(ks[10], rank, (rank, h * d_k)),
                    "w_g1": dense(ks[11], d, (d, rank)),
                    "w_g2": dense(ks[12], rank, (rank, h * d_v)),
                    "b_g": jnp.zeros((h * d_v,), jnp.float32),
                    "w_b": dense(ks[13], d, (d, h)),
                    "conv_w": dense(ks[14], taps, (width, taps)),
                    # alpha spread over (0, 1): exp(a_log) softplus(.) from
                    # hundredths to units
                    "a_log": jax.random.uniform(ks[15], (h,), jnp.float32, -3.0, 1.0),
                    "dt_bias": jax.random.normal(ks[16], (h * d_k,), jnp.float32),
                    "o_norm": jnp.ones((d_v,), jnp.float32),
                    "w_o": dense(ks[17], h * d_v, (h * d_v, d)),
                }
            else:
                layer["attn"] = {
                    "wq": dense(ks[8], d, (d, q)),
                    "wk": dense(ks[9], d, (d, kv)),
                    "wv": dense(ks[10], d, (d, kv)),
                    "wo": dense(ks[11], q, (q, d)),
                    "w_gate": dense(ks[12], d, (d, q)),
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
        family="kda_moe_lm",
        config=cfg,
        apply=apply,
        init=init,
        input_spec={"input_ids": TensorSpec("int32", ("batch", "seq"))},
        output_spec={"logits": TensorSpec("float32", ("batch", "seq", cfg["vocab_size"]))},
        # one chip holds its share of every layer: no partition rule, and
        # generation on a chip-group mesh is refused by name
        # (``_refuse_lane_state``)
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
        # over a lane's own pages, experts with no capacity: a row's answer
        # is its own
        engine_ready=True,
        cache_row=kv_cache_row(cfg),
        layer_state=layer_state,
    )
