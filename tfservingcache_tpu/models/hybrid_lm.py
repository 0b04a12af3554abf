"""hybrid_lm — decoder LM whose layers are of two kinds: gated short
convolutions, which keep a FIXED state a request, and grouped-query
attention, which keeps K/V rows that grow with it.

No reference counterpart (the reference serves opaque SavedModels). The
family covers the LFM2 lineage (LFM2-8B-A1B is its benchmark configuration:
18 of 24 layers convolutions of kernel 3, 6 attention with per-head QK-norm,
the first 2 layers a dense SwiGLU and the rest 32 experts of which a token
takes 4 by sigmoid score and a selection bias). For layer ``l``::

    h  = x + Op_l(rms(x; ln1))          x' = h + FFN_l(rms(h; ln2))

* ``Op_l`` of a ``conv`` layer (``conv_operator``): ``[B | C | X] = u W_in``;
  ``z = B * X``; ``y_t = sum_j w[:, j] z_{t-(L-1)+j}`` (depthwise, causal,
  ``z_{<0} = 0``); ``Op = (C * y) W_out``. What a request keeps in such a
  layer is its last ``L - 1`` rows of ``z`` and nothing that grows: the
  ``registry.LaneState`` the family declares for the layer, which
  ``models/generation.py`` carries beside the paged arena.
* ``Op_l`` of a ``full_attention`` layer: transformer_lm's attention block;
  the ``q_norm`` / ``k_norm`` leaves are ONE head long, which makes the norm
  per head (``transformer_lm._qkv``).
* ``FFN_l``: the dense SwiGLU ``mlp`` in the first ``n_dense_layers`` layers,
  then ``moe`` (``ops/moe.py``: sigmoid scores, ``bias`` for the selection
  only, gates normalised over their sum plus ``route_norm_eps``); a layer's
  params hold one or the other, and ``generation._ffn_block`` picks by that.

The head is the embedding (tied). ``layer_types`` in the config says which
layers are which; the ModelDef turns it into ``layer_state`` (a ``CacheRow``
or a ``LaneState`` a layer), and that, not the config key, is what the shared
generation code reads.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from tfservingcache_tpu.models.moe_lm import _moe_block
from tfservingcache_tpu.models.registry import (
    LaneState,
    ModelDef,
    TensorSpec,
    kv_cache_row,
    register,
)
from tfservingcache_tpu.models.transformer_lm import (
    _attention_block,
    _mlp_block,
    _output_logits,
    _rmsnorm,
)

CONV, ATTENTION = "conv", "full_attention"

DEFAULT_CONFIG: dict[str, Any] = {
    "vocab_size": 2048,
    "d_model": 256,
    "n_layers": 6,
    "layer_types": [CONV, CONV, ATTENTION, CONV, CONV, ATTENTION],
    "conv_kernel": 3,        # L: a conv layer keeps L - 1 rows a request
    "n_heads": 8,
    "n_kv_heads": 4,
    "n_dense_layers": 2,     # leading layers whose FFN is the dense SwiGLU
    "d_ff_dense": 512,
    "d_ff": 128,             # ONE expert's width
    "n_experts": 8,
    "top_k": 2,
    "norm_topk_prob": True,
    "route_score": "sigmoid",
    "route_scale": 1.0,
    "route_norm_eps": 1e-6,  # the term beside the sum of the chosen scores
    "rms_eps": 1e-5,
    "max_seq": 1024,
    "rope_theta": 1000000.0,
    "dtype": "bfloat16",
}


def conv_operator(conv: dict, u: jax.Array, state: jax.Array | None = None,
                  real_len: jax.Array | None = None):
    """The gated short convolution over ``u (B, T, d)`` (normed, in the
    compute dtype), ``T >= 1`` -> (``Op(u) (B, T, d)``, the state after).

    ``state (B, L-1, d)`` holds the request's last ``L - 1`` rows of ``z``
    before ``u`` (None = zeros: a request's beginning). The state returned is
    the one after ``real_len (B,)`` of the ``T`` tokens (None = all of them):
    a prompt padded to its bucket leaves the state AT ITS LENGTH, not at the
    bucket's end, and a row with ``real_len`` 0 keeps its state bit for bit.
    The taps run in float32 (three multiply-adds a column)."""
    b, t, d = u.shape
    taps = conv["w"].shape[-1]
    gate_in, gate_out, x = jnp.split(u @ conv["w_in"], 3, axis=-1)
    z = gate_in * x
    if state is None:
        state = jnp.zeros((b, taps - 1, d), z.dtype)
    zz = jnp.concatenate([state.astype(z.dtype), z], axis=1)   # (B, L-1+T, d)
    w = conv["w"].astype(jnp.float32)
    y = sum(w[:, j] * zz[:, j:j + t].astype(jnp.float32) for j in range(taps))
    out = (gate_out * y.astype(u.dtype)) @ conv["w_out"]
    if real_len is None:
        return out, zz[:, t:]
    after = jax.vmap(
        lambda rows, n: jax.lax.dynamic_slice_in_dim(rows, n, taps - 1, axis=0)
    )(zz, real_len.astype(jnp.int32))
    return out, after


@jax.named_scope("conv")
def conv_layer(layer: dict, x: jax.Array, state, real_len, cfg: dict):
    """A convolution layer's operator half as ``registry.LaneState`` declares
    it: the residual stream ``x`` before its norm and the lanes' slice ``state
    (B, L-1, d)`` -> (residual delta, the slice after ``real_len`` of the
    tokens at hand, nothing handed on); ``conv_operator`` under its norm."""
    dtype = jnp.dtype(cfg["dtype"])
    conv = jax.tree_util.tree_map(lambda w: w.astype(dtype), layer["conv"])
    out, after = conv_operator(
        conv, _rmsnorm(x, layer["ln1"], cfg.get("rms_eps", 1e-5)), state,
        real_len)
    return out, after, None


def layer_state_of(cfg: dict) -> tuple:
    """What each layer keeps of a request, from ``layer_types``: the K/V row
    for an attention layer, ``LaneState(L - 1, d_model)`` for a convolution."""
    row = kv_cache_row(cfg)
    lane = LaneState(int(cfg["conv_kernel"]) - 1, int(cfg["d_model"]),
                     operator=conv_layer)
    kinds = {CONV: lane, ATTENTION: row}
    types = list(cfg["layer_types"])
    if len(types) != int(cfg["n_layers"]) or set(types) - set(kinds):
        raise ValueError(
            f"layer_types must name {cfg['n_layers']} layers of "
            f"{sorted(kinds)}, got {types}")
    return tuple(kinds[t] for t in types)


def _forward(params: dict, input_ids: jax.Array, cfg: dict, mesh=None) -> jax.Array:  # static-bounded: mesh -- one Mesh object per runtime lifetime
    dtype = jnp.dtype(cfg["dtype"])
    eps = cfg["rms_eps"]
    partitioned = mesh is not None and mesh.size > 1
    cast = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda w: w.astype(dtype), tree)
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(dtype)
    for layer in params["layers"]:
        with jax.named_scope("layer"):
            a = _rmsnorm(x, layer["ln1"], eps)
            if "conv" in layer:
                with jax.named_scope("conv"):
                    x = x + conv_operator(cast(layer["conv"]), a)[0]
            else:
                x = x + _attention_block(cast(layer["attn"]), a, cfg, mesh)
            if "moe" in layer:
                x = x + _moe_block(layer, x, cfg, dtype,
                                   partitioned=partitioned)[0]
            else:
                x = x + _mlp_block(cast(layer["mlp"]),
                                   _rmsnorm(x, layer["ln2"], eps))
    return _output_logits(params, x, dtype, eps)


@register("hybrid_lm", DEFAULT_CONFIG)
def build(config: dict) -> ModelDef:
    cfg = config
    layer_state = layer_state_of(cfg)

    def make_apply(mesh=None):
        def apply(params, inputs):
            return {"logits": _forward(
                params, inputs["input_ids"].astype(jnp.int32), cfg, mesh)}

        return apply

    def init(rng):
        d, v, e = cfg["d_model"], cfg["vocab_size"], cfg["n_experts"]
        ff, ffd, taps = cfg["d_ff"], cfg["d_ff_dense"], cfg["conv_kernel"]
        n_heads, n_kv = cfg["n_heads"], cfg["n_kv_heads"]
        hd = d // n_heads
        keys = jax.random.split(rng, cfg["n_layers"] + 1)

        def dense(key, fan_in, shape):
            return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)

        layers = []
        for i, kind in enumerate(cfg["layer_types"]):
            ks = jax.random.split(keys[i], 9)
            layer = {"ln1": jnp.ones((d,), jnp.float32),
                     "ln2": jnp.ones((d,), jnp.float32)}
            if kind == CONV:
                layer["conv"] = {
                    "w_in": dense(ks[0], d, (d, 3 * d)),
                    "w": dense(ks[1], taps, (d, taps)),
                    "w_out": dense(ks[2], d, (d, d)),
                }
            else:
                layer["attn"] = {
                    "wq": dense(ks[0], d, (d, n_heads * hd)),
                    "wk": dense(ks[1], d, (d, n_kv * hd)),
                    "wv": dense(ks[2], d, (d, n_kv * hd)),
                    "wo": dense(ks[3], n_heads * hd, (n_heads * hd, d)),
                    "q_norm": jnp.ones((hd,), jnp.float32),
                    "k_norm": jnp.ones((hd,), jnp.float32),
                }
            if i < cfg["n_dense_layers"]:
                layer["mlp"] = {
                    "w1": dense(ks[4], d, (d, ffd)),
                    "w2": dense(ks[5], ffd, (ffd, d)),
                    "w3": dense(ks[6], d, (d, ffd)),
                }
            else:
                layer["moe"] = {
                    "router": dense(ks[4], d, (d, e)),
                    "bias": 0.02 * jax.random.normal(ks[8], (e,), jnp.float32),
                    "w1": dense(ks[5], d, (e, d, ff)),
                    "w2": dense(ks[6], ff, (e, ff, d)),
                    "w3": dense(ks[7], d, (e, d, ff)),
                }
            layers.append(layer)
        return {
            "embed": dense(keys[-1], d, (v, d)),
            "layers": layers,
            "ln_f": jnp.ones((d,), jnp.float32),
        }

    # moe_lm's rules; a convolution's three leaves stay whole on every chip
    # (its gates multiply column by column, so a column split of W_in would
    # only buy collectives)
    partition_rules = {
        "embed": (None, "model"),
        r"layers/\d+/attn/w[qkv]": (None, "model"),
        r"layers/\d+/attn/wo": ("model", None),
        r"layers/\d+/attn/[qk]_norm": (None,),
        r"layers/\d+/conv/.*": (None,),
        r"layers/\d+/mlp/w[13]": (None, "model"),
        r"layers/\d+/mlp/w2": ("model", None),
        r"layers/\d+/moe/(router|bias)": (None,),
        r"layers/\d+/moe/w[123]": ("expert", None, None),
        r".*ln.*": (None,),
    }

    def last_token_logits(outputs, dyn_sizes):
        # device-side slice at the last REAL position (seq is bucket-padded)
        logits = outputs["logits"]
        s = dyn_sizes.get("seq", logits.shape[1])
        b = dyn_sizes.get("batch", logits.shape[0])
        return logits[:b, s - 1, :]

    return ModelDef(
        family="hybrid_lm",
        config=cfg,
        apply=make_apply(None),
        init=init,
        input_spec={"input_ids": TensorSpec("int32", ("batch", "seq"))},
        output_spec={"logits": TensorSpec("float32", ("batch", "seq", cfg["vocab_size"]))},
        partition_rules=partition_rules,
        derived_outputs={
            "last_token_logits": (
                last_token_logits,
                TensorSpec("float32", ("batch", cfg["vocab_size"])),
            )
        },
        default_outputs=["last_token_logits"],
        store_param_dtype=cfg["dtype"],
        bind_mesh=make_apply,
        # no capacity, no dropped token, a convolution over a lane's own rows:
        # a row's answer is its own
        engine_ready=True,
        cache_row=kv_cache_row(cfg),
        layer_state=layer_state,
    )
