"""moe_lm — mixture-of-experts decoder LM: a dropless top-k expert layer.

No reference counterpart (the reference serves opaque SavedModels and
implements no parallelism — SURVEY.md §2 inventory). The family covers the
open top-k expert decoders (OLMoE-1B-7B is its benchmark configuration:
64 experts of width 1024, 8 a token, QK-norm, an untied head) and, at
``top_k`` 1, the Switch layer it began as. Attention, RoPE, the KV cache
layout and ``models/generation.py`` are transformer_lm's; what differs is
in the params: a layer holds ``moe`` (``router`` and the stacked SwiGLU
experts ``w1``, ``w3`` ``(e, d, ff)``, ``w2`` ``(e, ff, d)``) where the dense
family holds ``mlp``, and ``qk_norm`` / ``tie_embeddings`` add the
``q_norm`` / ``k_norm`` and ``lm_head`` leaves that switch those on
(``transformer_lm._qkv``, ``_output_logits``).

The expert layer is ``ops/moe.py``: router in float32, top-k over the
softmax of all experts, gates the softmax values themselves unless
``norm_topk_prob``, tokens grouped by expert and multiplied by the experts
they were routed to and no others. There is no capacity and no dropped
token, and a row's answer does not depend on the rows beside it, so the
family is engine-ready: it co-batches and shares decode steps like the
dense one.

A model may mix two kinds of attention layer (``layer_types``, one entry a
layer): ``full_attention`` keeps every row, ``sliding_attention`` the last
``sliding_window`` (a query reads itself and the ``sliding_window - 1``
positions before it). The ModelDef declares that a layer (``layer_state``: a
``CacheRow`` with a ``window``), and the shared code gives such a layer one
window of pages a lane in an arena of its own (``models/generation.py``). The
rotary goes by kind: window layers the plain ``rope_theta`` frequencies,
global layers what ``rope_full`` states (YaRN's blend, cos and sin times its
``attention_factor``; ``transformer_lm.rope_of``). ``head_dim`` is the head
width where it is not ``d_model / n_heads``. A config with none of these keys
(OLMoE's) builds the program it always built.

What a config may state besides, each absent = the program as it was
(Laguna-S-2.1 is the configuration that states them all): the query heads A
LAYER (``n_heads_per_layer``, read through ``registry.query_heads``; the KV
row is the same in every layer, so the arenas do not follow it); a theta of
the window layers' own (``rope_theta_window``) and a rotary over a share of a
global layer's head (``rope_full.partial``); an output gate of one value a
head (``attn_gate: "head"`` makes the ``attn/w_gate (d, heads)`` leaf, whose
shape says what it is); dense SwiGLU layers among the expert ones
(``mlp_only_layers``, of width ``d_ff_dense``: such a layer holds ``mlp``, and
``generation._ffn_block`` goes by what a layer holds); and the expert layer
of ONE CHIP of an expert-parallel host (``n_experts_held`` from
``expert_first``, ``route_score`` / ``route_scale``, a shared expert of
``shared_width`` under ``moe/shared``: ``_moe_block``'s keys).

Expert weights carry an ``("expert", None, None)`` partition rule, so on a
mesh with an "expert" axis each chip group holds E/ep experts; there the
grouped product is ``jax.lax.ragged_dot`` (the Pallas kernel is single-chip).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from tfservingcache_tpu.models.real_rows import over_real_rows, row_block
from tfservingcache_tpu.models.registry import (
    ModelDef,
    TensorSpec,
    head_width,
    kv_cache_row,
    query_heads,
    register,
)
from tfservingcache_tpu.models.transformer_lm import (
    _attention_block,
    _mlp_block,
    _output_logits,
    _rmsnorm,
)
from tfservingcache_tpu.ops.moe import moe_experts

DEFAULT_CONFIG: dict[str, Any] = {
    "vocab_size": 2048,
    "d_model": 256,
    "n_layers": 4,
    "n_heads": 8,
    "n_kv_heads": 8,
    "d_ff": 512,            # per-expert FFN width
    "n_experts": 8,
    "top_k": 1,             # experts a token (1 = Switch)
    "norm_topk_prob": False,  # gates = softmax values, not renormalised
    "qk_norm": False,       # RMSNorm over the whole q and k projections
    "tie_embeddings": True,  # False = a separate lm_head leaf
    "aux_loss_weight": 0.01,
    "max_seq": 1024,
    "rope_theta": 10000.0,
    "dtype": "bfloat16",
}


SLIDING, FULL = "sliding_attention", "full_attention"

# From this many bytes of routed rows on (every assignment's float32 answer
# before the gates sum them) a prefill's expert layer runs a block of rows at
# a time; every accepted cell's longest bucket stays below (Mistral-4's 8192
# x 4 x 4096 are half of it) and keeps its one call.
EXPERT_ROWS_BYTES = 1 << 30


def layer_state_of(cfg: dict) -> tuple:
    """What each layer keeps of a request, from ``layer_types``: the K/V row,
    with ``sliding_window`` as its window for a ``sliding_attention`` layer.
    () for a model whose layers are all global (no ``layer_types``: the
    ModelDef then fills in ``cache_row`` a layer)."""
    types = list(cfg.get("layer_types") or ())
    if not types:
        return ()
    window = int(cfg.get("sliding_window") or 0)
    if len(types) != int(cfg["n_layers"]) or set(types) - {SLIDING, FULL}:
        raise ValueError(
            f"layer_types must name {cfg['n_layers']} layers of "
            f"{[SLIDING, FULL]}, got {types}")
    if SLIDING in types and window < 1:
        raise ValueError("sliding_attention layers need a sliding_window >= 1")
    return tuple(kv_cache_row(cfg, window if t == SLIDING else 0)
                 for t in types)


@jax.named_scope("ffn")
def _moe_block(layer: dict, x: jax.Array, cfg: dict, dtype, row_mask=None,
               partitioned: bool = False, took=None) -> tuple[jax.Array, dict]:
    """The expert half of a layer over the residual stream ``x (B, S, D)``
    BEFORE its norm -> (residual delta, the layer's routing stats).
    ``row_mask (B*S,)`` marks rows whose answer nobody reads. A prefill whose
    routed rows would fill ``EXPERT_ROWS_BYTES`` (``B x S x top_k`` float32
    rows of the model's width: 2.1 GB at a 16384-token bucket of 8 experts a
    token at 4096) takes them a block of real rows at a time
    (``over_real_rows``) and hands back no stats: its caller reads none. What the config
    may add to the plain top-k layer: the router's ``route_score`` /
    ``route_scale`` / ``route_norm_eps``, the chip's share ``n_experts_held`` experts from
    ``expert_first`` (``ops.moe.moe_experts``' ``held``), and, where the layer
    holds ``moe/shared``, a dense SwiGLU expert every token takes, computed
    once beside the routed ones, over the row blocks that hold the ``took
    (B,)`` real rows of each example (None = all; ``over_real_rows``)."""
    b, s, d = x.shape
    moe = {w: layer["moe"][w] for w in ("router", "bias")
           if w in layer["moe"]}                        # routing stays f32
    moe.update((w, layer["moe"][w].astype(dtype)) for w in ("w1", "w2", "w3"))
    held = ((int(cfg.get("expert_first", 0)), int(cfg["n_experts_held"]))
            if "n_experts_held" in cfg else None)
    z = _rmsnorm(x, layer["ln2"], cfg.get("rms_eps", 1e-5)).reshape(b * s, d)

    def routed(z, row_mask):
        return moe_experts(
            z, moe, int(cfg["top_k"]),
            norm_topk=bool(cfg["norm_topk_prob"]), row_mask=row_mask,
            partitioned=partitioned, score=cfg.get("route_score", "softmax"),
            route_scale=float(cfg.get("route_scale", 1.0)), held=held,
            norm_eps=float(cfg.get("route_norm_eps", 0.0)))

    if (took is not None and row_block(s)
            and b * s * int(cfg["top_k"]) * d * 4 >= EXPERT_ROWS_BYTES):
        # a row's experts are its own: the real rows a block at a time
        def block(z, *mask):
            y, _ = routed(z.reshape(-1, d), mask[0].reshape(-1) if mask else None)
            return y.reshape(z.shape)

        masks = () if row_mask is None else (row_mask.reshape(b, s),)
        y = over_real_rows(block, (z.reshape(b, s, d), *masks), took)
        y, stats = y.reshape(b * s, d), None
    else:
        y, stats = routed(z, row_mask)
    if "shared" in layer["moe"]:
        with jax.named_scope("shared"):
            sh = jax.tree_util.tree_map(lambda w: w.astype(dtype),
                                        layer["moe"]["shared"])

            def shared(z):
                return (jax.nn.silu(z @ sh["w1"]) * (z @ sh["w3"])) @ sh["w2"]

            if took is not None and row_block(s):
                # the loop's blocks are rows of an example: not the flat rows
                y = y + over_real_rows(
                    shared, (z.reshape(b, s, d),), took).reshape(b * s, d)
            else:
                y = y + shared(z)
    return y.reshape(b, s, d), stats


def _forward(params: dict, input_ids: jax.Array, cfg: dict, mesh=None) -> tuple[jax.Array, jax.Array]:  # static-bounded: mesh -- one Mesh object per runtime lifetime
    dtype = jnp.dtype(cfg["dtype"])
    e = cfg["n_experts"]
    partitioned = mesh is not None and mesh.size > 1
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(dtype)
    aux_total = jnp.zeros((), jnp.float32)
    eps = cfg.get("rms_eps", 1e-5)
    kinds = layer_state_of(cfg) or (None,) * len(params["layers"])
    for depth, (layer, kind) in enumerate(zip(params["layers"], kinds)):
        with jax.named_scope("layer"):
            x = x + _attention_block(
                jax.tree_util.tree_map(lambda w: w.astype(dtype), layer["attn"]),
                _rmsnorm(x, layer["ln1"], eps),
                cfg,
                mesh,
                window=kind.window if kind else 0,
                depth=depth,
            )
            if "mlp" in layer:    # a dense layer among the expert ones
                x = x + _mlp_block(
                    jax.tree_util.tree_map(lambda w: w.astype(dtype), layer["mlp"]),
                    _rmsnorm(x, layer["ln2"], eps))
                continue
            y, stats = _moe_block(layer, x, cfg, dtype, partitioned=partitioned)
            x = x + y
        # Switch load-balance aux loss: e * sum_e(frac_tokens_e * mean_prob_e)
        frac = jnp.mean(jnp.sum(jax.nn.one_hot(stats["experts"], e), axis=1), axis=0)
        aux_total = aux_total + e * jnp.sum(frac * jnp.mean(stats["probs"], axis=0))
    logits = _output_logits(params, x, dtype, eps)
    expert_layers = sum("moe" in layer for layer in params["layers"])
    return logits, aux_total / max(expert_layers, 1)


@register("moe_lm", DEFAULT_CONFIG)
def build(config: dict) -> ModelDef:
    cfg = config

    def make_apply(mesh=None):
        def apply(params, inputs):
            logits, _ = _forward(
                params, inputs["input_ids"].astype(jnp.int32), cfg, mesh
            )
            return {"logits": logits}

        return apply

    apply = make_apply(None)

    def init(rng):
        d, v, ff = cfg["d_model"], cfg["vocab_size"], cfg["d_ff"]
        e = int(cfg.get("n_experts_held", cfg["n_experts"]))   # held HERE
        n_kv = cfg["n_kv_heads"]
        head_dim = head_width(cfg)
        dense_layers = set(cfg.get("mlp_only_layers") or ())
        keys = jax.random.split(rng, cfg["n_layers"] + 1)

        def dense(key, fan_in, shape):
            return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)

        def swiglu(key, width):
            k1, k2, k3 = jax.random.split(key, 3)
            return {"w1": dense(k1, d, (d, width)), "w2": dense(k2, width, (width, d)),
                    "w3": dense(k3, d, (d, width))}

        layers = []
        for i in range(cfg["n_layers"]):
            ks = jax.random.split(keys[i], 8)
            q = query_heads(cfg, i) * head_dim
            layer = {
                "attn": {
                    "wq": dense(ks[0], d, (d, q)),
                    "wk": dense(ks[1], d, (d, n_kv * head_dim)),
                    "wv": dense(ks[2], d, (d, n_kv * head_dim)),
                    "wo": dense(ks[3], q, (q, d)),
                },
                "ln1": jnp.ones((d,), jnp.float32),
                "ln2": jnp.ones((d,), jnp.float32),
            }
            if i in dense_layers:
                layer["mlp"] = swiglu(ks[4], cfg["d_ff_dense"])
            else:
                layer["moe"] = {
                    "router": dense(ks[4], d, (d, cfg["n_experts"])),
                    "w1": dense(ks[5], d, (e, d, ff)),
                    "w2": dense(ks[6], ff, (e, ff, d)),
                    "w3": dense(ks[7], d, (e, d, ff)),
                }
                if cfg.get("shared_width"):
                    layer["moe"]["shared"] = swiglu(
                        jax.random.fold_in(ks[5], 1), cfg["shared_width"])
            if cfg["qk_norm"]:
                layer["attn"]["q_norm"] = jnp.ones((q,), jnp.float32)
                layer["attn"]["k_norm"] = jnp.ones((n_kv * head_dim,), jnp.float32)
            if cfg.get("attn_gate") == "head":    # one value a head
                layer["attn"]["w_gate"] = dense(
                    jax.random.fold_in(ks[0], 1), d, (d, q // head_dim))
            layers.append(layer)
        params = {
            "embed": dense(keys[-1], d, (v, d)),
            "layers": layers,
            "ln_f": jnp.ones((d,), jnp.float32),
        }
        if not cfg["tie_embeddings"]:
            params["lm_head"] = dense(jax.random.fold_in(keys[-1], 1), d, (d, v))
        return params

    def loss(params, inputs, targets):
        logits, aux = _forward(params, inputs["input_ids"].astype(jnp.int32), cfg)
        labels = targets["labels"].astype(jnp.int32)
        logp = jax.nn.log_softmax(logits[:, :-1, :], axis=-1)
        tgt = labels[:, 1:]
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        return jnp.mean(nll) + cfg["aux_loss_weight"] * aux

    # Expert parallelism: expert-batched FFN weights shard over the "expert"
    # mesh axis (leading dim = experts); attention keeps the flagship's
    # megatron TP over "model". Rules referencing an absent mesh axis degrade
    # to replicated (parallel/sharding.spec_for), so the family runs on
    # data-only, data x expert, or data x expert x model meshes unchanged.
    partition_rules = {
        "embed": (None, "model"),
        "lm_head": (None, "model"),
        r"layers/\d+/attn/w[qkv]": (None, "model"),
        r"layers/\d+/attn/wo": ("model", None),
        r"layers/\d+/attn/[qk]_norm": (None,),
        r"layers/\d+/moe/router": (None,),
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
        family="moe_lm",
        config=cfg,
        apply=apply,
        init=init,
        input_spec={"input_ids": TensorSpec("int32", ("batch", "seq"))},
        output_spec={"logits": TensorSpec("float32", ("batch", "seq", cfg["vocab_size"]))},
        partition_rules=partition_rules,
        loss=loss,
        derived_outputs={
            "last_token_logits": (
                last_token_logits,
                TensorSpec("float32", ("batch", cfg["vocab_size"])),
            )
        },
        # same LM serving default as transformer_lm: next-token logits out of
        # the box, full (B, S, V) logits via output_filter=["logits"]
        default_outputs=["last_token_logits"],
        store_param_dtype=cfg["dtype"],
        # the shared attention block must know when it is traced into a
        # chip group's partitioned program (transformer_lm._attention_block)
        bind_mesh=make_apply,
        # no capacity, no dropped token: a row's answer is its own
        engine_ready=True,
        cache_row=kv_cache_row(cfg),
        layer_state=layer_state_of(cfg),
    )
