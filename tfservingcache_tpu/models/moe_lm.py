"""moe_lm — mixture-of-experts decoder LM (Switch-style top-1 routing).

No reference counterpart (the reference serves opaque SavedModels and
implements no parallelism — SURVEY.md §2 inventory); this family exists so
expert parallelism is a first-class, servable capability: expert weights
carry an ``("expert", …)`` partition rule, so on a mesh with an "expert"
axis each chip group holds E/ep experts and XLA inserts the dispatch/combine
all-to-alls from the shardings.

TPU-first routing design: the GShard/Switch dense-dispatch formulation —
one-hot dispatch/combine tensors contracted with einsum — keeps every shape
static under jit (no data-dependent gather), trades a capacity-factor bound
(dropped tokens pass through the residual) for MXU-friendly dense matmuls.
Routing runs in f32; expert FFNs in bf16.

Serving caveat inherent to capacity routing: expert capacity is computed
over the whole flattened (padded) batch, so which tokens drop depends on
batch composition — outputs are deterministic per padded shape but NOT
batch-composition-invariant. The generate coalescer therefore never
co-batches moe_lm requests (runtime/batcher.py).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from tfservingcache_tpu.models.registry import ModelDef, TensorSpec, register
from tfservingcache_tpu.models.transformer_lm import _attention_block, _rmsnorm

DEFAULT_CONFIG: dict[str, Any] = {
    "vocab_size": 2048,
    "d_model": 256,
    "n_layers": 4,
    "n_heads": 8,
    "n_kv_heads": 8,
    "d_ff": 512,            # per-expert FFN width
    "n_experts": 8,
    "capacity_factor": 1.25,
    "aux_loss_weight": 0.01,
    "max_seq": 1024,
    "rope_theta": 10000.0,
    "dtype": "bfloat16",
}


def _moe_block(params: dict, x: jax.Array, cfg: dict) -> tuple[jax.Array, jax.Array]:
    """Top-1 routed expert FFN over (B, S, D) -> (output, aux_loss).

    Dense GShard dispatch: tokens -> (token, expert, capacity_slot) one-hot,
    experts applied batched over their leading (sharded) axis, combine
    weighted by the router gate. Tokens past an expert's capacity drop (the
    residual connection carries them unchanged).
    """
    b, s, d = x.shape
    e = cfg["n_experts"]
    t = b * s
    capacity = max(1, math.ceil(cfg["capacity_factor"] * t / e))
    xt = x.reshape(t, d)

    router_logits = (xt.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(router_logits, axis=-1)              # (t, e) f32
    gate = jnp.max(probs, axis=-1)                              # (t,)
    expert_ix = jnp.argmax(probs, axis=-1)                      # (t,)
    onehot = jax.nn.one_hot(expert_ix, e, dtype=jnp.float32)    # (t, e)

    # position of each token within its expert's queue (0-based); tokens at
    # position >= capacity are dropped
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot          # (t, e)
    keep = onehot * (pos < capacity)
    dispatch = keep[..., None] * jax.nn.one_hot(
        pos.astype(jnp.int32), capacity, dtype=jnp.float32
    )
    # (t, e, c)

    # NOTE: the dispatch gather-matmul stays f32 — bf16 operands change the
    # EP-sharded cross-device reduction enough to break parity with the
    # replicated path (tests/test_parallel.py), and routing fidelity beats
    # the marginal MXU win here
    expert_in = jnp.einsum("tec,td->ecd", dispatch, xt.astype(jnp.float32))
    expert_in = expert_in.astype(x.dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, params["w1"]))
    out_e = jnp.einsum("ecf,efd->ecd", h, params["w2"])         # (e, c, d)

    combine = dispatch * gate[:, None, None]
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), out_e)

    # Switch load-balance aux loss: e * sum_e(frac_tokens_e * mean_prob_e)
    frac_tokens = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * mean_prob)
    return y.reshape(b, s, d), aux


def _forward(params: dict, input_ids: jax.Array, cfg: dict, mesh=None) -> tuple[jax.Array, jax.Array]:  # static-bounded: mesh -- one Mesh object per runtime lifetime
    dtype = jnp.dtype(cfg["dtype"])
    x = params["embed"][input_ids].astype(dtype)
    aux_total = jnp.zeros((), jnp.float32)
    for layer in params["layers"]:
        x = x + _attention_block(
            jax.tree_util.tree_map(lambda w: w.astype(dtype), layer["attn"]),
            _rmsnorm(x, layer["ln1"]),
            cfg,
            mesh,
        )
        moe_params = {
            "router": layer["moe"]["router"],  # stays f32 inside the block
            "w1": layer["moe"]["w1"].astype(dtype),
            "w2": layer["moe"]["w2"].astype(dtype),
        }
        y, aux = _moe_block(moe_params, _rmsnorm(x, layer["ln2"]), cfg)
        x = x + y
        aux_total = aux_total + aux
    x = _rmsnorm(x, params["ln_f"])
    logits = (x @ params["embed"].astype(dtype).T).astype(jnp.float32)
    return logits, aux_total / max(len(params["layers"]), 1)


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
        d, v, ff, e = cfg["d_model"], cfg["vocab_size"], cfg["d_ff"], cfg["n_experts"]
        n_heads, n_kv = cfg["n_heads"], cfg["n_kv_heads"]
        head_dim = d // n_heads
        keys = jax.random.split(rng, cfg["n_layers"] + 1)

        def dense(key, fan_in, shape):
            return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)

        layers = []
        for i in range(cfg["n_layers"]):
            ks = jax.random.split(keys[i], 7)
            layers.append(
                {
                    "attn": {
                        "wq": dense(ks[0], d, (d, n_heads * head_dim)),
                        "wk": dense(ks[1], d, (d, n_kv * head_dim)),
                        "wv": dense(ks[2], d, (d, n_kv * head_dim)),
                        "wo": dense(ks[3], n_heads * head_dim, (n_heads * head_dim, d)),
                    },
                    "moe": {
                        "router": dense(ks[4], d, (d, e)),
                        "w1": dense(ks[5], d, (e, d, ff)),
                        "w2": dense(ks[6], ff, (e, ff, d)),
                    },
                    "ln1": jnp.ones((d,), jnp.float32),
                    "ln2": jnp.ones((d,), jnp.float32),
                }
            )
        return {
            "embed": dense(keys[-1], d, (v, d)),
            "layers": layers,
            "ln_f": jnp.ones((d,), jnp.float32),
        }

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
        r"layers/\d+/attn/w[qkv]": (None, "model"),
        r"layers/\d+/attn/wo": ("model", None),
        r"layers/\d+/moe/router": (None,),
        r"layers/\d+/moe/w[12]": ("expert", None, None),
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
    )
