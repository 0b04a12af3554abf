"""transformer_lm — the flagship decoder-LM family (Llama/T5-XL-class,
BASELINE.json config #5: models that span >1 TPU chip, served by chip
groups the ring assigns).

TPU-first design:
  - bf16 matmuls (MXU), fp32 softmax/norm accumulation;
  - Pallas flash attention on TPU (ops/attention.py), jnp fallback on CPU;
  - pure-functional params pytree with explicit tensor-parallel partition
    rules (megatron-style: attention/MLP sharded over the "model" mesh axis,
    collectives inserted by XLA from the shardings — no hand-written NCCL,
    SURVEY.md §2 distributed-backend inventory);
  - weights stored in the serving dtype (bf16) in the artifact — the cold
    path is host->HBM bandwidth-bound, so artifact bytes are the latency.

Config presets cover smoke tests through llama-7b-class shapes.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from tfservingcache_tpu.models.registry import (
    ModelDef,
    TensorSpec,
    head_width,
    kv_cache_row,
    query_heads,
    register,
)
from tfservingcache_tpu.ops.attention import attention

DEFAULT_CONFIG: dict[str, Any] = {
    "vocab_size": 2048,
    "d_model": 256,
    "n_layers": 4,
    "n_heads": 8,
    "n_kv_heads": 4,       # GQA
    "d_ff": 1024,
    "max_seq": 1024,
    "rope_theta": 10000.0,
    "dtype": "bfloat16",
    # "auto" = flash kernel on TPU / jnp elsewhere. "ring" = context
    # parallelism: the sequence axis is sharded over the serving chip group
    # and K/V blocks rotate by ppermute (parallel/ring_attention.py) — for
    # long-context models whose attention working set exceeds one chip.
    "attention": "auto",
}

# llama-2-7b-class shape for multi-chip serving/benching
LLAMA7B_CONFIG: dict[str, Any] = {
    "vocab_size": 32000,
    "d_model": 4096,
    "n_layers": 32,
    "n_heads": 32,
    "n_kv_heads": 32,
    "d_ff": 11008,
    "max_seq": 4096,
    "rope_theta": 10000.0,
    "dtype": "bfloat16",
}


def _rmsnorm(x: jax.Array, gain: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * gain.astype(x.dtype)


def _layernorm(x: jax.Array, gain: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 * scale).astype(x.dtype) * gain.astype(x.dtype)
            + bias.astype(x.dtype))


def _norm(holder: dict, name: str, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """The norm a layer (or the params' root) holds under ``name``, chosen by
    what it holds: a LayerNorm (mean and variance, gain and bias) where a
    ``<name>_b`` leaf is there, else the RMSNorm every other model has, which
    traces exactly what it traced."""
    if name + "_b" in holder:
        return _layernorm(x, holder[name], holder[name + "_b"], eps)
    return _rmsnorm(x, holder[name], eps)


def plain_frequencies(d: int, theta: float) -> np.ndarray:
    """The ``d / 2`` plain rotary frequencies ``theta^(-2i/d)``, float64."""
    return float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)


def yarn_frequencies(d: int, theta: float, factor: float, original_max: float,
                     beta_fast: float, beta_slow: float) -> np.ndarray:
    """The ``d / 2`` rotary frequencies of a ``d``-wide head: plain
    ``theta^(-2i/d)`` at ``factor`` 1, else YaRN's blend of those (dimensions
    that turn more than ``beta_fast`` times within ``original_max`` positions)
    with the same divided by ``factor`` (fewer than ``beta_slow`` turns), a
    linear ramp between; the same blend at every position."""
    extra = plain_frequencies(d, theta)
    if float(factor) == 1.0:
        return extra.astype(np.float32)

    def correction_dim(turns: float) -> float:
        return (d * math.log(float(original_max) / (turns * 2 * math.pi))
                ) / (2 * math.log(float(theta)))

    low = max(math.floor(correction_dim(float(beta_fast))), 0)
    high = min(math.ceil(correction_dim(float(beta_slow))), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / float(factor) * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_of(cfg: dict, window: int = 0) -> tuple:
    """The rotary of one layer of ``cfg``'s model -> ``(frequencies | None,
    factor, turned)``: None = the plain ``rope_theta`` frequencies computed
    where they are applied (every model with one rotary, bit for bit as
    before); ``turned`` = the leading columns of a head that turn, 0 = all of
    them. A model with a rotary a layer KIND states the global layers' under
    ``rope_full`` (``yarn``: the factor, ``original_max``, ``beta_fast``,
    ``beta_slow``, ``attention_factor``, which multiplies cos and sin;
    ``partial``: the share of a head that turns, the frequencies and YaRN's
    ramp then taken over those columns alone); its window layers (``window``
    > 0) keep plain frequencies, at ``rope_theta_window`` where the config
    states a theta of their own."""
    full = dict(cfg.get("rope_full") or ())
    if window or not full:
        theta = cfg.get("rope_theta_window") if window else None
        if theta is None:
            return None, 1.0, 0
        return plain_frequencies(head_width(cfg), theta).astype(np.float32), 1.0, 0
    width = head_width(cfg)
    turned = int(width * float(full.get("partial", 1.0)))
    freqs = yarn_frequencies(
        turned, cfg["rope_theta"], full["yarn"], full["original_max"],
        full.get("beta_fast", 32.0), full.get("beta_slow", 1.0))
    return (freqs, float(full.get("attention_factor", 1.0)),
            turned if turned < width else 0)


def _rope(x: jax.Array, positions: jax.Array, theta: float,
          rope: tuple = (None, 1.0, 0)) -> jax.Array:
    """Rotary embedding over (B, H, S, D); ``rope`` = ``rope_of``'s answer."""
    d = x.shape[-1]
    freqs, factor, turned = rope
    if turned:    # a partial rotary: the leading columns turn, the rest pass
        head = _rope(x[..., :turned], positions, theta, (freqs, factor, 0))
        return jnp.concatenate([head, x[..., turned:]], axis=-1)
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)  # (d/2,)
    angles = positions[:, None].astype(jnp.float32) * jnp.asarray(freqs)[None, :]
    cos = jnp.cos(angles)[None, None]                                    # (1,1,S,d/2)
    sin = jnp.sin(angles)[None, None]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = x[..., 0::2], x[..., 1::2]
    rot = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rot.reshape(x.shape).astype(x.dtype)


def _qkv(attn: dict, h: jax.Array, n_heads: int, n_kv: int,
         eps: float = 1e-5):
    """The normed activations ``h (B, S, d)`` -> q ``(B, n_heads, S, hd)`` and
    k, v ``(B, n_kv, S, hd)``, not yet rotated: the one projection of every
    forward (here and the four in models/generation.py). ``q_norm`` /
    ``k_norm`` leaves in ``attn`` switch on QK-norm as OLMoE has it: an
    RMSNorm with a learned gain over the WHOLE query and key projection,
    before the heads are split. A gain of ONE head's length is the per-head
    form (an RMSNorm over each head's columns, the gain shared by the heads
    of a side): the leaf's shape says which; ``eps`` is the QK-norm's. A layer
    without the leaves traces the three products and nothing else."""
    b, s, _ = h.shape

    def proj(w, n, gain=None):
        t = h @ attn[w]
        hd = t.shape[-1] // n
        per_head = gain in attn and attn[gain].shape[-1] == hd
        if gain in attn and not per_head:
            t = _rmsnorm(t, attn[gain], eps)
        t = t.reshape(b, s, n, hd)
        if per_head:
            t = _rmsnorm(t, attn[gain], eps)
        return t.transpose(0, 2, 1, 3)

    return (proj("wq", n_heads, "q_norm"), proj("wk", n_kv, "k_norm"),
            proj("wv", n_kv))


@jax.named_scope("lm_head")
def _output_logits(params: dict, x: jax.Array, dtype,
                   eps: float = 1e-5) -> jax.Array:
    """Final norm and output head -> float32 logits (a stable softmax/argmax
    downstream). An ``lm_head (d, vocab)`` leaf is the untied head; without
    it the head is the embedding."""
    x = _norm(params, "ln_f", x, eps)
    if "lm_head" in params:
        return (x @ params["lm_head"].astype(dtype)).astype(jnp.float32)
    return (x @ params["embed"].astype(dtype).T).astype(jnp.float32)


def head_gate(attn: dict, a: jax.Array, heads: int) -> jax.Array:
    """The output gate of a layer whose ``attn`` holds ``w_gate``, from the
    layer's normed input ``a (B, S, d)`` -> ``sigmoid(a w_gate)`` laid out as
    the heads' outputs are, ``(B, heads, S, columns)``. The leaf's width
    against the query side's says which gate it is: ``heads x head width``
    columns are a value a head COLUMN, ``heads`` columns one value a HEAD
    (``columns`` 1: it multiplies the head's whole output)."""
    b, s, _ = a.shape
    with jax.named_scope("gate"):
        gate = (a @ attn["w_gate"]).reshape(b, s, heads, -1)
        return jax.nn.sigmoid(gate).transpose(0, 2, 1, 3)


@jax.named_scope("attn")
def _attention_block(params: dict, x: jax.Array, cfg: dict, mesh=None,  # static-bounded: mesh, window, depth -- one Mesh object per runtime lifetime; one window per model config; depth is the caller's unrolled loop index, below the model's depth
                     window: int = 0, depth: int | None = None) -> jax.Array:
    """``window`` > 0: a window layer (a query reads itself and the
    ``window - 1`` positions before it), with the rotary of its kind.
    ``depth``: the layer's index in a model whose query heads go by layer
    (``registry.query_heads``)."""
    b, s, _ = x.shape
    q, k, v = _qkv(params, x, query_heads(cfg, depth), cfg["n_kv_heads"])
    positions = jnp.arange(s)
    rope = rope_of(cfg, window)
    q = _rope(q, positions, cfg["rope_theta"], rope)
    k = _rope(k, positions, cfg["rope_theta"], rope)
    if (
        mesh is not None
        and cfg.get("attention") == "ring"
        and s % mesh.shape.get("model", 1) == 0
        and mesh.shape.get("model", 1) > 1
    ):
        # context parallelism: sequence sharded over the group's chips, K/V
        # rotating by ppermute — sequences too short for the ring (bucket <
        # group size) fall through to regular attention below
        from tfservingcache_tpu.parallel.ring_attention import ring_attention

        out = ring_attention(q, k, v, mesh, axis="model", causal=True)
    else:
        # GQA handled inside attention (grouped K/V, never materialized via
        # repeat — that would negate GQA's HBM saving at llama-7b scale).
        # On a chip group this block is traced into a GSPMD-partitioned
        # program, which the gate must know: it cannot see it from shapes.
        out = attention(q, k, v, causal=True,
                        partitioned=mesh is not None and mesh.size > 1,
                        window=window)                                   # (b,h,s,hd)
    if "w_gate" in params:
        out = out.astype(x.dtype) * head_gate(params, x, q.shape[1])
    # heads x head width: the hidden size for most models, not for all
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return out @ params["wo"]


@jax.named_scope("ffn")
def _mlp_block(params: dict, x: jax.Array) -> jax.Array:
    gate = jax.nn.silu(x @ params["w1"])
    up = x @ params["w3"]
    return (gate * up) @ params["w2"]


def _forward(params: dict, input_ids: jax.Array, cfg: dict, mesh=None) -> jax.Array:
    dtype = jnp.dtype(cfg["dtype"])
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(dtype)                    # (b,s,d)
    for layer in params["layers"]:
        with jax.named_scope("layer"):
            x = x + _attention_block(
                jax.tree_util.tree_map(lambda w: w.astype(dtype), layer["attn"]),
                _rmsnorm(x, layer["ln1"]),
                cfg,
                mesh,
            )
            x = x + _mlp_block(
                jax.tree_util.tree_map(lambda w: w.astype(dtype), layer["mlp"]),
                _rmsnorm(x, layer["ln2"]),
            )
    return _output_logits(params, x, dtype)


@register("transformer_lm", DEFAULT_CONFIG)
def build(config: dict) -> ModelDef:
    cfg = config
    ring = cfg.get("attention") == "ring"
    if ring and cfg["n_heads"] != cfg["n_kv_heads"]:
        raise ValueError(
            "attention='ring' requires n_heads == n_kv_heads (the ring "
            "rotates full K/V blocks; grouped-KV ring is not implemented)"
        )

    def make_apply(mesh=None):
        def apply(params, inputs):
            # logits only: the runtime pads the sequence axis to shape
            # buckets, and causal masking keeps valid positions exact — but
            # any "last token" reduction would land on padding, so sampling
            # stays client-side (or in the generate helper, which tracks
            # true lengths).
            logits = _forward(
                params, inputs["input_ids"].astype(jnp.int32), cfg, mesh
            )
            return {"logits": logits}

        return apply

    apply = make_apply(None)

    def init(rng):
        d, v, ff = cfg["d_model"], cfg["vocab_size"], cfg["d_ff"]
        n_heads, n_kv = cfg["n_heads"], cfg["n_kv_heads"]
        head_dim = d // n_heads
        keys = jax.random.split(rng, cfg["n_layers"] + 1)

        def dense(key, fan_in, shape):
            return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in))

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
                    "mlp": {
                        "w1": dense(ks[4], d, (d, ff)),
                        "w2": dense(ks[5], ff, (ff, d)),
                        "w3": dense(ks[6], d, (d, ff)),
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
        logits = _forward(params, inputs["input_ids"].astype(jnp.int32), cfg)
        labels = targets["labels"].astype(jnp.int32)
        # next-token cross entropy, ignoring the final position
        logp = jax.nn.log_softmax(logits[:, :-1, :], axis=-1)
        tgt = labels[:, 1:]
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        return jnp.mean(nll)

    if ring:
        # context parallelism owns the group's mesh axis for the SEQUENCE;
        # weights replicate (rule matches everything -> PartitionSpec())
        partition_rules = {r".*": ()}
    else:
        # Megatron-style tensor parallelism over the "model" mesh axis:
        # column-parallel QKV/W1/W3, row-parallel WO/W2 (XLA inserts the
        # all-reduces).
        partition_rules = {
            "embed": (None, "model"),
            r"layers/\d+/attn/w[qkv]": (None, "model"),
            r"layers/\d+/attn/wo": ("model", None),
            r"layers/\d+/mlp/w[13]": (None, "model"),
            r"layers/\d+/mlp/w2": ("model", None),
            r".*ln.*": (None,),
        }

    def last_token_logits(outputs, dyn_sizes):
        """Device-side slice at the last REAL position (runtime pads seq to a
        bucket, so -1 would land on padding). Ships (B, V) to host instead of
        (B, S, V) — the LM warm-path fix. Rows share one true length; ragged
        prompts belong to :generate, which tracks per-row lengths."""
        logits = outputs["logits"]
        s = dyn_sizes.get("seq", logits.shape[1])
        b = dyn_sizes.get("batch", logits.shape[0])
        return logits[:b, s - 1, :]

    return ModelDef(
        family="transformer_lm",
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
        # out-of-box predict ships the (B, V) next-token logits; the full
        # (B, S, V) tensor is opt-in via output_filter=["logits"] (at seq 128
        # vocab 4096 that's 8 MB of f32 per request — the round-2 0.5 qps)
        default_outputs=["last_token_logits"],
        # apply casts weights to cfg dtype anyway; storing them f32 doubled
        # the cold-path transfer (round-2 cold p50 3.14 s was ~80% device_put)
        store_param_dtype=cfg["dtype"],
        # the computation must know the serving group's mesh: ring mode
        # shards the sequence over it, and plain TP must keep the bare flash
        # kernel out of a partitioned program (_attention_block)
        bind_mesh=make_apply,
        engine_ready=True,
        cache_row=kv_cache_row(cfg),
    )
