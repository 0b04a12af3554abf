"""mla_moe_lm — a latent-attention (MLA) decoder with a shared expert beside
routed ones, as one chip of an expert-parallel host holds it.

No reference counterpart (SURVEY.md §2). The family is the DeepSeek-V3
lineage's layer (Mistral-Small-4-119B-2603 is its benchmark configuration:
``benchmark/configs/mistral-small-4-119b-2603.json``, reference
``benchmark/families/mla_moe.py``). For tokens ``x`` at positions ``p``, every
norm an RMSNorm with ``rms_eps``::

    a              = norm(x; ln1)
    c_q            = norm(a wq_a; q_a_norm)                  # q_lora_rank
    [q_n | q_r]_h  = c_q wq_b                                # heads x (nope | rope)
    [c_kv | k_r]   = a wkv_a ;  c_kv = norm(c_kv; kv_a_norm) # kv_lora_rank | rope
    [k_n | v]_h    = c_kv wkv_b                              # heads x (nope | v)
    s_h            = (q_n.k_n + rope(q_r).rope(k_r)) * scale
    h              = x + concat_h(softmax_causal(s_h) v_h) wo
    y              = h + the expert half of ``moe_lm._moe_block`` (sigmoid
                     scores, a selection bias, the held experts' part, the
                     shared expert once)

THE CACHE ROW is ``[c_kv | rope(k_r)]``, one a token a layer, shared by every
head (``registry.latent_cache_row``): 640 bytes where 32 K/V heads of 128
take 16 KiB. The two forms of the same attention:

* **expanded** (``apply``, a fresh prefill): ``k_n`` and ``v`` are made from
  ``c_kv`` for the tokens at hand and the heads attend through
  ``ops.attention.attention`` like any multi-head layer (head ``nope +
  rope``), so the flash gate takes a long prompt and no ``(heads, S, S)``
  score tensor exists;
* **absorbed** (every forward against a cache): ``wkv_b``'s key half moves to
  the query (``q_n wkv_b,K`` scored against ``c_kv`` itself) and its value
  half to the output (the weighted ``c_kv`` sum times ``wkv_b,V``), so a step
  reads the latent rows and never expands them
  (``ops.attention.paged_latent_attention``).

Rotary frequencies are YaRN's blend (``yarn_inv_freq``); ``scale`` is
``(nope + rope)^-0.5 m^2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1``
(cos and sin unscaled where ``mscale == mscale_all_dim``), and the
position-dependent query factor ``1 + llama4_beta ln(1 + floor(p /
rope_original_max))`` is computed though it is 1 below ``rope_original_max``.
Both multiply the query once, in ``latent_project``.

``n_experts`` is the router's width; ``n_experts_held`` from ``expert_first``
are the experts whose weights this chip has (``ops.moe.moe_experts``'
``held``). With ``n_experts_held == n_experts`` the model is whole.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from tfservingcache_tpu.models.moe_lm import _moe_block
from tfservingcache_tpu.models.real_rows import over_real_rows
from tfservingcache_tpu.models.registry import (
    ModelDef,
    TensorSpec,
    latent_cache_row,
    register,
)
from tfservingcache_tpu.models.transformer_lm import (
    _output_logits,
    _rmsnorm,
    yarn_frequencies,
)
from tfservingcache_tpu.ops.attention import attention

DEFAULT_CONFIG: dict[str, Any] = {
    "vocab_size": 2048,
    "d_model": 256,
    "n_layers": 2,
    "n_heads": 4,
    "q_lora_rank": 64,
    "kv_lora_rank": 32,
    "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 16,
    "v_head_dim": 32,
    "d_ff": 128,             # one routed expert's width
    "d_ff_shared": 128,      # the shared expert's width (0 = none)
    "n_experts": 8,          # the router's width
    "n_experts_held": 8,     # experts whose weights are here ...
    "expert_first": 0,       # ... starting at this one
    "top_k": 2,
    "norm_topk_prob": True,
    "route_score": "sigmoid",
    "route_scale": 1.0,
    "rms_eps": 1e-6,
    "max_seq": 1024,
    "rope_theta": 10000.0,
    "rope_factor": 1.0,      # YaRN; 1 = plain rotary frequencies
    "rope_beta_fast": 32.0,
    "rope_beta_slow": 1.0,
    "rope_original_max": 1024,
    "rope_mscale": 1.0,
    "rope_mscale_all_dim": 0.0,
    "llama4_beta": 0.0,
    "dtype": "bfloat16",
}


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies: plain ``theta^(-2i/d)``
    at ``rope_factor`` 1, else YaRN's blend (``transformer_lm.
    yarn_frequencies``, shared with ``moe_lm``'s global layers)."""
    return yarn_frequencies(
        int(cfg["qk_rope_head_dim"]), cfg["rope_theta"], cfg["rope_factor"],
        cfg["rope_original_max"], cfg["rope_beta_fast"], cfg["rope_beta_slow"])


def softmax_scale(cfg: dict) -> float:
    """``(nope + rope)^-0.5`` alone: what the attention kernels apply. YaRN's
    ``m^2`` rides on the query (``query_factor``)."""
    return 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])


def query_factor(cfg: dict, positions: jax.Array) -> jax.Array:
    """What multiplies a query at ``positions`` -> float32 of their shape:
    YaRN's ``m^2`` (``m = 0.1 mscale_all_dim ln(factor) + 1``) times the
    position-dependent ``1 + llama4_beta ln(1 + floor(p / original_max))``."""
    m = 1.0
    if float(cfg["rope_factor"]) > 1.0 and float(cfg["rope_mscale_all_dim"]):
        m = 0.1 * float(cfg["rope_mscale_all_dim"]) * math.log(
            float(cfg["rope_factor"])) + 1.0
    far = jnp.floor(positions.astype(jnp.float32) / float(cfg["rope_original_max"]))
    return (m * m) * (1.0 + float(cfg["llama4_beta"]) * jnp.log1p(far))


def _rope_rows(x: jax.Array, positions: jax.Array, inv_freq: np.ndarray) -> jax.Array:
    """Rotary embedding of ``x (B, S, ..., D)`` at ``positions (B, S)``,
    interleaved pairs, float32 angles (the same pairing as
    ``generation._rope_per_example``)."""
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    rot = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rot.reshape(x.shape).astype(x.dtype)


def latent_project(attn: dict, a: jax.Array, positions: jax.Array, cfg: dict,
                   took=None):
    """The normed activations ``a (B, S, d)`` at ``positions (B, S)`` ->
    ``q_n (B, S, H, nope)``, ``q_r (B, S, H, rope)`` (rotated; both carry
    ``query_factor``) and the cache rows ``(B, S, W)`` = ``[c_kv | rope(k_r) |
    zeros]`` at the stored width: the projections every forward shares.
    ``took (B,)`` says how many of each example's rows are real (None = all):
    a long prefill projects the row blocks that hold them
    (``over_real_rows``)."""
    h, nope, rope = cfg["n_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_eps"]
    inv_freq = yarn_inv_freq(cfg)

    def queries(a, positions):
        b, s, _ = a.shape
        c_q = _rmsnorm(a @ attn["wq_a"], attn["q_a_norm"], eps)
        q = (c_q @ attn["wq_b"]).reshape(b, s, h, nope + rope)
        q = (q * query_factor(cfg, positions)[:, :, None, None]).astype(a.dtype)
        return q[..., :nope], _rope_rows(q[..., nope:], positions, inv_freq)

    def cache_rows(a, positions):
        b, s, _ = a.shape
        ckr = a @ attn["wkv_a"]                                   # (B, S, rank + rope)
        c_kv = _rmsnorm(ckr[..., :rank], attn["kv_a_norm"], eps)
        k_r = _rope_rows(ckr[..., rank:], positions, inv_freq)
        pad = latent_cache_row(cfg).width - rank - rope
        return jnp.concatenate(
            [c_kv, k_r, jnp.zeros((b, s, pad), a.dtype)], axis=-1)

    with jax.named_scope("q_lora"):
        q_n, q_r = over_real_rows(queries, (a, positions), took)
    with jax.named_scope("kv_lora"):
        rows = over_real_rows(cache_rows, (a, positions), took)
    return q_n, q_r, rows


def expanded_attention(attn: dict, q_n, q_r, rows, cfg: dict,
                       partitioned: bool = False, took=None) -> jax.Array:
    """Causal self-attention among the S tokens at hand in the EXPANDED form
    -> the residual delta ``(B, S, d)``: ``k_n`` and ``v`` made from the rows'
    ``c_kv``, the shared ``rope(k_r)`` given to every head, then
    ``ops.attention.attention`` (the flash kernel where its gate admits).
    The expansion before the kernel and ``wo`` after it follow ``took`` as
    ``latent_project`` does."""
    b, _, h, nope = q_n.shape
    rank, rope, vd = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]

    def expand(q_n, q_r, rows):
        s = rows.shape[1]
        kv = (rows[..., :rank] @ attn["wkv_b"]).reshape(b, s, h, nope + vd)
        k_r = jnp.broadcast_to(rows[:, :, None, rank:rank + rope], (b, s, h, rope))
        q = jnp.concatenate([q_n, q_r], axis=-1).transpose(0, 2, 1, 3)
        k = jnp.concatenate([kv[..., :nope], k_r], axis=-1).transpose(0, 2, 1, 3)
        return q, k, kv[..., nope:].transpose(0, 2, 1, 3)

    def finish(out):
        out = out.transpose(0, 2, 1, 3)
        return out.reshape(b, out.shape[1], h * vd).astype(q_n.dtype) @ attn["wo"]

    q, k, v = over_real_rows(expand, (q_n, q_r, rows), took, out_axis=2)
    out = attention(q, k, v, causal=True, partitioned=partitioned)
    return over_real_rows(finish, (out,), took, in_axis=2)


def absorbed_query(attn: dict, q_n, q_r, cfg: dict) -> jax.Array:
    """-> ``(B, H, S, W)``: each head's query against a latent row, ``q_n
    wkv_b,K`` over the row's ``c_kv`` columns beside ``q_r`` over its
    ``rope(k_r)`` columns, zeros over the pad."""
    b, s, h, nope = q_n.shape
    rank, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    with jax.named_scope("absorb"):
        w_k = attn["wkv_b"].reshape(rank, h, nope + vd)[..., :nope]
        q_c = jnp.einsum("bshn,chn->bhsc", q_n, w_k).astype(q_n.dtype)
        pad = latent_cache_row(cfg).width - rank - q_r.shape[-1]
        return jnp.concatenate(
            [q_c, q_r.transpose(0, 2, 1, 3),
             jnp.zeros((b, h, s, pad), q_n.dtype)], axis=-1)


def absorbed_output(attn: dict, o_lat: jax.Array, cfg: dict, dtype) -> jax.Array:
    """The heads' weighted ``c_kv`` sums ``(B, H, S, rank)`` f32 -> the
    residual delta ``(B, S, d)``: ``wkv_b``'s value half, then ``wo``."""
    b, h, s, rank = o_lat.shape
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    with jax.named_scope("absorb"):
        w_v = attn["wkv_b"].reshape(rank, h, nope + vd)[..., nope:]
        out = jnp.einsum("bhsc,chv->bshv", o_lat.astype(dtype), w_v)
    return out.reshape(b, s, h * vd).astype(dtype) @ attn["wo"]


def dense_absorbed_attention(q_lat: jax.Array, rows: jax.Array,
                             positions: jax.Array, cfg: dict) -> jax.Array:
    """Absorbed attention of ``q_lat (B, H, S, W)`` at ``positions (B, S)``
    over a DENSE latent cache ``rows (B, L, W)`` (row ``l`` is position ``l``)
    -> f32 ``(B, H, S, rank)``: the solo decoder's and the prefix
    continuation's path, einsums with the paged reference's mask."""
    s = jnp.einsum("bhsw,blw->bhsl", q_lat, rows,
                   preferred_element_type=jnp.float32) * softmax_scale(cfg)
    mask = jnp.arange(rows.shape[1])[None, None, :] <= positions[:, :, None]
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), axis=-1)
    return jnp.einsum("bhsl,blc->bhsc", p.astype(rows.dtype),
                      rows[..., :cfg["kv_lora_rank"]],
                      preferred_element_type=jnp.float32)


def _forward(params: dict, input_ids: jax.Array, cfg: dict, mesh=None) -> jax.Array:  # static-bounded: mesh -- one Mesh object per runtime lifetime
    dtype = jnp.dtype(cfg["dtype"])
    b, s = input_ids.shape
    partitioned = mesh is not None and mesh.size > 1
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(dtype)
    for layer in params["layers"]:
        with jax.named_scope("layer"):
            with jax.named_scope("attn"):
                attn = jax.tree_util.tree_map(lambda w: w.astype(dtype), layer["attn"])
                q_n, q_r, rows = latent_project(
                    attn, _rmsnorm(x, layer["ln1"], cfg["rms_eps"]), positions, cfg)
                x = x + expanded_attention(attn, q_n, q_r, rows, cfg, partitioned)
            y, _ = _moe_block(layer, x, cfg, dtype, partitioned=partitioned)
            x = x + y
    return _output_logits(params, x, dtype, cfg["rms_eps"])


@register("mla_moe_lm", DEFAULT_CONFIG)
def build(config: dict) -> ModelDef:
    cfg = config
    if not 0 < cfg["n_experts_held"] <= cfg["n_experts"] - cfg["expert_first"]:
        raise ValueError(
            f"experts {cfg['expert_first']}..+{cfg['n_experts_held']} are not "
            f"inside the router's {cfg['n_experts']}")
    if cfg["v_head_dim"] != cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]:
        raise ValueError(
            "v_head_dim != qk_nope_head_dim + qk_rope_head_dim: the expanded "
            "form goes through ops.attention.attention, which takes one head size")
    if cfg["rope_factor"] > 1.0 and cfg["rope_mscale"] != cfg["rope_mscale_all_dim"]:
        raise ValueError(
            "rope_mscale != rope_mscale_all_dim: cos and sin would carry a "
            "factor of their own, which this family does not compute")

    def make_apply(mesh=None):
        def apply(params, inputs):
            return {"logits": _forward(
                params, inputs["input_ids"].astype(jnp.int32), cfg, mesh)}

        return apply

    def init(rng):
        d, v, ff, e = cfg["d_model"], cfg["vocab_size"], cfg["d_ff"], cfg["n_experts"]
        held, h = cfg["n_experts_held"], cfg["n_heads"]
        nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
        q_rank, rank, ffs = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["d_ff_shared"]
        keys = jax.random.split(rng, cfg["n_layers"] + 2)

        def dense(key, fan_in, shape):
            return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)

        layers = []
        for i in range(cfg["n_layers"]):
            ks = jax.random.split(keys[i], 13)
            moe = {
                "router": dense(ks[5], d, (d, e)),
                # small, so that it changes some selections and not all
                "bias": 0.02 * jax.random.normal(ks[6], (e,), jnp.float32),
                "w1": dense(ks[7], d, (held, d, ff)),
                "w2": dense(ks[8], ff, (held, ff, d)),
                "w3": dense(ks[9], d, (held, d, ff)),
            }
            if ffs:
                moe["shared"] = {"w1": dense(ks[10], d, (d, ffs)),
                                 "w2": dense(ks[11], ffs, (ffs, d)),
                                 "w3": dense(ks[12], d, (d, ffs))}
            layers.append({
                "attn": {
                    "wq_a": dense(ks[0], d, (d, q_rank)),
                    "wq_b": dense(ks[1], q_rank, (q_rank, h * (nope + rope))),
                    "wkv_a": dense(ks[2], d, (d, rank + rope)),
                    "wkv_b": dense(ks[3], rank, (rank, h * (nope + vd))),
                    "wo": dense(ks[4], h * vd, (h * vd, d)),
                    "q_a_norm": jnp.ones((q_rank,), jnp.float32),
                    "kv_a_norm": jnp.ones((rank,), jnp.float32),
                },
                "moe": moe,
                "ln1": jnp.ones((d,), jnp.float32),
                "ln2": jnp.ones((d,), jnp.float32),
            })
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
        family="mla_moe_lm",
        config=cfg,
        apply=make_apply(None),
        init=init,
        input_spec={"input_ids": TensorSpec("int32", ("batch", "seq"))},
        output_spec={"logits": TensorSpec("float32", ("batch", "seq", cfg["vocab_size"]))},
        # one chip's share of a layer is what this family holds: a chip group
        # would shard what is already a shard, so generation on a mesh is
        # refused by name (runtime/model_runtime.py) and nothing is declared
        partition_rules={},
        derived_outputs={
            "last_token_logits": (
                last_token_logits,
                TensorSpec("float32", ("batch", cfg["vocab_size"])),
            )
        },
        default_outputs=["last_token_logits"],
        store_param_dtype=cfg["dtype"],
        bind_mesh=make_apply,
        engine_ready=True,
        cache_row=latent_cache_row(cfg),
    )
