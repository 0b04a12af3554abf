"""The ``transformer_lm`` family: dense Llama-style decoders (Mistral,
SmolLM2) as the program's ``models/transformer_lm`` runs them.

A family is what every configuration of one architecture shares, in one
file the harness finds by the configuration's ``family`` key:

* ``PROGRAM_FAMILY``: the name the program's model registry builds;
* ``program_config(config)``: the configuration file's published keys -> the
  program's config for that family. Nothing is changed here: what the file
  says is what runs;
* ``leaf_shapes(mc)``, ``to_tree(mc, stacked)``, ``param_bytes(mc)``: the
  weights ``weights.py`` makes on the device from the seed, and the params
  pytree the program's loader expects;
* ``logits_many(mc, tree, sequences, last)``: the plain reference.

The plain reference is a dense decoder's forward pass in float32:
straightforward ``jax.numpy``, no cache, no kernels, no batching, under
``jax.default_matmul_precision("highest")`` (a float32 matmul on the TPU runs
in lower precision otherwise). It follows the published description of the
Mistral / SmolLM2 (Llama-style) block: RMSNorm -> grouped-query attention
with rotary embeddings -> residual -> RMSNorm -> SwiGLU MLP -> residual; a
final RMSNorm; logits against the output head.

Two departures from the published models, both shared with the program and
listed under ``departures`` in each configuration file:

* the output head is the embedding matrix (the program has no separate head;
  SmolLM2 ties its head as published, Mistral-7B does not);
* rotary pairs are interleaved ``(x[2i], x[2i+1])`` where the published code
  pairs ``(x[i], x[i + d/2])``: a fixed permutation of each head's columns,
  which random weights cannot tell apart.

The weights are the host copy the benchmark wrote the artifact from, one
layer at a time, so the reference needs a layer's float32 copy on the device
and no more. ``mc`` is the program's ``transformer_lm`` config.
"""

from __future__ import annotations

import functools
from typing import Any

import numpy as np

PROGRAM_FAMILY = "transformer_lm"
ALIGN = 16          # the artifact format's leaf alignment (weights.py)


def program_config(config: dict) -> dict:
    if config["hidden_size"] // config["num_attention_heads"] != config["head_dim"]:
        raise ValueError("head_dim != hidden_size / num_attention_heads: "
                         "the program derives the head size from the two")
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "d_ff": config["intermediate_size"],
        "max_seq": config["max_position_embeddings"],
        "rope_theta": float(config["rope_theta"]),
        "dtype": config["torch_dtype"],
    }


# -- the weights ------------------------------------------------------------

def leaf_shapes(mc: dict[str, Any]) -> dict[str, tuple[tuple[int, ...], int]]:
    """Stacked leaves -> (shape with the layer axis first, fan_in). ``mc`` is
    the program's ``transformer_lm`` config."""
    d, v, ff, n = mc["d_model"], mc["vocab_size"], mc["d_ff"], mc["n_layers"]
    hd = d // mc["n_heads"]
    q, kv = mc["n_heads"] * hd, mc["n_kv_heads"] * hd
    return {
        "embed": ((v, d), d),
        "attn/wq": ((n, d, q), d), "attn/wk": ((n, d, kv), d),
        "attn/wv": ((n, d, kv), d), "attn/wo": ((n, q, d), q),
        "mlp/w1": ((n, d, ff), d), "mlp/w2": ((n, ff, d), ff),
        "mlp/w3": ((n, d, ff), d),
    }


def param_bytes(mc: dict[str, Any]) -> int:
    """Bytes of one tenant's params.bin (bf16 matrices, f32 gains)."""
    import jax.numpy as jnp

    item = jnp.dtype(mc["dtype"]).itemsize
    mats = sum(int(np.prod(s)) for s, _ in leaf_shapes(mc).values())
    gains = (2 * mc["n_layers"] + 1) * mc["d_model"]
    return mats * item + gains * 4 + ALIGN * (9 * mc["n_layers"] + 2)


def to_tree(mc: dict[str, Any], stacked: dict[str, np.ndarray]) -> dict:
    """Host arrays -> the program's params pytree (views, no copy)."""
    d, n = mc["d_model"], mc["n_layers"]
    ones = np.ones((d,), np.float32)
    layers = [{
        "attn": {w: stacked[f"attn/{w}"][i] for w in ("wq", "wk", "wv", "wo")},
        "mlp": {w: stacked[f"mlp/{w}"][i] for w in ("w1", "w2", "w3")},
        "ln1": ones, "ln2": ones,
    } for i in range(n)]
    return {"embed": stacked["embed"], "layers": layers, "ln_f": ones}


# -- the plain reference ------------------------------------------------------

RMS_EPS = 1e-5


def _rmsnorm(x, gain):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS)) * gain


def _rope(x, theta: float):
    """(S, H, D) -> rotated, interleaved pairs, positions 0..S-1."""
    import jax.numpy as jnp

    s, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)


@functools.lru_cache(maxsize=8)
def _layer_fn(n_heads: int, n_kv: int, theta: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def layer(x, attn, mlp, ln1, ln2):
        f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
        s, d = x.shape
        hd = d // n_heads
        h = _rmsnorm(x, f32(ln1))
        q = _rope((h @ f32(attn["wq"])).reshape(s, n_heads, hd), theta)
        k = _rope((h @ f32(attn["wk"])).reshape(s, n_kv, hd), theta)
        v = (h @ f32(attn["wv"])).reshape(s, n_kv, hd)
        g = n_heads // n_kv
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
        causal = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        x = x + jnp.einsum("hqk,khd->qhd", p, v).reshape(s, d) @ f32(attn["wo"])
        h = _rmsnorm(x, f32(ln2))
        return x + (jax.nn.silu(h @ f32(mlp["w1"])) * (h @ f32(mlp["w3"]))) @ f32(mlp["w2"])

    @jax.jit
    def head(x, ln_f, embed):
        return _rmsnorm(x, ln_f.astype(jnp.float32)) @ embed.astype(jnp.float32).T

    return layer, head


def logits_many(mc: dict[str, Any], tree: dict, sequences,
                last: int = 1) -> list[np.ndarray]:
    """float32 logits ``(last, vocab)`` of the final ``last`` positions of
    each sequence; ``tree`` is the params pytree of host arrays. Layers are
    the outer loop, so each layer's weights cross to the device once."""
    import jax
    import jax.numpy as jnp

    layer, head = _layer_fn(int(mc["n_heads"]), int(mc["n_kv_heads"]),
                            float(mc["rope_theta"]))
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(tree["embed"])
        xs = [embed[np.asarray(ids, np.int32)].astype(jnp.float32)
              for ids in sequences]
        for lp in tree["layers"]:
            dev = jax.device_put((lp["attn"], lp["mlp"], lp["ln1"], lp["ln2"]))
            xs = [layer(x, *dev) for x in xs]
        ln_f = jnp.asarray(tree["ln_f"])
        return [np.asarray(head(x[-last:], ln_f, embed)) for x in xs]
