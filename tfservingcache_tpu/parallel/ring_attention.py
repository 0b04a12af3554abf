"""Ring attention: context parallelism for sequences too long for one chip.

First-class by design mandate (no reference counterpart — the reference
never touches tensors). Q/K/V are sharded along the sequence axis across a
mesh axis; each step computes attention of the local Q block against the
currently-held K/V block, then rotates K/V one hop around the ring with
``ppermute`` (ICI neighbor exchange), accumulating an online softmax exactly
like flash attention does across its K blocks. After P steps every Q block
has seen every K/V block while per-chip memory stays O(S/P).

Communication pattern: P-1 ppermute rounds of the K/V shards — bandwidth
equals one all-gather of K/V but overlapped with compute and never
materializing the full sequence on any chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attend(q, k, v, q_off, k_off, causal, acc, m, l):
    """One online-softmax update of (acc, m, l) with a K/V block at global
    offset ``k_off`` against Q at global offset ``q_off``.

    Operands stay in their INPUT dtype for the dots (bf16 runs at full MXU
    rate — upcasting first was the same half-rate mistake as the round-2
    flash kernel); scores/stats accumulate f32 via preferred_element_type,
    exactly the kernel's recipe (ops/attention.py)."""
    d = q.shape[-1]
    s = jax.lax.dot_general(
        q, k, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    ) / math.sqrt(d)                                      # (B, H, Sq, Sk) f32
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    )
    return acc * alpha + pv, m_new, l_new


def _ring_shard_fn(q, k, v, *, axis: str, n_shards: int, causal: bool,  # static-bounded: causal, interpret -- boolean domains
                   impl: str = "xla", interpret: bool = False):
    """Per-shard body under shard_map: local (B, H, S/P, D) blocks. K/V ride
    the ring in their input dtype — rotating bf16 instead of upcast f32
    halves the ppermute bytes on ICI.

    ``impl="flash"`` runs each hop through the Pallas carry kernel
    (ops/attention.flash_attention_carry): the per-hop (Sq/P x Sk/P) f32
    score matrix — 64 MB per head-batch at 4k local — never touches HBM,
    only the O(S/P x D) carry does. ``impl="xla"`` keeps the einsum body
    (the CPU-harness path and the fallback for shapes the kernel rejects)."""
    idx = jax.lax.axis_index(axis)
    s_local = q.shape[2]
    acc = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full(q.shape[:3] + (1,), NEG_INF, jnp.float32)
    l = jnp.zeros(q.shape[:3] + (1,), jnp.float32)
    q_off = idx * s_local

    k_cur, v_cur = k, v
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]
    for step in range(n_shards):
        # after `step` rotations, this chip holds the block that started at
        # ring position (idx - step) mod P
        src = (idx - step) % n_shards
        k_off = src * s_local
        if impl == "flash":
            from tfservingcache_tpu.ops.attention import flash_attention_carry

            acc, m, l = flash_attention_carry(
                q, k_cur, v_cur, acc, m, l, k_off - q_off, causal=causal,
                interpret=interpret,
            )
        else:
            acc, m, l = _block_attend(
                q, k_cur, v_cur, q_off, k_off, causal, acc, m, l
            )
        if step + 1 < n_shards:
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def _pick_impl(impl: str, s_local: int, heads: int, d: int) -> str:
    """"auto": Pallas carry kernel on TPU when the shard shape qualifies
    (128-multiple local seq, MXU-friendly head dim), einsum elsewhere. The
    choice is recorded (ops.attention.dispatch_tally)."""
    if impl != "auto":
        return impl
    from tfservingcache_tpu.ops.attention import (
        _kernel_refusal,
        _record_dispatch,
    )

    why = _kernel_refusal(d, heads, heads)
    if why is None and s_local % 128:
        why = f"local seq {s_local} not a multiple of 128"
    if why is None:
        _record_dispatch("ring_attention", "kernel", "flash_carry",
                         (s_local, heads, d))
        return "flash"
    _record_dispatch("ring_attention", "reference", why, (s_local, heads, d))
    return "xla"


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "causal", "impl", "interpret")
)
def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = "seq",
    causal: bool = True,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """(B, H, S, D) attention with S sharded over ``mesh[axis]``. The full
    sequence never resides on one chip."""
    n_shards = mesh.shape[axis]
    if q.shape[2] % n_shards:
        raise ValueError(f"sequence {q.shape[2]} not divisible by {n_shards} ring shards")
    impl = _pick_impl(impl, q.shape[2] // n_shards, q.shape[1], q.shape[3])
    spec = P(None, None, axis, None)
    fn = jax.shard_map(
        functools.partial(_ring_shard_fn, axis=axis, n_shards=n_shards,
                          causal=causal, impl=impl, interpret=interpret),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # pallas_call out_shapes carry no varying-mesh-axes metadata, which
        # the flash body trips over; in/out specs above are explicit. The
        # einsum path keeps shard_map's validation (ADVICE r4).
        check_vma=(impl != "flash"),
    )
    return fn(q, k, v)
