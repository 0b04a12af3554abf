"""Flash-attention kernel vs jnp reference, in Pallas interpret mode on CPU
(the same kernel compiles natively on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfservingcache_tpu.ops.attention import attention_reference, flash_attention


def rand_qkv(b, h, s, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, h, s, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s", [128, 256])
def test_flash_matches_reference(causal, s):
    q, k, v = rand_qkv(2, 3, s, 64)
    ref = attention_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_padded_seq():
    # S=160 pads to 256 internally; padded keys must not leak into softmax
    q, k, v = rand_qkv(1, 2, 160, 64, seed=1)
    for causal in (True, False):
        ref = attention_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_bf16():
    q, k, v = rand_qkv(1, 2, 128, 64, dtype=jnp.bfloat16, seed=2)
    ref = attention_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2, rtol=2e-2
    )


def _repeat_kv(k, v, g):
    return jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 1), (6, 3)])
def test_gqa_grouped_kv_matches_materialized_repeat(causal, hq, hkv):
    # GQA-native paths (reference einsum grouping + flash index-map) must
    # equal the naive repeat-K/V-to-full-heads computation exactly
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, hq, 128, 64))
    k = jax.random.normal(ks[1], (2, hkv, 128, 64))
    v = jax.random.normal(ks[2], (2, hkv, 128, 64))
    kr, vr = _repeat_kv(k, v, hq // hkv)
    want = attention_reference(q, kr, vr, causal=causal)
    got_ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want), atol=2e-5, rtol=2e-5)
    got_flash = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(got_flash), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_gqa_rejects_non_divisible_heads():
    q, k, v = rand_qkv(1, 3, 128, 64)
    k2, v2 = k[:, :2], v[:, :2]
    with pytest.raises(ValueError, match="multiple"):
        attention_reference(q, k2, v2)
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, k2, v2, interpret=True)


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)",
)
@pytest.mark.parametrize(
    "b,hq,hkv,s,d",
    [
        (4, 8, 4, 1024, 64),      # bench preset shape (GQA)
        (4, 32, 32, 2048, 128),   # llama-7b-class MHA shape
    ],
)
def test_flash_kernel_compiles_and_wins_on_tpu(b, hq, hkv, s, d):
    """Hardware proof for the Pallas kernel: compiles interpret=False,
    matches the jnp reference, and beats it at LM-serving shapes. Timing is
    chained on-device (utils/benchtime.py)."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)  # interpret=False: real Mosaic compile
    ref = attention_reference(q, k, v, causal=True)
    # error reduced ON DEVICE: no need to ship two full (B,H,S,D) tensors
    # to the host for one scalar
    err = float(
        jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    )
    assert err < 3e-2, f"flash kernel diverges from reference: max abs err {err}"

    t_flash = chained_device_time(
        lambda q, k, v: flash_attention(q, k, v, causal=True), (q, k, v)
    )
    t_ref = chained_device_time(
        lambda q, k, v: attention_reference(q, k, v, causal=True), (q, k, v)
    )
    # causal flash does ~half the full score matrix: 2 dots x (S*S/2) x D
    flops = 2 * 2 * b * hq * (s * s / 2) * d
    print(
        f"\n[kernel] shape b={b} hq={hq} hkv={hkv} s={s} d={d}: "
        f"flash {t_flash*1e3:.3f} ms ({flops/t_flash/1e12:.1f} TF/s), "
        f"jnp {t_ref*1e3:.3f} ms, speedup {t_ref/t_flash:.2f}x, "
        f"max_abs_err {err:.4f}",
        flush=True,
    )
    assert t_flash < t_ref, (
        f"flash {t_flash*1e3:.2f}ms not faster than jnp {t_ref*1e3:.2f}ms "
        f"at {(b, hq, hkv, s, d)}"
    )


@pytest.fixture
def force_streamed(monkeypatch):
    """Drop the resident-K/V limit to 0 so every shape takes the streamed
    3D-grid kernel (real long-context shapes are too slow for interpret
    mode; parity at small S covers the same code path)."""
    from tfservingcache_tpu.ops import attention as att

    monkeypatch.setattr(att, "KV_RESIDENT_LIMIT_BYTES", 0)
    # the jit cache keys on static args only — the limit is read at trace
    # time, so stale traces of the resident variant must be dropped
    att.flash_attention.clear_cache()
    yield
    att.flash_attention.clear_cache()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s", [256, 512])
def test_streamed_matches_reference(force_streamed, causal, s):
    q, k, v = rand_qkv(1, 2, s, 64, seed=4)
    ref = attention_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_streamed_gqa_and_padding(force_streamed):
    # GQA K/V index map + non-block-multiple S (320 pads; padded keys must
    # not leak) through the streamed kernel
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (2, 4, 320, 64))
    k = jax.random.normal(ks[1], (2, 2, 320, 64))
    v = jax.random.normal(ks[2], (2, 2, 320, 64))
    for causal in (True, False):
        ref = attention_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )


def test_long_context_dispatches_streamed():
    """No shape may reach pallas_call with K/V rows exceeding VMEM (VERDICT
    r3 next #5): the ring-servable lengths must select the streamed kernel,
    the hardware-proven serving shapes must keep the resident one."""
    from tfservingcache_tpu.ops.attention import (
        KV_RESIDENT_LIMIT_BYTES,
        flash_variant,
    )

    # proven serving shapes stay on the resident kernel
    assert flash_variant(1024, 64, 2) == "resident"
    assert flash_variant(2048, 128, 2) == "resident"
    # long-context: S=16k at d=128 bf16 is 8 MiB K+V — over any sane VMEM
    # budget — and must stream; same at f32 and at 64k
    assert flash_variant(16384, 128, 2) == "streamed"
    assert flash_variant(16384, 128, 4) == "streamed"
    assert flash_variant(65536, 128, 2) == "streamed"
    # the resident limit itself keeps K+V + double-buffering well under the
    # ~16 MiB/core VMEM (pallas_guide.md)
    assert KV_RESIDENT_LIMIT_BYTES * 2 <= 12 << 20


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)",
)
def test_streamed_kernel_on_tpu(monkeypatch):
    """Hardware proof for the streamed (long-context) kernel: Mosaic-compiles,
    matches the jnp reference when forced at a serving shape, and runs a real
    S=16k causal attention — a length whose K/V rows could never fit the
    resident kernel's VMEM layout."""
    from tfservingcache_tpu.ops import attention as att
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    # parity first: force streaming at a shape the reference can check
    monkeypatch.setattr(att, "KV_RESIDENT_LIMIT_BYTES", 0)
    att.flash_attention.clear_cache()
    try:
        ks = jax.random.split(jax.random.PRNGKey(11), 3)
        q = jax.random.normal(ks[0], (2, 8, 2048, 128), jnp.bfloat16)
        k = jax.random.normal(ks[1], (2, 8, 2048, 128), jnp.bfloat16)
        v = jax.random.normal(ks[2], (2, 8, 2048, 128), jnp.bfloat16)
        out = att.flash_attention(q, k, v, causal=True)
        ref = attention_reference(q, k, v, causal=True)
        err = float(
            jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
        )
        assert err < 3e-2, f"streamed kernel diverges: max abs err {err}"
    finally:
        monkeypatch.undo()
        att.flash_attention.clear_cache()

    # long-context: S=16k dispatches streamed by size (no forcing) and runs
    b, h, s, d = 1, 4, 16384, 128
    assert att.flash_variant(s, d, 2) == "streamed"
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.bfloat16)
    out = att.flash_attention(q, k, v, causal=True)
    mx = float(jnp.max(jnp.abs(out.astype(jnp.float32))))
    assert 0.0 < mx < 1e3, f"S=16k output not finite/sane: max abs {mx}"
    t = chained_device_time(
        lambda q, k, v: att.flash_attention(q, k, v, causal=True), (q, k, v)
    )
    flops = 2 * 2 * b * h * (s * s / 2) * d
    print(
        f"\n[kernel] streamed long-context b={b} h={h} s={s} d={d}: "
        f"{t*1e3:.3f} ms ({flops/t/1e12:.1f} TF/s)",
        flush=True,
    )


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)",
)
def test_carry_kernel_on_tpu():
    """Hardware proof for the ring-attention carry kernel: Mosaic-compiles
    (SMEM rel scalar + lane-1 stat blocks are the risky layouts), and
    chaining it over K/V chunks reproduces full attention at a serving
    shape."""
    from tfservingcache_tpu.ops.attention import NEG_INF, flash_attention_carry
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    b, h, s, d = 2, 8, 2048, 128
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.bfloat16)
    chunks = 4
    sl = s // chunks
    acc = jnp.zeros((b, h, s, d), jnp.float32)
    m = jnp.full((b, h, s, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s, 1), jnp.float32)
    for step in range(chunks):
        acc, m, l = flash_attention_carry(
            q, k[:, :, step * sl:(step + 1) * sl],
            v[:, :, step * sl:(step + 1) * sl],
            acc, m, l, step * sl, causal=True,
        )
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    ref = attention_reference(q, k, v, causal=True)
    err = float(
        jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    )
    assert err < 3e-2, f"carry kernel chain diverges: max abs err {err}"

    def chain(q, kk, vv):
        acc = jnp.zeros((b, h, s, d), jnp.float32)
        m = jnp.full((b, h, s, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((b, h, s, 1), jnp.float32)
        for step in range(chunks):
            acc, m, l = flash_attention_carry(
                q, kk[:, :, step * sl:(step + 1) * sl],
                vv[:, :, step * sl:(step + 1) * sl],
                acc, m, l, step * sl, causal=True,
            )
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    t = chained_device_time(chain, (q, k, v))
    flops = 2 * 2 * b * h * (s * s / 2) * d
    print(
        f"\n[kernel] carry chain b={b} h={h} s={s} d={d} chunks={chunks}: "
        f"{t*1e3:.3f} ms ({flops/t/1e12:.1f} TF/s)",
        flush=True,
    )


@pytest.mark.parametrize("causal", [True, False])
def test_carry_kernel_chained_matches_reference(causal):
    """flash_attention_carry chained over ring-style K/V chunks must equal
    full attention — the invariant ring_attention's flash impl rests on."""
    from tfservingcache_tpu.ops.attention import NEG_INF, flash_attention_carry

    b, h, s, d = 1, 2, 512, 64
    q, k, v = rand_qkv(b, h, s, d, seed=5)
    ref = attention_reference(q, k, v, causal=causal)
    chunks = 4
    sl = s // chunks
    acc = jnp.zeros((b, h, s, d), jnp.float32)
    m = jnp.full((b, h, s, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s, 1), jnp.float32)
    for step in range(chunks):
        acc, m, l = flash_attention_carry(
            q, k[:, :, step * sl:(step + 1) * sl],
            v[:, :, step * sl:(step + 1) * sl],
            acc, m, l, step * sl, causal=causal, interpret=True,
        )
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_carry_kernel_future_block_is_noop():
    """A fully-masked (future) causal block must leave the carry EXACTLY
    unchanged — exp(NEG_INF - NEG_INF) would otherwise corrupt l/acc when
    the carry is still at its initial state."""
    from tfservingcache_tpu.ops.attention import NEG_INF, flash_attention_carry

    b, h, s, d = 1, 2, 256, 64
    q, k, v = rand_qkv(b, h, s, d, seed=6)
    acc = jnp.zeros((b, h, s, d), jnp.float32)
    m = jnp.full((b, h, s, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s, 1), jnp.float32)
    acc2, m2, l2 = flash_attention_carry(
        q, k[:, :, :128], v[:, :, :128], acc, m, l, s + 128, causal=True,
        interpret=True,
    )
    assert float(jnp.max(jnp.abs(acc2))) == 0.0
    assert float(jnp.max(jnp.abs(l2))) == 0.0


def test_flash_uneven_blocks():
    # block_k not dividing block_q's padding: lcm padding keeps both exact
    q, k, v = rand_qkv(1, 2, 128, 64, seed=3)
    ref = attention_reference(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False, block_q=128, block_k=48, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
