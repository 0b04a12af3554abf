"""Parallel layer tests on the virtual 8-device CPU mesh (conftest forces
--xla_force_host_platform_device_count=8)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfservingcache_tpu.models.registry import build
from tfservingcache_tpu.ops.attention import attention_reference
from tfservingcache_tpu.parallel.mesh import chip_groups, group_mesh, make_mesh
from tfservingcache_tpu.parallel.ring_attention import ring_attention
from tfservingcache_tpu.parallel.sharding import (
    param_shardings,
    shard_params,
    spec_for,
)

SMALL = {
    "vocab_size": 128,
    "d_model": 64,
    "n_layers": 2,
    "n_heads": 4,
    "n_kv_heads": 4,
    "d_ff": 128,
    "max_seq": 64,
}


def test_make_mesh_and_groups():
    mesh = make_mesh({"data": 2, "model": 4})
    assert mesh.shape == {"data": 2, "model": 4}
    groups = chip_groups(jax.devices(), 4)
    assert len(groups) == 2 and len(groups[0]) == 4
    gm = group_mesh(jax.devices(), 4, 1)
    assert gm.shape == {"model": 4}
    with pytest.raises(ValueError):
        make_mesh({"data": 16})
    with pytest.raises(ValueError):
        chip_groups(jax.devices(), 3)


def test_spec_for_rules_degrade_without_axis():
    from jax.sharding import PartitionSpec as P

    mesh_tp = make_mesh({"model": 8})
    mesh_1 = make_mesh({"model": 1})
    rules = {r"layers/\d+/attn/w[qkv]": (None, "model")}
    assert spec_for("layers/0/attn/wq", rules, mesh_tp) == P(None, "model")
    assert spec_for("layers/0/attn/wq", rules, mesh_1) == P(None, None)
    assert spec_for("unmatched/path", rules, mesh_tp) == P()


def test_transformer_tp_sharded_forward_matches_single_device():
    model = build("transformer_lm", SMALL)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.array([[3, 1, 4, 1, 5, 9, 2, 6]], np.int32)
    expected = np.asarray(model.apply(params, {"input_ids": ids})["logits"])

    mesh = make_mesh({"model": 8})
    sharded = shard_params(params, model.partition_rules, mesh)
    # sanity: the big matmuls really are sharded over 8 devices
    wq = sharded["layers"][0]["attn"]["wq"]
    assert len(wq.sharding.device_set) == 8
    out = jax.jit(model.apply)(sharded, {"input_ids": jnp.asarray(ids)})
    got = np.asarray(out["logits"])
    # bf16 matmuls reduce in a different order across shards; allow bf16-level
    # noise but require near-perfect agreement overall
    np.testing.assert_allclose(got, expected, atol=5e-2, rtol=5e-2)
    corr = np.corrcoef(got.ravel(), expected.ravel())[0, 1]
    assert corr > 0.9999, corr


def test_param_shardings_cover_tree():
    model = build("transformer_lm", SMALL)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh({"model": 8})
    shardings = param_shardings(params, model.partition_rules, mesh)
    n_params = len(jax.tree_util.tree_leaves(params))
    n_shards = len(jax.tree_util.tree_leaves(shardings))
    assert n_params == n_shards


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    mesh = make_mesh({"seq": 8})
    b, h, s, d = 2, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32) for kk in ks)
    ref = attention_reference(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh, axis="seq", causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_flash_impl_matches_reference(causal):
    """The Pallas carry-kernel ring body (impl="flash", interpret on the CPU
    harness) must match the unsharded reference exactly like the einsum body
    does — same online softmax, score matrix never materialized."""
    mesh = make_mesh({"seq": 4})
    b, h, s, d = 1, 2, 4 * 128, 64  # local seq 128: the kernel's minimum
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32) for kk in ks)
    ref = attention_reference(q, k, v, causal=causal)
    out = ring_attention(
        q, k, v, mesh, axis="seq", causal=causal, impl="flash", interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_rejects_indivisible_seq():
    mesh = make_mesh({"seq": 8})
    q = jnp.zeros((1, 1, 60, 16))
    with pytest.raises(ValueError, match="not divisible"):
        ring_attention(q, q, q, mesh)


def test_runtime_serves_tp_sharded_model(tmp_path):
    from tfservingcache_tpu.config import ServingConfig
    from tfservingcache_tpu.models.registry import export_artifact
    from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
    from tfservingcache_tpu.types import Model, ModelId

    export_artifact("transformer_lm", str(tmp_path), name="lm_tp", version=1, config=SMALL)
    mesh = make_mesh({"model": 8})
    rt = TPUModelRuntime(ServingConfig(), mesh=mesh)
    try:
        model = Model(identifier=ModelId("lm_tp", 1), path=str(tmp_path / "lm_tp" / "1"))
        rt.ensure_loaded(model)
        ids = np.array([[3, 1, 4, 1, 5]], np.int32)
        out = rt.predict(model.identifier, {"input_ids": ids}, output_filter=["logits"])
        assert out["logits"].shape == (1, 5, 128)
        assert np.all(np.isfinite(out["logits"]))
    finally:
        rt.close()


# ---------------------------------------------------------------------------
# Expert parallelism (moe_lm) + pipeline parallelism
# ---------------------------------------------------------------------------

MOE_TINY = {
    "vocab_size": 64,
    "d_model": 32,
    "n_layers": 2,
    "n_heads": 4,
    "n_kv_heads": 4,
    "d_ff": 64,
    "n_experts": 4,
    "max_seq": 32,
}


def test_moe_expert_parallel_matches_replicated():
    """data x expert sharded MoE forward == replicated forward; expert
    weights actually land sharded over the expert axis."""
    from tfservingcache_tpu.parallel.sharding import batch_sharding

    model = build("moe_lm", MOE_TINY)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.arange(24, dtype=np.int32).reshape(2, 12) % MOE_TINY["vocab_size"]
    want = np.asarray(model.apply(params, {"input_ids": ids})["logits"])

    mesh = make_mesh({"data": 2, "expert": 4})
    sp = shard_params(params, model.partition_rules, mesh)
    assert "expert" in str(sp["layers"][0]["moe"]["w1"].sharding.spec)
    xs = jax.device_put(ids, batch_sharding(mesh))
    got = np.asarray(
        jax.jit(lambda p, i: model.apply(p, {"input_ids": i}))(sp, xs)["logits"]
    )
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_moe_flooded_expert_drops_nothing():
    """Identical tokens flood one expert: the layer has no capacity, so every
    row is answered alike (what a capacity of 0.1 x tokens / experts used to
    drop rode the residual and answered otherwise), under top-1 and top-2."""
    for top_k in (1, 2):
        model = build("moe_lm", {**MOE_TINY, "top_k": top_k})
        params = model.init(jax.random.PRNGKey(1))
        ids = np.ones((2, 16), np.int32)  # identical tokens -> one expert floods
        x = params["embed"][ids].astype(jnp.bfloat16)
        from tfservingcache_tpu.models.moe_lm import _moe_block

        y, stats = _moe_block(params["layers"][0], x, model.config, jnp.bfloat16)
        assert float(stats["experts_hit"]) == top_k
        assert float(stats["expert_rows_max"]) == ids.size
        y = np.asarray(y, np.float32).reshape(ids.size, -1)
        assert np.all(np.isfinite(y)) and np.abs(y[0]).max() > 0
        np.testing.assert_array_equal(y, np.broadcast_to(y[0], y.shape))


def test_pipeline_matches_sequential_and_grads():
    from tfservingcache_tpu.parallel.pipeline import pipeline_apply, stack_stage_params

    mesh = make_mesh({"stage": 4})
    rng = jax.random.PRNGKey(0)
    dim = 16
    stages = []
    for _ in range(4):
        k1, k2, rng = jax.random.split(rng, 3)
        stages.append(
            {"w": jax.random.normal(k1, (dim, dim)) / 4, "b": jax.random.normal(k2, (dim,)) / 4}
        )
    stacked = stack_stage_params(stages)

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    x = jax.random.normal(rng, (8, dim))
    want = x
    for p in stages:
        want = stage_fn(p, want)

    for n_micro in (4, 8):  # bubble-light and bubble-heavy schedules
        got = pipeline_apply(stage_fn, stacked, x, mesh, n_microbatches=n_micro)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    g = jax.grad(
        lambda sp: jnp.sum(pipeline_apply(stage_fn, sp, x, mesh, n_microbatches=4) ** 2)
    )(stacked)
    assert g["w"].shape == (4, dim, dim)
    assert bool(jnp.all(jnp.isfinite(g["w"])))


def test_pipeline_rejects_indivisible_batch():
    from tfservingcache_tpu.parallel.pipeline import pipeline_apply, stack_stage_params

    mesh = make_mesh({"stage": 4})
    stacked = stack_stage_params([{"w": jnp.eye(4)} for _ in range(4)])
    with pytest.raises(ValueError):
        pipeline_apply(lambda p, x: x @ p["w"], stacked, jnp.ones((6, 4)), mesh, n_microbatches=4)


def test_pipeline_rejects_stage_count_mismatch():
    # 8 stacked stages on a 4-stage mesh would silently run only every other
    # stage if block-sharded — must raise instead
    from tfservingcache_tpu.parallel.pipeline import pipeline_apply, stack_stage_params

    mesh = make_mesh({"stage": 4})
    stacked = stack_stage_params([{"w": jnp.eye(4)} for _ in range(8)])
    with pytest.raises(ValueError, match="mesh stages"):
        pipeline_apply(lambda p, x: x @ p["w"], stacked, jnp.ones((8, 4)), mesh, n_microbatches=4)


def test_ring_attention_serving_path(tmp_path):
    """Long-context config ("attention": "ring") served on an 8-chip group:
    the runtime binds the group mesh into the family's apply, the sequence
    axis rides the ring (weights replicated), and logits match an unsharded
    runtime. A bucket shorter than the ring falls back to regular attention
    and must also match."""
    from tfservingcache_tpu.config import ServingConfig
    from tfservingcache_tpu.models.registry import export_artifact
    from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
    from tfservingcache_tpu.types import Model, ModelId

    cfg = {
        "vocab_size": 128, "d_model": 64, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 4, "d_ff": 128, "max_seq": 128, "dtype": "bfloat16",
        "attention": "ring",
    }
    export_artifact("transformer_lm", str(tmp_path), name="ringlm", version=1,
                    config=cfg)
    mesh = group_mesh(jax.devices()[:8], 8, 0)
    rt_ring = TPUModelRuntime(ServingConfig(), mesh=mesh)
    rt_1 = TPUModelRuntime(ServingConfig())
    try:
        path = str(tmp_path / "ringlm" / "1")
        rt_ring.ensure_loaded(Model(identifier=ModelId("ringlm", 1), path=path))
        rt_1.ensure_loaded(Model(identifier=ModelId("ref", 1), path=path))
        # weights replicated on every group chip (ring owns the axis)
        loaded = rt_ring._resident.get(ModelId("ringlm", 1))
        wq = loaded.params["layers"][0]["attn"]["wq"]
        assert len(wq.sharding.device_set) == 8
        assert wq.sharding.is_fully_replicated
        ids = np.random.default_rng(0).integers(0, 128, (2, 16)).astype(np.int32)
        got = rt_ring.predict(
            ModelId("ringlm", 1), {"input_ids": ids}, output_filter=["logits"]
        )["logits"]
        want = rt_1.predict(
            ModelId("ref", 1), {"input_ids": ids}, output_filter=["logits"]
        )["logits"]
        np.testing.assert_allclose(got, want, atol=5e-2, rtol=5e-2)
        # short-seq fallback (bucket 4 < ring of 8): still correct
        short = ids[:, :3]
        got_s = rt_ring.predict(
            ModelId("ringlm", 1), {"input_ids": short}, output_filter=["logits"]
        )["logits"]
        want_s = rt_1.predict(
            ModelId("ref", 1), {"input_ids": short}, output_filter=["logits"]
        )["logits"]
        np.testing.assert_allclose(got_s, want_s, atol=5e-2, rtol=5e-2)
    finally:
        rt_ring.close()
        rt_1.close()
