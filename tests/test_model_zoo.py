"""Model-family coverage: every registered family inits, applies, losses,
exports, and serves through the runtime (BASELINE.json configs #1-#4)."""

import jax
import numpy as np
import pytest

from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.bert import TINY_CONFIG as BERT_TINY
from tfservingcache_tpu.models.resnet import TINY_CONFIG as RESNET_TINY
from tfservingcache_tpu.models.registry import build, export_artifact, families
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId

LM_TINY = {
    "vocab_size": 128,
    "d_model": 64,
    "n_layers": 2,
    "n_heads": 4,
    "n_kv_heads": 2,
    "d_ff": 128,
    "max_seq": 64,
}

CASES = {
    "half_plus_two": (None, {"x": np.array([2.0], np.float32)}, {"y": np.array([1.0], np.float32)}, None),
    "mnist_cnn": (
        {"width": 8},
        {"image": np.zeros((2, 28, 28, 1), np.float32)},
        {"label": np.array([1, 2], np.int32)},
        None,
    ),
    "bert": (
        BERT_TINY,
        {
            "input_ids": np.array([[1, 2, 3, 0]], np.int32),
            "attention_mask": np.array([[1, 1, 1, 0]], np.int32),
        },
        {"label": np.array([1], np.int32)},
        None,
    ),
    "resnet": (
        RESNET_TINY,
        {"image": np.zeros((1, 32, 32, 3), np.float32)},
        {"label": np.array([3], np.int32)},
        None,
    ),
    "transformer_lm": (
        LM_TINY,
        {"input_ids": np.array([[1, 2, 3]], np.int32)},
        {"labels": np.array([[1, 2, 3]], np.int32)},
        None,
    ),
    "moe_lm": (
        {**LM_TINY, "n_experts": 4, "top_k": 2},
        {"input_ids": np.array([[1, 2, 3, 4]], np.int32)},
        {"labels": np.array([[1, 2, 3, 4]], np.int32)},
        None,
    ),
}


def test_registry_lists_all_families():
    assert set(CASES) <= set(families())


@pytest.mark.parametrize("family", sorted(CASES))
def test_family_apply_and_loss(family):
    config, inputs, targets, _ = CASES[family]
    model = build(family, config)
    params = model.init(jax.random.PRNGKey(0))
    out = model.apply(params, inputs)
    assert set(out) == set(model.output_spec)
    for name, arr in out.items():
        assert np.all(np.isfinite(np.asarray(arr, np.float32))), name
    assert model.loss is not None
    loss = model.loss(params, inputs, targets)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("family", ["bert", "resnet"])
def test_family_serves_through_runtime(family, tmp_path):
    config, inputs, _, _ = CASES[family]
    export_artifact(family, str(tmp_path), name=f"{family}_t", version=1, config=config)
    rt = TPUModelRuntime(ServingConfig())
    try:
        model = Model(
            identifier=ModelId(f"{family}_t", 1), path=str(tmp_path / f"{family}_t" / "1")
        )
        rt.ensure_loaded(model)
        out = rt.predict(model.identifier, inputs)
        assert "logits" in out
    finally:
        rt.close()


def test_bert_mask_respected():
    # padding tokens must not change the [CLS] logits (mask additive -inf)
    model = build("bert", BERT_TINY)
    params = model.init(jax.random.PRNGKey(0))
    ids1 = {"input_ids": np.array([[5, 6, 7]], np.int32), "attention_mask": np.ones((1, 3), np.int32)}
    ids2 = {
        "input_ids": np.array([[5, 6, 7, 99, 42]], np.int32),
        "attention_mask": np.array([[1, 1, 1, 0, 0]], np.int32),
    }
    l1 = np.asarray(model.apply(params, ids1)["logits"])
    l2 = np.asarray(model.apply(params, ids2)["logits"])
    np.testing.assert_allclose(l1, l2, atol=2e-2, rtol=2e-2)


def test_t5_family_and_independent_seq_buckets(tmp_path):
    from tfservingcache_tpu.models.t5 import TINY_CONFIG as T5_TINY

    export_artifact("t5", str(tmp_path), name="t5t", version=1, config=T5_TINY)
    rt = TPUModelRuntime(ServingConfig())
    try:
        model = Model(identifier=ModelId("t5t", 1), path=str(tmp_path / "t5t" / "1"))
        rt.ensure_loaded(model)
        out = rt.predict(
            model.identifier,
            {
                "input_ids": np.ones((1, 7), np.int32),      # src=7 -> bucket 8
                "decoder_input_ids": np.ones((1, 3), np.int32),  # tgt=3 -> bucket 4
            },
        )
        assert out["logits"].shape == (1, 3, 256)  # tgt length, not src
    finally:
        rt.close()


def test_t5_padding_does_not_change_valid_logits():
    from tfservingcache_tpu.models.t5 import TINY_CONFIG as T5_TINY

    model = build("t5", T5_TINY)
    params = model.init(jax.random.PRNGKey(0))
    short = {
        "input_ids": np.array([[5, 6, 7]], np.int32),
        "decoder_input_ids": np.array([[9, 8]], np.int32),
    }
    padded = {
        "input_ids": np.array([[5, 6, 7, 0, 0]], np.int32),      # 0 = pad token
        "decoder_input_ids": np.array([[9, 8, 0, 0]], np.int32),
    }
    l_short = np.asarray(model.apply(params, short)["logits"])
    l_pad = np.asarray(model.apply(params, padded)["logits"])
    np.testing.assert_allclose(l_short[0], l_pad[0, :2], atol=2e-2, rtol=2e-2)


def test_bert_rejects_overlong_sequence():
    model = build("bert", BERT_TINY)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="exceeds max_seq"):
        model.apply(
            params,
            {
                "input_ids": np.ones((1, 70), np.int32),
                "attention_mask": np.ones((1, 70), np.int32),
            },
        )


def test_bert_non_power_of_two_max_seq_served(tmp_path):
    """The runtime's power-of-two bucket padding must clamp at BERT's pos-table
    cap (ModelDef.axis_caps): with max_seq=48, a 40-token request pads to 48
    (not 64, which the forward pass would reject), and a 50-token request gets
    a clear error instead of confident garbage."""
    from tfservingcache_tpu.runtime.base import RuntimeError_

    cfg = dict(BERT_TINY, max_seq=48)
    export_artifact("bert", str(tmp_path), name="b48", version=1, config=cfg)
    rt = TPUModelRuntime(ServingConfig())
    try:
        model = Model(identifier=ModelId("b48", 1), path=str(tmp_path / "b48" / "1"))
        rt.ensure_loaded(model)
        out = rt.predict(
            model.identifier,
            {
                "input_ids": np.ones((1, 40), np.int32),
                "attention_mask": np.ones((1, 40), np.int32),
            },
        )
        assert out["logits"].shape[0] == 1
        with pytest.raises(RuntimeError_, match="exceeds this model's maximum"):
            rt.predict(
                model.identifier,
                {
                    "input_ids": np.ones((1, 50), np.int32),
                    "attention_mask": np.ones((1, 50), np.int32),
                },
            )
    finally:
        rt.close()


def test_artifact_v2_roundtrip_and_v1_compat(tmp_path):
    """tpusc.v2 packed artifacts round-trip exactly (zero-copy manifest
    views), legacy tpusc.v1 msgpack artifacts stay readable, and a corrupt
    manifest is rejected loudly."""
    import json
    import os

    import jax
    from flax import serialization

    from tfservingcache_tpu.models.registry import (
        ARTIFACT_FORMAT,
        MODEL_JSON,
        PARAMS_FILE,
        ArtifactError,
        build,
        load_artifact,
        save_artifact,
    )

    cfg = {"vocab_size": 64, "d_model": 32, "n_layers": 2, "n_heads": 2,
           "n_kv_heads": 1, "d_ff": 64, "max_seq": 32, "dtype": "bfloat16"}
    model = build("transformer_lm", cfg)
    params = jax.device_get(model.init(jax.random.PRNGKey(0)))
    dest = str(tmp_path / "m" / "1")
    save_artifact(dest, model, params)
    meta = json.load(open(os.path.join(dest, MODEL_JSON)))
    assert meta["format"] == ARTIFACT_FORMAT == "tpusc.v2"
    assert os.path.exists(os.path.join(dest, "params.bin"))
    md, loaded = load_artifact(dest)
    # bf16 cast applied at save; structure (incl. list-of-layers) restored
    assert isinstance(loaded["layers"], list) and len(loaded["layers"]) == 2
    want = jax.tree_util.tree_map(
        lambda x: np.asarray(x).astype(np.asarray(x).dtype), params
    )
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_flatten_with_path(loaded)[0][:4],
        jax.tree_util.tree_flatten_with_path(want)[0][:4],
    ):
        assert np.asarray(a).shape == np.asarray(b).shape

    # v1 msgpack artifact still loads
    dest1 = str(tmp_path / "old" / "1")
    os.makedirs(dest1)
    json.dump(
        {"format": "tpusc.v1", "family": "transformer_lm", "config": cfg},
        open(os.path.join(dest1, MODEL_JSON), "w"),
    )
    with open(os.path.join(dest1, PARAMS_FILE), "wb") as f:
        f.write(serialization.to_bytes(params))
    _, old = load_artifact(dest1)
    assert isinstance(old["layers"], list) and len(old["layers"]) == 2

    # corrupt manifest -> ArtifactError, not garbage params
    meta["params"]["manifest"][0]["nbytes"] += 1
    json.dump(meta, open(os.path.join(dest, MODEL_JSON), "w"))
    with pytest.raises(ArtifactError, match="corrupt"):
        load_artifact(dest)
