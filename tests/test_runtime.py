"""TPUModelRuntime tests on the CPU backend (jit semantics identical; the
virtual 8-device mesh from conftest covers sharding elsewhere)."""

import numpy as np
import pytest

from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import export_artifact
from tfservingcache_tpu.runtime.base import ModelNotLoadedError, RuntimeError_
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime, next_bucket
from tfservingcache_tpu.types import Model, ModelId, ModelState
from tfservingcache_tpu.utils.metrics import Metrics


def make_model(tmp_path, family="half_plus_two", name=None, version=1, config=None):
    name = name or family
    path = export_artifact(family, str(tmp_path), name=name, version=version, config=config)
    return Model(identifier=ModelId(name, version), path=path, size_on_disk=1000)


@pytest.fixture(scope="module")
def runtime():
    rt = TPUModelRuntime(ServingConfig(hbm_capacity_bytes=1 << 30), Metrics())
    yield rt
    rt.close()


def test_cli_export_config_json(tmp_path, monkeypatch):
    """`tpuserve export --config-json` merges overrides over the family's
    defaults (and a custom seed varies the init) — users export custom-sized
    artifacts without writing Python."""
    import json

    from tfservingcache_tpu.cli import main as cli_main

    monkeypatch.setenv("TPUSC_SERVING_PLATFORM", "cpu")
    assert cli_main([
        "export", "transformer_lm", str(tmp_path), "--name", "x",
        "--seed", "3",
        "--config-json", '{"d_model": 128, "n_layers": 1, "vocab_size": 256}',
    ]) == 0
    with open(tmp_path / "x" / "1" / "model.json") as f:
        cfg = json.load(f)["config"]
    assert cfg["d_model"] == 128 and cfg["n_layers"] == 1
    assert cfg["n_heads"] == 8  # untouched default survives the merge
    assert cli_main(["export", "transformer_lm", str(tmp_path),
                     "--config-json", "notjson"]) == 2


def test_cold_stage_histograms_recorded(tmp_path):
    """Every cold load feeds tpusc_cold_stage_seconds{stage} — operators
    answer 'where do my cold seconds go' (and the int8 crossover) from
    /metrics instead of re-running under a profiler."""
    from tfservingcache_tpu.cache.disk_cache import ModelDiskCache
    from tfservingcache_tpu.cache.manager import CacheManager
    from tfservingcache_tpu.cache.providers.disk import DiskModelProvider
    from tfservingcache_tpu.models.registry import export_artifact
    from tfservingcache_tpu.utils.metrics import Metrics

    export_artifact("half_plus_two", str(tmp_path / "store"), name="m",
                    version=1)
    metrics = Metrics()
    rt = TPUModelRuntime(ServingConfig(), metrics=metrics)
    mgr = CacheManager(
        DiskModelProvider(str(tmp_path / "store")),
        ModelDiskCache(str(tmp_path / "cache"), capacity_bytes=1 << 30),
        rt, metrics,
    )
    try:
        mgr.ensure_servable(ModelId("m", 1))
        text = metrics.render().decode()
        for stage in ("provider_fetch", "artifact_read", "device_transfer",
                      "compile_warmup"):
            line = next(
                (ln for ln in text.splitlines()
                 if ln.startswith("tpusc_cold_stage_seconds_count")
                 and f'stage="{stage}"' in ln), None,
            )
            assert line is not None and float(line.split()[-1]) >= 1.0, stage
    finally:
        mgr.close()


def test_cli_warm_populates_compile_cache(tmp_path, monkeypatch):
    """`tpuserve warm <artifact>` compiles the serving programs through the
    real runtime and persists them in the compile cache directory — the deploy
    image bake step that turns a node's first cold load into a compile-cache
    hit (SURVEY §7 hard part (a))."""
    import os

    from tfservingcache_tpu.cli import main as cli_main
    from tfservingcache_tpu.models.registry import export_artifact

    import jax

    art = export_artifact("transformer_lm", str(tmp_path / "store"),
                          name="lm", version=1, config={
                              "vocab_size": 64, "d_model": 32, "n_layers": 1,
                              "n_heads": 2, "n_kv_heads": 1, "d_ff": 64,
                              "max_seq": 64, "dtype": "float32"})
    cache_dir = tmp_path / "xla-cache"
    monkeypatch.setenv("TPUSC_SERVING_COMPILE_CACHE_DIR", str(cache_dir))
    monkeypatch.setenv("TPUSC_SERVING_PLATFORM", "cpu")
    prior_cache_dir = jax.config.jax_compilation_cache_dir
    # jax initializes the persistent compilation cache AT MOST ONCE per
    # process: if any earlier test compiled with a cache dir configured,
    # this test's fresh dir would silently never receive entries (order-
    # dependent flake). Reset to pristine so warm's dir takes effect.
    from jax._src import compilation_cache as _cc

    _cc.reset_cache()
    try:
        # defaults (128/32) exceed max_seq 64: warm must CLAMP, not crash
        assert cli_main(["warm", art, "--batches", "1,2"]) == 0
        # the persistent cache holds compiled programs for serve to re-hit
        entries = [
            f for f in os.listdir(cache_dir) if not f.startswith(".")
        ] if cache_dir.exists() else []
        assert entries, "compile cache dir is empty after warm"
        # no cache dir configured -> the fixed default directory
        # (utils/compile_cache.py), never "no cache" and never a temp name
        from tfservingcache_tpu.utils import compile_cache

        default_dir = tmp_path / "default-cache"
        monkeypatch.setattr(compile_cache, "DEFAULT_DIR", str(default_dir))
        monkeypatch.setenv("TPUSC_SERVING_COMPILE_CACHE_DIR", "")
        assert cli_main(["warm", art, "--batches", "1"]) == 0
        assert jax.config.jax_compilation_cache_dir == str(default_dir)
    finally:
        # warm flips the PROCESS-GLOBAL jax compilation cache dir;
        # later tests' cold-compile behavior must not depend on this tmp dir
        jax.config.update("jax_compilation_cache_dir", prior_cache_dir)
        _cc.reset_cache()  # un-pin the tmp dir for later tests too


def test_next_bucket():
    assert [next_bucket(n) for n in (0, 1, 2, 3, 4, 5, 8, 9, 100)] == [
        1, 1, 2, 4, 4, 8, 8, 16, 128,
    ]


def test_load_predict_half_plus_two(runtime, tmp_path):
    model = make_model(tmp_path)
    runtime.ensure_loaded(model)
    assert runtime.state(model.identifier) == ModelState.AVAILABLE
    out = runtime.predict(model.identifier, {"x": np.array([1.0, 2.0, 5.0], np.float32)})
    np.testing.assert_allclose(out["y"], [2.5, 3.0, 4.5])
    # odd batch sizes exercise pad/slice (bucket=4 here)
    assert out["y"].shape == (3,)


def test_predict_input_validation(runtime, tmp_path):
    model = make_model(tmp_path, name="hpt_val")
    runtime.ensure_loaded(model)
    with pytest.raises(RuntimeError_, match="missing inputs"):
        runtime.predict(model.identifier, {})
    with pytest.raises(RuntimeError_, match="unknown inputs"):
        runtime.predict(model.identifier, {"x": np.ones(1, np.float32), "zz": np.ones(1)})
    with pytest.raises(ModelNotLoadedError):
        runtime.predict(ModelId("ghost", 1), {"x": np.ones(1, np.float32)})


def test_output_filter(runtime, tmp_path):
    model = make_model(tmp_path, family="mnist_cnn", name="mn1")
    runtime.ensure_loaded(model)
    img = np.random.default_rng(0).normal(size=(2, 28, 28, 1)).astype(np.float32)
    out = runtime.predict(model.identifier, {"image": img})
    assert set(out) == {"logits", "classes"} and out["logits"].shape == (2, 10)
    only = runtime.predict(model.identifier, {"image": img}, output_filter=["classes"])
    assert set(only) == {"classes"}
    with pytest.raises(RuntimeError_, match="unknown outputs"):
        runtime.predict(model.identifier, {"image": img}, output_filter=["nope"])


def test_derived_last_token_logits(runtime, tmp_path):
    """The LM warm-path fix: last_token_logits ships (B, V), sliced at the
    last REAL position despite seq padding, and matches the full logits."""
    tiny = {
        "vocab_size": 97, "d_model": 48, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 96, "max_seq": 64,
    }
    model = make_model(tmp_path, family="transformer_lm", name="lm_last", config=tiny)
    runtime.ensure_loaded(model)
    ids = np.random.default_rng(0).integers(1, 97, (3, 5)).astype(np.int32)  # pads: b->4, s->8
    full = runtime.predict(model.identifier, {"input_ids": ids}, output_filter=["logits"])
    last = runtime.predict(
        model.identifier, {"input_ids": ids}, output_filter=["last_token_logits"]
    )
    assert set(last) == {"last_token_logits"}
    assert last["last_token_logits"].shape == (3, 97)
    np.testing.assert_allclose(
        last["last_token_logits"], full["logits"][:, -1, :], atol=1e-5, rtol=1e-5
    )
    # advertised in the signature for metadata discovery
    _, out_spec, _ = runtime.signature(model.identifier)
    assert "last_token_logits" in out_spec
    # mixed filter: concrete + derived in one request
    both = runtime.predict(
        model.identifier, {"input_ids": ids},
        output_filter=["logits", "last_token_logits"],
    )
    assert set(both) == {"logits", "last_token_logits"}
    assert both["logits"].shape == (3, 5, 97)  # un-padded on device


def test_unload_and_states(runtime, tmp_path):
    model = make_model(tmp_path, name="hpt_unload", version=3)
    runtime.ensure_loaded(model)
    assert runtime.is_loaded(model.identifier)
    runtime.unload(model.identifier)
    assert not runtime.is_loaded(model.identifier)
    assert runtime.state(model.identifier) == ModelState.END
    states = runtime.states_for("hpt_unload")
    assert states[model.identifier] == ModelState.END


def test_hbm_lru_eviction(tmp_path):
    # capacity for ~2 half_plus_two param sets (2 scalars each, tiny) — use
    # max_items to force the eviction path deterministically
    rt = TPUModelRuntime(ServingConfig(hbm_capacity_bytes=1 << 20, max_concurrent_models=2))
    try:
        models = [make_model(tmp_path, name=f"t{i}", version=1) for i in range(3)]
        for m in models:
            rt.ensure_loaded(m)
        assert not rt.is_loaded(models[0].identifier)  # LRU evicted
        assert rt.is_loaded(models[1].identifier) and rt.is_loaded(models[2].identifier)
        assert rt.state(models[0].identifier) == ModelState.END
        # evicted model predicts fail until re-loaded
        with pytest.raises(ModelNotLoadedError):
            rt.predict(models[0].identifier, {"x": np.ones(1, np.float32)})
        rt.ensure_loaded(models[0])
        out = rt.predict(models[0].identifier, {"x": np.ones(2, np.float32)})
        np.testing.assert_allclose(out["y"], [2.5, 2.5])
    finally:
        rt.close()


def test_corrupt_artifact_fails_cleanly(runtime, tmp_path):
    bad_dir = tmp_path / "bad" / "1"
    bad_dir.mkdir(parents=True)
    (bad_dir / "model.json").write_text("{not json")
    model = Model(identifier=ModelId("bad", 1), path=str(bad_dir), size_on_disk=10)
    with pytest.raises(RuntimeError_):
        runtime.ensure_loaded(model)
    assert runtime.state(model.identifier) == ModelState.END


def test_signature(runtime, tmp_path):
    model = make_model(tmp_path, name="hpt_sig")
    runtime.ensure_loaded(model)
    inputs, outputs, method = runtime.signature(model.identifier)
    assert inputs["x"].dtype == "float32" and method == "tensorflow/serving/predict"
    assert "y" in outputs


def test_executable_shared_across_tenants_and_freed(tmp_path):
    from tfservingcache_tpu.models.registry import build

    rt = TPUModelRuntime(ServingConfig(hbm_capacity_bytes=1 << 20))
    try:
        m1 = make_model(tmp_path, name="shareA", version=1)
        m2 = make_model(tmp_path, name="shareB", version=1)
        rt.ensure_loaded(m1)
        rt.ensure_loaded(m2)
        key = build("half_plus_two").cache_key
        assert rt._jitted_by_key[key][1] == 2       # both tenants share one entry
        rt.unload(m1.identifier)
        assert rt._jitted_by_key[key][1] == 1
        rt.unload(m2.identifier)
        assert key not in rt._jitted_by_key         # last tenant freed the executable
    finally:
        rt.close()


def test_load_locks_pruned_after_failing_load(runtime, tmp_path):
    """A model whose load keeps failing never becomes resident, so the
    evict-side prune never fires for it — the failure path must drop the idle
    ``_load_locks`` entry itself or a storm of failing tenants grows the dict
    without bound (mirror of the soak's bounded-internals assertion)."""
    bad_dir = tmp_path / "cursed" / "1"
    bad_dir.mkdir(parents=True)
    (bad_dir / "model.json").write_text("{not json")
    mid = ModelId("cursed", 1)
    model = Model(identifier=mid, path=str(bad_dir), size_on_disk=10)
    for _ in range(3):
        with pytest.raises(RuntimeError_):
            runtime.ensure_loaded(model)
        assert mid not in runtime._load_locks
