"""Host-RAM warm tier: demotion/promotion correctness and budget discipline.

The tier retains evicted models' pre-packed transfer chunks + executable
handles (cache/host_tier.py) so re-admission skips provider fetch and host
decode. These tests pin the properties the tier must not lose:

  - output parity: a promoted model serves EXACTLY what a store-path load
    serves (bf16 + int8, several zoo families, token-level generate);
  - demotion -> promotion round-trips under concurrent traffic;
  - the byte budget evicts in LRU order and ``host_tier_bytes=0`` is
    byte-identical to the two-tier behavior;
  - a slow demotion (worker-thread repack) never blocks hits on other
    models (the eviction critical section stays device-op free);
  - CacheManager accounting: ``tpusc_reload_source`` tier mix and the
    inclusive discard on disk eviction.
"""

import os
import threading
import time

import numpy as np
import pytest

from tfservingcache_tpu.cache.disk_cache import ModelDiskCache, dir_size_bytes
from tfservingcache_tpu.cache.manager import CacheManager
from tfservingcache_tpu.cache.providers.disk import DiskModelProvider
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import export_artifact
from tfservingcache_tpu.runtime.base import ModelNotLoadedError
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId
from tfservingcache_tpu.utils.metrics import Metrics

FAMILY_INPUTS = {
    "half_plus_two": lambda: {"x": np.linspace(-1, 1, 4).astype(np.float32)},
    "mnist_cnn": lambda: {
        "image": np.random.default_rng(0)
        .normal(size=(2, 28, 28, 1))
        .astype(np.float32)
    },
    "transformer_lm": lambda: {
        "input_ids": np.arange(8, dtype=np.int32).reshape(1, 8)
    },
}


def export_model(family, store, name, **kw):
    export_artifact(family, str(store), name=name, version=1, **kw)
    path = os.path.join(str(store), name, "1")
    return Model(
        identifier=ModelId(name, 1), path=path, size_on_disk=dir_size_bytes(path)
    )


def make_runtime(host_tier_bytes, metrics=None, **cfg):
    cfg.setdefault("hbm_capacity_bytes", 1 << 30)
    return TPUModelRuntime(
        ServingConfig(**cfg), metrics, host_tier_bytes=host_tier_bytes
    )


@pytest.mark.parametrize("family", sorted(FAMILY_INPUTS))
def test_promotion_parity_bf16(family, tmp_path):
    """Store-path load vs demote->promote must produce identical outputs,
    and the runtime must report which tier served each load."""
    m = export_model(family, tmp_path, f"{family}-m", seed=11)
    rt = make_runtime(1 << 30)
    try:
        assert rt.ensure_loaded(m) == "disk"
        assert rt.host_tier_contains(m.identifier)  # eager retain at load
        inputs = FAMILY_INPUTS[family]()
        ref = rt.predict(m.identifier, inputs)
        assert rt.ensure_loaded(m) == "hbm"

        rt.unload(m.identifier)
        rt.drain_demotions()
        assert not rt.is_loaded(m.identifier)
        assert rt.ensure_loaded(m) == "host"
        got = rt.predict(m.identifier, inputs)
        assert set(got) == set(ref)
        for k in ref:
            np.testing.assert_array_equal(ref[k], got[k])
    finally:
        rt.close()


def test_promotion_parity_int8_and_token_level_generate(tmp_path):
    """int8 artifact: the tier retains the still-quantized chunks (half the
    float bytes) and promotion replays the on-device dequant — generate
    must be token-identical through both paths, including the repack
    branch (demotion re-created from the device copy)."""
    m = export_model("transformer_lm", tmp_path, "lmq", seed=3, quantize="int8")
    rt = make_runtime(1 << 30)
    ids = np.arange(6, dtype=np.int32).reshape(1, 6)
    try:
        assert rt.ensure_loaded(m) == "disk"
        ref_tokens = rt.generate(m.identifier, ids, max_new_tokens=8, seed=7)
        packed = rt._host_tier.size_of(m.identifier)
        # retained chunks are the int8 wire layout, not the dequantized tree
        assert packed < m.size_on_disk * 1.5

        rt.unload(m.identifier)
        rt.drain_demotions()
        assert rt.ensure_loaded(m) == "host"
        np.testing.assert_array_equal(
            ref_tokens, rt.generate(m.identifier, ids, max_new_tokens=8, seed=7)
        )

        # force the worker repack path: drop the retained entry while
        # resident, then evict — the demote worker re-creates it from the
        # (dequantized) device copy and parity must still hold
        rt._host_tier.remove(m.identifier)
        rt.unload(m.identifier)
        rt.drain_demotions()
        assert rt.host_tier_contains(m.identifier)
        assert rt.ensure_loaded(m) == "host"
        np.testing.assert_array_equal(
            ref_tokens, rt.generate(m.identifier, ids, max_new_tokens=8, seed=7)
        )
    finally:
        rt.close()


def test_round_trip_under_concurrent_requests(tmp_path):
    """Two models thrashing through a 1-slot HBM budget from several
    threads: every request must see correct outputs while each hit demotes
    the other model and promotes its own. Between a thread's ``ensure_loaded``
    and its ``predict`` the other model's threads may take the one place:
    that is what such a budget does under six threads, not a fault, and a
    request answers it as the serving path does (``local_backend.
    _predict_sync``): load again, then predict."""
    models = [
        export_model("half_plus_two", tmp_path, f"c{i}", seed=i) for i in range(2)
    ]
    rt = make_runtime(1 << 30, max_concurrent_models=1)
    x = {"x": np.ones(3, np.float32)}
    try:
        refs = []
        for m in models:
            rt.ensure_loaded(m)
            refs.append(rt.predict(m.identifier, x)["y"])
        errors = []

        def served(m):
            for _ in range(100):
                rt.ensure_loaded(m)
                try:
                    return rt.predict(m.identifier, x)["y"]
                except ModelNotLoadedError:  # eviction raced: load again
                    continue
            raise AssertionError(f"{m.identifier}: evicted before 100 predicts")

        def worker(m, ref):
            try:
                for _ in range(25):
                    np.testing.assert_array_equal(served(m), ref)
            except Exception as e:  # noqa: BLE001 - surfaced below
                errors.append(e)

        threads = [
            threading.Thread(target=worker, args=(m, r))
            for m, r in zip(models, refs)
            for _ in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        rt.drain_demotions()
        # both models ended up tier-resident; at most one still in HBM
        assert all(rt.host_tier_contains(m.identifier) for m in models)
        assert len(rt.resident_models()) <= 1
    finally:
        rt.close()


def test_budget_overflow_evicts_lru_order(tmp_path):
    """Tier budget sized for ~2 entries: the third insert evicts the least
    recently used entry, and a get() touch changes who that is."""
    models = [
        export_model("half_plus_two", tmp_path, f"b{i}", seed=i) for i in range(4)
    ]
    probe = make_runtime(1 << 30)
    try:
        probe.ensure_loaded(models[0])
        entry_bytes = probe._host_tier.size_of(models[0].identifier)
    finally:
        probe.close()

    metrics = Metrics()
    rt = make_runtime(int(entry_bytes * 2.5), metrics)
    try:
        a, b, c, d = (m.identifier for m in models)
        rt.ensure_loaded(models[0])
        rt.ensure_loaded(models[1])
        rt.ensure_loaded(models[2])  # budget holds 2: a (LRU) falls out
        assert not rt.host_tier_contains(a)
        assert rt.host_tier_contains(b) and rt.host_tier_contains(c)
        assert metrics.evictions.labels("host")._value.get() == 1
        assert rt._host_tier.total_bytes <= rt._host_tier.capacity_bytes

        rt._host_tier.get(b)  # touch: c becomes the LRU victim
        rt.ensure_loaded(models[3])
        assert not rt.host_tier_contains(c)
        assert rt.host_tier_contains(b) and rt.host_tier_contains(d)
        assert metrics.host_tier_bytes._value.get() == rt._host_tier.total_bytes
    finally:
        rt.close()


def test_zero_budget_is_todays_behavior(tmp_path):
    """host_tier_bytes=0 (the default): no tier object, no demote worker,
    every reload reports the full disk path."""
    m = export_model("half_plus_two", tmp_path, "z0", seed=1)
    rt = TPUModelRuntime(ServingConfig(hbm_capacity_bytes=1 << 30))
    try:
        assert rt._host_tier is None and rt._demote_queue is None
        assert rt.ensure_loaded(m) == "disk"
        assert not rt.host_tier_contains(m.identifier)
        out = rt.predict(m.identifier, {"x": np.ones(2, np.float32)})
        rt.unload(m.identifier)
        rt.drain_demotions()  # no-op without a tier
        assert rt.ensure_loaded(m) == "disk"
        np.testing.assert_array_equal(
            out["y"], rt.predict(m.identifier, {"x": np.ones(2, np.float32)})["y"]
        )
        # unload_and_discard degrades to plain unload
        rt.unload_and_discard(m.identifier)
        assert not rt.is_loaded(m.identifier)
    finally:
        rt.close()


def test_slow_demotion_does_not_block_other_models(tmp_path):
    """Satellite guard: demotion work (device_get + repack) runs on the
    worker thread, so even a pathologically slow demotion must not stall
    concurrent hits on other resident models."""
    ma = export_model("half_plus_two", tmp_path, "slow-a", seed=1)
    mb = export_model("half_plus_two", tmp_path, "slow-b", seed=2)
    rt = make_runtime(1 << 30)
    x = {"x": np.ones(2, np.float32)}
    try:
        rt.ensure_loaded(ma)
        rt.ensure_loaded(mb)
        ref_b = rt.predict(mb.identifier, x)["y"]

        real_impl = rt._demote_impl

        def slow_impl(mid, loaded):
            time.sleep(1.0)
            real_impl(mid, loaded)

        rt._demote_impl = slow_impl
        # force the repack path so the eviction actually queues work
        rt._host_tier.remove(ma.identifier)
        t0 = time.monotonic()
        rt.unload(ma.identifier)  # enqueues the slow demotion
        unload_s = time.monotonic() - t0

        t0 = time.monotonic()
        for _ in range(20):
            np.testing.assert_array_equal(rt.predict(mb.identifier, x)["y"], ref_b)
        hits_s = time.monotonic() - t0
        assert unload_s < 0.5, f"eviction blocked on demotion ({unload_s:.2f}s)"
        assert hits_s < 0.5, f"hits stalled behind demotion ({hits_s:.2f}s)"

        rt.drain_demotions()  # now the slow repack has landed
        assert rt.host_tier_contains(ma.identifier)
    finally:
        rt.close()


def test_manager_reload_source_mix_and_disk_evict_discard(tmp_path):
    """End-to-end through CacheManager: the tpusc_reload_source counter
    attributes each resolution to its serving tier, and a disk eviction
    discards the host-tier entry (inclusive tiers)."""
    store = tmp_path / "store"
    store.mkdir()
    m = export_model("half_plus_two", store, "mix", seed=5)
    mid = m.identifier
    metrics = Metrics()
    rt = make_runtime(1 << 30, metrics)
    cache = ModelDiskCache(str(tmp_path / "cache"), capacity_bytes=1 << 20)
    mgr = CacheManager(DiskModelProvider(str(store)), cache, rt, metrics)

    def src(tier):
        return metrics.reload_source.labels(tier)._value.get()

    try:
        mgr.ensure_servable(mid)
        assert src("store") == 1  # cold miss: provider fetch + full load
        mgr.ensure_servable(mid)
        assert src("hbm") == 1  # fully warm fast path

        rt.unload(mid)
        rt.drain_demotions()
        mgr.ensure_servable(mid)
        assert src("host") == 1  # STALE resolved by promotion

        rt._host_tier.remove(mid)
        rt.unload(mid)
        rt._host_tier.remove(mid)  # drop the re-demoted entry too
        rt.drain_demotions()
        rt._host_tier.remove(mid)
        mgr.ensure_servable(mid)
        assert src("disk") == 1  # STALE resolved by full artifact load

        # disk eviction must take the host-tier entry down with it
        assert rt.host_tier_contains(mid)
        cache.remove(mid)
        cache.drain_evictions()
        rt.drain_demotions()
        assert not rt.is_loaded(mid)
        assert not rt.host_tier_contains(mid)
        mgr.ensure_servable(mid)
        assert src("store") == 2  # true store path again
    finally:
        mgr.close()
