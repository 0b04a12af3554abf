import os

from tfservingcache_tpu.cache.disk_cache import ModelDiskCache
from tfservingcache_tpu.types import Model, ModelId


def write_artifact(cache: ModelDiskCache, mid: ModelId, nbytes: int) -> Model:
    path = cache.model_path(mid)
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "params.bin"), "wb") as f:
        f.write(b"z" * nbytes)
    return Model(identifier=mid, path=path, size_on_disk=nbytes)


def test_eviction_deletes_tree(tmp_path):
    cache = ModelDiskCache(str(tmp_path / "c"), capacity_bytes=250)
    a, b, c = ModelId("a", 1), ModelId("b", 1), ModelId("c", 1)
    for mid in (a, b):
        cache.put(write_artifact(cache, mid, 100))
    pa = cache.model_path(a)
    cache.put(write_artifact(cache, c, 100))  # evicts a
    cache.drain_evictions()
    assert not os.path.exists(pa)
    assert cache.get(a) is None
    assert cache.get(b) is not None and cache.get(c) is not None
    assert cache.total_bytes == 200


def test_out_of_band_deletion_detected(tmp_path):
    cache = ModelDiskCache(str(tmp_path / "c"), capacity_bytes=1000)
    mid = ModelId("m", 3)
    cache.put(write_artifact(cache, mid, 10))
    import shutil

    shutil.rmtree(cache.model_path(mid))
    assert cache.get(mid) is None  # double-check file existence (reference cachemanager.go:154-165)


def test_recover_index_after_restart(tmp_path):
    base = str(tmp_path / "c")
    cache = ModelDiskCache(base, capacity_bytes=1000)
    m1, m2 = ModelId("x", 1), ModelId("y", 2)
    cache.put(write_artifact(cache, m1, 100))
    cache.put(write_artifact(cache, m2, 200))
    # "restart": new instance over the same dir
    cache2 = ModelDiskCache(base, capacity_bytes=1000)
    assert set(cache2.list_models()) == {m1, m2}
    assert cache2.total_bytes == 300
    got = cache2.get(m1)
    assert got is not None and os.path.isdir(got.path)


def test_get_touches_recency(tmp_path):
    """Reads must promote to MRU: after touching the oldest entry, an
    over-budget put evicts the *next* least-recent model, not the touched
    one. (A touch=False regression here silently turns the LRU into FIFO —
    exactly what the hot tail of a churned tenant set can't survive.)"""
    cache = ModelDiskCache(str(tmp_path / "c"), capacity_bytes=250)
    a, b, c = ModelId("a", 1), ModelId("b", 1), ModelId("c", 1)
    cache.put(write_artifact(cache, a, 100))
    cache.put(write_artifact(cache, b, 100))
    assert cache.get(a) is not None  # a becomes MRU; b is now the victim
    cache.put(write_artifact(cache, c, 100))
    cache.drain_evictions()
    assert cache.get(b) is None
    assert cache.get(a) is not None and cache.get(c) is not None


def test_put_charges_actual_bytes_on_disk(tmp_path):
    """Eviction accounting must match reality: a provider-claimed size that
    drifts from the written tree is corrected at put() time, so the byte
    budget reflects what the disk actually holds."""
    cache = ModelDiskCache(str(tmp_path / "c"), capacity_bytes=700)
    mid = ModelId("drift", 1)
    model = write_artifact(cache, mid, 300)
    model.size_on_disk = 10  # the lie a stale manifest would tell
    cache.put(model)
    assert cache.total_bytes == 300
    assert cache.size_of(mid) == 300
    # and the budget enforces against the corrected number: two more real
    # 300-byte artifacts push the first out despite claimed tiny sizes
    # (3 x "10 claimed" would all fit; 3 x 300 actual cannot)
    for name in ("d2", "d3"):
        m = write_artifact(cache, ModelId(name, 1), 300)
        m.size_on_disk = 10
        cache.put(m)
    cache.drain_evictions()
    assert cache.total_bytes == 600
    assert cache.get(mid) is None  # LRU victim of the corrected accounting


def test_replace_put_does_not_delete_new_artifact(tmp_path):
    # Disk-tier replacement: same key, same path — the overwrite already
    # happened in place; the replace-callback must not rmtree the new files.
    cache = ModelDiskCache(str(tmp_path / "c"), capacity_bytes=1000)
    mid = ModelId("m", 1)
    cache.put(write_artifact(cache, mid, 10))
    cache.put(write_artifact(cache, mid, 20))
    got = cache.get(mid)
    assert got is not None and os.path.exists(got.path)
    assert cache.total_bytes == 20


def test_key_locks_pruned_after_missed_fetch(tmp_path):
    """A fetch that never lands (bad name, provider error) must not leave a
    permanent ``_key_locks`` entry: never cached means the evict-side prune
    never runs for it, so a storm of misses on bad names would otherwise grow
    the dict without bound."""
    cache = ModelDiskCache(str(tmp_path), capacity_bytes=1000)
    ghost = ModelId("ghost", 1)
    with cache.fetch_lock(ghost):
        assert ghost in cache._key_locks  # live while the fetch is in flight
    assert ghost not in cache._key_locks  # pruned: idle and non-resident

    # a fetch that DOES land keeps its lock for the eviction handshake
    mid = ModelId("real", 1)
    with cache.fetch_lock(mid):
        cache.put(write_artifact(cache, mid, 10))
    assert mid in cache._key_locks


def test_close_stops_the_eviction_worker(tmp_path):
    """The worker used to loop forever on a sentinel nothing sent: every
    cache ever built left a thread behind."""
    cache = ModelDiskCache(str(tmp_path / "c"), 1 << 20)
    worker = cache._evict_worker
    assert worker.is_alive()
    cache.close()
    cache.close()  # idempotent
    assert not worker.is_alive()
    # an eviction after close still happens, in the evicting thread
    for i in range(2):
        d = tmp_path / "c" / f"m{i}" / "1"
        d.mkdir(parents=True)
        (d / "blob").write_bytes(b"x" * (700 << 10))
        cache.put(Model(identifier=ModelId(f"m{i}", 1), path=str(d),
                        size_on_disk=700 << 10))
    assert not (tmp_path / "c" / "m0").exists()


def test_dropped_cache_takes_its_worker_with_it(tmp_path):
    """The worker holds its cache weakly: a cache that is dropped without
    close() is collected, and its finalizer stops the thread."""
    import gc

    cache = ModelDiskCache(str(tmp_path / "c"), 1 << 20)
    worker = cache._evict_worker
    del cache
    gc.collect()
    worker.join(timeout=5.0)
    assert not worker.is_alive()
