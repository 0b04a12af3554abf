"""The benchmark's fast writer produces what the program's loader reads."""

import json
import os

import numpy as np

import run as benchrun
import weights
from conftest import BENCH


def tiny_config():
    """-> (the family's module, the program's config at rehearsal sizes)."""
    with open(os.path.join(BENCH, "configs", "smollm2-360m.json")) as f:
        cfg = json.load(f)
    family = benchrun.load_family(cfg)
    return family, family.program_config(benchrun.deep_merge(cfg, cfg["rehearsal"]))


def test_artifacts_round_trip_through_the_programs_loader(tmp_path, monkeypatch):
    import jax

    from tfservingcache_tpu.models.registry import load_artifact

    family, mc = tiny_config()
    # a staging buffer of a few leaves: staged, flushed and direct writes all run
    monkeypatch.setattr(weights, "WRITE_CHUNK", 3 * 4096)
    kept, total, _split = weights.write_tenants(
        str(tmp_path), ["a", "b"], family, mc, seed=5, keep=2)
    assert total == 2 * os.path.getsize(tmp_path / "a" / "1" / "params.bin")
    assert total <= 2 * family.param_bytes(mc)
    loaded = []
    for name, tree in zip(("a", "b"), kept):
        model, params = load_artifact(str(tmp_path / name / "1"))
        assert model.config["d_model"] == mc["d_model"]
        got = jax.tree_util.tree_leaves(params)
        want = jax.tree_util.tree_leaves(tree)
        assert len(got) == len(want) == 9 * mc["n_layers"] + 2
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            assert np.array_equal(np.asarray(g).view(np.uint8),
                                  np.asarray(w).view(np.uint8))
        loaded.append(got)
    # two tenants of one seed differ, and the same seed gives the same bytes
    assert not np.array_equal(np.asarray(loaded[0][0]).view(np.uint8),
                              np.asarray(loaded[1][0]).view(np.uint8))
    again, _total, _ = weights.write_tenants(
        str(tmp_path / "again"), ["a"], family, mc, seed=5, keep=1)
    assert np.array_equal(
        np.asarray(again[0]["embed"]).view(np.uint8),
        np.asarray(kept[0]["embed"]).view(np.uint8))


def test_weights_have_the_initialisers_scale():
    family, mc = tiny_config()
    import jax

    stacked = jax.device_get(weights.make_on_device(family, mc, 3))
    for name, (shape, fan_in) in family.leaf_shapes(mc).items():
        a = np.asarray(stacked[name], np.float32)
        assert a.shape == shape
        assert abs(a.std() * np.sqrt(fan_in) - 1.0) < 0.1, name
