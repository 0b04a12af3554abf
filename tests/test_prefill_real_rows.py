"""An admission's prefill computes the prompt's REAL rows only (ISSUE 48):
``models/real_rows.over_real_rows`` runs a token-wise stage over the row
blocks that hold a real row, and a prefill's pad rows go to no expert.

Every cached family is built at the rehearsal widths of its cell's
configuration (float32, so that the looped and the whole stage are held to
each other at float32's own tolerance, as ``tests/test_delta_chunk_kernel.py``
holds the kernel to the block form) and prefilled at a 64-row bucket twice:
with the block floor dropped to 8 rows (eight blocks, the loop engaged) and
as the tree stands (a 64-row bucket runs whole: the text the parent traced).
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfservingcache_tpu.models import generation, real_rows, registry

BENCHMARK = os.path.join(os.path.dirname(__file__), "..", "benchmark")
BUCKET, BLOCK = 64, 8
# family case -> its cell's configuration file
FAMILIES = {
    "transformer_lm": "mistral-7b-v0.3",
    "moe_lm": "olmoe-1b-7b-0125",
    "mla_moe_lm": "mistral-small-4-119b-2603",
    "hybrid_lm": "lfm2-8b-a1b",
    "moe_lm-window": "mellum2-12b-a2.5b-instruct",
    "sambay_lm": "phi-4-mini-flash-reasoning",
    "olmo_hybrid_lm": "olmo-hybrid-7b",
}
# bucket / 2 + 1 (the shortest prompt a bucket takes), a block's edge and one
# either side of it, the whole bucket
REAL_LENS = (BUCKET // 2 + 1, 5 * BLOCK - 1, 5 * BLOCK, 5 * BLOCK + 1, BUCKET)


def _merged(base: dict, over: dict) -> dict:
    return {**base, **{k: _merged(base[k], v) if isinstance(v, dict)
                       and isinstance(base.get(k), dict) else v
                       for k, v in over.items()}}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def model(request):
    """-> (ModelDef at the rehearsal widths in float32, its static config,
    params)."""
    with open(os.path.join(BENCHMARK, "configs",
                           f"{FAMILIES[request.param]}.json")) as f:
        config = json.load(f)
    config = _merged(config, config["rehearsal"])
    spec = importlib.util.spec_from_file_location(
        f"real_rows_family_{config['family']}",
        os.path.join(BENCHMARK, "families", f"{config['family']}.py"))
    family = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(family)
    mdef = registry.build(family.PROGRAM_FAMILY,
                          {**family.program_config(config), "dtype": "float32"})
    return mdef, registry.static_config(mdef), mdef.init(jax.random.PRNGKey(0))


@pytest.fixture
def looped(monkeypatch):
    """The loop engaged at the test's bucket: eight blocks of 8 rows."""
    monkeypatch.setattr(real_rows, "MIN_BLOCK_ROWS", BLOCK)
    generation._slot_prefill_jit.clear_cache()
    yield
    generation._slot_prefill_jit.clear_cache()


def _prompt(seed: int, real: int, pad_seed: int | None = None):
    """A ``BUCKET``-row bucket whose first ``real`` ids are the prompt; the
    pad ids zeros, or drawn from ``pad_seed``."""
    ids = np.zeros((1, BUCKET), np.int32)
    ids[0, :real] = np.random.default_rng(seed).integers(1, 500, real)
    if pad_seed is not None:
        ids[0, real:] = np.random.default_rng(pad_seed).integers(
            1, 500, BUCKET - real)
    return ids


def _prefill(model, ids, real: int):
    """-> (first token, last logits, the K/V rows under ``real``, the lane
    state) of ``_slot_prefill_jit``, as numpy."""
    mdef, cfg_key, params = model
    tok, pk, pv, last, lane = generation._slot_prefill_jit(
        params, ids, np.asarray([real], np.int32), jax.random.PRNGKey(2),
        np.float32(0.0), np.int32(0), cfg_key=cfg_key, family=mdef.family)
    rows = [np.asarray(side)[:, :, :, :real] for side in (pk, pv)
            if side is not None]
    return (int(np.asarray(tok)[0]), np.asarray(last), rows,
            jax.tree_util.tree_map(np.asarray, lane))


def _close(got, want, what: str) -> None:
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        scale = max(1.0, float(np.abs(w).max()))
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4 * scale,
                                   err_msg=what)


@pytest.mark.parametrize("real", REAL_LENS)
def test_looped_prefill_answers_as_the_whole_stage(model, monkeypatch, real):
    ids = _prompt(real, real)
    generation._slot_prefill_jit.clear_cache()
    tok, last, rows, lane = _prefill(model, ids, real)       # the stage whole
    assert real_rows.row_block(BUCKET) == 0
    with monkeypatch.context() as m:
        m.setattr(real_rows, "MIN_BLOCK_ROWS", BLOCK)
        generation._slot_prefill_jit.clear_cache()
        assert real_rows.row_block(BUCKET) == BLOCK
        got = _prefill(model, ids, real)
    generation._slot_prefill_jit.clear_cache()
    _close(got[1], last, "last-position logits")
    _close(got[2], rows, "K/V rows under prompt_len")
    _close(got[3], lane, "lane state at prompt_len")
    # greedy: the same token unless the top two logits tie within rounding
    top = np.sort(last[0])[-2:]
    assert got[0] == tok or top[1] - top[0] < 1e-3


@pytest.mark.parametrize("real", (BUCKET // 2 + 1, 5 * BLOCK + 1))
def test_nothing_reads_a_pad_row(model, looped, real):
    """Two prompts that differ ONLY in their pad ids: the same first token,
    logits, real rows and lane state, bit for bit."""
    a = _prefill(model, _prompt(7, real), real)
    b = _prefill(model, _prompt(7, real, pad_seed=11), real)
    assert a[0] == b[0]
    for got, want in zip(jax.tree_util.tree_leaves(a[1:]),
                         jax.tree_util.tree_leaves(b[1:]), strict=True):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("bucket", (4096, 8192, 16384, 32768))
def test_block_is_a_function_of_the_bucket(bucket):
    block = real_rows.row_block(bucket)
    assert block == bucket // 8 >= 512
    # a prompt fills more than half of its bucket: at worst one block of pad
    # over the shortest prompt, where the bucket's own pad was all of it again
    worst = max(real_rows.rows_computed(p, bucket) / p
                for p in range(bucket // 2 + 1, bucket + 1, 37))
    assert worst == (bucket // 2 + block) / (bucket // 2 + 1) <= 1.25
    assert real_rows.rows_computed(bucket, bucket) == bucket


@pytest.mark.parametrize("bucket", (64, 512, 1024, 2048, 3000, 4100))
def test_short_and_odd_buckets_run_whole(bucket):
    assert real_rows.row_block(bucket) == 0
    assert real_rows.rows_computed(bucket // 2 + 1, bucket) == bucket
    x = jnp.ones((1, bucket, 3))
    text = jax.jit(lambda x, n: real_rows.over_real_rows(
        lambda x: x * 2.0, (x,), n)).lower(x, jnp.asarray([5])).as_text()
    assert "while" not in text


@pytest.mark.parametrize("took", ((33,), (39,), (40,), (41,), (64,), (1,),
                                  (0,), (12, 57), (64, 3)))
def test_host_count_is_the_devices_trip_count(monkeypatch, took):
    """``rows_computed`` (the counter's ``computed``) against the rows the
    device's loop set: a stage that writes ones marks every row it ran, the
    rows past the last block stay zero."""
    monkeypatch.setattr(real_rows, "MIN_BLOCK_ROWS", BLOCK)
    took = np.asarray(took, np.int32)
    x = jnp.zeros((len(took), BUCKET, 2))
    ran = jax.jit(lambda x, n: real_rows.over_real_rows(
        lambda x: jnp.ones_like(x), (x,), n))(x, took)
    ran = np.asarray(ran)[:, :, 0]
    computed = real_rows.rows_computed(int(took.max()), BUCKET)
    assert computed % BLOCK == 0
    assert (ran.sum(axis=1) == computed).all()          # trips x block, a row
    assert (ran[:, :computed] == 1).all() and (ran[:, computed:] == 0).all()


def test_rows_lie_along_the_named_axes_and_a_halo_reaches_back(monkeypatch):
    """Outputs along ``out_axis``, operands along ``in_axis``, and a stage
    whose row reads ``halo`` rows back: each against the stage run whole over
    the real rows."""
    monkeypatch.setattr(real_rows, "MIN_BLOCK_ROWS", BLOCK)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, BUCKET, 4)), jnp.float32)
    took = np.asarray([37, 50], np.int32)
    last = real_rows.rows_computed(50, BUCKET)

    def heads(x):                       # (B, T, 4) -> (B, 2, T, 2)
        return x.reshape(x.shape[0], x.shape[1], 2, 2).transpose(0, 2, 1, 3)

    out = jax.jit(lambda x, n: real_rows.over_real_rows(
        heads, (x,), n, out_axis=2))(x, took)
    np.testing.assert_array_equal(out[:, :, :last], heads(x)[:, :, :last])
    assert not np.asarray(out[:, :, last:]).any()
    back = jax.jit(lambda h, n: real_rows.over_real_rows(
        lambda h: h.transpose(0, 2, 1, 3).reshape(h.shape[0], h.shape[2], 4),
        (h,), n, in_axis=2))(heads(x), took)
    np.testing.assert_array_equal(back[:, :last], x[:, :last])

    halo = 3
    rows = jnp.concatenate([jnp.ones((2, halo, 4)), x], axis=1)

    def taps(rows):
        t = rows.shape[1] - halo
        return sum(float(j + 1) * rows[:, j:j + t] for j in range(halo + 1))

    got = jax.jit(lambda r, n: real_rows.over_real_rows(
        taps, (r,), n, halo=halo))(rows, took)
    np.testing.assert_allclose(got[:, :last], taps(rows)[:, :last], rtol=1e-6)
    assert not np.asarray(got[:, last:]).any()


@pytest.mark.parametrize("real", (BUCKET // 2 + 1, 5 * BLOCK + 1))
def test_pad_rows_go_to_no_expert(model, real):
    """An expert family's prefill routes its REAL rows: every expert layer's
    ``expert_rows_local`` is what the prompt alone, at its exact length,
    gives (real rows x ``top_k`` where the chip holds every expert), not the
    bucket's."""
    mdef, cfg_key, params = model
    cfg = dict(cfg_key)
    if "top_k" not in cfg:
        pytest.skip("no expert layer")
    local = generation.MOE_STATS.index("expert_rows_local")

    def stats_of(ids, real_len):
        stats: list = []
        rows = generation._DenseRows(
            generation.init_cache(cfg, 1, ids.shape[1]),
            jnp.zeros((1,), jnp.int32), ids.shape[1], cfg, True, real_len)
        generation._walk_layers(params, jnp.asarray(ids), rows, cfg,
                                logits_at=jnp.asarray([real - 1]),
                                moe_stats=stats)
        return np.asarray([float(s[local]) for s in stats])

    ids = _prompt(5, real, pad_seed=9)
    padded = stats_of(ids, jnp.asarray([real], jnp.int32))
    alone = stats_of(ids[:, :real], None)
    assert len(padded) > 0
    np.testing.assert_array_equal(padded, alone)
    if "n_experts_held" not in cfg:
        assert (padded == real * int(cfg["top_k"])).all()
    else:
        assert (padded <= real * int(cfg["top_k"])).all()
    assert (stats_of(ids, None) > padded).all()     # the bucket's, unmasked


def test_an_admission_counts_its_rows(tmp_path, monkeypatch):
    """``tpusc_prefill_rows_total``: where the runtime calls an admission's
    prefill it counts the prompt's tokens, their bucket and the rows the
    looped stages run, the host's mirror of the trip count the device takes."""
    from tfservingcache_tpu.config import ServingConfig
    from tfservingcache_tpu.models.registry import export_artifact
    from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
    from tfservingcache_tpu.types import Model, ModelId
    from tfservingcache_tpu.utils.metrics import Metrics

    monkeypatch.setattr(real_rows, "MIN_BLOCK_ROWS", BLOCK)
    generation._slot_prefill_jit.clear_cache()
    config = {"vocab_size": 97, "d_model": 32, "n_layers": 1, "n_heads": 2,
              "n_kv_heads": 1, "d_ff": 64, "max_seq": 128, "dtype": "float32",
              "rope_theta": 10000.0}
    export_artifact("transformer_lm", str(tmp_path), name="rows", version=1,
                    config=config, seed=0)
    metrics = Metrics()
    rt = TPUModelRuntime(ServingConfig(platform="cpu"), metrics)
    mid = ModelId("rows", 1)
    try:
        rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / "rows" / "1")))
        for p in (41, 64, 7):           # buckets 64, 64 and 8 (a whole stage)
            rt.slot_prefill(mid, np.arange(1, p + 1, dtype=np.int32), 0.0, 0, 1)
    finally:
        rt.close()
        generation._slot_prefill_jit.clear_cache()
    read = lambda kind: metrics.prefill_rows.labels(kind)._value.get()  # noqa: E731
    assert read("real") == 41 + 64 + 7
    assert read("bucket") == 64 + 64 + 8
    assert read("computed") == 48 + 64 + 8
