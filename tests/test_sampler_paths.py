"""The gated sampler (ISSUE 30): a decode step pays for what its live lanes
ask for — an ``argmax`` alone, the categorical draw, or the full-vocabulary
sort before it — and emits, on every path, the token the parent's always-pay-
for-everything formula emitted. The two ``_parent_*`` functions are verbatim
copies of that formula (commit 7625a7c) and are the oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfservingcache_tpu.models import generation
from tfservingcache_tpu.models.generation import (
    _sample,
    _sample_per_row,
    _sampling_lanes,
    sample_path,
)

S, V = 8, 97
PATHS = {"greedy", "sample", "topk"}


def _parent_sample(logits, rng, temperature, top_k):
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    k = jnp.clip(jnp.asarray(top_k, jnp.int32), 0, v)
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    kth = sorted_desc[:, jnp.clip(k - 1, 0, v - 1)][:, None]
    thresh = jnp.where((k > 0) & (k < v), kth, -jnp.inf)
    filt = jnp.where(logits < thresh, -1e30, logits)
    temp = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    sampled = jax.random.categorical(rng, filt / temp, axis=-1).astype(jnp.int32)
    return jnp.where(jnp.asarray(temperature, jnp.float32) <= 0.0, greedy, sampled)


def _parent_sample_per_row(logits, rng, temperature, top_k):
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    k = jnp.clip(top_k.astype(jnp.int32), 0, v)
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        sorted_desc, jnp.clip(k - 1, 0, v - 1)[:, None], axis=-1
    )
    thresh = jnp.where(((k > 0) & (k < v))[:, None], kth, -jnp.inf)
    filt = jnp.where(logits < thresh, -1e30, logits)
    temp = jnp.maximum(temperature.astype(jnp.float32), 1e-6)[:, None]
    sampled = jax.random.categorical(rng, filt / temp, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def _logits(seed=1, rows=S, vocab=V):
    return 3.0 * jax.random.normal(jax.random.PRNGKey(seed), (rows, vocab))


ALL = [True] * S
# name -> (temperature, top_k, active, the path the step must take)
MIXES = {
    "all_greedy": ([0.0] * S, [0] * S, ALL, "greedy"),
    "greedy_rows_with_a_top_k": ([0.0] * S, [5, 0, 40, 0, 0, 3, 0, 0], ALL, "greedy"),
    "all_sampled_no_top_k": ([0.8] * S, [0] * S, ALL, "sample"),
    "one_sampled_lane_no_top_k": ([0, 0, 0, 1.3, 0, 0, 0, 0], [0] * S, ALL, "sample"),
    "one_top_k_lane_among_greedy": (
        [0, 0, 0.8, 0, 0, 0, 0, 0], [0, 0, 5, 0, 0, 0, 0, 0], ALL, "topk"),
    "mixed_top_k_values": (
        [0.5, 0.9, 1.2, 0.0, 0.0, 0.3, 2.0, 1.0], [3, 0, 96, 5, 0, 1, 40, 12],
        ALL, "topk"),
    "top_k_at_or_over_vocab": (
        [0.7, 0.7, 0.0, 1.1, 0, 0, 0, 0], [V, V + 50, 0, 10**9, 0, 0, 0, 0],
        ALL, "sample"),
    "stale_inactive_sampled_top_k_lane": (
        [0, 0, 0, 0.8, 0, 0, 0, 0], [0, 0, 0, 40, 0, 0, 0, 0],
        [True, True, True, False, True, True, False, True], "greedy"),
    "stale_inactive_top_k_lane_beside_a_live_sampler": (
        [0, 0.9, 0, 0.8, 0, 0, 0, 0], [0, 0, 0, 40, 0, 0, 0, 0],
        [True, True, True, False, True, True, True, True], "sample"),
    "no_lane_live": ([0.8] * S, [7] * S, [False] * S, "greedy"),
}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_per_row_tokens_equal_the_parents_formula(mix):
    """(a) every live row's token is the parent's, on every mix of lanes and
    under several keys; the host helper names the path the mix takes."""
    temps, topks, active, path = MIXES[mix]
    t = jnp.asarray(temps, jnp.float32)
    k = jnp.asarray(topks, jnp.int32)
    a = np.asarray(active, bool)
    new = jax.jit(_sample_per_row)
    old = jax.jit(_parent_sample_per_row)
    for seed in range(4):
        logits, rng = _logits(seed), jax.random.PRNGKey(100 + seed)
        want = np.asarray(old(logits, rng, t, k))
        got = np.asarray(new(logits, rng, t, k, jnp.asarray(a)))
        assert (got[a] == want[a]).all(), (mix, seed)
        if a.all():
            # no mask given = every row counts
            assert (np.asarray(new(logits, rng, t, k)) == want).all(), (mix, seed)
    assert sample_path(a, np.asarray(temps, np.float32),
                       np.asarray(topks, np.int32), V) == path


@pytest.mark.parametrize("temperature,top_k", [
    (0.0, 0), (0.0, 7), (0.8, 0), (0.7, 8), (1.3, 1), (0.9, V), (1.1, V + 50),
    (2.25, 96),
])
def test_scalar_sampler_equals_the_parents_scalar_form(temperature, top_k):
    """(b) ``_sample`` is the broadcast wrapper: same tokens as the parent's
    scalar formula for traced scalars (ONE compiled program for all of them)."""
    new, old = jax.jit(_sample), jax.jit(_parent_sample)
    for seed in range(3):
        logits, rng = _logits(seed, rows=3), jax.random.PRNGKey(7 + seed)
        t, k = jnp.float32(temperature), jnp.int32(top_k)
        assert (np.asarray(new(logits, rng, t, k))
                == np.asarray(old(logits, rng, t, k))).all()


@pytest.mark.parametrize("temperature,top_k", [(0.0, 0), (0.7, 0), (0.7, 8)])
def test_seeded_generate_stream_equals_the_parents(monkeypatch, temperature, top_k):
    """(b) the solo decoder's whole seeded stream (prefill's first token and
    the scan) is the one the parent's sampler produced, and repeats."""
    from tfservingcache_tpu.models.generation import _generate_jit, generate
    from tfservingcache_tpu.models.registry import build

    tiny = {"vocab_size": V, "d_model": 32, "n_layers": 2, "n_heads": 4,
            "n_kv_heads": 2, "d_ff": 64, "max_seq": 32}
    model = build("transformer_lm", tiny)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.array([[5, 17, 40, 3], [9, 9, 2, 61]], np.int32)

    def roll():
        return np.asarray(generate(
            model, params, ids, max_new_tokens=6, temperature=temperature,
            top_k=top_k, rng=jax.random.PRNGKey(3)))

    got = roll()
    assert (got == roll()).all()
    monkeypatch.setattr(generation, "_sample", _parent_sample)
    _generate_jit.clear_cache()
    try:
        want = roll()
    finally:
        monkeypatch.undo()
        _generate_jit.clear_cache()
    assert (got == want).all()


def _sub_jaxprs(eqn):
    for val in eqn.params.values():
        for item in (val if isinstance(val, (tuple, list)) else (val,)):
            if isinstance(item, jax.extend.core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jax.extend.core.Jaxpr):
                yield item


def _primitives(jaxpr, through_cond: bool) -> set[str]:
    """Names of the primitives in ``jaxpr`` and what it calls; a ``cond``'s
    branches are entered only where ``through_cond``."""
    out: set[str] = set()
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        if eqn.primitive.name == "cond" and not through_cond:
            continue
        for sub in _sub_jaxprs(eqn):
            out |= _primitives(sub, through_cond)
    return out


def _conds(jaxpr):
    """The ``cond`` equations of ``jaxpr``, not those inside their branches."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        else:
            for sub in _sub_jaxprs(eqn):
                yield from _conds(sub)


def _is_random(name: str) -> bool:
    return name.startswith("random_") or "threefry" in name


@pytest.mark.parametrize("sampler", ["per_row", "scalar"])
def test_sort_and_draw_live_only_inside_cond_branches(sampler):
    """(c) structure of the traced sampler: no ``sort`` and no random bits
    outside a ``cond``; the outer ``cond`` has one branch with neither (the
    all-greedy step); inside the drawing branch the ``sort`` again stands
    only behind a ``cond`` of its own, beside a branch without it."""
    if sampler == "per_row":
        jaxpr = jax.make_jaxpr(_sample_per_row)(
            _logits(), jax.random.PRNGKey(0), jnp.zeros(S), jnp.zeros(S, jnp.int32),
            jnp.ones(S, bool)).jaxpr
    else:
        jaxpr = jax.make_jaxpr(_sample)(
            _logits(), jax.random.PRNGKey(0), jnp.float32(0.0), jnp.int32(0)).jaxpr
    outside = _primitives(jaxpr, through_cond=False)
    assert "sort" not in outside and not any(map(_is_random, outside))
    assert "argmax" in outside                     # every path pays the argmax
    assert "sort" in _primitives(jaxpr, through_cond=True)

    (outer,) = list(_conds(jaxpr))
    branches = [b.jaxpr for b in outer.params["branches"]]
    everything = [_primitives(b, through_cond=True) for b in branches]
    greedy = [p for p in everything if "sort" not in p]
    assert len(greedy) == 1
    assert not any(map(_is_random, greedy[0])) and "argmax" not in greedy[0]
    (draw,) = [b for b, p in zip(branches, everything) if "sort" in p]
    draw_outside = _primitives(draw, through_cond=False)
    assert "sort" not in draw_outside and any(map(_is_random, draw_outside))
    (inner,) = list(_conds(draw))
    inner_prims = [_primitives(b.jaxpr, through_cond=True)
                   for b in inner.params["branches"]]
    assert sorted("sort" in p for p in inner_prims) == [False, True]
    assert not any(_is_random(n) for p in inner_prims for n in p)


@pytest.mark.parametrize("vocab", [V, 50304])
def test_host_path_agrees_with_the_device_predicates(vocab):
    """(d) ``sample_path`` on numpy mirrors = the two ``any`` the device
    program branches on, for the same arrays (NaN temperatures, negative and
    over-vocabulary ``top_k`` included)."""

    @jax.jit
    def device(temperature, top_k, active):
        samples, wants_k = _sampling_lanes(
            temperature, jnp.clip(top_k, 0, vocab), vocab, active)
        return jnp.any(samples), jnp.any(wants_k)

    rng = np.random.default_rng(0)
    seen = set()
    for _ in range(60):
        temps = rng.choice(
            np.array([0.0, 0.0, 0.0, 0.8, -1.0, np.nan, 1e-9], np.float32), S)
        topks = rng.choice(
            np.array([0, 0, 0, 5, -3, vocab - 1, vocab, vocab + 7], np.int32), S)
        active = rng.random(S) < rng.choice([0.0, 0.3, 1.0])
        any_samples, any_k = (bool(x) for x in device(temps, topks, active))
        want = "topk" if any_k else "sample" if any_samples else "greedy"
        assert sample_path(active, temps, topks, vocab) == want
        seen.add(want)
    assert seen == PATHS


def test_paged_decode_chunk_is_one_program_for_every_sampling_config(tmp_path):
    """(e) greedy, sampled and top-k chunks (and a chunk with a stale retired
    lane) run ONE compiled ``_paged_decode_chunk_jit`` a chunk size: the
    gates are ``lax.cond`` on traced values, not a static flag."""
    from tfservingcache_tpu.config import ServingConfig
    from tfservingcache_tpu.models.generation import _paged_decode_chunk_jit
    from tfservingcache_tpu.models.registry import export_artifact
    from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
    from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
    from tfservingcache_tpu.types import Model, ModelId

    tiny = {"vocab_size": V, "d_model": 48, "n_layers": 2, "n_heads": 4,
            "n_kv_heads": 2, "d_ff": 96, "max_seq": 64}
    export_artifact("transformer_lm", str(tmp_path), name="lm", version=1, config=tiny)
    rt = TPUModelRuntime(ServingConfig(platform="cpu"))
    mid = ModelId("lm", 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / "lm" / "1")))
    eng = ContinuousGenerateEngine(rt, slots=4, chunk_tokens=4)
    try:
        ids = np.array([[5, 17, 40, 3]], np.int32)
        # 1 prefill token + two chunks of 4: only the chunk-4 program runs
        greedy = eng.generate(mid, ids, max_new_tokens=9)
        before = _paged_decode_chunk_jit._cache_size()
        for temp, k in [(0.31, 0), (0.77, 17), (1.5, 3), (0.0, 5), (2.25, V + 4)]:
            out = eng.generate(mid, ids, max_new_tokens=9, temperature=temp, top_k=k)
            assert out.shape == (1, 9) and (0 <= out).all() and (out < V).all()
        # the sampled requests' lanes are retired, their values still in place
        assert (eng.generate(mid, ids, max_new_tokens=9) == greedy).all()
        assert _paged_decode_chunk_jit._cache_size() == before, (
            "a sampling config compiled a decode chunk of its own")
    finally:
        eng.close()
        rt.close()
