"""One chip's share of an expert layer (``ops.moe.moe_experts``' ``held``), at
a small size on the CPU.

The deployment a share stands for divides a layer's experts between chips;
each chip routes over ALL of them, computes the answers of the experts it
holds and leaves the rest out. What ties the share to the model:

  * the routed parts that the four shares give, plus what every chip computes
    alike (the shared expert) counted ONCE, add up to what the uncut layer
    gives (the benchmark's reference, holding every expert);
  * a held range that is the whole layer is today's ``moe_experts`` bit for
    bit: OLMoE's path does not change;
  * an assignment to an expert held elsewhere reads no weight: ``experts_hit``
    and ``expert_rows_max`` count held experts, ``expert_rows_local`` the
    assignments that landed here;
  * a prefill-sized call of a share is the reference path's answer and the
    same bytes at every row tile, and its tile follows the assignments of the
    router's full width, not the held experts' count.

Float32 throughout; the tolerance against the reference (1e-5 of answers of
size 1) is the order of float32 sums.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfservingcache_tpu.models.moe_lm import _moe_block
from tfservingcache_tpu.ops import moe
from tfservingcache_tpu.ops.attention import dispatch_tally

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, D, FF, E, K = 29, 32, 16, 8, 3


def _layer(seed=0, shared=True):
    rng = np.random.default_rng(seed)
    w = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape) / np.sqrt(shape[-2]), jnp.float32)
    layer = {"router": w(D, E), "bias": jnp.asarray(0.3 * rng.standard_normal(E), jnp.float32),
             "w1": w(E, D, FF), "w3": w(E, D, FF), "w2": w(E, FF, D)}
    if shared:
        layer["shared"] = {"w1": w(D, FF), "w3": w(D, FF), "w2": w(FF, D)}
    return layer, jnp.asarray(rng.standard_normal((T, D)), jnp.float32)


def _share(layer, first, count):
    cut = {k: v for k, v in layer.items() if k in ("router", "bias")}
    cut.update({w: layer[w][first:first + count] for w in ("w1", "w2", "w3")})
    return cut


def _reference_experts(family: str):
    """The ``gates`` and ``add_experts`` of a benchmark family's plain
    reference holding EVERY expert of the layer."""
    spec = importlib.util.spec_from_file_location(
        f"bench_family_{family}", os.path.join(ROOT, "benchmark", "families", f"{family}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    routing = {"rms_eps": 1e-6, "top_k": K, "expert_first": 0, "n_experts_held": E,
               "norm_topk_prob": True, "route_scale": 2.5}
    if family == "mla_moe":
        mc = dict(routing, n_heads=2, qk_nope_head_dim=8, qk_rope_head_dim=8,
                  v_head_dim=16, kv_lora_rank=8, rope_theta=10000.0,
                  rope_factor=4.0, rope_beta_fast=32.0, rope_beta_slow=1.0,
                  rope_original_max=32, rope_mscale_all_dim=1.0, llama4_beta=0.1)
        *_, gates, add_experts, _head = module._fns(tuple(sorted(mc.items())))
    else:       # kda_moe: Solar-Open2's expert layer behind either mixer;
        import json     # laguna: Laguna-S-2.1's behind either kind of attention

        mc = dict(routing, n_heads=4, n_kv_heads=2, head_dim=8, linear_heads=2,
                  linear_key_dim=8, linear_value_dim=8,
                  linear_allow_neg_eigval=True)
        *_, gates, add_experts, _head = module._fns(json.dumps(mc, sort_keys=True))
    return gates, add_experts


# the deployments the benchmark's three share configurations stand for: an
# EP-4 host (Mistral-Small-4) and two EP-8 hosts (Solar-Open2; Laguna-S-2.1,
# whose router has no selection bias), each against its own family's plain
# reference
@pytest.mark.parametrize(
    "family,shares", [("mla_moe", 4), ("kda_moe", 8), ("laguna", 8)],
    ids=["four_shares_mla_moe", "eight_shares_kda_moe", "eight_shares_laguna"])
@pytest.mark.parametrize("interpret", [False, True], ids=["ragged_dot", "kernel"])
def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        monkeypatch, interpret, family, shares):
    """Through ``_moe_block`` (norm, routed share, shared expert): the sum of
    every share's answer minus all but one of the shared-expert answers is
    the reference's whole layer (every expert held: the family's ``gates`` and
    ``add_experts``)."""
    monkeypatch.setattr(moe, "MOE_KERNEL_INTERPRET", interpret)
    layer, x = _layer(1)
    if family == "laguna":
        layer.pop("bias")
    cfg = {"top_k": K, "norm_topk_prob": True, "route_score": "sigmoid",
           "route_scale": 2.5, "rms_eps": 1e-6, "n_experts": E}
    ln2 = jnp.asarray(1.0 + 0.1 * np.random.default_rng(2).standard_normal(D),
                      jnp.float32)
    held = E // shares
    parts, locals_ = [], []
    for first in range(0, E, held):
        y, stats = _moe_block(
            {"moe": dict(_share(layer, first, held), shared=layer["shared"]),
             "ln2": ln2}, x[None],
            dict(cfg, n_experts_held=held, expert_first=first), jnp.float32)
        parts.append(np.asarray(y[0], np.float64))
        locals_.append(float(stats["expert_rows_local"]))
        assert float(stats["experts_hit"]) <= held
    assert sum(locals_) == T * K            # every assignment landed on one chip
    only_shared, _ = _moe_block(
        {"moe": dict(_share(layer, 0, held), shared=layer["shared"]), "ln2": ln2},
        x[None], dict(cfg, n_experts_held=held, expert_first=0), jnp.float32,
        row_mask=jnp.zeros((T,), bool))     # no row routed: the shared expert alone
    total = sum(parts) - (shares - 1) * np.asarray(only_shared[0], np.float64)

    gates, add_experts = _reference_experts(family)
    with jax.default_matmul_precision("highest"):
        bias = (layer["bias"],) if "bias" in layer else ()
        z, y, weight = gates(x, ln2, layer["router"], *bias, layer["shared"])
        want = add_experts(y, z, weight, layer["w1"], layer["w3"], layer["w2"]) - x
    np.testing.assert_allclose(total, np.asarray(want), atol=1e-5, rtol=0)


@pytest.mark.parametrize("masked", [False, True])
def test_a_held_range_that_is_the_whole_layer_is_todays_path_bit_for_bit(masked):
    """``held=(0, E)`` against ``held=None`` (OLMoE's call): the same bytes,
    the same stats."""
    layer, x = _layer(3, shared=False)
    layer.pop("bias")
    mask = jnp.asarray(np.arange(T) % 3 != 0) if masked else None
    y0, s0 = moe.moe_experts(x, layer, K, row_mask=mask)
    y1, s1 = moe.moe_experts(x, layer, K, row_mask=mask, held=(0, E))
    assert np.array_equal(np.asarray(y0), np.asarray(y1))
    for name in ("experts_hit", "expert_rows_max", "expert_rows_local"):
        assert float(s0[name]) == float(s1[name])
    assert float(s0["expert_rows_local"]) == (int(mask.sum()) if masked else T) * K
    # what a training loss reads: the router's choices over the full width, a
    # masked row's set to ``E`` (no expert), with a share or without
    want = np.asarray(moe.route(x, layer["router"], K, False)[1])
    if masked:
        want = np.where(np.asarray(mask)[:, None], want, E)
    for s in (s0, s1, moe.moe_experts(x, _share(layer, 2, 2), K, row_mask=mask,
                                      held=(2, 2))[1]):
        assert np.array_equal(np.asarray(s["experts"]), want)
    # OLMoE's call traces the same program with the share spelled out: the
    # held range adds no equation to the output's computation
    trace = lambda **kw: str(jax.make_jaxpr(  # noqa: E731
        lambda x: moe.moe_experts(x, layer, K, **kw)[0])(x))
    assert trace().count("\n") <= trace(held=(0, E)).count("\n")


def test_assignments_to_experts_held_elsewhere_read_no_weight(monkeypatch):
    """The kernel's grid visits (row tile, expert) pairs that hold rows: with
    experts 2..3 held, only assignments to them are rows, the answer is the
    held assignments' by hand, and the stats count held experts."""
    monkeypatch.setattr(moe, "MOE_KERNEL_INTERPRET", True)
    layer, x = _layer(4, shared=False)
    gates, idx, _ = moe.route(x, layer["router"], K, True, "sigmoid", layer["bias"])
    idx = np.asarray(idx)
    held = _share(layer, 2, 2)
    y, stats = moe.moe_experts(x, held, K, norm_topk=True, score="sigmoid",
                               held=(2, 2))
    here = (idx >= 2) & (idx < 4)
    assert float(stats["expert_rows_local"]) == here.sum()
    assert float(stats["experts_hit"]) == len(set(idx[here].tolist()))
    assert float(stats["expert_rows_max"]) == max(
        (idx == e).sum() for e in (2, 3))
    # by hand: each token's held assignments only
    want = np.zeros((T, D))
    for t in range(T):
        for g, e in zip(np.asarray(gates)[t], idx[t]):
            if 2 <= e < 4:
                h = jax.nn.silu(x[t] @ layer["w1"][e]) * (x[t] @ layer["w3"][e])
                want[t] += float(g) * np.asarray(h @ layer["w2"][e])
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5, rtol=0)
    # a token none of whose experts is here answers exactly zero
    none_here = ~here.any(axis=1)
    assert none_here.any() and not np.asarray(y)[none_here].any()
    with pytest.raises(ValueError, match="the weights hold 2 experts"):
        moe.moe_experts(x, held, K, held=(0, 4))


@pytest.mark.parametrize("first", [0, 4], ids=["first_half", "second_half"])
def test_a_prefill_sized_share_is_the_reference_and_itself_at_every_tile(
        monkeypatch, first):
    """400 tokens x 3 = 1200 assignments over a router 8 wide with 4 experts
    held (fewer than ``DECODE_ROWS`` of them land here): the tile follows
    the call's ``tokens x top_k``, so the share takes ``PREFILL_TM`` like the
    uncut layer; the kernel's answer is the ``partitioned=True`` path's to
    float32 rounding, and the same bytes with the tile forced to 128, 256 and
    512."""
    monkeypatch.setattr(moe, "MOE_KERNEL_INTERPRET", True)
    layer, _ = _layer(5, shared=False)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((400, D)), jnp.float32)
    cut, kw = _share(layer, first, 4), dict(
        norm_topk=True, score="sigmoid", held=(first, 4))
    before = dict(dispatch_tally())
    y, stats = moe.moe_experts(x, cut, K, **kw)
    key = ("moe_experts", "kernel", f"interpret tm={moe.PREFILL_TM}")
    assert dispatch_tally().get(key, 0) == before.get(key, 0) + 1
    assert 0 < float(stats["expert_rows_local"]) <= moe.DECODE_ROWS < 400 * K
    want, _ = moe.moe_experts(x, cut, K, partitioned=True, **kw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5, rtol=0)
    for tm in (128, 256, 512):
        monkeypatch.setattr(moe, "row_tile", lambda a, e, tm=tm: tm)
        again, _ = moe.moe_experts(x, cut, K, **kw)
        assert np.array_equal(np.asarray(again), np.asarray(y))
