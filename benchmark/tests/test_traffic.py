import json
import os

import numpy as np

import traffic
from conftest import BENCH

MIX = {"verb": "generate", "tenants": 4, "zipf_s": 1.0, "arrival": "poisson",
       "rate_rps": 20.0,
       "prompt": {"lognormal": {"median": 48, "sigma": 0.8, "min": 8, "max": 200}},
       "output": {"lognormal": {"median": 12, "sigma": 0.8, "min": 2, "max": 40}}}


def test_same_seed_same_schedule_other_seed_other_schedule():
    a = traffic.compile_schedule(MIX, 7, 1000, 10.0)
    b = traffic.compile_schedule(MIX, 7, 1000, 10.0)
    c = traffic.compile_schedule(MIX, 8, 1000, 10.0)
    assert a == b
    assert [r.at_s for r in a] != [r.at_s for r in c]
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_the_horizon_fixes_the_count_and_the_last_request_is_inside_it():
    for horizon, rate in ((5.0, 20.0), (51.0, 1.1), (51.0, 0.8)):
        s = traffic.compile_schedule(dict(MIX, rate_rps=rate), 3, 1000, horizon)
        assert len(s) == int(np.ceil(rate * horizon))
        assert 0 <= s[0].at_s and 0.9 * horizon < s[-1].at_s < horizon


def test_lengths_are_clipped_and_tokens_avoid_the_pad():
    s = traffic.compile_schedule(MIX, 1, 1000, 30.0)
    assert len(s) == 600
    plens = np.array([len(r.prompt) for r in s])
    assert plens.min() >= 8 and plens.max() <= 200
    assert 35 < np.median(plens) < 65
    assert all(2 <= r.max_new <= 40 for r in s)
    assert all(1 <= t < 1000 for r in s for t in r.prompt)
    assert [r.index for r in s] == list(range(len(s)))
    assert all(a.at_s <= b.at_s for a, b in zip(s, s[1:]))


def test_zipf_ranks_tenants_and_rate_is_the_mean():
    s = traffic.compile_schedule(MIX, 5, 1000, 60.0)
    counts = np.bincount([r.tenant for r in s], minlength=4)
    assert counts[0] > counts[1] > counts[3]
    assert abs(len(s) / 60.0 - 20.0) < 2.0
    w = traffic.tenant_weights(4, 1.0)
    assert np.allclose(w, np.array([1, 1 / 2, 1 / 3, 1 / 4]) / (25 / 12))


def test_predict_mix_one_prompt_per_tenant():
    mix = {"verb": "predict", "tenants": 3, "zipf_s": 1.0, "rate_rps": 10.0,
           "prompt": {"choice": {"lens": [16]}}, "prompt_per_tenant": True}
    s = traffic.compile_schedule(mix, 2, 500, 10.0)
    by_tenant = {}
    for r in s:
        assert r.max_new == 0 and len(r.prompt) == 16
        assert by_tenant.setdefault(r.tenant, r.prompt) == r.prompt
    assert len(set(by_tenant.values())) == len(by_tenant) == 3


def test_bursts_sessions_and_shared_prefixes():
    mix = dict(MIX, arrival="burst", burst_size=4, burst_gap_s=2.0, tenants=1,
               turns=3, turn_gap_s=0.5, turn_suffix=5, shared_prefix_tokens=6)
    s = traffic.compile_schedule(mix, 1, 1000, 9.0)
    firsts = [r for r in s if r.turn == 0]
    assert sorted({r.at_s for r in firsts}) == [0.0, 2.0, 4.0, 6.0, 8.0]
    assert len({r.prompt[:6] for r in s}) == 1          # the system prompt
    convs = {}
    for r in s:
        convs.setdefault(r.conv, []).append(r)
    for turns in convs.values():
        for a, b in zip(turns, turns[1:]):
            assert b.prompt[:len(a.prompt)] == a.prompt  # history is a prefix
            assert len(b.prompt) == len(a.prompt) + 5


def test_every_cell_file_compiles_to_a_schedule():
    folder = os.path.join(BENCH, "workloads")
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name)) as f:
            cell = json.load(f)
        s = traffic.compile_schedule(cell["traffic"], 1, 32768, 20.0)
        assert s, name
        d = traffic.describe(s)
        assert d["requests"] == len(s)
        if cell["traffic"]["verb"] == "generate":
            assert d["longest_request"] <= 2048


def test_every_seed_offers_the_same_work_in_another_order():
    mix = MIX
    a = traffic.compile_schedule(mix, 1, 1000, 20.0)
    b = traffic.compile_schedule(mix, 2, 1000, 20.0)
    assert len(a) == len(b) == 400
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert (np.bincount([r.tenant for r in a]) == np.bincount([r.tenant for r in b])).all()
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]   # order differs
    assert [r.at_s for r in a] != [r.at_s for r in b]
    assert 0 <= a[0].at_s and a[-1].at_s < 20.0 and a[-1].at_s > 19.0
    gaps = np.diff([r.at_s for r in a])
    assert abs(gaps.mean() - 1 / 20.0) < 0.005 and gaps.std() > 0.6 * gaps.mean()
    plens = np.array([len(r.prompt) for r in a])
    assert plens.min() >= 8 and plens.max() <= 200 and abs(np.median(plens) - 48) <= 1
    assert a == traffic.compile_schedule(mix, 1, 1000, 20.0)


ORDERED = dict(MIX, order={"base_seed": 3, "swap_ranks": 4})


def _ranks(values):
    return np.argsort(np.argsort(values, kind="stable"), kind="stable")


def test_order_gives_every_seed_one_load_profile():
    """With ``order`` the seed moves a value by fewer than ``swap_ranks``
    ranks at any place in the arrival order: the same load, second by
    second, from other requests."""
    a = traffic.compile_schedule(ORDERED, 1, 1000, 20.0)
    b = traffic.compile_schedule(ORDERED, 2**31 + 5, 1000, 20.0)
    assert len(a) == len(b) == 400
    for get in (lambda r: len(r.prompt), lambda r: r.max_new):
        va, vb = np.array([get(r) for r in a]), np.array([get(r) for r in b])
        assert sorted(va) == sorted(vb)
        assert (va != vb).any()                        # another order ...
        # ... of near-equal values: a quantile and its third neighbour
        assert (np.abs(va - vb) <= np.maximum(2, 0.12 * va)).all()
    assert (np.bincount([r.tenant for r in a]) == np.bincount([r.tenant for r in b])).all()
    ta, tb = np.array([r.at_s for r in a]), np.array([r.at_s for r in b])
    assert (ta != tb).any() and np.abs(ta - tb).max() < 0.5    # of 20 s
    assert all(x.prompt != y.prompt for x, y in zip(a, b))     # token ids
    assert a == traffic.compile_schedule(ORDERED, 1, 1000, 20.0)


def test_order_swap_ranks_1_leaves_the_seed_the_tokens_only():
    mix = dict(MIX, order={"base_seed": 3, "swap_ranks": 1})
    a = traffic.compile_schedule(mix, 1, 1000, 10.0)
    b = traffic.compile_schedule(mix, 2, 1000, 10.0)
    assert [(r.at_s, len(r.prompt), r.max_new, r.tenant) for r in a] == \
           [(r.at_s, len(r.prompt), r.max_new, r.tenant) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_order_base_seed_names_the_profile_and_absent_order_is_the_seeds():
    a = traffic.compile_schedule(ORDERED, 1, 1000, 10.0)
    b = traffic.compile_schedule(dict(MIX, order={"base_seed": 4, "swap_ranks": 4}),
                                 1, 1000, 10.0)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    ra, rb = _ranks([r.max_new for r in a]), _ranks([r.max_new for r in b])
    assert np.abs(ra - rb).max() > 50                  # another profile
    # without ``order`` two seeds differ as far as two base seeds do
    c = traffic.compile_schedule(MIX, 1, 1000, 10.0)
    d = traffic.compile_schedule(MIX, 2, 1000, 10.0)
    assert np.abs(_ranks([r.max_new for r in c])
                  - _ranks([r.max_new for r in d])).max() > 50


def test_the_expert_cell_fixes_its_order_and_the_dense_cells_do_not():
    cells = {}
    for name in ("olmoe-chat-steady", "mistral7b-chat-steady", "smollm2-tenants-churn"):
        with open(os.path.join(BENCH, "workloads", name + ".json")) as f:
            cells[name] = json.load(f)
    assert cells["olmoe-chat-steady"]["traffic"]["order"] == {"base_seed": 1, "swap_ranks": 4}
    assert "order" not in cells["mistral7b-chat-steady"]["traffic"]
    assert "order" not in cells["smollm2-tenants-churn"]["traffic"]
    mix = cells["olmoe-chat-steady"]["traffic"]
    a = traffic.compile_schedule(mix, 11, 50304, 51.0)
    b = traffic.compile_schedule(mix, 2**31 + 12, 50304, 51.0)
    assert len(a) == len(b) == 128
    assert sum(len(r.prompt) for r in a) == sum(len(r.prompt) for r in b)
    assert sum(r.max_new for r in a) == sum(r.max_new for r in b)
    # the four longest gaps are 1.4-2.2 s: swapping them moves what follows
    assert max(abs(x.at_s - y.at_s) for x, y in zip(a, b)) < 1.0
