import json
import os

import pytest

import trace_reduce as tr
from conftest import HERE

D0 = "/device:TPU:0"
OPS = tr.OPS_LINE


def rows_synthetic():
    """Two decode steps by hand: ops at [0,10) [10,30) [50,60) [60,100) us,
    a ``while`` that wraps the first two, and the wall-clock mark."""
    us = 1000
    return [
        (D0, OPS, "%while.1", 0, 30 * us),
        (D0, OPS, "%fusion.3", 0, 10 * us),
        (D0, OPS, "%paged_decode.7", 10 * us, 20 * us),
        (D0, OPS, "%fusion.4", 50 * us, 10 * us),
        (D0, OPS, "%paged_decode.8", 60 * us, 40 * us),
        (D0, "XLA Modules", "jit_step", 0, 100 * us),
        ("/host:CPU", "python", tr.WALL_MARK + str(2_000_000_000), 5 * us, 0),
    ]


def test_busy_idle_and_kernel_sums_by_hand():
    out = tr.reduce(rows_synthetic())
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(100e-6)
    assert out["busy_s"] == pytest.approx(80e-6)        # the gap is [30,50)
    assert out["kernels"]["paged_decode"] == {
        "seconds": pytest.approx(60e-6), "calls": 2}
    assert out["kernels"]["fusion"]["calls"] == 2
    assert "while" not in out["kernels"]                 # a wrapper
    assert out["device_ops"][0] == ["paged_decode", pytest.approx(60e-6)]
    assert out["idle_gaps"] == [["host: nothing recorded", pytest.approx(20e-6)]]


def test_gaps_are_named_by_the_host_state_that_covers_them():
    # trace 5 us = wall 2.0 s, so the gap [30,50) us is wall 2.000025..2.000045
    states = [("engine boundary", 2.000020, 2.000050),
              ("waiting for a request", 0.0, 10.0)]
    out = tr.reduce(rows_synthetic(), states)
    assert out["idle_gaps"] == [["engine boundary", pytest.approx(20e-6)]]
    out = tr.reduce(rows_synthetic(), states[1:])
    assert out["idle_gaps"][0][0] == "waiting for a request"


def test_union_and_names():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert tr.union_ns([]) == 0
    assert tr.kernel_name("%fusion.123") == "fusion"
    assert tr.kernel_name("paged_decode_attention_kernel.4") == "paged_decode_attention_kernel"
    assert tr.kernel_name("copy") == "copy"
    assert tr.is_wrapper("%while.12") and not tr.is_wrapper("%while_body_fusion")


def test_no_device_plane_gives_zeros():
    out = tr.reduce([("/host:CPU", "python", "x", 0, 5)])
    assert out["busy_s"] == 0.0 and out["device_ops"] == []


def test_recorded_trace_from_the_chip():
    """A slice of a real v5e trace (rows as ``load_xplane`` gives them),
    reduced two ways: by ``reduce`` and by a brute-force count on a grid."""
    path = os.path.join(HERE, "data", "small_trace.json")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    with open(path) as f:
        rec = json.load(f)
    rows = [tuple(r) for r in rec["rows"]]
    out = tr.reduce(rows)
    ops = [(n, s, d) for p, ln, n, s, d in rows
           if p.startswith(tr.DEVICE_PLANE) and ln == OPS and not tr.is_wrapper(n)]
    lo = min(s for _n, s, _d in ops)
    hi = max(s + d for _n, s, d in ops)
    step = max(1, (hi - lo) // 200000)
    covered = 0
    marks = bytearray((hi - lo) // step + 1)
    for _n, s, d in ops:
        for i in range((s - lo) // step, (s + d - lo) // step):
            marks[i] = 1
    covered = sum(marks) * step
    assert out["busy_s"] == pytest.approx(covered / 1e9, rel=0.02)
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert out["busy_s"] == pytest.approx(rec["expected"]["busy_s"], rel=1e-6)
    k = out["kernels"][rec["expected"]["kernel"]]
    assert k["calls"] == rec["expected"]["kernel_calls"]
    assert k["seconds"] == pytest.approx(rec["expected"]["kernel_seconds"], rel=1e-6)
