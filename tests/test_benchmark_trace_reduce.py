"""benchmark/trace_reduce.py under tier-1: the reduction from a device trace
to ``busy_s`` / ``idle_gaps`` / kernel seconds that every traced benchmark run
goes through (PR 27 was lost to its old gaps x states loop running past the
driver's time limit). The oracle (``reduce`` as it stood before PR 28) and the
cases are those of ``benchmark/tests/test_trace_reduce.py``; the module is
imported by path, as ``tests/test_trace_scopes_tool.py`` imports its tool, and
the recorded captures are read where the benchmark keeps them."""

import gzip
import importlib.util
import json
import os
import random
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(HERE, "..", "benchmark")
BENCH_DATA = os.path.join(BENCH, "tests", "data")


def _load_tool():
    spec = importlib.util.spec_from_file_location(
        "benchmark_trace_reduce", os.path.join(BENCH, "trace_reduce.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tr = _load_tool()      # stdlib imports only; jax is imported inside load_xplane

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
    path = os.path.join(BENCH_DATA, "small_trace.json")
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


# -- the gap-naming sweep against the loop it replaced (PR 28) ----------------

def reduce_by_the_old_loop(rows, host_states=(), top=10):
    """``reduce`` as it stood before PR 28, kept as the oracle: every gap
    walks ALL the states in list order until one covers half of it."""
    per_dev = tr.device_ops(rows)
    if not per_dev:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0,
                "device_ops": [], "idle_gaps": [], "kernels": {}}
    lo = min(ops[0][1] for ops in per_dev.values())
    hi = max(max(s + d for _n, s, d in ops) for ops in per_dev.values())
    busy = [tr.union_ns((s, s + d) for _n, s, d in ops) for ops in per_dev.values()]
    kernels = {}
    for ops in per_dev.values():
        for name, _s, d in ops:
            k = kernels.setdefault(tr.kernel_name(name), [0.0, 0])
            k[0] += d / 1e9 / len(per_dev)
            k[1] += 1
    offset = tr.wall_offset_ns(rows)
    labelled, longest = {}, []
    for s, e in tr.gaps(next(iter(per_dev.values())), lo, hi):
        label = "host: nothing recorded"
        if offset is not None:
            ws, we = (s + offset) / 1e9, (e + offset) / 1e9
            best = 0.0
            for name, hs, he in host_states:
                cover = min(we, he) - max(ws, hs)
                if cover > best and cover >= 0.5 * (we - ws):
                    label, best = name, cover
                    break
        labelled[label] = labelled.get(label, 0.0) + (e - s) / 1e9
        longest.append(((e - s) / 1e9, label))
    longest.sort(reverse=True)
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(per_dev),
        "device_ops": sorted(([k, v[0]] for k, v in kernels.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in labelled.items()),
                            key=lambda kv: -kv[1])[:top],
        "longest_gaps": [[lab, sec] for sec, lab in longest[:top]],
        "kernels": {k: {"seconds": v[0], "calls": v[1]}
                    for k, v in kernels.items()},
    }


WALL0 = 1_790_000_000.0      # the wall clock at trace time 0, seconds


def synthetic_rows(n_ops, seed, step_ops=350, planes=1):
    """Decode steps by the hundred small operations with a short gap after
    most of them and a longer one between steps, one ``while`` a step."""
    rng = random.Random(seed)
    rows, t = [], 1000
    names = [f"%fusion.{i} = bf16[8,128] fusion(...)" for i in range(40)] + [
        "%paged_decode_attention_kernel.3 = f32[32,4096] custom-call(...)",
        "%copy.9 = bf16[4096] copy(...)"]
    for i in range(n_ops):
        if i % step_ops == 0:
            t += rng.randrange(20_000, 400_000)
            rows.append(("/device:TPU:0", OPS, f"%while.{i}", t,
                         step_ops * 9000))
        dur = rng.randrange(500, 8000)
        for p in range(planes):
            rows.append((f"/device:TPU:{p}", OPS, rng.choice(names), t + 7 * p, dur))
        t += dur + (rng.randrange(1, 3000) if rng.random() < 0.9 else 0)
    rows.append(("/host:CPU", "python",
                 tr.WALL_MARK + str(int(WALL0 * 1e9) + 5000), 5000, 0))
    return rows, t


def ring_and_requests(end_ns, n_boundaries, n_requests, seed):
    """Host states as ``run.host_states`` builds them: ring boundaries in
    time order (they touch; some admit), then requests that overlap one
    another and the boundaries, then the span itself."""
    rng = random.Random(seed)
    span = end_ns / 1e9
    states, t = [], WALL0 - 0.3 * span
    step = 1.6 * span / n_boundaries
    for _ in range(n_boundaries):
        d = rng.uniform(0.7, 1.0) * step
        states.append(("boundary, admitting" if rng.random() < 0.15
                       else "boundary, chunk only", t + 0.02 * step, t + d))
        t += d
    for _ in range(n_requests):
        a = WALL0 + rng.uniform(-0.3, 1.1) * span
        states.append(("request in flight", a, a + rng.uniform(0.02, 0.4) * span))
    states.append(("waiting for a request", WALL0, WALL0 + span))
    return states


def assert_same(new, old):
    assert new.keys() == old.keys()
    for key in old:
        assert new[key] == old[key], key     # floats too: the same arithmetic


def recorded_rows():
    with open(os.path.join(BENCH_DATA, "small_trace.json")) as f:
        return [tuple(r) for r in json.load(f)["rows"]]


def toy_capture(tmp_path):
    src = os.path.join(BENCH_DATA, "toy_v5e.xplane.pb.gz")
    dst = tmp_path / "toy.xplane.pb"
    with gzip.open(src, "rb") as f:
        dst.write_bytes(f.read())
    return src, str(dst)


def states_over(rows, n, seed):
    """``n`` seeded states laid over the rows' own span."""
    ops = [r for r in rows if r[0].startswith(tr.DEVICE_PLANE)]
    off = tr.wall_offset_ns(rows) or 0
    lo = (min(r[3] for r in ops) + off) / 1e9
    hi = (max(r[3] + r[4] for r in ops) + off) / 1e9
    rng = random.Random(seed)
    out = []
    for i in range(n):
        a = rng.uniform(lo - 0.1 * (hi - lo), hi)
        out.append((f"state {i % 5}", a, a + rng.uniform(0.0, 0.3) * (hi - lo)))
    return out


@pytest.mark.parametrize("n_states", [0, 1, 7, 60])
def test_sweep_equals_the_old_loop_on_the_recorded_trace(n_states):
    rows = recorded_rows()
    states = states_over(rows, n_states, seed=n_states)
    assert_same(tr.reduce(rows, states), reduce_by_the_old_loop(rows, states))


@pytest.mark.parametrize("n_states", [0, 5, 40])
def test_sweep_equals_the_old_loop_on_the_toy_capture(tmp_path, n_states):
    rows = tr.load_xplane(toy_capture(tmp_path)[1])
    assert sum(r[0].startswith(tr.DEVICE_PLANE) for r in rows) > 50
    # the server's own capture: no benchmark annotation, so one is put in
    assert tr.wall_offset_ns(rows) is None
    rows.append(("/host:CPU", "python", tr.WALL_MARK + str(int(WALL0 * 1e9)), 0, 0))
    states = states_over(rows, n_states, seed=n_states)
    new = tr.reduce(rows, states)
    assert_same(new, reduce_by_the_old_loop(rows, states))
    if n_states:
        assert len(new["idle_gaps"]) > 1       # some named, some not


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("order", ["as built", "shuffled", "reversed"])
def test_sweep_equals_the_old_loop_on_seeded_rows(seed, order):
    """Overlapping states, states out of time order, and no list order the
    sweep could lean on."""
    rows, end = synthetic_rows(6000, seed, planes=2 if seed == 3 else 1)
    states = ring_and_requests(end, 60, 25, seed)
    if order == "shuffled":
        random.Random(seed).shuffle(states)
    elif order == "reversed":
        states.reverse()
    new = tr.reduce(rows, states)
    assert_same(new, reduce_by_the_old_loop(rows, states))
    # reversed, the span itself comes first and names every gap it holds
    assert len(new["idle_gaps"]) >= (2 if order == "reversed" else 3)


def one_gap_rows():
    """Operations at [0,10) and [30,40) us: one gap, [10,30) us, wall
    ``WALL0 + 10 us .. + 30 us`` (the mark ties trace 0 to ``WALL0``)."""
    us = 1000
    return [(D0, OPS, "%a.1", 0, 10 * us), (D0, OPS, "%b.2", 30 * us, 10 * us),
            ("/host:CPU", "python", tr.WALL_MARK + str(int(WALL0 * 1e9)), 0, 0)]


def at(us_from, us_to, label):
    return (label, WALL0 + us_from * 1e-6, WALL0 + us_to * 1e-6)


@pytest.mark.parametrize("states, label", [
    ([at(0, 100, "first"), at(0, 100, "second")], "first"),
    ([at(0, 100, "second"), at(0, 100, "first")], "second"),
    # the earlier entry wins though the later one covers more and starts sooner
    ([at(15, 30, "late but listed first"), at(0, 40, "whole")],
     "late but listed first"),
    ([at(0, 19.8, "49 %")], "host: nothing recorded"),
    ([at(0, 20.2, "51 %")], "51 %"),
    ([at(0, 19.8, "49 %"), at(19.8, 50, "51 % after it")], "51 % after it"),
    ([at(40, 90, "after the gap"), at(-50, 10, "before the gap")],
     "host: nothing recorded"),
    ([], "host: nothing recorded"),
])
def test_which_state_names_a_gap(states, label):
    rows = one_gap_rows()
    out = tr.reduce(rows, states)
    assert out["idle_gaps"] == [[label, pytest.approx(20e-6)]]
    assert_same(out, reduce_by_the_old_loop(rows, states))


def test_no_wall_mark_names_nothing():
    rows = one_gap_rows()[:2]
    out = tr.reduce(rows, [at(0, 100, "covers it")])
    assert out["idle_gaps"][0][0] == "host: nothing recorded"
    assert_same(out, reduce_by_the_old_loop(rows, [at(0, 100, "covers it")]))


def test_a_capture_sized_span_reduces_in_linear_time():
    """400,000 operations against 900 states (an OLMoE span is 450,000
    against 880). The old loop's cost is gaps x states: timed here on a
    twentieth of the rows and scaled, on the same machine in the same test,
    it must miss the sweep's whole time five times over."""
    rows, end = synthetic_rows(400_000, seed=7)
    states = ring_and_requests(end, 770, 129, seed=7)
    t0 = time.perf_counter()
    new = tr.reduce(rows, states)
    t_new = time.perf_counter() - t0
    part, part_end = synthetic_rows(20_000, seed=7)
    part_states = ring_and_requests(part_end, 770, 129, seed=7)
    t0 = time.perf_counter()
    old = reduce_by_the_old_loop(part, part_states)
    t_old = 20 * (time.perf_counter() - t0)
    assert_same(tr.reduce(part, part_states), old)
    assert sum(k["calls"] for k in new["kernels"].values()) == 400_000
    assert t_new < t_old / 5, (t_new, t_old)


def test_load_xplane_reads_the_recorded_capture_as_before(tmp_path):
    """The rows today's loader gives are the rows the loop over every event
    of every line gave (kept here), from the file and from its ``.gz``."""
    from jax.profiler import ProfileData

    gz, path = toy_capture(tmp_path)
    before = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(tr.DEVICE_PLANE)
        for line in plane.lines:
            if device and line.name != OPS:
                continue
            for ev in line.events:
                if device or ev.name.startswith(tr.WALL_MARK):
                    before.append((plane.name, line.name, ev.name,
                                   int(ev.start_ns), int(ev.duration_ns)))
    rows = tr.load_xplane(path)
    assert rows == before and len(rows) > 50
    assert tr.load_xplane(gz) == rows
    assert {r[1] for r in rows if r[0].startswith(tr.DEVICE_PLANE)} == {OPS}
