"""benchmark/capture_programs.py and its five readers under tier-1 (ISSUE 35):
a traced run's capture by PROGRAM EXECUTION. The arithmetic runs on rows built
by hand; the loader on two recorded v5e captures: ``toy_v5e.xplane.pb.gz`` (PR
23: three launches of one toy program) and ``cut_lfm2_v5e.xplane.pb.gz``, cut
from a traced run of ``lfm2-longgen-steady`` (PR 33's program, so without the
``tpusc.chunk_launch`` span): 570 ms of its ``XLA Modules`` line and of the
engine thread's annotations and launches, 227 ms of its ``XLA Ops`` line (the
tail of one decode chunk, an admission, one whole chunk, a decode-only
boundary, the head of the next chunk), operation names cut at `` = ``, stats
other than ``run_id`` / ``tf_op`` / ``program_id`` dropped. ``tools/
trace_scopes.py`` reads the same capture to the same figures.

The benchmark's modules are imported with ``benchmark/`` on ``sys.path`` for
this module's tests only."""

import importlib.util
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(HERE, "..", "benchmark")
DATA = os.path.join(HERE, "data")
CUT = os.path.join(DATA, "cut_lfm2_v5e.xplane.pb.gz")
BENCH_MODULES = ("capture_programs", "capture_scopes", "measure", "run", "client",
                 "survey", "kernel_costs_hybrid", "kernel_costs_moe", "kernel_costs")
NEW = ["chunk_launch_p50_ms", "chunk_gap_p50_ms", "launches_per_chunk",
       "decode_bubble_ms_per_step", "insert_ms_per_admission"]


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        import capture_programs
        import capture_scopes
        import measure
        import run as benchrun

        yield types.SimpleNamespace(cp=capture_programs, cs=capture_scopes,
                                    measure=measure, run=benchrun)
    finally:
        sys.path.remove(BENCH)
        for name in list(sys.modules):
            if name in BENCH_MODULES or name.startswith("bench_layer_metrics_"):
                del sys.modules[name]


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "trace_scopes", os.path.join(HERE, "..", "tools", "trace_scopes.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- (a) the arithmetic, on rows built by hand ---------------------------------
# Device times in microseconds (below, x 1000 = ns); host time = device + 5.
# K = a key program, C = a decode chunk, P = a prefill, I = the page insert.
#   boundary 1: K1 [10,20)  C1 [30,130) with a HOLE: operations [30,70) [90,130)
#   boundary 2: K2 [160,170) C2 [180,280)      back to back: gap 50 - 10 = 40
#   (the engine waits 1010 us for a request)
#   boundary 3: K3 [1310,1320) C3 [1330,1430)  a waiting gap: must NOT count
#   boundary 4: P [1440,1490) I [1500,1530) K4 [1535,1545) C4 [1550,1650)
#               an insert between its chunks: must not count
#   boundary 5: K5 [1670,1680) C5 [1690,1790)  back to back: gap 40 - 10 = 30
DECODE, KEYS = "jit__paged_decode_chunk_jit", "jit__threefry_split"
PROGRAMS = [(KEYS, 10, 20, 1), (DECODE, 30, 130, 2), (KEYS, 160, 170, 3),
            (DECODE, 180, 280, 4), (KEYS, 1310, 1320, 5), (DECODE, 1330, 1430, 6),
            ("jit__slot_prefill_jit", 1440, 1490, 7),
            ("jit__paged_insert_jit", 1500, 1530, 8), (KEYS, 1535, 1545, 9),
            (DECODE, 1550, 1650, 10), (KEYS, 1670, 1680, 11), (DECODE, 1690, 1790, 12)]
OPS = [(10, 20), (30, 70), (90, 130), (160, 170), (180, 280), (1310, 1320),
       (1330, 1430), (1440, 1490), (1500, 1530), (1535, 1545), (1550, 1650),
       (1670, 1680), (1690, 1790)]
ENGINE = [
    ("tpusc.boundary", 5, 140), ("tpusc.decode_chunk", 6, 139),
    ("tpusc.chunk_launch", 7, 37), ("tpusc.chunk_fetch", 37, 138),
    ("tpusc.boundary", 141, 290), ("tpusc.decode_chunk", 145, 288),
    ("tpusc.chunk_launch", 146, 187), ("tpusc.chunk_fetch", 187, 287),
    ("tpusc.boundary", 1300, 1440), ("tpusc.decode_chunk", 1302, 1438),
    ("tpusc.chunk_launch", 1303, 1337), ("tpusc.chunk_fetch", 1337, 1437),
    ("tpusc.boundary", 1441, 1660), ("tpusc.admit", 1442, 1548),
    ("tpusc.prefill", 1443, 1500), ("tpusc.decode_chunk", 1549, 1658),
    ("tpusc.chunk_launch", 1550, 1557), ("tpusc.chunk_fetch", 1557, 1657),
    ("tpusc.boundary", 1661, 1800), ("tpusc.decode_chunk", 1665, 1798),
    ("tpusc.chunk_launch", 1666, 1697), ("tpusc.chunk_fetch", 1697, 1797)]
ENQUEUE = {1: 13, 2: 33, 3: 163, 4: 184, 5: 1313, 6: 1334, 7: 1444, 8: 1503,
           9: 1538, 10: 1554, 11: 1673, 12: 1694}
COMPLETE = {2: 136}


def by_hand(cp, programs=PROGRAMS, engine=ENGINE, enqueue=ENQUEUE, complete=COMPLETE):
    k = 1000.0
    return cp.reduce(
        [(n, s * k, e * k, r) for n, s, e, r in programs],
        np.asarray([s * k for s, _e in OPS]), np.asarray([e * k for _s, e in OPS]),
        [(n, s * k, e * k) for n, s, e in engine],
        {r: t * k for r, t in enqueue.items()}, {r: t * k for r, t in complete.items()})


def test_an_execution_is_its_wall_its_busy_time_and_the_gap_before_it(bench):
    cap = by_hand(bench.cp)
    assert cap["span"] == (10e3, 1790e3)
    assert cap["busy_ns"] == 610e3 and cap["idle_ns"] == 1170e3
    table = cap["programs"]
    assert table["name"][1] == DECODE
    # the execution with a hole: 100 us of wall, 80 busy, 20 idle INSIDE it
    assert (table["busy"][1], table["idle"][1]) == (80e3, 20e3)
    assert [i for i in table["idle"] if i] == [20e3]
    assert np.isnan(table["gap"][0]) and list(table["gap"][1:4]) == [10e3, 30e3, 10e3]
    rows = {r[0]: r[1:] for r in bench.cp.by_program(cap)}
    runs, wall, busy, idle, gap = rows[DECODE]
    assert (runs, wall, busy, idle) == (5, pytest.approx(500e-6),
                                        pytest.approx(480e-6), pytest.approx(20e-6))
    assert gap == pytest.approx((10 + 10 + 10 + 5 + 10) / 5 * 1e-6)
    assert rows["jit__paged_insert_jit"][:2] == (1, pytest.approx(30e-6))


def test_the_clock_shift_is_bounded_by_every_launch(bench):
    cap = by_hand(bench.cp)
    # a program starts after its enqueue (low), ends before its completion (high)
    assert cap["shift"] == (4e3, 6e3, 12)
    quiet = bench.cp.reduce(
        [(n, s * 1e3, e * 1e3, r) for n, s, e, r in PROGRAMS],
        np.asarray([s * 1e3 for s, _e in OPS]), np.asarray([e * 1e3 for _s, e in OPS]),
        [], {}, {})
    assert quiet["shift"] == (0.0, 0.0, 0) and not bench.cp.counted(quiet)
    # the clocks drift over a span, so the bounds may cross by as much (on the
    # chip: 0.501 / 0.485 ms, 1.459 / 1.363 ms): the middle stands
    drifted = by_hand(bench.cp, complete={2: 133.5})
    assert drifted["shift"] == (4e3, 3.5e3, 12)
    assert drifted["causes"][bench.cp.IN_LAUNCH] == pytest.approx(cap["causes"][bench.cp.IN_LAUNCH], rel=0.1)
    # bounds half a millisecond the wrong way are clocks that cannot be tied
    assert by_hand(bench.cp, complete={2: -400.0})["shift"] == (0.0, 0.0, 0)


def test_only_decode_only_back_to_back_boundaries_count(bench):
    cap = by_hand(bench.cp)
    chunks = cap["chunks"]
    assert [c["launches"] for c in chunks] == [2, 2, 4, 2]
    assert [c["counts"] for c in chunks] == [True, False, False, True]
    waited, admitted = chunks[1], chunks[2]
    # the waiting gap: decode-only, but the engine slept a millisecond before it
    assert waited["decode_only"] and not waited["back_to_back"]
    assert waited["gap_ns"] == 1040e3
    # the insert between the chunks: back to back, but not decode-only
    assert admitted["back_to_back"] and not admitted["decode_only"]
    assert admitted["between"] == ["jit__slot_prefill_jit", "jit__paged_insert_jit", KEYS]
    good = bench.cp.counted(cap)
    assert [c["gap_ns"] for c in good] == [40e3, 30e3]     # the key program's 10 us is not idle
    assert good[0]["boundary"] == (141e3, 290e3)


def test_a_chunk_is_tied_to_its_boundary_by_the_enqueue_inside_the_launch_span(bench):
    # the last chunk's enqueue is not in the capture: no boundary, not counted
    lost = {r: t for r, t in ENQUEUE.items() if r != 12}
    cap = by_hand(bench.cp, enqueue=lost)
    assert [c["counts"] for c in cap["chunks"]] == [True, False, False, False]
    assert cap["chunks"][3]["boundary"] is None
    # an enqueue that falls outside every launch span ties to nothing either
    late = {**ENQUEUE, 12: 1699}
    assert not by_hand(bench.cp, enqueue=late)["chunks"][3]["counts"]
    # a program older than the span: the enqueue inside tpusc.decode_chunk
    older = [m for m in ENGINE if m[0] not in ("tpusc.chunk_launch", "tpusc.chunk_fetch")]
    cap = by_hand(bench.cp, engine=older)
    assert [c["counts"] for c in cap["chunks"]] == [True, False, False, True]
    assert cap["causes"][bench.cp.IN_CHUNK] > 0
    assert bench.cp.IN_LAUNCH not in cap["causes"]


def test_idle_time_by_cause_sums_to_the_spans_idle(bench):
    cp = bench.cp
    cap = by_hand(cp)
    causes = {k: v / 1e3 for k, v in cap["causes"].items() if v}
    assert causes == {
        f"inside {DECODE}": 20.0, cp.IN_LAUNCH: 85.0, cp.IN_FETCH: 9.0,
        cp.IN_CHUNK: 7.0, cp.IN_BOUNDARY: 18.0, cp.NO_BOUNDARY: 1013.0,
        cp.IN_ADMISSION: 18.0}
    assert sum(cap["causes"].values()) == cap["idle_ns"]
    sides = cp.identity(cap)
    # gap = fetch return + boundary work + launch to the device's start
    assert sides["gap"] == pytest.approx(0.035)
    assert sides["fetch"] + sides["boundary"] + sides["launch"] == pytest.approx(0.045)
    text = "\n".join(cp.report(cap))
    assert "4.000..6.000 ms" not in text and "0.004..0.006 ms" in text
    assert "2 x 2 launches: jit__threefry_split, then the chunk" in text
    assert ("idle gap before each of their launches, medians ms: jit__threefry_split 0.025, "
            "jit__paged_decode_chunk_jit 0.010") in text


def test_nothing_to_read_is_an_empty_answer(bench):
    cap = bench.cp.reduce([], np.asarray([]), np.asarray([]), [], {}, {})
    assert cap["programs"] is None and cap["chunks"] == [] and cap["causes"] == {}
    assert bench.cp.by_program(cap) == []


# -- the recorded captures ------------------------------------------------------

def test_the_toy_capture_as_far_as_it_goes(bench, tool):
    """Three launches of one program, no decode chunk: executions, busy time and
    the clock shift read as the tool reads them; no chunk metric."""
    path = os.path.join(DATA, "toy_v5e.xplane.pb.gz")
    cap = bench.cp.load(path)
    assert cap["device"].startswith("/device:TPU:")
    (name, runs, wall, busy, idle, gap), = bench.cp.by_program(cap)
    assert (name, runs) == ("jit_step", 3)
    assert 0 < busy <= wall and idle == pytest.approx(wall - busy)
    assert gap > 2e-3                                 # the host slept 2 ms in tpusc.emit
    assert cap["shift"] == (1264245.0, 1691159.0, 3)  # tests/test_trace_scopes_tool.py
    assert cap["chunks"] == []
    assert sum(cap["causes"].values()) == pytest.approx(cap["idle_ns"])
    assert set(cap["marks"]) == {"tpusc.boundary", "tpusc.decode_chunk", "tpusc.emit"}


def test_the_cut_capture_reads_as_the_issues_table(bench):
    cp = bench.cp
    cap = cp.load(CUT)
    rows = {r[0]: r[1:] for r in cp.by_program(cap)}
    runs, wall, busy, idle, _gap = rows[DECODE]
    assert runs == 3 and idle / wall < 0.003          # in-program idle under 0.3 %
    assert rows["jit__paged_insert_jit"][:2] == (1, pytest.approx(8.741e-3, rel=1e-3))
    # two chunks follow another: across the admission (not counted), and one
    # decode-only boundary: 4 launches a chunk, the device idle 8.6 ms before it
    assert [c["counts"] for c in cap["chunks"]] == [False, True]
    skipped, good = cap["chunks"]
    assert not skipped["decode_only"] and "jit__paged_insert_jit" in skipped["between"]
    assert good["launches"] == 4 and good["between"] == [
        "jit_convert_element_type", "jit__threefry_seed", "jit__threefry_split"]
    assert good["gap_ns"] == pytest.approx(8.599e6, rel=1e-3)
    low, high, launches = cap["shift"]
    assert launches == 17 and 0.9e6 < low < high < 1.3e6
    assert sum(cap["causes"].values()) == pytest.approx(cap["idle_ns"])
    # PR 33's program has no child span: its launch path reads under decode_chunk
    assert cap["causes"][cp.IN_CHUNK] > 0.3 * cap["idle_ns"]
    assert cp.IN_LAUNCH not in cap["causes"]


def test_the_tool_and_the_benchmark_print_the_same_figures(bench, tool):
    cap = bench.cp.load(CUT)
    rows = tool.load(CUT)
    mine = {r[0]: r[1:] for r in bench.cp.by_program(cap)}
    theirs = {r[0]: r[1:] for r in tool.by_execution(rows)}
    assert set(mine) == set(theirs)
    for name, row in mine.items():
        assert theirs[name] == pytest.approx(row, rel=1e-9, abs=1e-12), name
    lo, hi, launches = tool.clock_shift_ns(rows)
    assert (lo, hi, launches) == tuple(int(x) for x in cap["shift"])
    causes = dict(tool.idle_by_cause(rows, (lo + hi) // 2))
    assert set(causes) == set(cap["causes"])
    for cause, ns in cap["causes"].items():
        assert causes[cause] == pytest.approx(ns / 1e9, rel=1e-9, abs=1e-9), cause
    assert sum(causes.values()) == pytest.approx(cap["idle_ns"] / 1e9)


# -- (d) the five readers --------------------------------------------------------

def make_run(bench, steps, platform="tpu"):
    r = bench.measure.Run(cell={}, config={}, program_config={"n_layers": 2},
                          server={}, device={"platform": platform, "kind": "TPU v5 lite"},
                          seconds=10.0, t0=100.0, t_end=125.0)
    r.before = {"t": 100.0, "t_wall": 1000.0, "prom": {}}
    r.after = {"prom": {}}
    r.steps, r.trace = list(steps), {"kernels": {}}
    r.trace_wall = (1004.0, 1008.0)
    return r


def ring_step(t_wall, chunk=8, admitted=0, launch_ms=5.0, **kw):
    return dict({"t_wall": t_wall, "engine": "continuous", "step_ms": 250.0,
                 "chunk": chunk, "active": 4, "admitted": admitted, "retired": 0,
                 "prefill_ms": 0.0, "chunk_ms": 240.0, "emit_ms": 1.0,
                 "launch_ms": launch_ms}, **kw)


STEPS = [ring_step(1003.0, launch_ms=4.0), ring_step(1005.0, admitted=1, launch_ms=6.0),
         ring_step(1006.0, admitted=2, launch_ms=5.0), ring_step(1007.0, chunk=0, launch_ms=0.0),
         ring_step(1030.0, launch_ms=50.0)]                 # after the window


def reader(bench, name):
    return bench.run.load_reader("per_layer", name)


def test_readers_read_the_capture_by_hand(bench, monkeypatch):
    cap = by_hand(bench.cp)
    monkeypatch.setattr(bench.cp, "capture_of", lambda run: cap)
    run = make_run(bench, STEPS)
    assert reader(bench, "chunk_launch_p50_ms")(run) == (5.0, 3)   # the window's 4, 6, 5
    assert reader(bench, "chunk_gap_p50_ms")(run) == (pytest.approx(0.035), 2)
    assert reader(bench, "launches_per_chunk")(run) == (2.0, 2)
    # 20 us of holes over the ring's decode steps inside the span: boundaries
    # ending at 1005 and 1006 lie inside it whole, 8 steps each
    value, steps = reader(bench, "decode_bubble_ms_per_step")(run)
    assert (value, steps) == (pytest.approx(0.020 / 16), 16)
    assert reader(bench, "insert_ms_per_admission")(run) == (pytest.approx(0.030), 1)


@pytest.mark.parametrize("name", NEW)
def test_a_rehearsal_shows_counts_and_no_value(bench, name):
    run = make_run(bench, STEPS, platform="cpu")
    want = {"chunk_launch_p50_ms": (5.0, 3), "chunk_gap_p50_ms": (0.0, 2),
            "launches_per_chunk": (0.0, 2), "decode_bubble_ms_per_step": (0.0, 16),
            "insert_ms_per_admission": (0.0, 3)}[name]
    assert reader(bench, name)(run) == want


@pytest.mark.parametrize("name", NEW)
def test_readers_give_nothing_where_nothing_is_to_be_read(bench, name, monkeypatch):
    """The parent's program (a ring without ``launch_ms``), a capture that cannot
    be found, a span with no decode chunk, an untraced run: nothing, no raise."""
    old_ring = [{k: v for k, v in s.items() if k != "launch_ms"} for s in STEPS]
    monkeypatch.setattr(bench.cp, "capture_of", lambda run: None)
    assert reader(bench, name)(make_run(bench, old_ring)) is None
    empty = bench.cp.reduce(
        [("jit_step", 0.0, 10.0, 1)], np.asarray([0.0]), np.asarray([10.0]), [], {}, {})
    monkeypatch.setattr(bench.cp, "capture_of", lambda run: empty)
    if name != "chunk_launch_p50_ms":
        assert reader(bench, name)(make_run(bench, STEPS)) is None
        untraced = make_run(bench, STEPS)
        untraced.trace_wall = None
        assert reader(bench, name)(untraced) is None
    idle_ring = [ring_step(1005.0, chunk=0)]
    assert reader(bench, name)(make_run(bench, idle_ring, platform="cpu")) is None


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_chunk_uploads_mean_reads_the_ring_and_nothing_of_an_older_one(bench, platform):
    """PR 36's reader: the mean of ring ``uploads`` over the window's boundaries
    that ran a chunk (a count, so a rehearsal shows it too); the parent's ring
    has no such field and gives nothing."""
    read = reader(bench, "chunk_uploads_mean")
    steps = [dict(s, uploads=u) for s, u in zip(STEPS, (7, 3, 0, 0, 7))]
    assert read(make_run(bench, steps, platform=platform)) == (pytest.approx(10 / 3), 3)
    assert read(make_run(bench, STEPS, platform=platform)) is None
    assert read(make_run(bench, [ring_step(1005.0, chunk=0, uploads=0)],
                         platform=platform)) is None


def test_the_capture_is_found_as_capture_scopes_finds_it_and_reported_once(
        bench, monkeypatch, capsys):
    monkeypatch.setattr(bench.cs, "find_capture", lambda wall: CUT)
    run = make_run(bench, STEPS)
    cap = bench.cp.capture_of(run)
    assert cap is bench.cp.capture_of(run)            # cached a process
    out = capsys.readouterr().out
    assert out.count("capture by program execution") == 1
    assert "idle seconds by cause" in out and "1 x 4 launches" in out
    monkeypatch.setattr(bench.cs, "find_capture", lambda wall: None)
    assert bench.cp.capture_of(run) is None
    run.trace_wall = None
    assert bench.cp.capture_of(run) is None


def test_the_five_metrics_are_appended_for_the_four_generate_cells():
    import json

    with open(os.path.join(HERE, "..", "BENCHMARK.json")) as f:
        bench_json = json.load(f)
    # PR 35's five, then PR 36's count of what a chunk's launch uploaded; PR 39
    # appended its window layers' five after them and its cell to their lists,
    # PR 40 the share of chunks launched ahead, PR 41 its five and its cell,
    # PR 46 its four and its cell, PR 47 the chunked rule's kernel's share,
    # PR 48 the rows a prefill computes a real row (the seven generating cells),
    # PR 49 its Kimi-delta-attention layers' three and its cell, PR 50 the
    # channel-decay chunked rule's kernel's share (the twin of PR 47's), PR 51
    # the bring-up account's six (every cell; the two of the arena the eight
    # generating cells)
    # PR 53 the five of a model whose query heads go by layer (its cell alone)
    # and its cell on every list the generating cells are on
    cells = [w["name"] for w in bench_json["workloads"]]
    heads = bench_json["per_layer"][-5:]
    assert [m["name"] for m in heads] == [
        "heads_window_decode_roofline", "heads_global_decode_roofline",
        "heads_window_prefill_roofline", "attn_kind_ms_per_step",
        "share_sparse_experts_roofline"]
    for m in heads:
        assert m["workloads"] == ["laguna-repoctx-steady"]
        assert (m["source"], m["moves"]) == ("device_trace", "tpot_p50_ms")
        assert (m["unit"], m["better"]) == (
            ("ms", "lower") if m["name"] == "attn_kind_ms_per_step"
            else ("%", "higher"))
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    bench_json["per_layer"] = bench_json["per_layer"][:-5]
    assert {m["layer"] for m in heads} <= {m["layer"] for m in bench_json["per_layer"]}
    account = bench_json["per_layer"][-6:]
    assert [m["name"] for m in account] == [
        "setup_trace_lower_s", "setup_compile_s", "setup_load_s",
        "setup_engine_build_s", "setup_unexplained_s", "device_unowned_peak_bytes"]
    for m in account:
        assert (m["better"], m["source"]) == ("lower", "host_clock")
        assert set(cells) - set(m["workloads"]) <= {"smollm2-tenants-churn"}
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    bench_json["per_layer"] = bench_json["per_layer"][:-6]
    twin = bench_json["per_layer"].pop()
    assert twin["name"] == "kda_chunk_roofline"
    assert twin["workloads"] == ["solaropen2-docreport-steady"]
    assert (twin["unit"], twin["better"], twin["source"], twin["moves"]) == (
        "%", "higher", "device_trace", "tpot_p50_ms")
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", twin["name"] + ".py"))
    kda = bench_json["per_layer"][-3:]
    assert [m["name"] for m in kda] == [
        "kda_layers_ms_per_step", "kda_prefill_ms_per_ktok", "kda_step_roofline"]
    for m in kda:
        assert m["workloads"] == ["solaropen2-docreport-steady"]
        assert (m["source"], m["moves"]) == ("device_trace", "tpot_p50_ms")
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    assert (kda[-1]["unit"], kda[-1]["better"]) == ("%", "higher")
    bench_json["per_layer"] = bench_json["per_layer"][:-3]
    tail = bench_json["per_layer"][-23:-17]
    assert [m["name"] for m in tail] == NEW + ["chunk_uploads_mean"]
    assert tail[-1]["unit"] == "operands" and tail[-1]["source"] == "program_counter"
    assert [m["name"] for m in bench_json["per_layer"][-17:]] == [
        "window_decode_ms_per_call", "window_decode_roofline",
        "window_prefill_roofline", "window_pages_read_mean",
        "global_decode_roofline", "chunks_ahead_share",
        "ssm_layers_ms_per_step", "gmu_layers_ms_per_step",
        "ssm_prefill_ms_per_ktok", "shared_kv_decode_roofline",
        "shared_pages_read_mean", "gdn_layers_ms_per_step",
        "gdn_prefill_ms_per_ktok", "gdn_step_roofline", "state_write_lanes_mean",
        "gdn_chunk_roofline", "prefill_rows_per_real_row"]
    last = bench_json["per_layer"][-2]
    assert last["workloads"] == ["olmohybrid-longdoc-steady"]
    assert (last["unit"], last["better"], last["source"]) == (
        "%", "higher", "device_trace")
    assert last["moves"] == "tpot_p50_ms"
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", last["name"] + ".py"))
    cells = ["mistral7b-chat-steady", "olmoe-chat-steady", "mistral4-docqa-steady",
             "lfm2-longgen-steady", "mellum2-codectx-mixed",
             "phi4flash-reasoning-steady", "olmohybrid-longdoc-steady",
             "solaropen2-docreport-steady", "laguna-repoctx-steady"]
    layers = {m["layer"] for m in bench_json["per_layer"][:-23]}
    assert {m["layer"] for m in kda} | {twin["layer"]} <= layers
    assert last["layer"] in layers
    rows = bench_json["per_layer"][-1]
    assert rows["workloads"] == cells and rows["layer"] in layers
    assert (rows["unit"], rows["better"], rows["source"], rows["moves"]) == (
        "rows", "lower", "program_counter", "tpot_p50_ms")
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", rows["name"] + ".py"))
    for m in tail:
        assert m["workloads"] == cells and m["moves"] == "tpot_p50_ms"
        assert m["better"] == "lower" and m["layer"] in layers
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))


# Since PR 48 a long slot prefill's token-wise stages run in a loop over the row
# blocks that hold real rows, INSIDE the stage's scope: what the device then
# reports for a looped stage's operations.
LOOPED_PREFILL = {
    "jit(_slot_prefill_jit)/layer/ffn/while/body/dot_general": [0.0120, 48],
    "jit(_slot_prefill_jit)/layer/ffn/while/body/dynamic_update_slice": [0.0010, 48],
    "jit(_slot_prefill_jit)/layer/gdn/proj/while/body/dot_general": [0.0070, 36],
    "jit(_slot_prefill_jit)/layer/gdn/gate/while/body/mul": [0.0020, 36],
    "jit(_slot_prefill_jit)/layer/gdn/chunk/jit(delta_chunk_kernel)/pallas_call": [0.0030, 6],
    "jit(_slot_prefill_jit)/layer/attn/global/while/body/dot_general": [0.0015, 8],
    # where a loop OUTSIDE the stage's scope would put the same operations
    "jit(_slot_prefill_jit)/layer/while/body/ffn/dot_general": [9.0, 1],
    "jit(_paged_decode_chunk_jit)/while/body/closed_call/layer/ffn/dot_general": [0.5, 64],
}


@pytest.mark.parametrize("scope, seconds, events", [
    ("layer/ffn", 0.0130, 96), ("layer/gdn", 0.0120, 78),
    ("layer/gdn/proj", 0.0070, 36), ("layer/gdn/gate", 0.0020, 36),
    ("layer/gdn/chunk", 0.0030, 6), ("layer/attn", 0.0015, 8),
    ("layer/attn/global", 0.0015, 8)])
def test_a_looped_stage_is_read_under_its_scope(bench, scope, seconds, events):
    """``layer/ffn/while/body/...`` runs through ``layer/ffn`` for every reader
    by scope; ``layer/while/body/ffn`` would not, and the decode chunk's own
    operations are another program's."""
    got = bench.cs.scope_seconds(LOOPED_PREFILL, "_slot_prefill_jit", scope)
    assert got == (pytest.approx(seconds), events)
