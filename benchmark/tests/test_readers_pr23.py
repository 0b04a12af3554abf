"""The readers ISSUE 23 added, each on a synthetic ``Run``: three of the
ring's split (``chunk_ms``, ``prefill_ms``), two of a request's own span tree
(``pool_wait``, ``evict``), one of ``tpusc_pool_wait_seconds`` (kept for the
time-to-first-token cell, reported by no cell yet). Every one gives nothing,
and does not raise, on what a program older than the PR would hand it."""

import json
import os

import pytest

import run as benchrun
from client import new_record
from conftest import ROOT, result_line, run_cell
from measure import Run

NEW = {"mistral7b-chat-steady": {"chunk_step_p50_ms", "prefill_stall_p50_ms",
                                 "boundary_host_p50_ms"},
       "smollm2-tenants-churn": {"protocol_self_p50_ms", "load_evict_p50_ms"}}


def make_run(steps=(), records=()) -> Run:
    r = Run(cell={}, config={}, program_config={}, server={}, device={},
            seconds=10.0, t0=100.0, t_end=125.0)
    r.before = {"t": 100.0, "t_wall": 1000.0, "prom": {}}
    r.after = {"prom": {}}
    r.steps, r.records = list(steps), list(records)
    return r


def step(t_wall, step_ms, chunk, admitted, prefill_ms=None, chunk_ms=None,
         emit_ms=None) -> dict:
    s = {"t_wall": t_wall, "engine": "continuous", "step_ms": step_ms,
         "chunk": chunk, "active": 4, "admitted": admitted, "retired": 0}
    if chunk_ms is not None:
        s.update(prefill_ms=prefill_ms, chunk_ms=chunk_ms, emit_ms=emit_ms)
    return s


STEPS = [
    step(1001.0, 640.0, 8, 0, 0.0, 624.0, 1.0),     # chunk only: 78 a step
    step(1002.0, 700.0, 8, 1, 40.0, 640.0, 1.0),    # admitted one: 80 a step
    step(1003.0, 780.0, 8, 2, 90.0, 656.0, 2.0),    # admitted two: 82 a step
    step(1004.0, 30.0, 0, 1, 25.0, 0.0, 0.0),       # prefill-only boundary
    step(1020.0, 999.0, 8, 1, 500.0, 400.0, 1.0),   # after the window
]


def reader(name):
    return benchrun.load_reader("per_layer", name)


def test_chunk_step_reads_every_boundary_that_ran_a_chunk():
    assert reader("chunk_step_p50_ms")(make_run(STEPS)) == (80.0, 3)
    # the older reader can only use the boundary that admitted nothing
    assert reader("decode_step_p50_ms")(make_run(STEPS)) == (80.0, 1)


def test_prefill_stall_reads_the_admitting_boundaries():
    assert reader("prefill_stall_p50_ms")(make_run(STEPS)) == (40.0, 3)


def test_boundary_host_is_what_prefill_and_chunk_leave_of_the_step():
    value, n = reader("boundary_host_p50_ms")(make_run(STEPS))
    assert n == 3 and value == pytest.approx(20.0)      # 16, 20, 34


@pytest.mark.parametrize("name", sorted(NEW["mistral7b-chat-steady"]))
def test_ring_readers_give_nothing_on_a_ring_without_the_split(name):
    old = [step(1001.0 + i, 640.0, 8, i % 2) for i in range(4)]
    assert reader(name)(make_run(old)) is None
    assert reader(name)(make_run([])) is None


def span(name, duration_s, children=(), **attrs) -> dict:
    return {"name": name, "duration_s": duration_s, "attrs": attrs,
            "children": list(children)}


def predict(index, due, root) -> dict:
    rec = new_record("predict", "tenant00", index, due, 128, 0)
    rec.update(ok=True, end=due + 0.1, span=root)
    return rec


def cold_root(evicts=(0.004,)) -> dict:
    load = span("load", 0.060, [span("device_transfer", 0.046),
                                span("transfer_sync", 0.002)]
                + [span("evict", e, victim="t@1", bytes=7, demoted="retained")
                   for e in evicts], tier="host")
    return span("rest", 0.100, [
        span("pool_wait", 0.001, what="codec"),
        span("pool_wait", 0.002, what="predict"),
        span("ensure_servable", 0.070, [load]),
        span("infer", 0.012),
        span("pool_wait", 0.001, what="codec")])


def test_protocol_self_is_the_root_minus_waits_ensure_and_infer():
    warm = span("rest", 0.020, [span("pool_wait", 0.001, what="codec"),
                                span("ensure_servable", 0.001),
                                span("infer", 0.012)])
    old = span("rest", 0.020, [span("ensure_servable", 0.001),
                               span("infer", 0.012)])       # no pool_wait spans
    recs = [predict(0, 101.0, cold_root()), predict(1, 102.0, warm),
            predict(2, 103.0, old), predict(3, 104.0, None),
            predict(4, 120.0, warm)]                        # after the window
    value, n = reader("protocol_self_p50_ms")(make_run(records=recs))
    assert n == 2 and value == pytest.approx((14.0 + 6.0) / 2)
    assert reader("protocol_self_p50_ms")(make_run(records=recs[2:4])) is None


def test_load_evict_sums_the_victims_of_one_host_load():
    disk = span("rest", 0.5, [span("load", 0.4, [span("evict", 0.009)],
                                   tier="disk")])
    recs = [predict(0, 101.0, cold_root((0.004,))),
            predict(1, 102.0, cold_root((0.003, 0.005))),
            predict(2, 103.0, cold_root(())),               # room was there
            predict(3, 104.0, disk)]
    value, n = reader("load_evict_p50_ms")(make_run(records=recs))
    assert n == 2 and value == pytest.approx(6.0)           # 4 and 3 + 5
    assert reader("load_evict_p50_ms")(make_run(records=recs[2:])) is None


def test_pool_wait_mean_is_the_histograms_growth_over_the_window():
    r = make_run()
    key = 'tpusc_pool_wait_seconds_%s{what="%s"}'
    r.before["prom"] = {key % ("sum", "generate"): 1.0, key % ("count", "generate"): 10.0,
                        key % ("sum", "codec"): 0.5, key % ("count", "codec"): 20.0}
    r.after["prom"] = {key % ("sum", "generate"): 3.0, key % ("count", "generate"): 20.0,
                       key % ("sum", "codec"): 0.6, key % ("count", "codec"): 50.0,
                       'tpusc_pool_wait_seconds_bucket{le="0.001",what="codec"}': 45.0}
    assert reader("pool_wait_mean_ms")(r) == (pytest.approx(52.5), 40)
    r.after = r.before
    assert reader("pool_wait_mean_ms")(r) is None
    assert reader("pool_wait_mean_ms")(make_run()) is None   # no such family


LAYER = {"chunk_step_p50_ms": "model step models/generation.py",
         "prefill_stall_p50_ms": "engine runtime/batcher.py",
         "boundary_host_p50_ms": "engine runtime/batcher.py",
         "protocol_self_p50_ms": "protocol protocol/rest.py local_backend.py",
         "load_evict_p50_ms": "runtime load runtime/model_runtime.py"}
ENTRIES = [(cell, name) for cell in sorted(NEW) for name in sorted(NEW[cell])]


@pytest.mark.parametrize("cell, name", ENTRIES)
def test_benchmark_json_holds_each_of_the_five_entries_once(cell, name):
    """By name, not by place: later PRs append entries after these, and add
    their own cells to a metric's list (OLMoE's to the three ring readers)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    found = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(found) == 1
    entry = found[0]
    assert cell in entry["workloads"]
    other = next(c for c in NEW if c != cell)
    assert other not in entry["workloads"]
    assert (entry["layer"], entry["unit"], entry["source"]) == (
        LAYER[name], "ms", "program_span")
    assert entry["moves"] == ("tpot_p50_ms" if "chat" in cell else "cold_p50_s")
    assert all(m["name"] != "pool_wait_mean_ms" for m in bench["per_layer"])
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", "pool_wait_mean_ms.py"))


@pytest.mark.parametrize("cell", sorted(NEW))
def test_traced_rehearsal_prints_a_sample_count_for_each_new_reader(cell):
    r = run_cell(ROOT, "--workload", cell, "--seed", "2147483659", "--seconds", "3",
                 "--trace", "1", "--rehearsal")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    res = result_line(r.stdout)
    assert res is not None and res["correct"] is True
    for name in NEW[cell]:
        assert res["metrics"][f"rehearsal.{name}.samples"]["value"] >= 1
        line = next(ln for ln in r.stdout.splitlines()
                    if ln.startswith(f"metric {name} ="))
        assert " over " in line and line.endswith(" samples")
