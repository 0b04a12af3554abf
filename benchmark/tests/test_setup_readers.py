"""The six readers of the program's bring-up account (PR 51), each over a
hand-made ``Run``: its value from the Prometheus snapshot at the window's
start, and None (never an exception) over what a program older than the
account exposes, so that the parent's program under these files prints
"nothing to read, left out"."""

import json
import os

import pytest

import run as benchrun
from client import parse_prometheus
from conftest import ROOT
from measure import Run

READERS = ("setup_trace_lower_s", "setup_compile_s", "setup_load_s",
           "setup_engine_build_s", "setup_unexplained_s",
           "device_unowned_peak_bytes")
GB = 1 << 30

# the window opened at wall 1000; the harness's checks took the 20 s before it
ACCOUNT = """
tpusc_program_build_seconds_total{program="_slot_prefill_jit",stage="trace"} 6.0
tpusc_program_build_seconds_total{program="_slot_prefill_jit",stage="lower"} 3.0
tpusc_program_build_seconds_total{program="_slot_prefill_jit",stage="cache_load"} 2.0
tpusc_program_build_seconds_total{program="_paged_decode_chunk_jit",stage="trace"} 1.0
tpusc_program_build_seconds_total{program="_paged_decode_chunk_jit",stage="lower"} 0.5
tpusc_program_build_seconds_total{program="_paged_decode_chunk_jit",stage="compile"} 8.0
tpusc_program_build_seconds_total{program="reference_forward",stage="trace"} 4.0
tpusc_program_build_seconds_total{program="reference_forward",stage="compile"} 5.0
tpusc_program_build_seconds_created{program="_slot_prefill_jit",stage="trace"} 950.0
tpusc_program_build_seconds_created{program="_slot_prefill_jit",stage="lower"} 951.0
tpusc_program_build_seconds_created{program="_slot_prefill_jit",stage="cache_load"} 952.0
tpusc_program_build_seconds_created{program="_paged_decode_chunk_jit",stage="trace"} 960.0
tpusc_program_build_seconds_created{program="_paged_decode_chunk_jit",stage="lower"} 961.0
tpusc_program_build_seconds_created{program="_paged_decode_chunk_jit",stage="compile"} 962.0
tpusc_program_build_seconds_created{program="reference_forward",stage="trace"} 985.0
tpusc_program_build_seconds_created{program="reference_forward",stage="compile"} 986.0
tpusc_program_builds_total{cache="hit",program="_slot_prefill_jit"} 4.0
tpusc_program_builds_total{cache="miss",program="_paged_decode_chunk_jit"} 2.0
tpusc_program_builds_total{cache="miss",program="reference_forward"} 1.0
tpusc_cold_stage_seconds_sum{stage="server_start"} 0.5
tpusc_cold_stage_seconds_sum{stage="backend_init"} 0.25
tpusc_cold_stage_seconds_sum{stage="load"} 7.0
tpusc_cold_stage_seconds_sum{stage="load_overlap"} 1.25
tpusc_cold_stage_seconds_sum{stage="engine_build"} 1.5
tpusc_cold_stage_seconds_sum{stage="first_run"} 2.5
tpusc_device_bytes{stage="load",what="in_use"} 6442450944.0
tpusc_device_bytes{stage="load",what="peak"} 6442450944.0
tpusc_device_bytes{stage="load",what="reserved"} 0.0
tpusc_device_bytes{stage="first_run:_slot_prefill_jit",what="in_use"} 8589934592.0
tpusc_device_bytes{stage="first_run:_slot_prefill_jit",what="peak"} 10737418240.0
tpusc_device_bytes{stage="first_run:_slot_prefill_jit",what="reserved"} 2147483648.0
"""
# what the parent's program has of these: the loads' own stages, the owners
OLD = """
tpusc_cold_stage_seconds_sum{stage="provider_fetch"} 1.0
tpusc_cold_stage_seconds_sum{stage="artifact_read"} 2.0
tpusc_cold_stage_seconds_sum{stage="device_transfer"} 3.0
tpusc_cold_stage_seconds_sum{stage="transfer_sync"} 0.25
tpusc_cold_stage_seconds_sum{stage="compile_warmup"} 9.0
tpusc_cold_stage_seconds_count{stage="artifact_read"} 1.0
tpusc_hbm_bytes_in_use{group="0"} 5368709120.0
tpusc_hbm_bytes_peak{group="0"} 5368709120.0
tpusc_kv_arena_bytes{kind="global",model="all_models"} 1073741824.0
tpusc_kv_arena_bytes{kind="window",model="all_models"} 0.0
tpusc_lane_state_bytes{model="all_models"} 536870912.0
"""


def make_run(text: str) -> Run:
    r = Run(cell={}, config={}, program_config={}, server={}, device={},
            seconds=10.0, t0=100.0, t_end=125.0)
    r.before = {"t": 100.0, "t_wall": 1000.0, "prom": parse_prometheus(text)}
    r.after = {"prom": parse_prometheus(text)}
    r.setup_s = 70.0
    r.setup_split = {"write_s": 9.0, "weight_bytes": 5e9, "server_up_s": 14.5,
                     "warmup_s": 35.0, "checks_s": 20.0, "check_worst_std": 0.01}
    return r


def reader(name):
    return benchrun.load_reader("per_layer", name)


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_over_a_program_older_than_the_account(name):
    """The parent's program under these files: each reader left out."""
    assert reader(name)(make_run(OLD)) is None
    assert reader(name)(make_run("")) is None
    empty = make_run("")
    empty.before, empty.after = {}, {}
    assert reader(name)(empty) is None


def test_trace_lower_is_pythons_share_the_references_left_out(capsys):
    # 6 + 3 + 1 + 0.5; the reference's 4 s were first booked inside the checks
    assert reader("setup_trace_lower_s")(make_run(ACCOUNT + OLD)) == (10.5, 2)
    out = capsys.readouterr().out
    assert "_slot_prefill_jit 9.00, _paged_decode_chunk_jit 1.50" in out
    assert "reference_forward" not in out


def test_compile_reads_compile_and_cache_load_and_names_a_late_build(capsys):
    run = make_run(ACCOUNT + OLD)
    assert reader("setup_compile_s")(run) == (10.0, 7)
    out = capsys.readouterr().out
    assert "'hit': 4" in out and "'miss': 3" in out
    assert "INSIDE THE WINDOW" not in out
    run.after["prom"] = parse_prometheus(
        (ACCOUNT + OLD).replace(
            'tpusc_program_builds_total{cache="hit",program="_slot_prefill_jit"} 4.0',
            'tpusc_program_builds_total{cache="hit",program="_slot_prefill_jit"} 5.0'))
    assert reader("setup_compile_s")(run) == (10.0, 7)
    assert "INSIDE THE WINDOW: _slot_prefill_jit (hit) x1" in capsys.readouterr().out


def test_load_sums_the_loads_own_stages_without_the_warm_up(capsys):
    assert reader("setup_load_s")(make_run(ACCOUNT + OLD)) == (6.25, 4)
    out = capsys.readouterr().out
    assert "compile_warmup 9.00" in out and "load (wall less builds) 7.00" in out


def test_engine_build_reads_its_stage():
    assert reader("setup_engine_build_s")(make_run(ACCOUNT + OLD)) == 1.5


def test_the_parts_add_up_to_setup_s(capsys):
    import setup_account

    run = make_run(ACCOUNT + OLD)
    parts = setup_account.setup_parts(run)
    assert sum(parts.values()) == pytest.approx(run.setup_s)
    assert parts["harness_before_server_s"] == 14.0
    assert (parts["trace_s"], parts["lower_s"]) == (7.0, 3.5)
    assert (parts["compile_s"], parts["cache_load_s"]) == (8.0, 2.0)
    assert parts["load_overlap_s"] == -1.25
    # 70 - 14 - 0.5 - 6.25 + 1.25 - 1.5 - 7 - 3.5 - 8 - 2 - 2.5 - 20
    assert reader("setup_unexplained_s")(run) == pytest.approx(6.0)
    out = capsys.readouterr().out
    assert "unexplained_s 6.00" in out and "the reference's own builds: 9.00" in out


def test_unowned_is_the_highest_peak_less_what_is_owned(capsys):
    owned = 5 * GB + 1 * GB + GB // 2
    assert reader("device_unowned_peak_bytes")(make_run(ACCOUNT + OLD)) == (
        10 * GB - owned, 2)
    out = capsys.readouterr().out
    assert f"= {owned}" in out and f"{2 * GB}" in out     # reserved, by stage
    assert "end-of-run peak_bytes_in_use" in out


def test_the_six_are_declared_with_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    generate = [c for c in cells if c != "smollm2-tenants-churn"]
    declared = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert list(declared) == list(READERS)       # appended, in this order
    for name, m in declared.items():
        assert m["source"] == "host_clock" and m["better"] == "lower"
        engine = name in ("setup_engine_build_s", "device_unowned_peak_bytes")
        assert m["workloads"] == (generate if engine else cells), name
        assert m["moves"] == (
            "tpot_p50_ms" if name == "device_unowned_peak_bytes" else "setup_s")
