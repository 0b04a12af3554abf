"""Every cell runs end to end on the CPU at its ``rehearsal`` sizes, and what
it prints there is counts, never a device number."""

import json
import os

import pytest

from conftest import RESULT_KEYS, ROOT, result_line, run_cell

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
DEVICE_METRICS = {m["name"] for k in ("end_to_end", "per_layer")
                  for m in BENCHMARK[k]}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_on_the_cpu(cell, trace):
    r = run_cell(ROOT, "--workload", cell, "--seed", "3", "--seconds", "3",
                 "--trace", str(trace), "--rehearsal")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    res = result_line(r.stdout)
    assert res is not None, r.stdout[-2000:]
    assert set(res) == RESULT_KEYS            # no breakdown off the chip
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # counts only: nothing under a device metric's name, every unit a count
    assert res["metrics"] and not set(res["metrics"]) & DEVICE_METRICS
    assert all(m["unit"] == "count" and m["value"] >= 1
               for m in res["metrics"].values())
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in BENCHMARK[kind]
            if cell in m.get("workloads", [cell])}
    # a traced rehearsal has no device plane: the kernel readers find nothing
    want -= {"paged_decode_ms_per_call", "paged_decode_roofline"}
    got = {k.split(".", 1)[1].rsplit(".", 1)[0] for k in res["metrics"]}
    assert got == want
    for line in ("device: platform=cpu", "attention dispatch tally",
                 "compilations inside the window: 0", "generator lateness",
                 "survey:", "peak device bytes", "setup split",
                 "requests due in the window"):
        assert line in r.stdout, line
    assert " over " in r.stdout and " samples" in r.stdout


def test_off_the_chip_without_rehearsal_there_is_no_result():
    r = run_cell(ROOT, "--workload", CELLS[0], "--seed", "1", "--seconds", "2",
                 "--trace", "0", timeout=120)
    assert r.returncode != 0
    assert result_line(r.stdout) is None
    assert "measures on a TPU only" in r.stderr


def test_unknown_cell_is_an_error():
    r = run_cell(ROOT, "--workload", "no-such-cell", "--rehearsal", timeout=120)
    assert r.returncode != 0 and result_line(r.stdout) is None
