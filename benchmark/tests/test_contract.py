"""BENCHMARK.json against the rules a benchmark is refused for before a
single run (the builder's contract, as PERF.md section 2 and README.md state
them), and against the files it names."""

import json
import os
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|(hidden|intermediate|latent|state|proj\w*)_size"
                   r"|head_size|expansion|experts_per_tok")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    B = json.load(_f)


def line(s, lo=1, hi=200):
    return isinstance(s, str) and lo <= len(s) <= hi and "\n" not in s and "\t" not in s


def test_top_level_shape():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(B["command"]) <= 32 and all(line(w) for w in B["command"])
    assert 1 <= len(B["paths"]) <= 16 and all(PATH.match(p) for p in B["paths"])
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    # a full check fits: 2 + 14 x cells runs, with the full 24 cells
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for word in B["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in B["paths"])


def test_configs():
    assert 1 <= len(B["configs"]) <= 24
    names = [c["name"] for c in B["configs"]]
    files = [c["file"] for c in B["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in B["workloads"]}
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert PATH.match(c["file"])
        assert any(c["file"].startswith(p + "/") for p in B["paths"])
        assert len(c["reduced"]) <= 16
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
            assert key in cfg and key in cfg["reduced_why"]
        # every published value is there; only the keys in `reduced` differ
        for key, value in cfg["source_values"].items():
            if key in c["reduced"]:
                assert cfg[key] != value, key
            else:
                assert cfg[key] == value, key
        assert cfg["source"] == c["source"] and cfg["name"] == c["name"]


def test_workloads():
    cells = B["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in B["configs"]}
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        path = os.path.join(BENCH, "workloads", w["name"] + ".json")
        with open(path) as f:
            cell = json.load(f)
        assert cell["config"] == w["config"] and cell["name"] == w["name"]
        # every option a cell or its configuration sets has its reason
        for holder in (cell, json.load(open(os.path.join(
                BENCH, "configs", w["config"] + ".json")))):
            for group in holder.get("server", {}).values():
                for option in group:
                    assert option in holder["server_why"], option


def test_metrics():
    cells = {w["name"] for w in B["workloads"]}
    e2e, layer = B["end_to_end"], B["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    e2e_cells = {m["name"]: set(m.get("workloads", cells)) for m in e2e}
    assert "setup_s" in e2e_cells and e2e_cells["setup_s"] == cells
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert os.path.exists(os.path.join(BENCH, "end_to_end", m["name"] + ".py"))
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])
        assert m["moves"] in e2e_cells
        # reported only where the metric it moves is
        assert set(m.get("workloads", cells)) <= e2e_cells[m["moves"]]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        mine = [m for m in e2e if cell in m.get("workloads", cells)]
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)
        assert any(cell in m.get("workloads", cells) for m in layer)


def test_files_under_paths_have_plain_names():
    for folder, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"
                   and not d.startswith("bench-work-")]
        for f in files:
            rel = os.path.relpath(os.path.join(folder, f), ROOT)
            assert PATH.match(rel), rel
