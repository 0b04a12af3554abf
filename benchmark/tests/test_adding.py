"""A later PR adds a model family, a configuration, a cell and a metric as
NEW files plus one ``BENCHMARK.json`` entry each, and edits no file that
exists (README.md). Proved by doing exactly that in a temporary copy and
running the new cell."""

import json
import os
import shutil

from conftest import ROOT, result_line, run_cell


def checkout_copy(tmp_path) -> str:
    """A temporary checkout: a copy of ``benchmark/`` beside the program."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "bench-work-*"))
    os.symlink(os.path.join(ROOT, "tfservingcache_tpu"),
               os.path.join(root, "tfservingcache_tpu"))
    return root


def test_dummy_family_config_cell_and_metric_drop_in(tmp_path):
    root = checkout_copy(tmp_path)
    before = {}
    for folder, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(folder, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(root, "benchmark")
    # 0. a model family: config mapping, weight layout and plain reference in
    # one file found by the configuration's `family` key (here a copy of the
    # dense decoder's under another name, which the program builds as the
    # family the file's PROGRAM_FAMILY says)
    shutil.copy(os.path.join(b, "families", "transformer_lm.py"),
                os.path.join(b, "families", "dummy_family.py"))
    # 1. a configuration: its file of sizes
    with open(os.path.join(b, "configs", "smollm2-360m.json")) as f:
        config = json.load(f)
    config.update(name="dummy-lm", family="dummy_family")
    with open(os.path.join(b, "configs", "dummy-lm.json"), "w") as f:
        json.dump(config, f)
    bench["configs"].append({
        "name": "dummy-lm", "source": "https://example.org/dummy",
        "file": "benchmark/configs/dummy-lm.json", "reduced": [],
        "why": "test"})
    # 2. a cell: a data file of traffic parameters
    with open(os.path.join(b, "workloads", "smollm2-tenants-churn.json")) as f:
        cell = json.load(f)
    cell.update(name="dummy-bursts", config="dummy-lm")
    cell["rehearsal"]["traffic"].update(arrival="burst", burst_size=3,
                                        burst_gap_s=0.5)
    with open(os.path.join(b, "workloads", "dummy-bursts.json"), "w") as f:
        json.dump(cell, f)
    bench["workloads"].append({"name": "dummy-bursts", "config": "dummy-lm",
                               "traffic": "bursts", "chips": 1, "why": "test"})
    # 3. a per-layer metric: a reader of its own
    with open(os.path.join(b, "layer_metrics", "dummy_answered.py"), "w") as f:
        f.write("def read(run):\n"
                "    n = sum(r['ok'] for r in run.due_in_window())\n"
                "    return float(n), n\n")
    bench["per_layer"].append({
        "name": "dummy_answered", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "benchmark client",
        "moves": "cold_p50_s", "workloads": ["dummy-bursts"]})
    for m in bench["end_to_end"]:
        if m["name"] == "cold_p50_s":
            m["workloads"].append("dummy-bursts")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    r = run_cell(root, "--workload", "dummy-bursts", "--seed", "1",
                 "--seconds", "3", "--trace", "1", "--rehearsal")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    res = result_line(r.stdout)
    assert res is not None and res["correct"] is True
    assert list(res["metrics"]) == ["rehearsal.dummy_answered.samples"]
    assert res["metrics"]["rehearsal.dummy_answered.samples"]["value"] >= 3
    assert "model: dummy-lm (dummy_family) as run" in r.stdout
    # no file the benchmark already had was edited
    for p, data in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == data, p


def test_a_configuration_of_an_unknown_family_gives_no_result(tmp_path):
    root = checkout_copy(tmp_path)
    path = os.path.join(root, "benchmark", "configs", "smollm2-360m.json")
    with open(path) as f:
        config = json.load(f)
    config["family"] = "no_such_family"
    with open(path, "w") as f:
        json.dump(config, f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    r = run_cell(root, "--workload", "smollm2-tenants-churn", "--rehearsal",
                 timeout=120)
    assert r.returncode != 0 and result_line(r.stdout) is None
    assert "no_such_family" in r.stderr


def test_a_stream_left_unanswered_fails_the_run(tmp_path):
    """Requests still unanswered when the run stops waiting are failures:
    ``correct`` is false, so dropping slow streams cannot read as a gain."""
    root = checkout_copy(tmp_path)
    path = os.path.join(root, "benchmark", "workloads",
                        "mistral7b-chat-steady.json")
    with open(path) as f:
        cell = json.load(f)
    # streams far longer than the window, and no wait for them after it
    fixed = lambda n: {"lognormal": {"median": n, "sigma": 0.1,  # noqa: E731
                                     "min": n, "max": n}}
    cell["rehearsal"]["traffic"].update(output=fixed(200), prompt=fixed(16))
    cell["rehearsal"]["drain_s"] = 0.0
    with open(path, "w") as f:
        json.dump(cell, f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    r = run_cell(root, "--workload", "mistral7b-chat-steady", "--seed", "2",
                 "--seconds", "2", "--trace", "0", "--rehearsal")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    res = result_line(r.stdout)
    assert res is not None
    assert res["failed"] >= 1 and res["correct"] is False
    assert res["failed"] <= res["attempted"]
    assert "still unanswered when the run stopped waiting" in r.stdout
