"""``chunks_ahead_share`` on a synthetic ``Run``: the mean of ring ``ahead``
over the window's boundaries that ran a chunk; nothing, and no error, on a
ring without the field (a program older than PR 40)."""

import json
import os

import run as benchrun
from measure import Run

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = ["mistral7b-chat-steady", "olmoe-chat-steady", "mistral4-docqa-steady",
         "lfm2-longgen-steady", "mellum2-codectx-mixed"]


def make_run(steps) -> Run:
    r = Run(cell={}, config={}, program_config={}, server={},
            device={"platform": "tpu", "kind": "TPU v5 lite"},
            seconds=10.0, t0=100.0, t_end=125.0)
    r.before = {"t": 100.0, "t_wall": 1000.0, "prom": {}}
    r.after = {"prom": {}}
    r.steps, r.records, r.trace = list(steps), [], None
    return r


def step(t_wall, chunk, ahead=None, admitted=0) -> dict:
    s = {"t_wall": t_wall, "engine": "continuous", "step_ms": 40.0,
         "chunk": chunk, "active": 2, "admitted": admitted, "retired": 0}
    if ahead is not None:
        s["ahead"] = ahead
    return s


def test_share_of_the_windows_chunks():
    read = benchrun.load_reader("per_layer", "chunks_ahead_share")
    steps = [step(999.0, 8, 1),                    # before the window
             step(1001.0, 8, 0, admitted=1), step(1002.0, 8, 1), step(1003.0, 8, 1),
             step(1004.0, 0, 0, admitted=1),       # a prefill-only boundary
             step(1005.0, 4, 1), step(1006.0, 8, 0),
             step(1011.0, 8, 1)]                   # after it
    assert read(make_run(steps)) == (3 / 5, 5)
    # an engine that never chains (a mesh, an attached draft) reads 0, not nothing
    assert read(make_run([step(1001.0, 8, 0), step(1002.0, 8, 0)])) == (0.0, 2)


def test_nothing_on_a_ring_without_the_field():
    read = benchrun.load_reader("per_layer", "chunks_ahead_share")
    assert read(make_run([step(1001.0, 8), step(1002.0, 8)])) is None
    assert read(make_run([step(1004.0, 0, 0)])) is None
    assert read(make_run([])) is None


def test_the_entry_is_the_last_one_and_lists_the_generate_cells():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        entry = json.load(f)["per_layer"][-1]
    assert entry == {
        "name": "chunks_ahead_share", "unit": "share", "better": "higher",
        "source": "program_counter", "layer": "engine runtime/batcher.py",
        "moves": "tpot_p50_ms", "workloads": CELLS}
