"""``kv_write_lanes_mean`` on a synthetic ``Run``: the mean ring
``write_lanes`` over the window's boundaries that ran a chunk; nothing, and no
error, on a ring without the field (a program older than PR 32)."""

import run as benchrun
from measure import Run


def make_run(steps) -> Run:
    r = Run(cell={}, config={}, program_config={}, server={},
            device={"platform": "tpu", "kind": "TPU v5 lite"},
            seconds=10.0, t0=100.0, t_end=125.0)
    r.before = {"t": 100.0, "t_wall": 1000.0, "prom": {}}
    r.after = {"prom": {}}
    r.steps, r.records, r.trace = list(steps), [], None
    return r


def step(t_wall, chunk, write_lanes=None) -> dict:
    s = {"t_wall": t_wall, "engine": "continuous", "step_ms": 40.0,
         "chunk": chunk, "active": 2, "admitted": 0, "retired": 0}
    if write_lanes is not None:
        s["write_lanes"] = write_lanes
    return s


def test_mean_over_the_windows_chunks():
    read = benchrun.load_reader("per_layer", "kv_write_lanes_mean")
    steps = [step(999.0, 8, 32),          # before the window
             step(1001.0, 8, 4), step(1002.0, 8, 4), step(1003.0, 4, 8),
             step(1004.0, 0, 0),          # a prefill-only boundary
             step(1005.0, 8, 16),
             step(1011.0, 8, 32)]         # after it
    assert read(make_run(steps)) == ((4 + 4 + 8 + 16) / 4, 4)


def test_nothing_on_a_ring_without_the_field():
    read = benchrun.load_reader("per_layer", "kv_write_lanes_mean")
    assert read(make_run([step(1001.0, 8), step(1002.0, 8)])) is None
    assert read(make_run([])) is None
