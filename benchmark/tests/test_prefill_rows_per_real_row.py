"""``prefill_rows_per_real_row`` on a synthetic ``Run``: the window's growth
of ``tpusc_prefill_rows_total{kind="computed"}`` over ``{kind="real"}``;
nothing, and no error, where the program has no such counter (the parent of
PR 48) or the window held no admission."""

import run as benchrun
from measure import Run

KEY = 'tpusc_prefill_rows_total{kind="%s"}'


def make_run(before: dict, after: dict) -> Run:
    r = Run(cell={}, config={}, program_config={}, server={},
            device={"platform": "tpu", "kind": "TPU v5 lite"},
            seconds=10.0, t0=100.0, t_end=125.0)
    r.before = {"t": 100.0, "t_wall": 1000.0, "prom": before}
    r.after = {"prom": after}
    r.steps, r.records, r.trace = [], [], None
    return r


def rows(real, bucket, computed) -> dict:
    return {KEY % "real": real, KEY % "bucket": bucket,
            KEY % "computed": computed}


def test_the_windows_growth(capsys):
    read = benchrun.load_reader("per_layer", "prefill_rows_per_real_row")
    run = make_run(rows(1000.0, 2048.0, 2048.0),       # the warm-up's
                   rows(1000.0 + 5000.0, 2048.0 + 8192.0, 2048.0 + 5120.0))
    assert read(run) == (5120.0 / 5000.0, 5000)
    assert "1.638" in capsys.readouterr().out          # bucket over real


def test_nothing_without_the_counter_or_an_admission():
    read = benchrun.load_reader("per_layer", "prefill_rows_per_real_row")
    assert read(make_run({}, {})) is None
    assert read(make_run({"tpusc_other_total": 1.0},
                         {"tpusc_other_total": 9.0})) is None
    same = rows(1000.0, 2048.0, 2048.0)
    assert read(make_run(same, dict(same))) is None
