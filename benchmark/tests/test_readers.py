"""Readers on a synthetic ``Run``: the judged ones, and the two kept for the
time-to-first-token cell PERF.md's Open questions name (``ttft_p90_ms``,
``queue_wait_mean_ms``), which no cell of ``BENCHMARK.json`` reports yet."""

import run as benchrun
from client import new_record
from measure import Run


def stream(index: int, due: float, first: float | None, tokens: int,
           gap: float = 0.1, ok: bool = True) -> dict:
    rec = new_record("generate", "tenant00", index, due, 16, tokens)
    if first is not None:
        rec["token_t"] = [due + first + gap * i for i in range(tokens)]
        rec["end"] = rec["token_t"][-1]
    rec["ok"] = ok
    return rec


def make_run(records) -> Run:
    r = Run(cell={}, config={}, program_config={}, server={}, device={},
            seconds=10.0, t0=100.0, t_end=125.0)
    r.records = records
    return r


def test_ttft_ranks_an_unanswered_request_above_every_value():
    recs = [stream(i, 100.0 + i, 0.2 + 0.01 * i, 5) for i in range(9)]
    read = benchrun.load_reader("end_to_end", "ttft_p90_ms")
    answered, n = read(make_run(recs))
    assert n == 9 and 270.0 < answered <= 280.0
    # one more request, never answered: it had waited 16 s when the run ended
    lost = stream(9, 109.0, None, 5, ok=False)
    with_miss, n = read(make_run(recs + [lost]))
    assert n == 10 and with_miss > 280.0
    all_lost, _ = read(make_run([stream(i, 100.0 + i, None, 5, ok=False)
                                 for i in range(4)]))
    assert all_lost >= 22_000.0          # the time they had already waited
    # due after the window: not counted
    assert read(make_run([stream(0, 111.0, 0.2, 5)])) is None


def test_tpot_reads_only_complete_answers_of_the_window():
    recs = [stream(0, 101.0, 0.3, 9, gap=0.08), stream(1, 102.0, 0.3, 9, gap=0.10),
            stream(2, 103.0, 0.3, 9, gap=0.12),
            stream(3, 104.0, 0.3, 3, gap=5.0, ok=False),     # cut short
            stream(4, 120.0, 0.3, 9, gap=9.0)]               # after the window
    value, n = benchrun.load_reader("end_to_end", "tpot_p50_ms")(make_run(recs))
    assert n == 3 and abs(value - 100.0) < 1e-6


def test_queue_wait_is_the_histograms_growth_over_the_window():
    r = make_run([])
    key = 'tpusc_request_phase_seconds_%s{model="m",phase="queue"}'
    other = 'tpusc_request_phase_seconds_%s{model="m",phase="prefill"}'
    r.before = {"prom": {key % "sum": 1.0, key % "count": 10.0,
                         other % "sum": 50.0, other % "count": 10.0}}
    r.after = {"prom": {key % "sum": 4.0, key % "count": 20.0,
                        other % "sum": 90.0, other % "count": 20.0}}
    read = benchrun.load_reader("layer_metrics", "queue_wait_mean_ms")
    assert read(r) == (300.0, 10)
    r.after = r.before
    assert read(r) is None
