"""Readers on a synthetic ``Run``: the judged ones, and the two kept for the
time-to-first-token cell PERF.md's Open questions name (``ttft_p90_ms``,
``queue_wait_mean_ms``), which no cell of ``BENCHMARK.json`` reports yet."""

import pytest

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


# -- paged_decode_roofline: a mean over the traced span's calls (PR 28) -------

MISTRAL = {"n_layers": 8, "n_heads": 32, "n_kv_heads": 8, "d_model": 4096}
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
TO_WALL = 900.0      # wall = monotonic + 900


def traced_run(steps, records, span, kernel_seconds, kernel_calls) -> Run:
    r = Run(cell={}, config={}, program_config=MISTRAL, server={}, device=V5E,
            seconds=51.0, t0=100.0, t_end=160.0)
    r.before = {"t": 100.0, "t_wall": 100.0 + TO_WALL}
    r.steps, r.records, r.trace_wall = steps, records, span
    r.trace = {"kernels": {"paged_decode_attention_kernel": {
        "seconds": kernel_seconds, "calls": kernel_calls}}}
    return r


def boundary(wall_start, wall_end, active, chunk=8) -> dict:
    return {"t_wall": wall_end, "step_ms": (wall_end - wall_start) * 1e3,
            "chunk": chunk, "active": active, "admitted": 0}


def chat(index, prompt, first_wall, tokens, gap=0.01) -> dict:
    """A stream whose first token reaches the client at ``first_wall``."""
    rec = new_record("generate", "tenant00", index, first_wall - TO_WALL - 0.1,
                     prompt, tokens)
    rec["token_t"] = [first_wall - TO_WALL + gap * i for i in range(tokens)]
    rec["end"], rec["ok"] = rec["token_t"][-1], True
    return rec


def least_s(tokens, lanes) -> float:
    import kernel_costs

    return kernel_costs.roofline(
        kernel_costs.paged_decode(tokens, lanes, 32, 8, 128),
        kernel_costs.peaks(V5E["kind"]))["seconds"]


def test_paged_roofline_is_a_mean_over_the_span_not_one_instant():
    """The live tokens double half-way through the span. Whether the second
    stream starts a millisecond before the span's middle or a millisecond
    after it, the reading is the same, and it is the mean's: an instant's
    would read 1 to 2 apart."""
    from measure import live_tokens

    read = benchrun.load_reader("per_layer", "paged_decode_roofline")
    span = (1010.0, 1014.0)
    steps = [boundary(1010.0 + 0.5 * i, 1010.5 + 0.5 * i, 1 if i < 4 else 2)
             for i in range(8)]
    calls = 8 * 8 * 8                      # boundaries x chunk x layers
    seconds = calls * 40e-6
    readings, instants = [], []
    for nudge in (-0.001, +0.001):
        recs = [chat(0, 1000, 1005.0, 1500),            # streams all through
                chat(1, 1000, 1012.0 + nudge, 300)]     # joins at the middle
        run = traced_run(steps, recs, span, seconds, calls)
        value, n = read(run)
        readings.append(value)
        instants.append(live_tokens(run, 1012.0 - TO_WALL))
        assert n == calls
    assert readings[0] == pytest.approx(readings[1], rel=1e-3)   # a token apart
    assert instants[0] - instants[1] >= 1000            # what one instant sees
    # the mean of the two halves' shares, by hand: tokens at each boundary's
    # middle are the prompt(s) plus what had arrived by then
    by_hand = 0.0
    for i in range(8):
        mid = 1010.25 + 0.5 * i
        tokens = 1000 + min(1500, int((mid - 1005.0) / 0.01) + 1)
        if i >= 4:
            tokens += 1000 + min(300, int((mid - 1012.0) / 0.01) + 1)
        by_hand += 64 * least_s(tokens, 1 if i < 4 else 2)
    assert readings[0] == pytest.approx(100.0 * by_hand / seconds, rel=1e-3)
    assert 0.0 < readings[0] < 100.0


def test_paged_roofline_of_one_boundary_is_the_reading_of_its_middle():
    """A span that is one boundary: the old reader's answer (the live tokens
    and lanes at the span's middle against the mean time of a call)."""
    read = benchrun.load_reader("per_layer", "paged_decode_roofline")
    recs = [chat(0, 700, 1009.0, 900), chat(1, 300, 1009.5, 900),
            chat(2, 500, 1020.0, 10)]                   # after the span
    run = traced_run([boundary(1010.0, 1014.0, 2)], recs, (1010.0, 1014.0),
                     64 * 25e-6, 64)
    value, n = read(run)
    tokens = 700 + 301 + 300 + 251          # prompts + arrived by wall 1012.0
    assert n == 64
    assert value == pytest.approx(100.0 * least_s(tokens, 2) / 25e-6, rel=1e-9)
    # a boundary half inside the span stands for half its calls
    run.steps = [boundary(1008.0, 1012.0, 2)]
    half, _ = read(run)
    at_1010 = 700 + 101 + 300 + 51
    assert half == pytest.approx(
        100.0 * 32 * least_s(at_1010, 2) / (64 * 25e-6), rel=1e-9)


@pytest.mark.parametrize("what", ["no ring", "no chunk in the span", "no trace",
                                  "no kernel in the trace", "untraced"])
def test_paged_roofline_gives_nothing_without_ring_or_trace(what):
    read = benchrun.load_reader("per_layer", "paged_decode_roofline")
    run = traced_run([boundary(1010.0, 1014.0, 2)], [chat(0, 700, 1009.0, 900)],
                     (1010.0, 1014.0), 64 * 25e-6, 64)
    assert read(run) is not None
    if what == "no ring":
        run.steps = []
    elif what == "no chunk in the span":
        run.steps = [boundary(1000.0, 1004.0, 2), boundary(1011.0, 1012.0, 0, chunk=0)]
    elif what == "no trace":
        run.trace = None
    elif what == "no kernel in the trace":
        run.trace = {"kernels": {"fusion": {"seconds": 1.0, "calls": 9}}}
    else:
        run.trace_wall = None
    assert read(run) is None
