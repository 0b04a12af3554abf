"""The chunked delta rule's costs by hand (``kernel_costs_gdn_chunk.py``) and
ISSUE 47's reader on synthetic ``Run``s: a made-up run with known prefills
gives a known least time, the calls expected are the span's prefills times the
LINEAR layers (6 of 8) and are printed against the kernel's events, a run with
no kernel events (every program before PR 47) gives nothing and does not
raise, a rehearsal gives a count."""

import pytest

import kernel_costs_gdn_chunk as costs
import run as benchrun
from client import new_record
from measure import Run

L, F, S = "linear_attention", "full_attention", "sliding_attention"
MC = {"n_layers": 8, "layer_types": [L, L, L, F] * 2, "n_heads": 30,
      "n_kv_heads": 30, "d_model": 3840, "d_ff": 11008, "linear_heads": 30,
      "linear_key_dim": 96, "linear_value_dim": 192}
OLD_MC = {"n_layers": 8, "layer_types": [S, S, S, F] * 2, "sliding_window": 1024,
          "n_heads": 32, "n_kv_heads": 4, "head_dim": 128, "d_model": 2304}
V5E = costs.peaks("TPU v5 lite")
STATE_BYTES = 2 * 30 * 96 * 192 * 4
TOKEN_BYTES = 30 * (2 * 96 + 192) * 2 + 2 * 30 * 4 + 30 * 192 * 4
TOKEN_FLOPS = 30 * (6 * 64 * 96 + 4 * 64 * 192 + 6 * 96 * 192)


def make_run(steps, records, kernels=None, platform="tpu", mc=MC) -> Run:
    r = Run(cell={}, config={}, program_config=mc,
            server={"serving": {"kv_page_tokens": 16}},
            device={"platform": platform, "kind": "TPU v5 lite"},
            seconds=10.0, t0=100.0, t_end=125.0)
    r.before = {"t": 100.0, "t_wall": 1000.0, "prom": {}}
    r.after = {"prom": {}}
    r.steps, r.records = list(steps), list(records)
    r.trace = None if kernels is None else {"kernels": kernels}
    r.trace_wall = (1004.0, 1008.0)
    return r


def admit(t_wall, step_ms=300.0, prefill_ms=100.0) -> dict:
    return {"t_wall": t_wall, "engine": "continuous", "step_ms": step_ms,
            "chunk": 8, "active": 3, "admitted": 1, "retired": 0,
            "prefill_ms": prefill_ms}


def record(prompt_len, first_token_wall, tokens=50):
    first = first_token_wall - 1000.0 + 100.0               # monotonic
    r = new_record("generate", "tenant00", 0, first - 0.5, prompt_len, 512)
    r["token_t"] = [first + 0.01 * i for i in range(tokens)]
    r["ok"] = True
    return r


def reader():
    return benchrun.load_reader("per_layer", "gdn_chunk_roofline")


# two admitting boundaries inside the span, one before it
STEPS = [admit(1005.3), admit(1006.3), admit(1002.3)]
RECORDS = [record(6000, 1005.15), record(1500, 1006.15), record(9000, 1002.15)]


def test_a_layers_rule_over_a_prompt_by_hand():
    assert STATE_BYTES == 4423680 and TOKEN_BYTES == 46320
    assert TOKEN_FLOPS == 30 * 196608
    cost = costs.chunk_rule(6000, 30, 96, 192)
    assert cost == {"bytes": STATE_BYTES + 6000 * TOKEN_BYTES,
                    "flops": 6000 * TOKEN_FLOPS}
    best = costs.roofline(cost, V5E)
    # 127 FLOP a byte under the ridge of 240: the bytes decide
    assert best["bound"] == "memory"
    assert best["seconds"] == pytest.approx((STATE_BYTES + 6000 * TOKEN_BYTES) / 819e9)
    # no token: the state's round trip alone
    assert costs.chunk_rule(0, 30, 96, 192) == {"bytes": STATE_BYTES, "flops": 0}


def test_the_prefills_the_span_held_at_their_true_lengths():
    run = make_run(STEPS, RECORDS, {})
    assert costs.prefills(run) == [(6000, pytest.approx(1.0)),
                                   (1500, pytest.approx(1.0))]
    # half of the first prefill inside the span
    run.trace_wall = (1005.05, 1008.0)
    assert costs.prefills(run)[0] == (6000, pytest.approx(0.5))
    assert costs.prefills(make_run(STEPS, RECORDS, {}, mc=OLD_MC)) is None
    assert costs.prefills(make_run(STEPS[2:], RECORDS[2:], {})) is None


def test_known_prefills_give_a_known_least_time(capsys):
    # 12 events (2 prefills x 6 layers) that took 30 ms in all
    kernels = {"delta_chunk_kernel": {"seconds": 0.030, "calls": 12},
               "fusion": {"seconds": 1.0, "calls": 500}}
    value, events = reader()(make_run(STEPS, RECORDS, kernels))
    least = 6 * (2 * STATE_BYTES + 7500 * TOKEN_BYTES) / 819e9
    assert events == 12
    assert value == pytest.approx(100 * least / 0.030)
    assert 5 < value < 105
    out = capsys.readouterr().out
    assert "12.0 calls expected" in out and "12 in the trace" in out


def test_the_kernel_is_found_by_name_among_the_events():
    kernels = {"delta_chunk_kernel.7": {"seconds": 0.010, "calls": 6},
               "delta_chunk_kernel.9": {"seconds": 0.020, "calls": 6},
               "paged_decode_kernel": {"seconds": 9.0, "calls": 700}}
    assert costs.kernel_time(make_run(STEPS, RECORDS, kernels)) == (
        pytest.approx(0.030), 12)


@pytest.mark.parametrize("case", ["no_kernel_events", "no_linear_layer",
                                  "no_prefill_in_the_span", "untraced"])
def test_nothing_to_read_gives_none_and_does_not_raise(case):
    """What the PARENT's program hands the reader in this cell (a trace with
    no such kernel), and what every other cell does."""
    kernels = {"fusion": {"seconds": 1.0, "calls": 500}}
    run = {
        "no_kernel_events": make_run(STEPS, RECORDS, kernels),
        "no_linear_layer": make_run(
            STEPS, RECORDS, {"delta_chunk_kernel": {"seconds": 1.0, "calls": 6}},
            mc=OLD_MC),
        "no_prefill_in_the_span": make_run(STEPS[2:], RECORDS[2:], kernels),
        "untraced": make_run(STEPS, RECORDS, None),
    }[case]
    if case == "untraced":
        run.trace_wall = None
    assert reader()(run) is None


def test_a_rehearsal_shows_the_calls_expected_as_a_count():
    got = reader()(make_run(STEPS, RECORDS, {}, platform="cpu"))
    assert got == (0.0, 12)
