"""The chunked delta rule's costs where the decay is a channel's, by hand
(``kernel_costs_kda_chunk.py``), and ISSUE 50's reader on synthetic ``Run``s,
the twin of ``test_gdn_chunk_roofline.py``: a made-up run with known prefills
gives a known least time, the calls expected are the span's prefills times the
LINEAR layers (3 of 4) and are printed against the kernel's events, a run with
no kernel events (every program before PR 50) gives nothing and does not
raise, a rehearsal gives a count; and the two twins keep apart: a head-decay
model's run gives nothing here, a channel-decay model's nothing there."""

import pytest

import kernel_costs_kda_chunk as costs
import run as benchrun
from test_gdn_chunk_roofline import MC as HEAD_MC
from test_gdn_chunk_roofline import OLD_MC, RECORDS, STEPS, make_run

L, F = "linear_attention", "full_attention"
MC = {"n_layers": 4, "layer_types": [L, L, L, F], "n_heads": 64,
      "n_kv_heads": 8, "head_dim": 128, "d_model": 4096, "linear_heads": 64,
      "linear_key_dim": 128, "linear_value_dim": 128, "linear_gate_rank": 128}
V5E = costs.peaks("TPU v5 lite")
STATE_BYTES = 2 * 64 * 128 * 128 * 4
TOKEN_BYTES = 64 * (2 * 128 + 128) * 2 + 64 * 128 * 4 + 64 * 4 + 64 * 128 * 4
TOKEN_FLOPS = 64 * (8 * 64 * 128 + 4 * 64 * 128 + 6 * 128 * 128 + 3 * 128
                    + 128 * 128 / 64)
HEAD_KERNEL, CHANNEL_KERNEL = "delta_chunk_kernel", "delta_channel_chunk_kernel"


def reader(name="kda_chunk_roofline"):
    return benchrun.load_reader("per_layer", name)


def test_a_layers_rule_over_a_prompt_by_hand():
    assert STATE_BYTES == 8388608 and TOKEN_BYTES == 114944
    assert TOKEN_FLOPS == 64 * 197248
    cost = costs.chunk_rule(6000, 64, 128, 128)
    assert cost == {"bytes": STATE_BYTES + 6000 * TOKEN_BYTES,
                    "flops": 6000 * TOKEN_FLOPS}
    best = costs.roofline(cost, V5E)
    # 110 FLOP a byte under the ridge of 240: the bytes decide
    assert 100 < cost["flops"] / cost["bytes"] < 120
    assert best["bound"] == "memory"
    assert best["seconds"] == pytest.approx((STATE_BYTES + 6000 * TOKEN_BYTES) / 819e9)
    # no token: the state's round trip alone
    assert costs.chunk_rule(0, 64, 128, 128) == {"bytes": STATE_BYTES, "flops": 0}
    # the channel's terms: more operations and more bytes a token than the
    # head-decay rule at the same widths
    import kernel_costs_gdn_chunk as head
    plain = head.chunk_rule(6000, 64, 128, 128)
    assert cost["flops"] > plain["flops"] and cost["bytes"] > plain["bytes"]


def test_the_prefills_the_span_held_at_their_true_lengths():
    run = make_run(STEPS, RECORDS, {}, mc=MC)
    assert costs.prefills(run) == [(6000, pytest.approx(1.0)),
                                   (1500, pytest.approx(1.0))]
    run.trace_wall = (1005.05, 1008.0)
    assert costs.prefills(run)[0] == (6000, pytest.approx(0.5))
    for other in (OLD_MC, HEAD_MC):        # no linear layer; a decay a head
        assert costs.prefills(make_run(STEPS, RECORDS, {}, mc=other)) is None
    assert costs.prefills(make_run(STEPS[2:], RECORDS[2:], {}, mc=MC)) is None


def test_known_prefills_give_a_known_least_time(capsys):
    # 15 events (a 6000-token prompt a block at a time: 4 a layer, a
    # 1500-token one whole: 1 a layer, x 3 layers) that took 40 ms in all
    kernels = {CHANNEL_KERNEL: {"seconds": 0.040, "calls": 15},
               "fusion": {"seconds": 1.0, "calls": 500}}
    value, events = reader()(make_run(STEPS, RECORDS, kernels, mc=MC))
    least = 3 * (2 * STATE_BYTES + 7500 * TOKEN_BYTES) / 819e9
    assert events == 15
    assert value == pytest.approx(100 * least / 0.040)
    assert 1 < value < 100
    out = capsys.readouterr().out
    assert "6.0 calls expected" in out and "15 events in the trace" in out


def test_the_kernel_is_found_by_name_among_the_events():
    kernels = {CHANNEL_KERNEL + ".7": {"seconds": 0.010, "calls": 3},
               CHANNEL_KERNEL + ".9": {"seconds": 0.020, "calls": 12},
               HEAD_KERNEL: {"seconds": 5.0, "calls": 60},
               "paged_decode_kernel": {"seconds": 9.0, "calls": 700}}
    assert costs.kernel_time(make_run(STEPS, RECORDS, kernels, mc=MC)) == (
        pytest.approx(0.030), 15)


@pytest.mark.parametrize("case", ["no_kernel_events", "no_linear_layer",
                                  "no_prefill_in_the_span", "untraced"])
def test_nothing_to_read_gives_none_and_does_not_raise(case):
    """What the PARENT's program hands the reader in this cell (a trace with
    no such kernel), and what every other cell does."""
    kernels = {"fusion": {"seconds": 1.0, "calls": 500}}
    run = {
        "no_kernel_events": make_run(STEPS, RECORDS, kernels, mc=MC),
        "no_linear_layer": make_run(
            STEPS, RECORDS, {CHANNEL_KERNEL: {"seconds": 1.0, "calls": 6}},
            mc=OLD_MC),
        "no_prefill_in_the_span": make_run(STEPS[2:], RECORDS[2:], kernels, mc=MC),
        "untraced": make_run(STEPS, RECORDS, None, mc=MC),
    }[case]
    if case == "untraced":
        run.trace_wall = None
    assert reader()(run) is None


def test_a_rehearsal_shows_the_calls_expected_as_a_count():
    got = reader()(make_run(STEPS, RECORDS, {}, platform="cpu", mc=MC))
    assert got == (0.0, 6)


@pytest.mark.parametrize("model,metric", [("head", "kda_chunk_roofline"),
                                          ("channel", "gdn_chunk_roofline")])
def test_the_twins_keep_apart(model, metric):
    """A head-decay model's run (its own kernel's events in the trace) gives
    None here: ``is_kda`` says so before any event is looked at. A
    channel-decay model's run gives None in ``gdn_chunk_roofline``: its
    kernel's name is no part of this one's, and this one's no part of its."""
    assert HEAD_KERNEL not in CHANNEL_KERNEL and CHANNEL_KERNEL not in HEAD_KERNEL
    mc, kernel = {"head": (HEAD_MC, HEAD_KERNEL), "channel": (MC, CHANNEL_KERNEL)}[model]
    run = make_run(STEPS, RECORDS, {kernel: {"seconds": 0.030, "calls": 12}}, mc=mc)
    assert reader(metric)(run) is None
    own = "gdn_chunk_roofline" if model == "head" else "kda_chunk_roofline"
    assert reader(own)(run) is not None
