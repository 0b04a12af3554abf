"""The delta rule's one-token step with a decay a channel by hand
(``kernel_costs_kda.py``: bytes and FLOPs at Solar-Open2's widths) and the
three readers ISSUE 49 added, each on a synthetic ``Run``: the step's calls are
counted over the LINEAR layers (3 of 4) at the ring's live lanes, the scope
reader divides by the span's decode steps, the prefill reader by the prompt
tokens whose prefill the span held, the roofline share counts live lanes' bytes
only. Every reader gives nothing, and does not raise, on what another program
hands it (Olmo-Hybrid's config, whose decay is a head's; a config with no
linear layer; no scope in the capture)."""

import pytest

import capture_scopes
import kernel_costs_kda as costs
from test_kernel_costs_gdn import (
    DECODE,
    PREFILL,
    STEPS,
    capture,
    make_run,
    reader,
    record,
    step,
)
from test_kernel_costs_gdn import MC as OLMO_MC
from test_kernel_costs_gdn import OLD_MC

L, F = "linear_attention", "full_attention"
# Solar-Open2-250B as the cell runs it: one period
MC = {"n_layers": 4, "layer_types": [F, L, L, L], "n_heads": 64, "n_kv_heads": 8,
      "head_dim": 128, "d_model": 4096, "d_ff": 1280, "linear_heads": 64,
      "linear_key_dim": 128, "linear_value_dim": 128, "linear_gate_rank": 128,
      "n_experts": 320, "n_experts_held": 40, "top_k": 8}
V5E = costs.peaks("TPU v5 lite")
LANE_BYTES = 8388608 + 49152 + 33024 + 32768
OPS = {
    DECODE + "layer/kda/proj/dot_general": [0.0240, 144],
    DECODE + "layer/kda/step/mul": [0.0160, 48],
    DECODE + "layer/kda/gate/mul": [0.0080, 48],
    DECODE + "layer/attn/global/pallas_call": [0.0300, 16],
    DECODE + "layer/kdax/step/dot_general": [9.0, 1],     # another scope's name
    PREFILL + "layer/kda/chunk/while": [0.0450, 3],
    PREFILL + "layer/kda/proj/dot_general": [0.0150, 12],
    PREFILL + "layer/attn/pallas_call": [0.5, 1],
}


def test_a_step_call_by_hand():
    # one live lane: S 64 x 128 x 128 x 4 B = 4,194,304 B read and as many
    # written; q, k, v 3 x 64 x 128 in bf16 = 49,152; decays 64 x 128 x 4 and
    # write strengths 64 x 4 = 33,024; the float32 output 64 x 128 x 4 = 32,768
    cost = costs.step(1, 64, 128, 128)
    assert cost == {"bytes": LANE_BYTES, "flops": 7 * 64 * 128 * 128}
    assert LANE_BYTES == 8503552
    best = costs.roofline(cost, V5E)
    assert best["bound"] == "memory"
    assert best["seconds"] == pytest.approx(LANE_BYTES / 819e9)
    assert costs.step(8, 64, 128, 128)["bytes"] == 8 * LANE_BYTES
    assert costs.step(0, 64, 128, 128) == {"bytes": 0, "flops": 0}


def test_the_model_is_told_by_its_gates_rank():
    assert costs.is_kda(MC)
    assert not costs.is_kda(OLMO_MC) and not costs.is_kda(OLD_MC)
    assert not costs.is_kda({"n_layers": 4, "linear_gate_rank": 8})


def test_calls_a_step_are_the_linear_layers_at_the_rings_live_lanes():
    run = make_run(STEPS, trace={"kernels": {}}, mc=MC)
    assert costs.step_calls(run) == [(4, 24.0), (2, 24.0)]      # 8 x 3 a boundary
    assert costs.step_calls(make_run(STEPS, trace={"kernels": {}}, mc=OLMO_MC)) is None


def test_roofline_share_counts_the_live_lanes_bytes_only(monkeypatch, capsys):
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(OPS))
    run = make_run(STEPS, trace={"kernels": {}}, mc=MC)
    value, calls = reader("kda_step_roofline")(run)
    least = (24 * 4 + 24 * 2) * LANE_BYTES / 819e9
    assert calls == 48 and value == pytest.approx(100 * least / 0.0160)
    assert 0 < value < 100
    assert "48 calls expected from the ring at 3.00 live lanes" in capsys.readouterr().out


def test_scope_reader_divides_by_the_spans_steps(monkeypatch):
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(OPS))
    run = make_run(STEPS, trace={"kernels": {}}, mc=MC)
    # 16 decode steps: 48 ms under layer/kda -> 3.0 ms a step
    assert reader("kda_layers_ms_per_step")(run) == (pytest.approx(3.0), 16)


def test_prefill_reader_divides_by_the_prompt_tokens_the_span_held(monkeypatch):
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(OPS))
    steps = [step(1005.3, 3, step_ms=300.0, admitted=1, prefill_ms=100.0)]
    first = 1005.15 - 1000.0 + 100.0            # monotonic
    run = make_run(steps, trace={"kernels": {}}, records=[record(6000, first, 50)],
                   mc=MC)
    # 60 ms under layer/kda (the chunked rule among it) over 6 thousand tokens
    assert reader("kda_prefill_ms_per_ktok")(run) == (pytest.approx(10.0), 6000)


NEW = ("kda_layers_ms_per_step", "kda_prefill_ms_per_ktok", "kda_step_roofline")


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("mc", [OLMO_MC, OLD_MC], ids=["olmo_hybrid", "no_linear_layer"])
def test_new_readers_give_nothing_on_another_program(monkeypatch, name, mc):
    """Another model's program (a capture that HAS ``layer/kda`` scopes would
    still not be read: the config decides), and this PR's program on a capture
    without the scopes, on no capture, and untraced."""
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(OPS))
    admit = [step(1005.3, 3, step_ms=300.0, admitted=1, prefill_ms=100.0)]
    records = [record(600, 105.15, 50)]
    other = make_run(STEPS + admit, trace={"kernels": {}}, mc=mc, records=records)
    assert reader(name)(other) is None
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(
        {DECODE + "layer/ffn/dot_general": [0.1, 10]}))
    bare = make_run(STEPS + admit, trace={"kernels": {}}, records=records, mc=MC)
    assert reader(name)(bare) is None
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: None)
    assert reader(name)(make_run(STEPS + admit, trace={"kernels": {}},
                                 records=records, mc=MC)) is None
    untraced = make_run(STEPS, records=records, mc=MC)
    untraced.trace_wall = None
    assert reader(name)(untraced) is None


@pytest.mark.parametrize("name", NEW)
def test_a_rehearsal_shows_counts_and_no_value(name):
    admit = [step(1005.3, 3, step_ms=300.0, admitted=1, prefill_ms=100.0)]
    run = make_run(STEPS + admit, trace={"kernels": {}}, platform="cpu",
                   records=[record(600, 105.15, 50)], mc=MC)
    want = {"kda_layers_ms_per_step": 16, "kda_prefill_ms_per_ktok": 600,
            "kda_step_roofline": 48}[name]
    got = reader(name)(run)
    assert got[0] == 0.0 and got[1] >= want
