"""The delta rule's one-token step by hand (``kernel_costs_gdn.py``: bytes and
FLOPs at two lane counts) and the readers ISSUE 46 added, each on a synthetic
``Run``: the step's calls are counted over the LINEAR layers (6 of 8) at the
ring's live lanes, the scope readers divide by the span's decode steps, the
prefill reader by the prompt tokens whose prefill the span held, the roofline
share counts live lanes' bytes only. Every reader gives nothing, and does not
raise, on what a program older than the PR hands it (no ``linear_attention``
in the program's config, no ring field, no scope in the capture)."""

import pytest

import capture_scopes
import kernel_costs_gdn as costs
import run as benchrun
from client import new_record
from measure import Run

L, F, S = "linear_attention", "full_attention", "sliding_attention"
# Olmo-Hybrid-7B as the cell runs it: 8 layers, two periods
MC = {"n_layers": 8, "layer_types": [L, L, L, F] * 2, "n_heads": 30,
      "n_kv_heads": 30, "d_model": 3840, "d_ff": 11008, "linear_heads": 30,
      "linear_key_dim": 96, "linear_value_dim": 192}
OLD_MC = {"n_layers": 8, "layer_types": [S, S, S, F] * 2, "sliding_window": 1024,
          "n_heads": 32, "n_kv_heads": 4, "head_dim": 128, "d_model": 2304}
V5E = costs.peaks("TPU v5 lite")
LANE_BYTES = 2 * 2211840 + 30 * (2 * 96 + 192) * 2 + 2 * 30 * 4 + 30 * 192 * 4


def make_run(steps, trace=None, platform="tpu", records=(), mc=MC) -> Run:
    r = Run(cell={}, config={}, program_config=mc,
            server={"serving": {"kv_page_tokens": 16}},
            device={"platform": platform, "kind": "TPU v5 lite"},
            seconds=10.0, t0=100.0, t_end=125.0)
    r.before = {"t": 100.0, "t_wall": 1000.0, "prom": {}}
    r.after = {"prom": {}}
    r.steps, r.records, r.trace = list(steps), list(records), trace
    r.trace_wall = (1004.0, 1008.0)
    return r


def step(t_wall, active, chunk=8, step_ms=250.0, admitted=0, prefill_ms=0.0,
         state_lanes=16) -> dict:
    return {"t_wall": t_wall, "engine": "continuous", "step_ms": step_ms,
            "chunk": chunk, "active": active, "admitted": admitted,
            "retired": 0, "prefill_ms": prefill_ms,
            "state_lanes": state_lanes if chunk else 0}


def record(prompt_len, first_token_at, tokens, max_new=512):
    r = new_record("generate", "tenant00", 0, first_token_at - 0.5, prompt_len,
                   max_new)
    r["token_t"] = [first_token_at + 0.01 * i for i in range(tokens)]
    r["ok"] = True
    return r


def reader(name):
    return benchrun.load_reader("per_layer", name)


def capture(ops=None, host=None, device="/device:TPU:0"):
    return {"ops": ops or {}, "host": host or {}, "device": device}


# two boundaries wholly inside the span: 16 decode steps at 4 and at 2 lanes
STEPS = [step(1005.0, 4), step(1006.0, 2),
         step(1002.0, 3),                            # before the span
         step(1007.0, 2, chunk=0, admitted=1)]       # ran no chunk
DECODE = "jit(_paged_decode_chunk_jit)/while/body/closed_call/"
PREFILL = "jit(_slot_prefill_jit)/"
OPS = {
    DECODE + "layer/gdn/proj/dot_general": [0.0240, 288],
    DECODE + "layer/gdn/step/mul": [0.0160, 96],
    DECODE + "layer/gdn/gate/mul": [0.0080, 96],
    DECODE + "layer/attn/global/pallas_call": [0.0300, 48],
    DECODE + "layer/gdnx/step/dot_general": [9.0, 1],     # another scope's name
    PREFILL + "layer/gdn/chunk/while": [0.0450, 6],
    PREFILL + "layer/gdn/proj/dot_general": [0.0150, 24],
    PREFILL + "layer/attn/pallas_call": [0.5, 2],
}


# -- the costs, by hand -----------------------------------------------------------

def test_a_step_call_by_hand():
    # one live lane: S 30 x 96 x 192 x 4 B = 2,211,840 B read and as many
    # written; q, k 30 x 96 and v 30 x 192 in bf16 = 23,040; two gates 240;
    # the float32 output 30 x 192 x 4 = 23,040; FLOPs 7 x 30 x 96 x 192
    cost = costs.step(1, 30, 96, 192)
    assert cost == {"bytes": LANE_BYTES, "flops": 7 * 30 * 96 * 192}
    assert LANE_BYTES == 4470000
    best = costs.roofline(cost, V5E)
    assert best["bound"] == "memory"
    assert best["seconds"] == pytest.approx(LANE_BYTES / 819e9)
    # the live lanes' and no other's: 4 of 16 cost 4 lanes' bytes
    assert costs.step(4, 30, 96, 192)["bytes"] == 4 * LANE_BYTES
    assert costs.step(0, 30, 96, 192) == {"bytes": 0, "flops": 0}


def test_layers_are_counted_by_kind():
    assert costs.layer_counts(MC) == {"linear": 6, "full": 2}
    assert costs.layer_counts(OLD_MC) is None
    assert costs.layer_counts({"n_layers": 4}) is None


def test_calls_a_step_are_the_linear_layers_at_the_rings_live_lanes():
    run = make_run(STEPS, trace={"kernels": {}})
    assert costs.step_calls(run) == [(4, 48.0), (2, 48.0)]      # 8 x 6 a boundary
    assert costs.step_calls(make_run(STEPS, mc=OLD_MC)) is None
    # a boundary half inside the span counts half
    run.trace_wall = (1004.875, 1008.0)
    assert costs.step_calls(run)[0] == (4, 24.0)


def test_roofline_share_counts_the_live_lanes_bytes_only(monkeypatch, capsys):
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(OPS))
    run = make_run(STEPS, trace={"kernels": {}})
    value, calls = reader("gdn_step_roofline")(run)
    least = (48 * 4 + 48 * 2) * LANE_BYTES / 819e9
    assert calls == 96 and value == pytest.approx(100 * least / 0.0160)
    assert 0 < value < 100
    assert "96 calls expected from the ring at 3.00 live lanes" in capsys.readouterr().out


def test_scope_readers_divide_by_the_spans_steps(monkeypatch):
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(OPS))
    run = make_run(STEPS, trace={"kernels": {}})
    # 16 decode steps: 48 ms under layer/gdn -> 3.0 ms a step
    assert reader("gdn_layers_ms_per_step")(run) == (pytest.approx(3.0), 16)


def test_prefill_reader_divides_by_the_prompt_tokens_the_span_held(monkeypatch):
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(OPS))
    # an admitting boundary 1005.0 .. 1005.3 whose 100 ms of prefill lie inside
    # the span, and the request (6000 prompt tokens) whose first token follows
    steps = [step(1005.3, 3, step_ms=300.0, admitted=1, prefill_ms=100.0)]
    first = 1005.15 - 1000.0 + 100.0            # monotonic
    run = make_run(steps, trace={"kernels": {}}, records=[record(6000, first, 50)])
    assert costs.prefill_tokens(run) == pytest.approx(6000.0)
    # 60 ms under layer/gdn (the chunked rule among it) over 6 thousand tokens
    assert reader("gdn_prefill_ms_per_ktok")(run) == (pytest.approx(10.0), 6000)
    # half of the prefill inside the span: half the tokens
    run.trace_wall = (1005.05, 1008.0)
    assert costs.prefill_tokens(run) == pytest.approx(3000.0)
    # a span that held no prefill
    assert costs.prefill_tokens(make_run(STEPS, records=[record(600, 90.0, 50)])) is None


def test_state_write_lanes_is_the_rings_field_where_a_chunk_ran():
    run = make_run(STEPS)
    assert reader("state_write_lanes_mean")(run) == (pytest.approx(16.0), 3)
    assert reader("state_write_lanes_mean")(make_run(
        [{**s, "state_lanes": 0} for s in STEPS])) is None
    assert reader("state_write_lanes_mean")(make_run(
        [{k: v for k, v in s.items() if k != "state_lanes"} for s in STEPS])) is None


NEW = ("gdn_layers_ms_per_step", "gdn_prefill_ms_per_ktok", "gdn_step_roofline",
       "state_write_lanes_mean")


@pytest.mark.parametrize("name", NEW)
def test_new_readers_give_nothing_on_an_older_program(monkeypatch, name):
    """The parent's program in an accepted cell (no ``linear_attention`` layer,
    no ``state_lanes`` in its ring), and this PR's program on a capture without
    the scopes."""
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(
        {DECODE + "layer/ffn/dot_general": [0.1, 10]}))
    old_steps = [{k: v for k, v in s.items() if k != "state_lanes"} for s in STEPS]
    admit = [step(1005.3, 3, step_ms=300.0, admitted=1, prefill_ms=100.0)]
    records = [record(600, 105.15, 50)]
    old = make_run(old_steps, trace={"kernels": {}}, mc=OLD_MC, records=records)
    assert reader(name)(old) is None
    if name != "state_write_lanes_mean":
        bare = make_run(STEPS + admit, trace={"kernels": {}}, records=records)
        assert reader(name)(bare) is None
        monkeypatch.setattr(capture_scopes, "capture_of", lambda run: None)
        assert reader(name)(make_run(STEPS + admit, trace={"kernels": {}},
                                     records=records)) is None
        untraced = make_run(STEPS, records=records)
        untraced.trace_wall = None
        assert reader(name)(untraced) is None


@pytest.mark.parametrize("name", NEW[:3])
def test_a_rehearsal_shows_counts_and_no_value(name):
    admit = [step(1005.3, 3, step_ms=300.0, admitted=1, prefill_ms=100.0)]
    run = make_run(STEPS + admit, trace={"kernels": {}}, platform="cpu",
                   records=[record(600, 105.15, 50)])
    want = {"gdn_layers_ms_per_step": 16, "gdn_prefill_ms_per_ktok": 600,
            "gdn_step_roofline": 96}[name]
    got = reader(name)(run)
    assert got[0] == 0.0 and got[1] >= want
