"""The decode call over a SHARED global layer by hand (``kernel_costs_sambay.
py``: bytes and FLOPs at two shapes) and the readers ISSUE 41 added, each on a
synthetic ``Run``: the calls are counted over the layers that READ the one
arena layer (8 of 32; ``n_layers`` would read four times the truth), the scope
readers divide by the span's decode steps, the prefill reader by the prompt
tokens whose prefill the span held. Every reader gives nothing, and does not
raise, on what a program older than the PR hands it (no ``cross_attention`` in
the program's config, no ring field, no scope in the capture)."""

import pytest

import capture_scopes
import kernel_costs_sambay as costs
import run as benchrun
from client import new_record
from measure import Run

M, S, F, G, X = ("mamba", "sliding_attention", "full_attention", "gmu",
                 "cross_attention")
# Phi-4-mini-flash-reasoning as the cell runs it: 32 layers
TYPES = [M, S] * 8 + [M, F] + [G, X] * 7
MC = {"n_layers": 32, "layer_types": TYPES, "sliding_window": 512,
      "n_heads": 40, "n_kv_heads": 20, "d_model": 2560, "d_ff": 10240}
OLD_MC = {"n_layers": 8, "layer_types": [S, S, S, F] * 2, "sliding_window": 1024,
          "n_heads": 32, "n_kv_heads": 4, "head_dim": 128, "d_model": 2304}
V5E = costs.peaks("TPU v5 lite")


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


def step(t_wall, active, shared, chunk=8, step_ms=250.0, admitted=0,
         prefill_ms=0.0) -> dict:
    return {"t_wall": t_wall, "engine": "continuous", "step_ms": step_ms,
            "chunk": chunk, "active": active, "admitted": admitted,
            "retired": 0, "prefill_ms": prefill_ms, "shared_pages": shared,
            "window_pages": 30.0}


def record(prompt_len, first_token_at, tokens, max_new=2048):
    r = new_record("generate", "tenant00", 0, first_token_at - 0.5, prompt_len,
                   max_new)
    r["token_t"] = [first_token_at + 0.01 * i for i in range(tokens)]
    r["ok"] = True
    return r


def reader(name):
    return benchrun.load_reader("per_layer", name)


def capture(ops=None, host=None, device="/device:TPU:0"):
    return {"ops": ops or {}, "host": host or {}, "device": device}


# two boundaries wholly inside the span: 16 decode steps
STEPS = [step(1005.0, 2, 500.0), step(1006.0, 2, 520.0),
         step(1002.0, 2, 480.0),                     # before the span
         step(1007.0, 2, 0.0, chunk=0, admitted=1)]  # ran no chunk
# two lanes streaming through the span: 300 + ~500 and 1000 + ~500 tokens
RECORDS = [record(300, 100.0, 900), record(1000, 100.0, 900)]
DECODE = "jit(_paged_decode_chunk_jit)/while/body/closed_call/"
PREFILL = "jit(_slot_prefill_jit)/"
OPS = {
    DECODE + "layer/ssm/dot_general": [0.0240, 432],
    DECODE + "layer/ssm/step/mul": [0.0080, 144],
    DECODE + "layer/gmu/dot_general": [0.0160, 224],
    DECODE + "layer/attn/cross/pallas_call": [0.0300, 112],
    DECODE + "layer/ssmx/dot_general": [9.0, 1],          # another scope's name
    PREFILL + "layer/ssm/scan/while": [0.0450, 9],
    PREFILL + "layer/ssm/dot_general": [0.0150, 36],
    PREFILL + "layer/gmu/dot_general": [0.5, 14],
}


# -- the costs, by hand -----------------------------------------------------------

def test_a_shared_decode_call_by_hand():
    # one lane of 3000 tokens: K and V rows of 10 pairs x 128 = 20 heads x 64,
    # two sides, 2 B: 5120 B a token = 15360000; queries 40 x 64 x 2 B = 5120
    # and the float32 output 20 pairs x 128 x 4 B = 10240; FLOPs 6 x 64 x 40 a
    # token (a score over 64, a value product over 128, two a multiply-add)
    cost = costs.shared_decode(3000, 1, 40, 20, 64)
    assert cost == {"bytes": 3000 * 5120 + 5120 + 10240,
                    "flops": 3000 * 6 * 64 * 40}
    best = costs.roofline(cost, V5E)
    assert best["bound"] == "memory"
    assert best["seconds"] == pytest.approx(cost["bytes"] / 819e9)
    # 12 lanes of 1500 tokens on average
    cost = costs.shared_decode(18000, 12, 40, 20, 64)
    assert cost["bytes"] == 18000 * 5120 + 12 * 15360


def test_layers_are_counted_by_what_they_read():
    assert costs.layer_counts(MC) == {"mamba": 9, "gmu": 7, "readers": 8}
    assert costs.layer_counts(OLD_MC) is None
    assert costs.layer_counts({"n_layers": 4}) is None


def test_calls_a_step_are_the_readers_not_n_layers():
    run = make_run(STEPS, trace={"kernels": {}}, records=RECORDS)
    calls = costs.shared_decode_calls(run)
    assert [round(c) for _t, _l, c in calls] == [64, 64]      # 8 x 8 a boundary
    assert all(lanes == 2 for _t, lanes, _c in calls)
    # tokens at a boundary's middle: prompt + what the client had received
    # (the first boundary ran 1004.75 .. 1005.0: 4.875 s after the first token)
    assert calls[0][0] == 300 + 1000 + 2 * 488
    assert costs.shared_decode_calls(make_run(STEPS, mc=OLD_MC)) is None


def test_roofline_share_counts_eight_calls_a_step(capsys):
    run = make_run(STEPS, trace={"kernels": {
        "paged_decode_kernel": {"seconds": 0.004, "calls": 128},
        "paged_window_decode_kernel": {"seconds": 0.9, "calls": 128}}},
        records=RECORDS)
    value, calls = reader("shared_kv_decode_roofline")(run)
    want = sum(c * costs.roofline(costs.shared_decode(t, l, 40, 20, 64), V5E)[
        "seconds"] for t, l, c in costs.shared_decode_calls(run))
    assert calls == 128 and value == pytest.approx(100 * want / 0.004)
    assert 0 < value < 100
    out = capsys.readouterr().out
    assert "8 layers read one arena layer" in out
    assert "128 calls expected from the ring, 128 in the trace" in out


def test_scope_readers_divide_by_the_spans_steps(monkeypatch):
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(OPS))
    run = make_run(STEPS, trace={"kernels": {}}, records=RECORDS)
    # 16 decode steps: 32 ms of layer/ssm -> 2.0 ms a step, 16 ms of layer/gmu
    assert reader("ssm_layers_ms_per_step")(run) == (pytest.approx(2.0), 16)
    assert reader("gmu_layers_ms_per_step")(run) == (pytest.approx(1.0), 16)


def test_prefill_reader_divides_by_the_prompt_tokens_the_span_held(monkeypatch):
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(OPS))
    # an admitting boundary 1005.0 .. 1005.3 whose 100 ms of prefill lie inside
    # the span, and the request (600 prompt tokens) whose first token follows it
    steps = [step(1005.3, 3, 500.0, step_ms=300.0, admitted=1, prefill_ms=100.0)]
    first = 1005.15 - 1000.0 + 100.0            # monotonic
    run = make_run(steps, trace={"kernels": {}}, records=[record(600, first, 50)])
    assert costs.prefill_tokens(run) == pytest.approx(600.0)
    # 60 ms under layer/ssm (the scan among it) over 0.6 thousand tokens
    assert reader("ssm_prefill_ms_per_ktok")(run) == (pytest.approx(100.0), 600)
    # half of the prefill inside the span: half the tokens
    run.trace_wall = (1005.05, 1008.0)
    assert costs.prefill_tokens(run) == pytest.approx(300.0)


def test_shared_pages_is_the_rings_field_where_a_chunk_ran():
    run = make_run(STEPS)
    assert reader("shared_pages_read_mean")(run) == (pytest.approx(500.0), 3)
    assert reader("shared_pages_read_mean")(make_run(
        [{**s, "shared_pages": 0.0} for s in STEPS])) is None
    assert reader("shared_pages_read_mean")(make_run(
        [{k: v for k, v in s.items() if k != "shared_pages"} for s in STEPS])) is None


NEW = ("ssm_layers_ms_per_step", "gmu_layers_ms_per_step",
       "ssm_prefill_ms_per_ktok", "shared_kv_decode_roofline",
       "shared_pages_read_mean")


@pytest.mark.parametrize("name", NEW)
def test_new_readers_give_nothing_on_an_older_program(monkeypatch, name):
    """The parent's program in an accepted cell (no ``cross_attention`` layer,
    no ``shared_pages`` in its ring), and this PR's program on a capture
    without the scopes or the kernel."""
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(
        {DECODE + "layer/ffn/dot_general": [0.1, 10]}))
    old_steps = [{k: v for k, v in s.items() if k != "shared_pages"} for s in STEPS]
    admit = [step(1005.3, 3, 0.0, step_ms=300.0, admitted=1, prefill_ms=100.0)]
    records = RECORDS + [record(600, 105.15, 50)]
    old = make_run(old_steps, trace={"kernels": {
        "paged_decode_kernel": {"seconds": 0.1, "calls": 256}}}, mc=OLD_MC,
        records=records)
    assert reader(name)(old) is None
    if name != "shared_pages_read_mean":
        bare = make_run(STEPS + admit, trace={"kernels": {}}, records=records)
        assert reader(name)(bare) is None
        monkeypatch.setattr(capture_scopes, "capture_of", lambda run: None)
        if name != "shared_kv_decode_roofline":
            assert reader(name)(make_run(STEPS + admit, trace={"kernels": {}},
                                         records=records)) is None
        untraced = make_run(STEPS, records=RECORDS)
        untraced.trace_wall = None
        assert reader(name)(untraced) is None


@pytest.mark.parametrize("name", NEW[:4])
def test_a_rehearsal_shows_counts_and_no_value(name):
    admit = [step(1005.3, 3, 0.0, step_ms=300.0, admitted=1, prefill_ms=100.0)]
    run = make_run(STEPS + admit, trace={"kernels": {}}, platform="cpu",
                   records=RECORDS + [record(600, 105.15, 50)])
    want = {"ssm_layers_ms_per_step": 16, "gmu_layers_ms_per_step": 16,
            "ssm_prefill_ms_per_ktok": 600, "shared_kv_decode_roofline": 128}[name]
    got = reader(name)(run)
    assert got[0] == 0.0 and got[1] >= want
