"""The attention calls of a model whose query heads go by layer, by hand
(``kernel_costs_heads.py``: bytes and FLOPs of the two decode calls and the two
flash calls at a layer's OWN head count), the count of the layers that hold
experts, and the five readers ISSUE 53 added, each on a synthetic ``Run``. A
padded GQA group is an implementation's choice: the costs count 6 and 9. Every
reader gives nothing, and does not raise, on what a program older than the PR
hands it (no ``n_heads_per_layer`` in the program's config, no ring field, no
kernel or scope of the names it reads)."""

import pytest

import capture_scopes
import kernel_costs
import kernel_costs_heads as costs
import kernel_costs_mla
import kernel_costs_window
import run as benchrun
from client import new_record
from measure import Run

S, F = "sliding_attention", "full_attention"
# Laguna-S-2.1 at the 9 layers the cell runs: layer 0 global and dense, then
# s s s F s s s F; 72 query heads in a window layer, 48 in a global one
TYPES = [F, S, S, S, F, S, S, S, F]
MC = {"n_layers": 9, "layer_types": TYPES, "sliding_window": 512,
      "n_heads": 48, "n_heads_per_layer": [72 if t == S else 48 for t in TYPES],
      "n_kv_heads": 8, "head_dim": 128, "d_model": 3072, "d_ff": 1024,
      "mlp_only_layers": [0], "top_k": 10, "n_experts": 256,
      "n_experts_held": 32, "expert_first": 0}
# Mellum2's: window layers, ONE head count
OLD_MC = {"n_layers": 8, "layer_types": [S, S, S, F] * 2, "sliding_window": 1024,
          "n_heads": 32, "n_kv_heads": 4, "head_dim": 128, "d_model": 2304,
          "d_ff": 896, "top_k": 8, "n_experts": 64}
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


def step(t_wall, active, chunk=8, step_ms=250.0, admitted=0, prefill_ms=0.0,
         rows_local=0.0, hit=0.0) -> dict:
    return {"t_wall": t_wall, "engine": "continuous", "step_ms": step_ms,
            "chunk": chunk, "active": active, "admitted": admitted,
            "retired": 0, "prefill_ms": prefill_ms, "window_pages": 33.0,
            "expert_rows_local": rows_local, "experts_hit": hit}


def record(prompt_len, first_token_at, tokens, max_new=400):
    r = new_record("generate", "tenant00", 0, first_token_at - 0.5, prompt_len,
                   max_new)
    r["token_t"] = [first_token_at + 0.01 * i for i in range(tokens)]
    r["ok"] = True
    return r


def reader(name):
    return benchrun.load_reader("per_layer", name)


# -- the costs, by hand -----------------------------------------------------------

def test_the_heads_of_each_kind_come_from_the_config_as_run():
    assert costs.kind_heads(MC, S) == [72] * 6 and costs.kind_heads(MC, F) == [48] * 3
    assert costs.has_heads_a_layer(MC) and not costs.has_heads_a_layer(OLD_MC)
    assert costs.expert_layers(MC) == 8
    assert costs.expert_layers(OLD_MC) == 8          # every layer holds experts


def test_a_window_decode_call_by_hand():
    # one lane of 6000 tokens: its last 512 are positions 5488..5999 = pages
    # 343..374 = 32 pages x 16 tokens x 4 KiB (8 KV heads x 128 x 2 sides x 2 B)
    #   = 2097152 B; 72 queries in bf16 and 72 float32 outputs of 128:
    #   72 x 128 x 6 = 55296; FLOPs 2 x 2 x 512 x 72 x 128 = 18874368
    cost = costs.window_decode([6000], MC, 16)
    assert cost == {"bytes": 32 * 16 * 4096 + 55296, "flops": 18874368}
    # 6001 tokens end one past a page's edge: 33 pages, a whole ring
    assert costs.window_decode([6001], MC, 16)["bytes"] == 33 * 16 * 4096 + 55296
    # the program's ONE n_heads (48) would undercount queries, outputs, FLOPs
    one = kernel_costs_window.window_decode([6000], 512, 16, 48, 8, 128)
    assert one["bytes"] == cost["bytes"] - 24 * 128 * 6
    assert one["flops"] * 72 == cost["flops"] * 48
    assert costs.roofline(cost, V5E)["bound"] == "memory"


def test_a_global_decode_call_by_hand():
    # 3 lanes holding 20000 live tokens: 20000 x 4 KiB of rows; 48 queries and
    # outputs a lane: 3 x 48 x 128 x 6 = 110592; FLOPs 2 x 2 x 20000 x 48 x 128
    cost = costs.global_decode(20000, 3, MC)
    assert cost == {"bytes": 20000 * 4096 + 110592, "flops": 491520000}
    assert cost == kernel_costs.paged_decode(20000, 3, 48, 8, 128)
    best = costs.roofline(cost, V5E)
    assert best["bound"] == "memory"
    assert best["seconds"] == pytest.approx(cost["bytes"] / 819e9)   # 0.100 ms


def test_the_two_flash_calls_by_hand():
    # a window layer over 6144 tokens: 512 x 513 / 2 + 5632 x 512 = 3014912
    # pairs; FLOPs 4 x 72 x 128 x pairs = 111141715968 (0.111 TFLOP);
    # q and the output 2 x 72 heads, k and v 2 x 8: 160 x 6144 x 128 x 2 B
    cost = costs.window_flash(6144, MC)
    assert cost == {"bytes": 160 * 6144 * 128 * 2, "flops": 4 * 72 * 128 * 3014912}
    assert costs.roofline(cost, V5E)["bound"] == "compute"
    # a global layer: the whole causal triangle at 48 heads,
    # 6144 x 6145 / 2 = 18877440 pairs = 0.464 TFLOP; 112 x 6144 x 128 x 2 B
    cost = costs.global_flash(6144, MC)
    assert cost == {"bytes": 112 * 6144 * 128 * 2, "flops": 4 * 48 * 128 * 18877440}
    # a prompt inside one window is the same triangle in both kinds
    assert (costs.window_flash(300, MC)["flops"] * 48
            == costs.global_flash(300, MC)["flops"] * 72)


# -- the calls a traced span held ----------------------------------------------------

STEPS = [step(1001.1, 0, chunk=0, admitted=1, prefill_ms=40.0),
         step(1003.0, 2, rows_local=3.0, hit=3.0),
         # began at 1004.3 with a prefill of 200 ms, all of it inside the span
         step(1004.75, 0, chunk=0, step_ms=450.0, admitted=1, prefill_ms=200.0),
         step(1005.0, 2, rows_local=2.0, hit=2.0),
         step(1006.0, 3, rows_local=4.0, hit=3.0),
         step(1007.0, 0, chunk=0),
         # began at 1019.75: the third request's admission, after the span
         step(1020.25, 0, chunk=0, step_ms=500.0, admitted=1, prefill_ms=400.0),
         step(1030.0, 1, rows_local=1.0, hit=1.0)]
# mono = wall - 900; the span is mono [104, 108]
RECORDS = [record(3000, 101.0, 390),       # streaming all through the span
           record(6144, 104.5, 300),       # first token inside the span
           record(16384, 120.0, 10)]       # after it


def trace(window_s=0.0, window_n=0, flash_s=0.0, flash_n=0, global_s=0.0,
          global_n=0, experts_s=0.0, experts_n=0):
    kernels = {"fusion": {"seconds": 1.0, "calls": 1000}}
    for name, s, n in (("paged_window_decode_kernel", window_s, window_n),
                       ("flash_window_kernel", flash_s, flash_n),
                       ("paged_decode_attention_kernel", global_s, global_n),
                       ("moe_grouped_matmul_kernel", experts_s, experts_n)):
        if n:
            kernels[name] = {"seconds": s, "calls": n}
    return {"kernels": kernels}


def test_share_calls_count_the_layers_that_hold_experts():
    run = make_run(STEPS, records=RECORDS)
    theirs = kernel_costs_mla.share_calls(run)
    mine = costs.share_calls(run)
    # the same rows and experts hit; 8 calls a step where n_layers says 9
    assert [(r, e) for r, e, _c in mine] == [(r, e) for r, e, _c in theirs]
    assert [c for _r, _e, c in theirs[:2]] == [pytest.approx(8 * 9)] * 2
    assert [c for _r, _e, c in mine[:2]] == [pytest.approx(8 * 8)] * 2
    # the prefill whose first token came inside the span: a call a layer that
    # holds experts, of the held eighth of 6144 x 10 rows
    assert mine[-1] == (pytest.approx(6144 * 10 / 8), pytest.approx(32.0), 8)
    assert run.program_config["n_layers"] == 9              # the run is not touched
    bare = [{k: v for k, v in s.items() if k != "expert_rows_local"} for s in STEPS]
    assert costs.share_calls(make_run(bare, records=RECORDS)) is None


# -- the readers -------------------------------------------------------------------

def test_heads_window_decode_roofline_counts_72_heads(capsys):
    run = make_run(STEPS, trace(window_s=0.0096, window_n=96), records=RECORDS)
    value, n = reader("heads_window_decode_roofline")(run)
    # the boundaries that ended at 1005 and 1006: 8 steps x 6 window layers
    least = 0.0
    for tokens in ([3388, 6182], [3390, 6282]):
        least += 48 * costs.window_decode(tokens, MC, 16)["bytes"] / 819e9
    assert n == 96 and value == pytest.approx(100 * least / 0.0096)
    assert 0 < value < 100
    out = capsys.readouterr().out
    assert "[72, 72, 72, 72, 72, 72] query heads over 8 KV heads" in out
    assert "2.00 lanes a call" in out
    # the reader of ONE head count reads lower on the same run
    old, _ = reader("window_decode_roofline")(run)
    assert old < value
    for nothing in (make_run(STEPS, trace(), records=RECORDS),
                    make_run(STEPS, trace(window_s=1.0, window_n=9),
                             records=RECORDS, mc=OLD_MC)):
        assert reader("heads_window_decode_roofline")(nothing) is None
    assert reader("heads_window_decode_roofline")(
        make_run(STEPS, None, platform="cpu", records=RECORDS)) == (0.0, 96)


def test_heads_global_decode_roofline_counts_48_heads_over_the_global_layers(capsys):
    run = make_run(STEPS, trace(global_s=0.0048, global_n=48), records=RECORDS)
    value, n = reader("heads_global_decode_roofline")(run)
    least = sum(24 * costs.global_decode(t, ln, MC)["bytes"] / 819e9
                for t, ln in ((3388 + 6182, 2), (3390 + 6282, 3)))
    assert n == 48 and value == pytest.approx(100 * least / 0.0048)
    assert "[48, 48, 48] query heads over 8 KV heads; 48 calls expected" in (
        capsys.readouterr().out)
    # the window layers' events are not its events
    assert reader("heads_global_decode_roofline")(make_run(
        STEPS, trace(window_s=0.0048, window_n=96), records=RECORDS)) is None
    assert reader("heads_global_decode_roofline")(make_run(
        STEPS, trace(global_s=1.0, global_n=9), records=RECORDS, mc=OLD_MC)) is None
    assert reader("heads_global_decode_roofline")(
        make_run(STEPS, None, platform="cpu", records=RECORDS)) == (0.0, 48)


def test_heads_window_prefill_roofline_counts_72_heads_and_never_scales(capsys):
    least = 6 * costs.window_flash(6144, MC)["flops"] / 197e12
    run = make_run(STEPS, trace(flash_s=0.009, flash_n=6), records=RECORDS)
    assert reader("heads_window_prefill_roofline")(run) == (
        pytest.approx(100 * least / 0.009), 6)
    assert "6.0 calls expected" in capsys.readouterr().out
    # the span held half of the prefill: half of its calls' least time
    run = make_run(STEPS, trace(flash_s=0.0045, flash_n=3), records=RECORDS)
    run.trace_wall = (1004.4, 1008.0)
    assert reader("heads_window_prefill_roofline")(run) == (
        pytest.approx(100 * least / 2 / 0.0045), 3)
    for nothing in (make_run(STEPS, trace(flash_s=0.009, flash_n=6), records=RECORDS[:1]),
                    make_run(STEPS, trace(), records=RECORDS),
                    make_run(STEPS, trace(flash_s=0.009, flash_n=6),
                             records=RECORDS, mc=OLD_MC)):
        assert reader("heads_window_prefill_roofline")(nothing) is None
    assert reader("heads_window_prefill_roofline")(
        make_run(STEPS, None, platform="cpu", records=RECORDS)) == (0.0, 6)


def test_share_sparse_experts_roofline_is_nine_eighths_below_the_dense_count(capsys):
    run = make_run(STEPS, trace(experts_s=0.02, experts_n=2 * 136), records=RECORDS)
    value, n = reader("share_sparse_experts_roofline")(run)
    dense_count, _ = reader("moe_share_experts_roofline")(run)
    assert n == 136 and dense_count == pytest.approx(value * 9 / 8)
    assert "8 of 9 layers hold experts; 136 calls expected" in capsys.readouterr().out
    assert reader("share_sparse_experts_roofline")(
        make_run(STEPS, trace(), records=RECORDS)) is None
    assert reader("share_sparse_experts_roofline")(
        make_run(STEPS, trace(experts_s=0.02, experts_n=8), records=RECORDS,
                 mc=OLD_MC)) is None
    assert reader("share_sparse_experts_roofline")(
        make_run(STEPS, None, platform="cpu", records=RECORDS)) == (0.0, 136)


def test_attn_kind_ms_per_step_reports_the_window_layers_and_prints_the_pair(
        monkeypatch, capsys):
    ops = {"jit(_paged_decode_chunk_jit)/while/body/layer/attn/window/dot": [0.012, 96],
           "jit(_paged_decode_chunk_jit)/while/body/layer/attn/window/gate/dot": [0.004, 96],
           "jit(_paged_decode_chunk_jit)/while/body/layer/attn/global/dot": [0.006, 48],
           "jit(_paged_decode_chunk_jit)/while/body/layer/attn/dot": [0.5, 144],
           "jit(_slot_prefill_jit)/layer/attn/window/dot": [9.0, 6]}
    monkeypatch.setattr(capture_scopes, "capture_of",
                        lambda run: {"ops": ops, "device": "/device:TPU:0"})
    run = make_run(STEPS, trace(), records=RECORDS)
    # 16 decode steps in the span: (0.012 + 0.004) s over them = 1 ms a step
    assert reader("attn_kind_ms_per_step")(run) == (pytest.approx(1.0), 16)
    out = capsys.readouterr().out
    assert "layer/attn/window 1.0000 ms (6 layers, 0.1667 a layer)" in out
    assert "layer/attn/global 0.3750 ms (3 layers, 0.1250 a layer)" in out
    assert "the gates 0.2500 ms" in out
    # a model of one head count, a program without the scopes, no capture
    assert reader("attn_kind_ms_per_step")(
        make_run(STEPS, trace(), records=RECORDS, mc=OLD_MC)) is None
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: {
        "ops": {"jit(_paged_decode_chunk_jit)/layer/attn/dot": [1.0, 9]},
        "device": "/device:TPU:0"})
    assert reader("attn_kind_ms_per_step")(run) is None
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: None)
    assert reader("attn_kind_ms_per_step")(run) is None
    assert reader("attn_kind_ms_per_step")(
        make_run(STEPS, None, platform="cpu", records=RECORDS)) == (0.0, 16)


@pytest.mark.parametrize("name", [
    "heads_window_decode_roofline", "heads_global_decode_roofline",
    "heads_window_prefill_roofline", "attn_kind_ms_per_step",
    "share_sparse_experts_roofline"])
def test_a_reader_gives_nothing_and_does_not_raise_on_an_older_program(name):
    """What the parent hands a reader in a traced run: a config with no heads
    a layer (and, for the oldest, no window and no share), a bare ring, a
    trace with none of the names."""
    bare = [{"t_wall": 1005.0, "engine": "continuous", "step_ms": 250.0,
             "chunk": 8, "active": 2, "admitted": 0, "retired": 0}]
    for mc in (OLD_MC, {"n_layers": 8, "n_heads": 32, "n_kv_heads": 8,
                        "d_model": 4096, "d_ff": 14336}):
        for tr in (trace(), None):
            assert reader(name)(make_run(bare, tr, records=RECORDS, mc=mc)) is None
