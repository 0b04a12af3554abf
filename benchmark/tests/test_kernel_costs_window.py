"""A window layer's two attention calls by hand (``kernel_costs_window.py``:
bytes and FLOPs at two shapes each) and the readers ISSUE 39 added, each on a
synthetic ``Run``: the calls are counted over the layers that KEEP A WINDOW (6
of 8; the global layers' over the other 2), a lane reads the pages of its
last ``min(tokens, window)`` tokens, a prefill's FLOPs are the window's and it
counts for the share of its boundary's prefill time inside the span. Every reader gives nothing, and
does not raise, on what a program older than the PR hands it (no
``sliding_window`` in the program's config, no ring field, no kernel of the
new names in the trace)."""

import pytest

import kernel_costs_window as costs
import run as benchrun
from client import new_record
from measure import Run

S, F = "sliding_attention", "full_attention"
# Mellum2-12B-A2.5B at the 8 layers the cell runs: 6 window layers, 2 global
MC = {"n_layers": 8, "layer_types": [S, S, S, F] * 2, "sliding_window": 1024,
      "n_heads": 32, "n_kv_heads": 4, "head_dim": 128, "d_model": 2304,
      "d_ff": 896, "top_k": 8, "n_experts": 64}
OLD_MC = {"n_layers": 8, "top_k": 8, "n_experts": 64, "d_model": 2048,
          "d_ff": 1024, "n_heads": 16, "n_kv_heads": 16}
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


def step(t_wall, active, pages, chunk=8, step_ms=250.0, admitted=0,
         prefill_ms=0.0) -> dict:
    return {"t_wall": t_wall, "engine": "continuous", "step_ms": step_ms,
            "chunk": chunk, "active": active, "admitted": admitted,
            "retired": 0, "prefill_ms": prefill_ms, "window_pages": pages}


def record(prompt_len, first_token_at, tokens, max_new=400):
    """A request whose first token came at monotonic ``first_token_at`` and
    that has received ``tokens`` so far, one every 10 ms."""
    r = new_record("generate", "tenant00", 0, first_token_at - 0.5, prompt_len,
                   max_new)
    r["token_t"] = [first_token_at + 0.01 * i for i in range(tokens)]
    r["ok"] = True
    return r


def reader(name):
    return benchrun.load_reader("per_layer", name)


# -- the costs, by hand -----------------------------------------------------------

def test_a_decode_call_by_hand():
    # one lane of 600 tokens (under the window): positions 0..599 lie in pages
    # 0..37 = 38 pages x 16 tokens x 2 KiB (4 KV heads x 128 x 2 sides x 2 B)
    #   = 1245184 bytes; queries + output 32 x 128 x (2 + 4) = 24576
    #   FLOPs 2 x 2 x 600 x 32 x 128 = 9830400
    cost = costs.window_decode([600], 1024, 16, 32, 4, 128)
    assert cost == {"bytes": 38 * 16 * 2048 + 24576, "flops": 9830400}
    # one lane of 5000 tokens: the last 1024 are positions 3976..4999, pages
    # 248..312 = 65 pages (a ring), 2129920 bytes, whatever lies before them
    cost = costs.window_decode([5000], 1024, 16, 32, 4, 128)
    assert cost == {"bytes": 65 * 16 * 2048 + 24576,
                    "flops": 2 * 2 * 1024 * 32 * 128}
    # two lanes add; 4992 ends on a page's edge: positions 3968..4991 = 64 pages
    cost = costs.window_decode([600, 4992], 1024, 16, 32, 4, 128)
    assert cost["bytes"] == (38 + 64) * 16 * 2048 + 2 * 24576
    best = costs.roofline(cost, V5E)
    assert best["bound"] == "memory"
    assert best["seconds"] == pytest.approx(cost["bytes"] / 819e9)
    # a global call over the same lanes would read 38 + 312 pages
    assert 5592 // 16 + 1 == 350


def test_a_flash_call_by_hand():
    # 512 tokens, all inside one window: sum_i (i + 1) = 512 x 513 / 2 = 131328
    #   FLOPs 4 x 32 x 128 x 131328 = 2151677952
    #   q and the output 2 x 32, k and v 2 x 4 heads: 72 x 512 x 128 x 2 B
    cost = costs.window_flash(512, 1024, 32, 4, 128)
    assert cost == {"bytes": 72 * 512 * 128 * 2, "flops": 2151677952}
    # 8192 tokens: 1024 x 1025 / 2 + 7168 x 1024 = 7864832 pairs = 0.129 TFLOP
    # where the whole causal triangle's 8192 x 8193 / 2 are 0.55
    cost = costs.window_flash(8192, 1024, 32, 4, 128)
    assert cost["flops"] == 4 * 32 * 128 * 7864832 == 128857407488
    assert 4 * 32 * 128 * (8192 * 8193 // 2) == 549822922752
    best = costs.roofline(cost, V5E)
    assert best["bound"] == "compute"
    assert best["seconds"] == pytest.approx(128857407488 / 197e12)  # 0.654 ms


def test_window_layers_are_counted_from_the_config_as_run():
    assert costs.window_layers(MC) == 6 and costs.global_layers(MC) == 2
    assert costs.global_layers(OLD_MC) == 0
    assert costs.window_layers(OLD_MC) == 0
    assert costs.window_layers(dict(MC, sliding_window=0)) == 0


# -- the calls a traced span held ----------------------------------------------------

STEPS = [step(1001.1, 0, 0.0, chunk=0, admitted=1, prefill_ms=40.0),
         step(1003.0, 2, 40.0),
         # began at 1004.3 with a prefill of 200 ms, all of it inside the span
         step(1004.75, 0, 0.0, chunk=0, step_ms=450.0, admitted=1,
              prefill_ms=200.0),
         step(1005.0, 2, 51.5), step(1006.0, 3, 55.0),
         step(1007.0, 0, 0.0, chunk=0),
         # began at 1019.75: the third request's admission, after the span
         step(1020.25, 0, 0.0, chunk=0, step_ms=500.0, admitted=1,
              prefill_ms=400.0),
         step(1030.0, 1, 65.0)]
# mono = wall - 900; the span is mono [104, 108]
RECORDS = [record(600, 101.0, 390),        # streaming all through the span
           record(5000, 104.5, 300),       # first token inside the span
           record(7168, 120.0, 10)]        # after it


def test_decode_calls_are_chunk_times_window_layers_at_each_lanes_tokens():
    run = make_run(STEPS, records=RECORDS)
    calls = costs.decode_calls(run)
    # the boundaries that ended at 1005 and 1006 (250 ms each) lie inside
    assert [c for _t, c in calls] == [pytest.approx(8 * 6)] * 2
    first, second = (t for t, _c in calls)
    # at mono 104.875 the first request holds 600 + 388 tokens, the second
    # 5000 + 38; at 105.875 the first has all the 390 it got, the second
    # one hundred more
    assert first == [988, 5038] and second == [990, 5138]
    assert costs.decode_calls(make_run(STEPS, records=RECORDS, mc=OLD_MC)) is None
    none = make_run(STEPS, records=RECORDS)
    none.trace_wall = None
    assert costs.decode_calls(none) is None


def test_flash_calls_weigh_a_prefill_by_what_the_span_held_of_it():
    run = make_run(STEPS, records=RECORDS)
    # the second request's first token (wall 1004.5) follows the boundary that
    # began at 1004.3: its prefill, 1004.3 .. 1004.5, lies inside the span
    assert costs.flash_calls(run) == [(5000, pytest.approx(6))]
    # a span that opens in the middle of that prefill holds half of its calls
    run.trace_wall = (1004.4, 1008.0)
    assert costs.flash_calls(run) == [(5000, pytest.approx(3))]
    # one that opens when the first token leaves holds none of it
    run.trace_wall = (1004.5, 1008.0)
    assert costs.flash_calls(run) == []
    assert costs.flash_calls(make_run(STEPS, records=RECORDS, mc=OLD_MC)) is None
    # a ring that kept no admission (a program older than ``prefill_ms``)
    bare = [{k: v for k, v in s.items() if k != "prefill_ms"} for s in STEPS]
    assert costs.flash_calls(make_run(bare, records=RECORDS)) == []


def test_global_decode_calls_are_chunk_times_the_global_layers():
    calls = costs.global_decode_calls(make_run(STEPS, records=RECORDS))
    assert [(t, ln) for t, ln, _c in calls] == [(988 + 5038, 2), (990 + 5138, 3)]
    assert [c for _t, _l, c in calls] == [pytest.approx(8 * 2)] * 2
    assert costs.global_decode_calls(
        make_run(STEPS, records=RECORDS, mc=OLD_MC)) is None


# -- the readers -------------------------------------------------------------------

def trace(decode_s=0.0, decode_n=0, flash_s=0.0, flash_n=0):
    kernels = {"paged_decode_attention_kernel": {"seconds": 9.0, "calls": 48},
               "fusion": {"seconds": 1.0, "calls": 1000}}
    if decode_n:
        kernels["paged_window_decode_kernel"] = {"seconds": decode_s,
                                                 "calls": decode_n}
    if flash_n:
        kernels["flash_window_kernel"] = {"seconds": flash_s, "calls": flash_n}
    return {"kernels": kernels}


def test_window_decode_ms_per_call_reads_the_kernel_by_its_own_name():
    run = make_run(STEPS, trace(decode_s=0.0048, decode_n=96), records=RECORDS)
    assert reader("window_decode_ms_per_call")(run) == (pytest.approx(0.05), 96)
    # a global call's events are not a window call's
    assert reader("window_decode_ms_per_call")(
        make_run(STEPS, trace(), records=RECORDS)) is None
    # a rehearsal shows the calls the ring says the span held, as a count
    assert reader("window_decode_ms_per_call")(
        make_run(STEPS, None, platform="cpu", records=RECORDS)) == (0.0, 96)


def test_window_decode_roofline_is_least_over_measured(capsys):
    run = make_run(STEPS, trace(decode_s=0.0048, decode_n=96), records=RECORDS)
    value, n = reader("window_decode_roofline")(run)
    least = 0.0
    for tokens in ([988, 5038], [990, 5138]):
        cost = costs.window_decode(tokens, 1024, 16, 32, 4, 128)
        least += 48 * cost["bytes"] / 819e9
    assert n == 96 and value == pytest.approx(100 * least / 0.0048)
    assert 0 < value < 100
    assert "2.00 lanes a call" in capsys.readouterr().out
    assert reader("window_decode_roofline")(
        make_run(STEPS, trace(), records=RECORDS)) is None


def test_window_prefill_roofline_is_least_over_measured_and_never_scaled(capsys):
    cost = costs.window_flash(5000, 1024, 32, 4, 128)
    least = 6 * cost["flops"] / 197e12
    run = make_run(STEPS, trace(flash_s=0.009, flash_n=6), records=RECORDS)
    assert reader("window_prefill_roofline")(run) == (
        pytest.approx(100 * least / 0.009), 6)
    assert "6.0 calls expected" in capsys.readouterr().out
    # a trace that holds fewer events than the records predict is a MISCOUNT
    # the share shows (here over 100 %), with both counts in the line: the
    # least time is not shrunk to the events held
    run = make_run(STEPS, trace(flash_s=0.0005, flash_n=4), records=RECORDS)
    value, n = reader("window_prefill_roofline")(run)
    assert n == 4 and value == pytest.approx(100 * least / 0.0005) and value > 100
    assert "6.0 calls expected" in capsys.readouterr().out
    # the span held half of the prefill: half of its calls' least time
    run = make_run(STEPS, trace(flash_s=0.0045, flash_n=3), records=RECORDS)
    run.trace_wall = (1004.4, 1008.0)
    assert reader("window_prefill_roofline")(run) == (
        pytest.approx(100 * least / 2 / 0.0045), 3)
    # no prefill in the span, or no such kernel: nothing
    assert reader("window_prefill_roofline")(
        make_run(STEPS, trace(flash_s=0.009, flash_n=6), records=RECORDS[:1])) is None
    assert reader("window_prefill_roofline")(
        make_run(STEPS, trace(), records=RECORDS)) is None
    assert reader("window_prefill_roofline")(
        make_run(STEPS, None, platform="cpu", records=RECORDS)) == (0.0, 6)


def test_global_decode_roofline_counts_the_global_layers_at_the_stated_head(capsys):
    import kernel_costs

    run = make_run(STEPS, trace(), records=RECORDS)
    run.trace["kernels"]["paged_decode_attention_kernel"] = {
        "seconds": 0.0032, "calls": 32}
    value, n = reader("global_decode_roofline")(run)
    least = sum(16 * kernel_costs.paged_decode(t, ln, 32, 4, 128)["bytes"] / 819e9
                for t, ln in ((988 + 5038, 2), (990 + 5138, 3)))
    assert n == 32 and value == pytest.approx(100 * least / 0.0032)
    assert "2 of 8 layers keep every row; 32 calls expected" in capsys.readouterr().out
    # the window layers' events are not its events
    only_window = make_run(STEPS, {"kernels": {"paged_window_decode_kernel": {
        "seconds": 0.0048, "calls": 96}}}, records=RECORDS)
    assert reader("global_decode_roofline")(only_window) is None
    assert reader("global_decode_roofline")(
        make_run(STEPS, None, platform="cpu", records=RECORDS)) == (0.0, 32)


def test_window_pages_read_mean_reads_the_ring():
    run = make_run(STEPS)
    # the window is wall [1000, 1010]: four boundaries, three ran a chunk
    assert reader("window_pages_read_mean")(run) == (
        pytest.approx((40.0 + 51.5 + 55.0) / 3), 3)
    old = [{k: v for k, v in s.items() if k != "window_pages"} for s in STEPS]
    assert reader("window_pages_read_mean")(make_run(old)) is None
    zero = [dict(s, window_pages=0.0) for s in STEPS]
    assert reader("window_pages_read_mean")(make_run(zero)) is None


@pytest.mark.parametrize("name", [
    "window_decode_ms_per_call", "window_decode_roofline",
    "window_prefill_roofline", "window_pages_read_mean",
    "global_decode_roofline"])
def test_every_reader_gives_nothing_on_an_older_program(name):
    """The parent's program under this PR's benchmark files: no window in the
    config as run, no ring field, no kernel of the new names."""
    old_steps = [{k: v for k, v in s.items() if k != "window_pages"}
                 for s in STEPS]
    run = make_run(old_steps, trace(), records=RECORDS, mc=OLD_MC)
    assert reader(name)(run) is None
    run.trace_wall = None
    assert reader(name)(run) is None
