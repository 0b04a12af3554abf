"""The expert layer's costs (``kernel_costs_moe.py``) by hand, and the four
readers ISSUE 25 added, each on a synthetic ``Run``. Every reader gives
nothing, and does not raise, on what a program older than the PR hands it
(no routing fields in the ring, no such kernel in the trace)."""

import pytest

import kernel_costs_moe
import run as benchrun
from client import new_record
from measure import Run


def test_grouped_experts_bytes_and_flops_by_hand():
    # 32 rows (4 lanes x 8 experts a token) over 25 distinct experts of
    # 2048 x 1024, bf16:
    #   weights: 25 experts x 3 matrices x 2048 x 1024 x 2 bytes = 314572800
    #   rows in and out: 2 x 32 x 2048 x 2 bytes = 262144
    #   FLOPs: 32 rows x 3 products x 2 x 2048 x 1024 = 402653184
    cost = kernel_costs_moe.grouped_experts(32, 25, 2048, 1024)
    assert cost == {"bytes": 314572800 + 262144, "flops": 402653184}


def test_decode_is_memory_bound_and_a_long_prefill_compute_bound():
    peak = kernel_costs_moe.peaks("TPU v5 lite")
    step = kernel_costs_moe.roofline(
        kernel_costs_moe.grouped_experts(32, 25, 2048, 1024), peak)
    assert step["bound"] == "memory"
    assert step["seconds"] == pytest.approx(314834944 / 819e9)
    # 1536 tokens x 8: 12288 rows over all 64 experts: 155 GFLOP against
    # 0.9 GB -> 0.79 ms of products, 1.1 ms of bytes: still the weights
    long = kernel_costs_moe.grouped_experts(12288, 64, 2048, 1024)
    assert kernel_costs_moe.roofline(long, peak)["bound"] == "memory"
    # ... and compute-bound once the rows outweigh the weights
    huge = kernel_costs_moe.grouped_experts(65536, 64, 2048, 1024)
    assert kernel_costs_moe.roofline(huge, peak)["bound"] == "compute"


# -- the readers ----------------------------------------------------------------

MC = {"n_layers": 8, "top_k": 8, "n_experts": 64, "d_model": 2048, "d_ff": 1024}


def make_run(steps, trace=None, platform="tpu", records=()) -> Run:
    r = Run(cell={}, config={}, program_config=MC, server={},
            device={"platform": platform, "kind": "TPU v5 lite"},
            seconds=10.0, t0=100.0, t_end=125.0)
    r.before = {"t": 100.0, "t_wall": 1000.0, "prom": {}}
    r.after = {"prom": {}}
    r.steps, r.records, r.trace = list(steps), list(records), trace
    r.trace_wall = (1004.0, 1008.0)
    return r


def step(t_wall, active, hit=None, rows_max=None, chunk=8, step_ms=250.0) -> dict:
    s = {"t_wall": t_wall, "engine": "continuous", "step_ms": step_ms,
         "chunk": chunk, "active": active, "admitted": 0, "retired": 0}
    if hit is not None:
        s.update(experts_hit=hit, expert_rows_max=rows_max)
    return s


STEPS = [step(1003.0, 4, 25.0, 2.0), step(1005.0, 4, 27.0, 2.5),
         step(1006.0, 8, 40.0, 3.5), step(1007.0, 0, 0.0, 0.0, chunk=0),
         step(1030.0, 9, 50.0, 4.0)]                     # after the window


def reader(name):
    return benchrun.load_reader("per_layer", name)


def test_counter_readers_take_the_window_boundaries_that_ran_a_chunk():
    run = make_run(STEPS)
    assert reader("experts_hit_mean")(run) == (pytest.approx(92.0 / 3), 3)
    assert reader("expert_rows_max_mean")(run) == (pytest.approx(8.0 / 3), 3)


def test_every_reader_gives_nothing_on_a_program_without_the_expert_layer():
    old = make_run([step(1005.0, 4), step(1006.0, 8)],
                   trace={"kernels": {"paged_decode_attention_kernel":
                                      {"seconds": 0.02, "calls": 900}}})
    for name in ("experts_hit_mean", "expert_rows_max_mean",
                 "moe_experts_ms_per_call", "moe_experts_roofline"):
        assert reader(name)(old) is None, name
    # a dense model on the new program: the fields are there and zero
    dense = make_run([step(1005.0, 4, 0.0, 0.0)], trace={"kernels": {}})
    for name in ("experts_hit_mean", "expert_rows_max_mean",
                 "moe_experts_ms_per_call", "moe_experts_roofline"):
        assert reader(name)(dense) is None, name


def trace_of(seconds, events):
    return {"kernels": {"moe_grouped_matmul_kernel":
                        {"seconds": seconds, "calls": events}}}


def test_ms_per_call_counts_two_kernel_events_a_layer():
    # 16 decode steps x 8 layers = 128 calls = 256 events in 64 ms
    run = make_run(STEPS, trace=trace_of(0.064, 256))
    assert reader("moe_experts_ms_per_call")(run) == (pytest.approx(0.5), 128)


def test_roofline_sums_the_least_time_of_the_spans_calls(capsys):
    # the span [1004, 1008] holds the boundaries that ended at 1005 and 1006
    # whole (250 ms each) and the one at 1007 that ran no chunk: 2 x 8 steps
    # x 8 layers; plus one prefill of 100 tokens whose first token came at
    # wall 1005.5 (monotonic 105.5)
    rec = new_record("generate", "tenant00", 0, 104.0, 100, 16)
    rec.update(ok=True, token_t=[105.5, 105.8])
    run = make_run(STEPS, trace=trace_of(0.080, 2 * (128 + 8)), records=[rec])
    peak = kernel_costs_moe.peaks("TPU v5 lite")

    def least(rows, hit):
        return kernel_costs_moe.roofline(
            kernel_costs_moe.grouped_experts(rows, hit, 2048, 1024), peak)["seconds"]

    want = 64 * least(32, 27.0) + 64 * least(64, 40.0) + 8 * least(
        800, 64 * (1 - (63 / 64) ** 800))
    value, calls = reader("moe_experts_roofline")(run)
    assert calls == 136 and value == pytest.approx(100 * want / 0.080)
    assert "memory-bound" in capsys.readouterr().out


def test_a_boundary_half_inside_the_span_counts_half():
    half = [step(1004.125, 4, 30.0, 2.0)]         # [1003.875, 1004.125]
    calls = kernel_costs_moe.traced_calls(make_run(half))
    assert calls == [(32, 30.0, pytest.approx(0.5 * 8 * 8))]


def test_a_rehearsal_shows_the_spans_call_count_and_no_value():
    run = make_run(STEPS, trace={"kernels": {}}, platform="cpu")
    for name in ("moe_experts_ms_per_call", "moe_experts_roofline"):
        assert reader(name)(run) == (0.0, 128)
