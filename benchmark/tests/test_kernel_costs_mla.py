"""The latent decode kernel's costs (``kernel_costs_mla.py``) by hand at two
shapes, the share reader at a quarter share against a hand count, and the
four readers ISSUE 31 added, each on a synthetic ``Run``. Every reader gives
nothing, and does not raise, on what a program older than the PR hands it
(no ``expert_rows_local`` in the ring, no such kernel in the trace, a model
that is not a latent one)."""

import pytest

import kernel_costs_mla
import run as benchrun
from client import new_record
from measure import Run


def test_latent_decode_bytes_and_flops_by_hand():
    # Mistral-Small-4's row: 256 + 64 columns, 32 heads, bf16. 2 lanes holding
    # 3000 + 1096 = 4096 live tokens:
    #   rows: 4096 tokens x 320 x 2 bytes = 2621440 (640 B a token, whatever
    #     the arena pads it to)
    #   queries in (bf16, 320 wide) + outputs out (f32, 256 wide):
    #     2 lanes x 32 heads x (320 x 2 + 256 x 4) = 106496
    #   FLOPs: 2 x 32 heads x (320 + 256) x 4096 tokens = 150994944
    cost = kernel_costs_mla.latent_decode(4096, 2, 32, 256, 64)
    assert cost == {"bytes": 2621440 + 106496, "flops": 150994944}
    # a small one: 1 lane, 10 tokens, 4 heads over a 24 + 16 row, float32
    #   rows 10 x 40 x 4 = 1600; q and out 4 x (40 x 4 + 24 x 4) = 1024
    #   FLOPs 2 x 4 x (40 + 24) x 10 = 5120
    cost = kernel_costs_mla.latent_decode(10, 1, 4, 24, 16, itemsize=4)
    assert cost == {"bytes": 1600 + 1024, "flops": 5120}


def test_latent_decode_stays_memory_bound_at_group_32_on_a_v5e():
    # every head reads the same row: 2 x 32 x 576 = 36864 FLOPs against 640
    # bytes a token = 57.6 FLOPs a byte, under the chip's 197e12 / 819e9 = 240:
    # memory-bound, by a factor of four and not of sixty
    peak = kernel_costs_mla.peaks("TPU v5 lite")
    best = kernel_costs_mla.roofline(
        kernel_costs_mla.latent_decode(100000, 32, 32, 256, 64), peak)
    assert best["bound"] == "memory"
    assert best["seconds"] == pytest.approx(
        (100000 * 640 + 32 * 32 * (640 + 1024)) / 819e9)


# -- the readers ----------------------------------------------------------------

MC = {"n_layers": 6, "top_k": 4, "n_experts": 128, "n_experts_held": 32,
      "d_model": 4096, "d_ff": 2048, "n_heads": 32, "kv_lora_rank": 256,
      "qk_rope_head_dim": 64}
DENSE = {"n_layers": 8, "n_heads": 32, "n_kv_heads": 8, "d_model": 4096}


def make_run(steps, trace=None, platform="tpu", records=(), mc=MC) -> Run:
    r = Run(cell={}, config={}, program_config=mc, server={},
            device={"platform": platform, "kind": "TPU v5 lite"},
            seconds=10.0, t0=100.0, t_end=125.0)
    r.before = {"t": 100.0, "t_wall": 1000.0, "prom": {}}
    r.after = {"prom": {}}
    r.steps, r.records, r.trace = list(steps), list(records), trace
    r.trace_wall = (1004.0, 1008.0)
    return r


def step(t_wall, active, hit=None, local=None, chunk=8, step_ms=250.0) -> dict:
    s = {"t_wall": t_wall, "engine": "continuous", "step_ms": step_ms,
         "chunk": chunk, "active": active, "admitted": 0, "retired": 0}
    if hit is not None:
        s.update(experts_hit=hit, expert_rows_max=2.0)
    if local is not None:
        s["expert_rows_local"] = local
    return s


# 8 lanes x 4 = 32 assignments a step, a quarter of them (8.5, 7.5) here
STEPS = [step(1003.0, 8, 7.0, 8.0), step(1005.0, 8, 7.5, 8.5),
         step(1006.0, 8, 6.5, 7.5), step(1007.0, 0, 0.0, 0.0, chunk=0),
         step(1030.0, 9, 8.0, 9.0)]                      # after the window


def reader(name):
    return benchrun.load_reader("per_layer", name)


NEW = ("latent_decode_ms_per_call", "latent_decode_roofline",
       "moe_share_experts_roofline", "expert_rows_local_mean")


def test_rows_local_mean_takes_the_window_boundaries_that_ran_a_chunk():
    assert reader("expert_rows_local_mean")(make_run(STEPS)) == (
        pytest.approx(24.0 / 3), 3)


def test_every_reader_gives_nothing_on_a_program_older_than_the_share():
    # the parent: a ring without expert_rows_local, a trace without the latent
    # kernel, the program's config of another model
    old = make_run([step(1005.0, 4, 25.0), step(1006.0, 8, 30.0)], mc=DENSE,
                   trace={"kernels": {"paged_decode_attention_kernel":
                                      {"seconds": 0.02, "calls": 900}}})
    for name in NEW:
        assert reader(name)(old) is None, name
    # a latent model's config but nothing of the kernel in the trace
    bare = make_run([step(1005.0, 4, 25.0)], trace={"kernels": {}})
    for name in NEW:
        assert reader(name)(bare) is None, name


def test_share_reader_at_a_quarter_share_against_a_hand_count(capsys):
    # the span [1004, 1008] holds the boundaries that ended at 1005 and 1006
    # whole: 2 x 8 steps x 6 layers = 96 calls, of 8.5 rows over 7.5 held
    # experts and of 7.5 rows over 6.5; plus one prefill of 1000 tokens whose
    # first token came at wall 1005.5: 6 calls of 1000 x 4 / 4 = 1000 rows
    # over 32 x (1 - (127/128)^4000) = 32.0 held experts
    rec = new_record("generate", "tenant00", 0, 104.0, 1000, 16)
    rec.update(ok=True, token_t=[105.5, 105.8])
    trace = {"kernels": {"moe_grouped_matmul_kernel":
                         {"seconds": 0.120, "calls": 2 * (96 + 6)}}}
    run = make_run(STEPS, trace=trace, records=[rec])
    calls = kernel_costs_mla.share_calls(run)
    assert [(round(r, 3), round(h, 3), round(c, 3)) for r, h, c in calls] == [
        (8.5, 7.5, 48.0), (7.5, 6.5, 48.0), (1000.0, 32.0, 6.0)]
    peak = kernel_costs_mla.peaks("TPU v5 lite")

    def least(rows, hit):
        # by hand: hit x 3 x 4096 x 2048 x 2 bytes of weights + 2 x rows x
        # 4096 x 2 bytes of rows, over 819e9; or rows x 3 x 2 x 4096 x 2048
        # FLOPs over 197e12
        return max((hit * 50331648 + rows * 16384) / 819e9,
                   rows * 50331648 / 197e12)

    want = 48 * least(8.5, 7.5) + 48 * least(7.5, 6.5) + 6 * least(
        1000, 32 * (1 - (127 / 128) ** 4000))
    value, n = reader("moe_share_experts_roofline")(run)
    assert n == 102 and value == pytest.approx(100 * want / 0.120)
    # what moe_experts_roofline would reckon for the same decode calls: 32
    # rows, four times the share's, which is why this cell is not in its list
    assert 4 * 8.0 == STEPS[1]["active"] * MC["top_k"]
    assert "102 in the trace" in capsys.readouterr().out


def test_latent_readers_take_the_kernels_events_by_its_own_name(capsys):
    # one request streaming through the span: 2000 prompt tokens + what the
    # client had received; 96 calls expected, 96 events of the latent kernel
    rec = new_record("generate", "tenant00", 0, 100.5, 2000, 600)
    rec.update(ok=True, token_t=[101.0 + 0.02 * i for i in range(500)])
    trace = {"kernels": {
        "paged_latent_decode_kernel": {"seconds": 0.0096, "calls": 96},
        "paged_decode_attention_kernel": {"seconds": 9.0, "calls": 7}}}
    run = make_run(STEPS, trace=trace, records=[rec])
    assert reader("latent_decode_ms_per_call")(run) == (pytest.approx(0.1), 96)
    value, n = reader("latent_decode_roofline")(run)
    peak = kernel_costs_mla.peaks("TPU v5 lite")
    want = 0.0
    for mid in (104.875, 105.875):              # the boundaries' middles
        tokens = 2000 + sum(t <= mid for t in rec["token_t"])
        want += 48 * kernel_costs_mla.roofline(kernel_costs_mla.latent_decode(
            tokens, 8, 32, 256, 64), peak)["seconds"]
    assert n == 96 and value == pytest.approx(100 * want / 0.0096)
    assert "96 calls expected from the ring, 96 in the trace" in \
        capsys.readouterr().out


def test_a_rehearsal_shows_counts_and_no_value():
    run = make_run(STEPS, trace={"kernels": {}}, platform="cpu")
    for name in ("latent_decode_ms_per_call", "latent_decode_roofline",
                 "moe_share_experts_roofline"):
        assert reader(name)(run) == (0.0, 96), name
