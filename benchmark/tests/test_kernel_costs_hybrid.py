"""The expert kernel's calls in a model whose layers are of several kinds
(``kernel_costs_hybrid.py``) by hand, and the four readers ISSUE 33 added,
each on a synthetic ``Run``: calls are counted by the layers that HOLD
experts (12 of 14) and by the layers that attend (3 of 14), not ``n_layers``.
Every reader gives nothing, and does not raise, on what a program older than
the PR hands it (no ``layer_types`` in the program's config, no scope or span
of the new names in the capture)."""

import pytest

import kernel_costs_hybrid
import kernel_costs_moe
import run as benchrun
import capture_scopes
from client import new_record
from measure import Run

TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "conv", "full_attention", "conv",
         "conv", "conv"]
# LFM2-8B-A1B at the 14 layers the cell runs: 11 conv, 3 attention, 12 hold experts
MC = {"n_layers": 14, "layer_types": TYPES, "n_dense_layers": 2, "top_k": 4,
      "n_experts": 32, "d_model": 2048, "d_ff": 1792, "d_ff_dense": 7168}
OLD_MC = {"n_layers": 8, "top_k": 8, "n_experts": 64, "d_model": 2048,
          "d_ff": 1024}


def make_run(steps, trace=None, platform="tpu", records=(), mc=MC) -> Run:
    r = Run(cell={}, config={}, program_config=mc, server={},
            device={"platform": platform, "kind": "TPU v5 lite"},
            seconds=10.0, t0=100.0, t_end=125.0)
    r.before = {"t": 100.0, "t_wall": 1000.0, "prom": {}}
    r.after = {"prom": {}}
    r.steps, r.records, r.trace = list(steps), list(records), trace
    r.trace_wall = (1004.0, 1008.0)
    return r


def step(t_wall, active, hit, chunk=8, step_ms=250.0, admitted=0) -> dict:
    return {"t_wall": t_wall, "engine": "continuous", "step_ms": step_ms,
            "chunk": chunk, "active": active, "admitted": admitted,
            "retired": 0, "experts_hit": hit, "expert_rows_max": 2.0}


STEPS = [step(1003.0, 10, 24.0), step(1005.0, 10, 24.0, admitted=1),
         step(1006.0, 12, 25.0, admitted=2), step(1007.0, 0, 0.0, chunk=0),
         step(1030.0, 9, 22.0)]                           # after the window


def reader(name):
    return benchrun.load_reader("per_layer", name)


def test_layers_are_counted_by_kind():
    assert kernel_costs_hybrid.layer_counts(MC) == {
        "conv": 11, "attn": 3, "dense": 2, "moe": 12}
    assert kernel_costs_hybrid.layer_counts(OLD_MC) is None


def test_an_expert_call_by_hand():
    # 48 rows (12 lanes x 4 experts a token) over 25 distinct experts of
    # 2048 x 1792, bf16:
    #   weights: 25 x 3 x 2048 x 1792 x 2 bytes = 550502400
    #   rows in and out: 2 x 48 x 2048 x 2 bytes = 393216
    #   FLOPs: 48 x 3 x 2 x 2048 x 1792 = 1056964608
    cost = kernel_costs_hybrid.grouped_experts(48, 25, 2048, 1792)
    assert cost == {"bytes": 550502400 + 393216, "flops": 1056964608}
    best = kernel_costs_hybrid.roofline(
        cost, kernel_costs_hybrid.peaks("TPU v5 lite"))
    assert best["bound"] == "memory"
    assert best["seconds"] == pytest.approx(550895616 / 819e9)   # 0.673 ms


def test_calls_a_step_are_the_layers_that_hold_experts_not_n_layers():
    run = make_run(STEPS)
    mine = kernel_costs_hybrid.traced_calls(run)
    theirs = kernel_costs_moe.traced_calls(run)
    # the span [1004, 1008] holds the boundaries that ended at 1005 and 1006
    assert mine == [(40, 24.0, pytest.approx(8 * 12)),
                    (48, 25.0, pytest.approx(8 * 12))]
    assert [c for *_, c in theirs] == [pytest.approx(8 * 14)] * 2   # 14 / 12 of it
    # a prefill inside the span: 12 calls of prompt x 4 rows
    rec = new_record("generate", "tenant00", 0, 104.0, 100, 16)
    rec.update(ok=True, token_t=[105.5, 105.8])
    with_prefill = kernel_costs_hybrid.traced_calls(make_run(STEPS, records=[rec]))
    assert with_prefill[-1] == (400, pytest.approx(32 * (1 - (31 / 32) ** 400)), 12)


def trace_of(seconds, events):
    return {"kernels": {"moe_grouped_matmul_kernel":
                        {"seconds": seconds, "calls": events}}}


def test_roofline_share_counts_twelve_calls_a_step(capsys):
    # 16 steps x 12 layers = 192 calls = 384 kernel events in 150 ms
    run = make_run(STEPS, trace=trace_of(0.150, 384))
    peak = kernel_costs_hybrid.peaks("TPU v5 lite")

    def least(rows, hit):
        return kernel_costs_hybrid.roofline(
            kernel_costs_hybrid.grouped_experts(rows, hit, 2048, 1792), peak)["seconds"]

    want = 96 * least(40, 24.0) + 96 * least(48, 25.0)
    value, calls = reader("hybrid_experts_roofline")(run)
    assert calls == 192 and value == pytest.approx(100 * want / 0.150)
    assert 80.0 < value < 90.0
    assert "12 of 14 layers hold experts" in capsys.readouterr().out
    # the reader that counts n_layers would read 14 / 12 of that share
    other, _ = reader("moe_experts_roofline")(run)
    assert other == pytest.approx(value * 14 / 12)


def capture(ops=None, host=None, device="/device:TPU:0"):
    return {"ops": ops or {}, "host": host or {}, "device": device}


DECODE = "jit(_paged_decode_chunk_jit)/while/body/closed_call/"
OPS = {
    DECODE + "layer/conv/dot_general": [0.0090, 352],       # two products a layer
    DECODE + "layer/conv/mul": [0.0030, 176],
    DECODE + "layer/attn/dot_general": [0.0400, 48],
    DECODE + "layer/attn/gather": [0.0080, 96],
    DECODE + "layer/ffn/experts/pallas_call": [0.1300, 384],
    DECODE + "layer/conversion/dot_general": [9.0, 1],     # another scope's name
    "jit(_slot_prefill_jit)/layer/conv/dot_general": [0.5000, 22],   # a prefill
    "": [1.0, 5],
}


def test_scope_seconds_takes_whole_path_elements_of_one_program():
    assert capture_scopes.scope_seconds(
        OPS, "_paged_decode_chunk_jit", "layer/conv") == (pytest.approx(0.012), 528)
    assert capture_scopes.scope_seconds(
        OPS, "_paged_decode_chunk_jit", "layer/attn") == (pytest.approx(0.048), 144)
    assert capture_scopes.scope_seconds(OPS, "_slot_prefill_jit", "layer/conv")[1] == 22
    assert capture_scopes.scope_seconds(OPS, "_paged_decode_chunk_jit", "layer/kv") == (0.0, 0)


def test_conv_and_attention_readers_divide_by_the_spans_steps(monkeypatch):
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(OPS))
    run = make_run(STEPS, trace={"kernels": {}})
    # 16 decode steps in the span: 12 ms of layer/conv -> 0.75 ms a step;
    # 48 ms of layer/attn over 16 x 3 calls -> 1.0 ms a call
    assert reader("conv_layers_ms_per_step")(run) == (pytest.approx(0.75), 16)
    assert reader("attn_ref_ms_per_call")(run) == (pytest.approx(1.0), 48)


def test_state_insert_reads_the_host_annotation(monkeypatch):
    spans = [0.00011, 0.00015, 0.00040]
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(
        OPS, {"tpusc.state_insert": spans, "tpusc.prefill": [0.02]}))
    run = make_run(STEPS, trace={"kernels": {}})
    assert reader("state_insert_p50_ms")(run) == (pytest.approx(0.15), 3)


NEW = ("conv_layers_ms_per_step", "attn_ref_ms_per_call", "state_insert_p50_ms",
       "hybrid_experts_roofline")


@pytest.mark.parametrize("name", NEW)
def test_new_readers_give_nothing_on_an_older_program(monkeypatch, name):
    """The parent's program: no ``layer_types`` in its config; and this PR's
    program on a capture without the scopes, the span or the kernel."""
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: capture(
        {DECODE + "layer/ffn/dot_general": [0.1, 10]}))
    old = make_run(STEPS, trace=trace_of(0.1, 256), mc=OLD_MC)
    if name != "state_insert_p50_ms":
        assert reader(name)(old) is None
    bare = make_run(STEPS, trace={"kernels": {}})
    assert reader(name)(bare) is None
    monkeypatch.setattr(capture_scopes, "capture_of", lambda run: None)
    assert reader(name)(make_run(STEPS, trace={"kernels": {}})) is None
    untraced = make_run(STEPS)
    untraced.trace_wall = None
    assert reader(name)(untraced) is None


@pytest.mark.parametrize("name", NEW)
def test_a_rehearsal_shows_counts_and_no_value(name):
    run = make_run(STEPS, trace={"kernels": {}}, platform="cpu")
    want = {"conv_layers_ms_per_step": 16, "attn_ref_ms_per_call": 48,
            "state_insert_p50_ms": 3, "hybrid_experts_roofline": 192}[name]
    assert reader(name)(run) == (0.0, want)


@pytest.mark.parametrize("case, mtime, marks, found", [
    ("this run's: written after the span began, marked inside it",
     1010.0, [1_002_000_000_000], True),
    ("another run's, left behind: older than the span", 900.0,
     [1_002_000_000_000], False),
    ("a later run's in the same directories: marked outside the span",
     1500.0, [1_400_000_000_000], False),
    ("no mark at all", 1010.0, [], False),
])
def test_a_capture_is_held_to_the_runs_own_span(monkeypatch, case, mtime, marks, found):
    monkeypatch.setattr(capture_scopes, "captures", lambda: ["/x/bench.xplane.pb"])
    monkeypatch.setattr(capture_scopes.os.path, "getmtime", lambda path: mtime)
    monkeypatch.setattr(capture_scopes, "load", lambda path: {"wall_marks": marks})
    got = capture_scopes.find_capture((1000.0, 1004.0))
    assert (got == "/x/bench.xplane.pb") is found, case


CONV = "jit(_paged_decode_chunk_jit)/while/body/layer/conv/dot_general"
TEXTS = {
    (7, "slice-start.3"): "%slice-start.3 = ((bf16[2048,6144]), bf16[512,6144]{1,0:S(1)}, s32[]) "
                          "async-start(bf16[2048,6144] %w_in.1)",
    (7, "slice-done.3"): "%slice-done.3 = bf16[512,6144]{1,0:S(1)} async-done(((bf16[2048,6144]), "
                         "bf16[512,6144]{1,0:S(1)}, s32[]) %slice-start.3)",
    (7, "fusion.9"): "%fusion.9 = bf16[32,6144] fusion(bf16[32,2048] %fusion.8, "
                     "bf16[512,6144]{1,0:S(1)} %slice-done.3), kind=kOutput",
    (7, "copy.813"): "%copy.813 = bf16[3,8193,8,16,64]{4,3,2,1,0} copy(bf16[3,8193,8,16,64]"
                     "{1,4,3,2,0} %arena_k.1)",
    (7, "while.332"): "%while.332 = (s32[], bf16[3,8193,8,16,64]) while((s32[], "
                      "bf16[3,8193,8,16,64]) %tuple.5), condition=%c, body=%b",
    (8, "slice-done.3"): "%slice-done.3 = bf16[8]{0:S(1)} async-done((bf16[64]) %slice-start.1)",
}


@pytest.mark.parametrize("name, path", [
    ("slice-done.3", CONV),     # the wait for a weight: its user's path
    ("slice-start.3", CONV),    # through its done, one hop further
    ("copy.813", None),         # used by a tuple into the loop: no device event
    ("while.332", None),        # a wrapper is never given a path
])
def test_what_the_compiler_added_takes_its_users_scope(name, path):
    scoped = {TEXTS[7, "fusion.9"]: CONV}
    got = capture_scopes.consumer_scopes(TEXTS, scoped)
    assert got.get(TEXTS[7, name]) == path
    # another program's instruction of the same name is another instruction
    assert TEXTS[8, "slice-done.3"] not in got


def test_the_recorded_capture_is_read_by_scope():
    """``tests/data/toy_v5e.xplane.pb.gz`` (a recorded v5e capture): its device
    operations come back under their ``jax.named_scope`` paths."""
    import os

    from conftest import HERE

    got = capture_scopes.load(os.path.join(HERE, "data", "toy_v5e.xplane.pb.gz"))
    assert got["device"].startswith("/device:TPU:")
    total = sum(sec for sec, _n in got["ops"].values())
    named = sum(sec for path, (sec, _n) in got["ops"].items() if "/" in path)
    assert total > 0 and named > 0.5 * total
