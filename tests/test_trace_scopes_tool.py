"""tools/trace_scopes.py on a small recorded capture: a toy program with the
program's scope names and ``tpusc.*`` annotations, 3 launches on one v5e chip
(my chip run, PR 23). ``toy_v5e_rows.json`` is what ``load`` gave for
``toy_v5e.xplane.pb.gz`` (operation names cut at `` = ``): the reductions run
on the rows, the loader on the capture itself."""

import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "trace_scopes", os.path.join(HERE, "..", "tools", "trace_scopes.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def rows():
    with open(os.path.join(DATA, "toy_v5e_rows.json")) as f:
        return [tuple(r) for r in json.load(f)]


def test_executions_by_program(tool, rows):
    """A program's executions: how many, device wall, busy (the union of the
    operations inside them), idle inside, the mean idle gap before one."""
    (name, count, wall, busy, idle, gap), = tool.by_execution(rows)
    assert (name, count) == ("jit_step", 3)
    # the first execution's wall begins 16 ns before its first operation: outside the span
    assert wall == pytest.approx(332.688e-6, rel=1e-3)
    assert 0.9 * wall < busy <= wall and idle == pytest.approx(wall - busy)
    assert gap == pytest.approx(3.4e-3, rel=0.1)         # two gaps: 2 ms asleep in tpusc.emit each
    assert tool.by_execution([]) == []


def test_device_seconds_by_scope_names_each_operations_owner(tool, rows):
    table = tool.by_scope(rows)
    by_key = {r[:3]: r[3:] for r in table}
    assert by_key[("jit_step", "layer/attn", "convolution_tanh_fusion")] == (
        pytest.approx(138.809e-6), 12)
    assert by_key[("jit_step", "layer/kv_write", "dynamic-update-slice")][1] == 12
    assert by_key[("jit_step", "lm_head", "convolution_reduce_fusion")][1] == 3
    # what the compiler added carries no scope, and says so
    assert by_key[("jit_step", "(no scope)", "copy-done")][1] == 6
    # the while wrapper spans its children and is left out
    assert not [r for r in table if r[2] == "while"]
    assert [r[3] for r in table] == sorted((r[3] for r in table), reverse=True)
    total = sum(r[3] for r in table)
    assert total == pytest.approx(tool.by_execution(rows)[0][2], rel=0.05)


def test_clock_shift_is_bounded_by_every_launch(tool, rows):
    lo, hi, launches = tool.clock_shift_ns(rows)
    assert (lo, hi, launches) == (1264245, 1691159, 3)
    # without the runtime's launch events the capture cannot say
    quiet = [r for r in rows if r[2] not in ("DoEnqueueProgram", "CompleteCallbacks")]
    assert tool.clock_shift_ns(quiet) == (0, 0, 0)
    # the clocks drift over a span: bounds that cross by a few microseconds still
    # give the shift (their middle); by half a millisecond, nothing
    def completed_sooner(by_ns):
        return [(p, l, n, s - by_ns if n == "CompleteCallbacks" else s, d, x)
                for p, l, n, s, d, x in rows]
    assert tool.clock_shift_ns(completed_sooner(430_000)) == (1264245, 1261159, 3)
    assert tool.clock_shift_ns(completed_sooner(1_000_000)) == (0, 0, 0)


def test_idle_time_goes_to_its_cause(tool, rows):
    """Inside a program under its name; between two under the innermost annotation
    the engine's thread had open (``tpusc.emit`` and the boundary itself read
    "boundary host work"); the causes sum to the span's idle time."""
    lo, hi, _ = tool.clock_shift_ns(rows)
    table = dict(tool.idle_by_cause(rows, (lo + hi) // 2))
    work, chunk = tool.CAUSE["tpusc.boundary"], tool.CAUSE["tpusc.decode_chunk"]
    # two gaps between three launches: the host slept 2 ms in tpusc.emit in each
    assert table[work] == pytest.approx(5.04e-3, rel=0.03)
    # the toy program has no child span: its launch path reads under decode_chunk
    assert 1.0e-3 < table[chunk] < 2.5e-3
    assert tool.CAUSE["tpusc.chunk_launch"] not in table
    assert table["inside jit_step"] < 0.1e-3
    runs = tool.executions(rows)
    span_idle = (runs[-1][2] - runs[0][1] - sum(r[3] for r in runs)) / 1e9
    assert sum(table.values()) == pytest.approx(span_idle)
    # unshifted, the device's events sit before the host launched them: the
    # same idle time lands elsewhere, which is why the shift is applied
    raw = dict(tool.idle_by_cause(rows))
    assert raw.get(work, 0.0) != pytest.approx(table[work], rel=0.02)
    assert sum(raw.values()) == pytest.approx(span_idle)
    assert tool.idle_by_cause([]) == []


def test_bring_up_host_events_are_listed_by_stage(tool, rows):
    """The bring-up account's host events (``utils/bring_up.py``): counted and
    summed by stage, on whatever thread; the ``first_run`` marker's name carries
    its program. The toy capture holds none."""
    assert tool.bring_up_marks(rows) == []
    host = "/host:CPU"
    more = list(rows) + [
        (host, "pool-1", "tpusc.load", 10, 3_000_000_000, {}),
        (host, "pool-1", "tpusc.device_transfer", 20, 2_000_000_000, {}),
        (host, "pool-2", "tpusc.load", 30, 1_000_000_000, {}),
        (host, "engine", "tpusc.engine_build", 40, 500_000_000, {}),
        (host, "engine", "tpusc.first_run#program=_slot_prefill_jit,wall_ms=812.0#",
         50, 1_000, {}),
        (host, "main", "tpusc.server_start", 5, 250_000_000, {}),
        (host, "engine", "tpusc.boundary", 60, 9_000_000, {}),     # not the account's
    ]
    assert tool.bring_up_marks(more) == [
        ("tpusc.server_start", 1, 0.25), ("tpusc.load", 2, 4.0),
        ("tpusc.device_transfer", 1, 2.0), ("tpusc.engine_build", 1, 0.5),
        ("tpusc.first_run", 1, 1e-6)]


def test_loader_reads_scopes_off_the_capture(tool, rows):
    loaded = tool.load(os.path.join(DATA, "toy_v5e.xplane.pb.gz"))
    assert len(loaded) == len(rows)
    cut = [(p, l, n.split(" = ")[0], s, d, x) for p, l, n, s, d, x in loaded]
    assert cut == rows
    scopes = {x["scope"] for *_r, x in loaded if x.get("scope")}
    assert "jit(step)/while/body/closed_call/layer/attn/dot_general" in scopes
    assert {n for _p, _l, n, *_r in loaded if n.startswith("tpusc.")} == {
        "tpusc.boundary", "tpusc.decode_chunk", "tpusc.emit"}


def test_wire_reader_and_name_helpers(tool):
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed32
    msg = bytes([0x08, 0xAC, 0x02, 0x12, 0x02]) + b"ab" + bytes([0x1D, 1, 0, 0, 0])
    assert list(tool.wire_fields(msg)) == [(1, 300), (2, b"ab"), (3, b"\x01\x00\x00\x00")]
    assert tool.short("%fusion.12 = bf16[8]{0} fusion(%p)") == "fusion"
    assert tool.short("jit__paged_decode_chunk_jit(123456)") == "jit__paged_decode_chunk_jit"
    assert tool.short("%paged_decode_attention_kernel.3") == "paged_decode_attention_kernel"
    assert tool.scope_of("jit(f)/jit(main)/while/body/closed_call/layer/kv_write/scatter") \
        == "layer/kv_write"
    assert tool.scope_of("jit(f)/transpose") == "(no scope)"
    assert tool.scope_of(None) == "(no scope)"
    assert tool.main([]) == 2
