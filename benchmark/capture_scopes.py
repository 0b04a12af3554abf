"""A traced run's capture by the program's OWN names: device seconds under a
``jax.named_scope`` path, and the host's ``tpusc.*`` annotations.

``trace_reduce.py`` names a device operation by its HLO instruction
(``fusion.12``), which says nothing of the layer it belongs to; the scope path
JAX gave the instruction (``jit(_paged_decode_chunk_jit)/while/body/layer/conv/
dot_general``) is kept by libtpu in the event's METADATA (stat ``tf_op``),
which ``jax.profiler.ProfileData`` hides. It is read here off the protobuf
wire format, as ``tools/trace_scopes.py`` reads it for an operator (XSpace
.planes=1; XPlane .name=2 .event_metadata=4 .stat_metadata=5; XEventMetadata
.name=2 .stats=5; XStat .metadata_id=1 .str=5). A fused operation carries ONE
path, that of the instruction the fusion is named after, so a fusion that
straddles two scopes counts under one of them: sums by scope are good to a
fusion's length, not exact. What the compiler adds itself carries no path at
all; ``consumer_scopes`` gives it its user's.

A reader gets ``measure.Run``, which does not carry the capture's path, so
the capture is looked for where the harness may have written it (``bench-work-*/
trace/**/*.xplane.pb`` under ``survey.candidates``) and HELD TO THIS RUN: the
harness opens every traced span with a ``bench_wall_<ns>`` annotation
(``run.py`` ``trace_part``), and a capture counts only if it carries one whose
time lies inside the run's own ``trace_wall``. A capture some other run left
behind is never read as this run's. Where nothing matches, or the capture has
no device plane (a rehearsal), the readers have nothing to read.
"""

from __future__ import annotations

import collections
import functools
import glob
import gzip
import os
import re

import survey

DEVICE, OPS = "/device:TPU:", "XLA Ops"
WRAPPERS = ("while", "conditional", "call")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


WALL_MARK = "bench_wall_"


def captures() -> list[str]:
    """Every capture the harness may have written, newest first."""
    found = []
    for place in survey.candidates(ROOT):
        found += glob.glob(os.path.join(
            place, "bench-work-*", "trace", "plugins", "profile", "*",
            "*.xplane.pb"))
    return sorted(set(found), key=os.path.getmtime, reverse=True)


def find_capture(trace_wall: tuple[float, float]) -> str | None:
    """The capture of the span ``trace_wall`` (wall clock seconds): written
    after the span began, and marked by the harness inside it."""
    lo, hi = trace_wall
    for path in captures():
        if os.path.getmtime(path) < lo:
            break                         # older than the span: another run's
        if any(lo <= ns / 1e9 <= hi for ns in load(path)["wall_marks"]):
            return path
    return None


def wire_fields(buf: bytes):
    """(field number, value) of one protobuf message: int (varint) or bytes."""
    def varint(i):
        val = shift = 0
        while True:
            c = buf[i]
            i, val, shift = i + 1, val | (c & 0x7F) << shift, shift + 7
            if c < 0x80:
                return val, i
    i = 0
    while i < len(buf):
        key, i = varint(i)
        if key & 7 == 0:
            val, i = varint(i)
        else:
            n, i = (8, i) if key & 7 == 1 else (4, i) if key & 7 == 5 else varint(i)
            val, i = buf[i:i + n], i + n
        yield key >> 3, val


def op_scopes(raw: bytes) -> dict[str, str]:
    """{device event name: its scope path (``tf_op``)} of every device plane.
    An operation the compiler added carries no path: the asynchronous fetch
    of a weight into fast memory (``copy-start`` / ``copy-done``,
    ``slice-start`` / ``slice-done``) above all. It takes the path of the
    operation that USES its result (``consumer_scopes``), so a layer's sum
    holds the waits for its own weights."""
    out: dict[str, str] = {}
    for no, plane in wire_fields(raw):
        fields = list(wire_fields(plane)) if no == 1 else []
        if not any(f == 2 and v.startswith(DEVICE.encode()) for f, v in fields):
            continue
        stat = {}
        for entry in (dict(wire_fields(v)) for f, v in fields if f == 5):
            stat[entry.get(1, 0)] = dict(
                wire_fields(entry.get(2, b""))).get(2, b"").decode()
        texts: dict[tuple[int, str], str] = {}
        for meta in (list(wire_fields(dict(wire_fields(v)).get(2, b"")))
                     for f, v in fields if f == 4):
            got = {stat.get(s.get(1)): s
                   for s in (dict(wire_fields(sv)) for g, sv in meta if g == 5)}
            name = next((x for g, x in meta if g == 2), b"").decode()
            head = INSTRUCTION.match(name)
            if head:
                texts[got.get("program_id", {}).get(3, 0), head.group(1)] = name
            path = got.get("tf_op", {}).get(5)
            if isinstance(path, bytes):
                out[name] = path.decode().rstrip(":")
        out.update(consumer_scopes(texts, out))
    return out


INSTRUCTION = re.compile(r"%([\w.\-]+)")
HOPS = 4


def consumer_scopes(texts: dict[tuple[int, str], str],
                    scoped: dict[str, str]) -> dict[str, str]:
    """For every instruction without a scope path, the path of what uses it.
    ``texts`` is ``{(program, instruction): the event's name}``, and an event's
    name IS the instruction's HLO text (``%copy-done.3 = bf16[...] copy-done(
    (...) %copy-start.3)``), so its operands are the ``%names`` right of the
    ``=``. The users are followed through at most ``HOPS`` further unscoped
    instructions (a start's user is its done); of several users' paths the
    most frequent. An instruction used only by what is no device event (a
    tuple into a loop: the arena's copies at a decode chunk's entry and exit)
    stays without a path."""
    users: dict[tuple[int, str], list[str]] = {}
    for (program, short), text in texts.items():
        for operand in set(INSTRUCTION.findall(text.partition(" = ")[2])):
            if operand != short and (program, operand) in texts:
                users.setdefault((program, operand), []).append(short)

    def paths(program: int, short: str, hops: int) -> list[str]:
        found = []
        for user in users.get((program, short), ()):
            path = scoped.get(texts[program, user])
            if path:
                found.append(path)
            elif hops:
                found += paths(program, user, hops - 1)
        return found

    out = {}
    for (program, short), text in texts.items():
        if text not in scoped and not is_wrapper(text):
            found = paths(program, short, HOPS)
            if found:
                out[text] = collections.Counter(found).most_common(1)[0][0]
    return out


def is_wrapper(name: str) -> bool:
    base = name.split(" = ", 1)[0].strip().lstrip("%")
    return base.split(".")[0] in WRAPPERS


@functools.lru_cache(maxsize=4)
def load(path: str) -> dict:
    """-> {"ops": {scope path: [seconds, events]} of the first device's
    operations (wrappers left out), "host": {annotation: [seconds, ...]} of
    every ``tpusc.*`` event on the host's planes, "wall_marks": the ``<ns>``
    of every ``bench_wall_<ns>`` annotation}."""
    from jax.profiler import ProfileData

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        raw = f.read()
    scopes = op_scopes(raw)
    ops: dict[str, list] = {}
    host: dict[str, list[float]] = {}
    marks: list[int] = []
    device = None
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith(DEVICE):
            if device not in (None, plane.name):
                continue                      # one device's operations
            device = plane.name
            for line in plane.lines:
                if line.name != OPS:
                    continue
                for ev in line.events:
                    if is_wrapper(ev.name):
                        continue
                    acc = ops.setdefault(scopes.get(ev.name, ""), [0.0, 0])
                    acc[0] += ev.duration_ns / 1e9
                    acc[1] += 1
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("tpusc."):
                        host.setdefault(ev.name, []).append(ev.duration_ns / 1e9)
                    elif ev.name.startswith(WALL_MARK):
                        marks.append(int(ev.name[len(WALL_MARK):]))
    return {"ops": ops, "host": host, "device": device, "wall_marks": marks}


def scope_seconds(ops: dict, program: str, scope: str) -> tuple[float, int]:
    """Device (seconds, events) of the operations of ``program`` (a part of the
    path's first element: ``_paged_decode_chunk_jit``) whose path runs through
    ``scope`` (``layer/conv``: whole path elements, in order, adjacent)."""
    want = "/" + scope.strip("/") + "/"
    seconds, events = 0.0, 0
    for path, (sec, n) in ops.items():
        head, _, rest = path.partition("/")
        if program in head and want in "/" + rest + "/":
            seconds += sec
            events += n
    return seconds, events


def capture_of(run) -> dict | None:
    """This run's capture, loaded once a process; None where nothing was
    traced or no capture carries this run's mark."""
    if not run.trace_wall:
        return None
    path = find_capture(run.trace_wall)
    return load(path) if path else None


# -- what the ring says the traced span held ----------------------------------

DECODE_PROGRAM = "_paged_decode_chunk_jit"


def decode_steps(run) -> float:
    """Decode steps inside the traced span: every ring boundary that ran a
    chunk gives ``chunk`` steps, weighted by its share inside the span."""
    from measure import chunk_boundaries

    if not run.trace_wall:
        return 0.0
    return sum(share * s["chunk"] for s, _mid, share in chunk_boundaries(run))


def decode_scope_ms(run, scope: str, calls_a_step: int):
    """Device milliseconds a call of the operations under ``scope`` in the
    decode chunk's program, ``calls_a_step`` calls a decode step -> (value,
    calls); a count alone off the chip; None where nothing is to be read."""
    steps = decode_steps(run)
    if steps <= 0 or calls_a_step <= 0:
        return None
    calls = steps * calls_a_step
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(calls))
    capture = capture_of(run)
    if capture is None or capture["device"] is None:
        return None
    seconds, events = scope_seconds(capture["ops"], DECODE_PROGRAM, scope)
    if not events:
        return None
    return seconds / calls * 1e3, max(1, round(calls))
