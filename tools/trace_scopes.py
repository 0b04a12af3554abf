#!/usr/bin/env python3
"""Read a profiler capture (``POST /monitoring/profiler``) by the program's own
names: ``python tools/trace_scopes.py <file.xplane.pb[.gz] | capture dir>`` prints
device seconds by program | ``jax.named_scope`` path | operation (``XLA Ops``,
wrappers left out); every program's executions (``XLA Modules``: how many, device
wall, busy = the union of the operations inside them, idle INSIDE them, the mean
idle gap BEFORE one); and the device's idle time by cause: inside a program, or
between two under the innermost ``tpusc.*`` annotation the engine's thread had
open meanwhile (``utils/tracing.host_span``: ``tpusc.chunk_launch`` and
``tpusc.chunk_fetch`` split ``tpusc.decode_chunk``); and the bring-up account's
host events a capture held (``utils/bring_up.py``: ``tpusc.server_start``,
``tpusc.load`` and its stages, ``tpusc.engine_build``, the ``tpusc.first_run``
marker a new program's first execution leaves: a capture of the churn cell
holds loads, one that met a compile a first run). A device that waits inside
a program has bubbles between its operations; one that waits between two waits
for the host. ``benchmark/capture_programs.py`` reads a traced benchmark run's
capture the same way (one test holds the two to the same figures).

Events come from ``jax.profiler.ProfileData``. It hides an event's METADATA stats,
where libtpu keeps an operation's scope path (``tf_op``) and ``program_id``: those
are read off the protobuf wire format (XSpace .planes=1; XPlane .name=2
.event_metadata=4 .stat_metadata=5; XEventMetadata .name=2 .stats=5; XStat
.metadata_id=1 .uint64=3 .int64=4 .str=5). The device's clock lags the host's in a
capture; ``clock_shift_ns`` bounds the lag from every launch, matched by ``run_id``.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
import sys
from collections import defaultdict

DEVICE, OPS, MODULES, MARK = "/device:TPU:", "XLA Ops", "XLA Modules", "tpusc."
WRAPPERS = ("while", "conditional", "call")
DRIFT_NS = 500_000
NOISE = re.compile(r"^(jit\(.*\)|jit|while|body|cond|closed_call|checkpoint|pjit|)$")
# idle time between two programs, by the innermost engine annotation open; an
# ``admit`` that holds a prefill is an admission's
CAUSE = {"tpusc.chunk_launch": "launch path (under tpusc.chunk_launch)",
         "tpusc.chunk_fetch": "a chunk's device end to tpusc.chunk_fetch's close",
         "tpusc.decode_chunk": "under tpusc.decode_chunk outside its two child spans",
         "tpusc.boundary": "boundary host work (emit, ring, admit)",
         "tpusc.prefill": "admission path (before prefill / insert / lane insert)",
         None: "no boundary open"}
CAUSE.update({"tpusc.emit": CAUSE["tpusc.boundary"], "tpusc.admit": CAUSE["tpusc.boundary"],
              "tpusc.state_insert": CAUSE["tpusc.prefill"]})
# the bring-up account's stages (utils/bring_up.py) and a load's own
BRING_UP = ("server_start", "backend_init", "provider_fetch", "load", "artifact_read",
            "host_dequant", "device_transfer", "device_dequant", "transfer_sync",
            "compile_warmup", "engine_build", "first_run")
# a row: (plane, line, name, start_ns, dur_ns, {"scope", "program", "run_id"})


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


def op_metadata(raw: bytes) -> dict[str, dict]:
    """{device event name: {"scope": tf_op, "program": program_id}}."""
    out: dict[str, dict] = {}
    for no, plane in wire_fields(raw):
        fields = list(wire_fields(plane)) if no == 1 else []
        if not any(f == 2 and v.startswith(DEVICE.encode()) for f, v in fields):
            continue
        stat = {}
        for entry in (dict(wire_fields(v)) for f, v in fields if f == 5):
            stat[entry.get(1, 0)] = dict(wire_fields(entry.get(2, b""))).get(2, b"").decode()
        for meta in (list(wire_fields(dict(wire_fields(v)).get(2, b"")))
                     for f, v in fields if f == 4):
            got = {stat.get(s.get(1)): s.get(5, s.get(3, s.get(4)))
                   for s in (dict(wire_fields(sv)) for g, sv in meta if g == 5)}
            if "tf_op" in got or "program_id" in got:
                name = next((x for g, x in meta if g == 2), b"").decode()
                out[name] = {"scope": (got.get("tf_op") or b"").decode().rstrip(":"),
                             "program": got.get("program_id")}
    return out


def load(path: str) -> list[tuple]:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb*"), recursive=True))[-1]
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        raw = f.read()
    meta, rows = op_metadata(raw), []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        device = plane.name.startswith(DEVICE)
        for line in plane.lines:
            if device and line.name not in (OPS, MODULES):
                continue
            for ev in line.events:
                run_id = None
                if line.name == MODULES or ev.name in ("DoEnqueueProgram", "CompleteCallbacks"):
                    run_id = next((int(v) for k, v in ev.stats if k == "run_id"), None)
                if device or ev.name.startswith(MARK) or run_id is not None:
                    rows.append((plane.name, line.name, ev.name, int(ev.start_ns),
                                 int(ev.duration_ns), dict(meta.get(ev.name, {}), run_id=run_id)))
    return rows


def short(name: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..)`` -> ``fusion``; ``jit_f(123)`` -> ``jit_f``."""
    base = name.split(" = ", 1)[0].strip().lstrip("%").split("(", 1)[0]
    head, _, tail = base.rpartition(".")
    return head if head and tail.isdigit() else base


def scope_of(path: str | None) -> str:
    """``jit(f)/while/body/closed_call/layer/attn/dot_general`` -> ``layer/attn``."""
    return "/".join(p for p in (path or "").split("/")[:-1] if not NOISE.match(p)) or "(no scope)"


def device_rows(rows, line: str) -> list[tuple]:
    dev = min((r[0] for r in rows if r[0].startswith(DEVICE)), default=None)
    return [r for r in rows if r[0] == dev and r[1] == line
            and (line == MODULES or short(r[2]) not in WRAPPERS)]


def totals(keyed) -> list[tuple]:
    """[(key tuple, ns)] -> [(*key, seconds, count)], largest first."""
    acc = defaultdict(lambda: [0.0, 0])
    for key, ns in keyed:
        acc[key][0] += ns / 1e9
        acc[key][1] += 1
    return sorted(((*k, v[0], v[1]) for k, v in acc.items()), key=lambda r: -r[-2])


def by_scope(rows) -> list[tuple]:
    programs = {int(m.group(1)): short(r[2]) for r in device_rows(rows, MODULES)
                if (m := re.search(r"\((\d+)\)$", r[2]))}
    return totals(((programs.get(r[5].get("program"), "?"), scope_of(r[5].get("scope")),
                    short(r[2])), r[4]) for r in device_rows(rows, OPS))


def clock_shift_ns(rows) -> tuple[int, int, int]:
    """(low, high, launches): device time + a shift in [low, high] = host time
    (``DoEnqueueProgram`` <= device start, device end <= ``CompleteCallbacks``)."""
    run = {r[5]["run_id"]: (r[3], r[3] + r[4]) for r in device_rows(rows, MODULES)}
    lo, hi, n = -(1 << 62), 1 << 62, 0
    for plane, _l, name, s, _d, x in rows:
        if not plane.startswith(DEVICE) and x.get("run_id") in run:
            if name == "DoEnqueueProgram":
                lo, n = max(lo, s - run[x["run_id"]][0]), n + 1
            elif name == "CompleteCallbacks":
                hi = min(hi, s - run[x["run_id"]][1])
    # the clocks drift by up to 0.1 ms over a 4 s span: over hundreds of launches
    # the bounds often cross by that much, and the middle is still the shift
    return (lo, hi, n) if n and hi < 1 << 62 and lo - hi <= DRIFT_NS else (0, 0, 0)


def executions(rows) -> list[tuple]:
    """[(program, start, end, busy, idle gap before)] of the first device, ns, in
    time order, clipped to the operations' span; one sweep: the busy intervals
    (the operations' union) are met in order by gap, execution, gap, execution."""
    ops = sorted((r[3], r[3] + r[4]) for r in device_rows(rows, OPS))
    busy: list[list[int]] = []
    for s, e in ops:
        if busy and s <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], e)
        else:
            busy.append([s, e])
    if not busy:
        return []
    lo, hi, at = busy[0][0], busy[-1][1], 0

    def take(a: int, b: int) -> int:
        """Busy time inside [a, b); calls come in rising order."""
        nonlocal at
        while at < len(busy) and busy[at][1] <= a:
            at += 1
        total, k = 0, at
        while k < len(busy) and busy[k][0] < b:
            total += min(busy[k][1], b) - max(busy[k][0], a)
            k += 1
        return total

    out, last = [], None
    for r in sorted(device_rows(rows, MODULES), key=lambda r: r[3]):
        s, e = max(r[3], lo), min(r[3] + r[4], hi)
        if e <= s:
            continue
        gap = None if last is None else (s - last) - take(last, s)
        out.append((short(r[2]), s, e, take(s, e), gap))
        last = e
    return out


def by_execution(rows) -> list[tuple]:
    """[(program, executions, device wall s, busy s, idle inside s, mean gap before
    s)], the largest wall first."""
    acc = defaultdict(lambda: [0, 0.0, 0.0, []])
    for name, s, e, busy, gap in executions(rows):
        row = acc[name]
        row[0], row[1], row[2] = row[0] + 1, row[1] + (e - s) / 1e9, row[2] + busy / 1e9
        if gap is not None:
            row[3].append(gap / 1e9)
    return sorted(((n, r[0], r[1], r[2], r[1] - r[2], sum(r[3]) / len(r[3]) if r[3] else 0.0)
                   for n, r in acc.items()), key=lambda r: -r[2])


def engine_marks(rows) -> list[tuple]:
    """[(name, start, end)] of the thread that opens ``tpusc.boundary``: the
    engine's. A request's spans on the serving threads name no idle time."""
    lines = {(r[0], r[1]) for r in rows if r[2] == "tpusc.boundary"}
    return [(r[2], r[3], r[3] + r[4]) for r in rows
            if (r[0], r[1]) in lines and r[2].startswith(MARK)]


def idle_by_cause(rows, shift_ns: int = 0) -> list[tuple]:
    """[(cause, idle seconds)], largest first, summing to the span's idle time: the
    idle time inside each program under its name; the idle time between two
    executions under the innermost annotation the engine's thread had open
    (device clock + ``shift_ns``). One sweep: gaps and annotations both in time
    order, the annotations (they nest: one thread) as a stack."""
    marks = sorted(engine_marks(rows), key=lambda m: (m[1], -m[2]))
    prefills = [m[1] for m in marks if m[0] == "tpusc.prefill"]
    cuts, names, stack = [], [], []      # innermost annotation from each cut on

    def close(until) -> None:
        while stack and stack[-1][2] <= until:
            cuts.append(stack.pop()[2])
            names.append(stack[-1][0] if stack else None)

    for name, s, e in marks:
        close(s)
        if name == "tpusc.admit":
            i = bisect.bisect_left(prefills, s)
            if i < len(prefills) and prefills[i] < e:
                name = "tpusc.prefill"
        stack.append((name, s, e))
        cuts.append(s)
        names.append(name)
    close(float("inf"))
    acc: dict[str, float] = defaultdict(float)
    runs, k = executions(rows), -1
    for (name, s, e, busy, gap), before in zip(runs, [None] + runs[:-1]):
        acc[f"inside {name}"] += (e - s - busy) / 1e9
        if not gap or before is None:
            continue
        a, b = before[2] + shift_ns, s + shift_ns
        scale = gap / (b - a)             # 1 unless something ran outside a program
        while k + 1 < len(cuts) and cuts[k + 1] <= a:
            k += 1
        while a < b:
            nxt = min(b, cuts[k + 1]) if k + 1 < len(cuts) else b
            acc[CAUSE.get(names[k] if k >= 0 else None, CAUSE["tpusc.boundary"])] += (
                (nxt - a) * scale / 1e9)
            if nxt < b:
                k += 1
            a = nxt
    return sorted(acc.items(), key=lambda kv: -kv[1])


def bring_up_marks(rows) -> list[tuple]:
    """[(name, count, seconds)] of the bring-up account's host events, in
    ``BRING_UP``'s order: a stage's span on whatever thread ran it.
    ``tpusc.first_run`` is a MARKER at its call's return (the call's wall and
    the program ride in its name, ``#program=..,wall_ms=..#``)."""
    acc: dict[str, list] = {}
    for _plane, _line, name, _s, dur, _x in rows:
        base = name.split("#", 1)[0]
        if base.startswith(MARK) and base[len(MARK):] in BRING_UP:
            row = acc.setdefault(base, [0, 0.0])
            row[0], row[1] = row[0] + 1, row[1] + dur / 1e9
    return [(MARK + n, *acc[MARK + n]) for n in BRING_UP if MARK + n in acc]


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    rows = load(argv[0])
    lo, hi, n = clock_shift_ns(rows)
    print("\ndevice seconds by program | scope | operation (top 40)\n   seconds   count")
    for *key, sec, count in by_scope(rows)[:40]:
        print(f"  {sec:8.4f} {count:7d}  {' | '.join(key)}")
    print("\nprogram executions: count, device wall s, busy s, idle inside s, "
          "mean idle gap before ms")
    for name, count, wall, busy, idle, gap in by_execution(rows):
        print(f"  {count:7d} {wall:8.4f} {busy:8.4f} {idle:8.4f} {gap * 1e3:8.3f}  {name}")
    causes = idle_by_cause(rows, (lo + hi) // 2)
    print(f"\ndevice idle seconds by cause, {sum(sec for _c, sec in causes):.4f} in all "
          f"(device clock + {(lo + hi) / 2e6:.3f} ms; bounds {lo / 1e6:.3f}..{hi / 1e6:.3f} "
          f"from {n} launches)")
    for cause, sec in causes:
        if sec > 0:
            print(f"  {sec:8.4f}  {cause}")
    marks = bring_up_marks(rows)
    if marks:
        print("\nbring-up host events (utils/bring_up.py): count, seconds")
        for name, count, sec in marks:
            print(f"  {count:7d} {sec:8.4f}  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
