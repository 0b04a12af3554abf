#!/usr/bin/env python3
"""Read a profiler capture (``POST /monitoring/profiler``) by the program's own
names: ``python tools/trace_scopes.py <file.xplane.pb[.gz] | capture dir>`` prints
device seconds by program (``XLA Modules``), by program | ``jax.named_scope`` path |
operation (``XLA Ops``, wrappers left out), and the device's idle time by the
innermost ``tpusc.*`` host annotation (``utils/tracing.host_span``) open meanwhile.

Events come from ``jax.profiler.ProfileData``. It hides an event's METADATA stats,
where libtpu keeps an operation's scope path (``tf_op``) and ``program_id``: those
are read off the protobuf wire format (XSpace .planes=1; XPlane .name=2
.event_metadata=4 .stat_metadata=5; XEventMetadata .name=2 .stats=5; XStat
.metadata_id=1 .uint64=3 .int64=4 .str=5). The device's clock lags the host's in a
capture; ``clock_shift_ns`` bounds the lag from every launch, matched by ``run_id``.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
import sys
from collections import defaultdict

DEVICE, OPS, MODULES, MARK = "/device:TPU:", "XLA Ops", "XLA Modules", "tpusc."
WRAPPERS = ("while", "conditional", "call")
NOISE = re.compile(r"^(jit\(.*\)|jit|while|body|cond|closed_call|checkpoint|pjit|)$")
# the engine's nesting; any other annotation ranks below, latest start first
RANK = {"tpusc.boundary": 1, "tpusc.admit": 2, "tpusc.prefill": 3,
        "tpusc.decode_chunk": 3, "tpusc.emit": 3}
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


def by_program(rows) -> list[tuple]:
    return totals(((short(r[2]),), r[4]) for r in device_rows(rows, MODULES))


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
    return (lo, hi, n) if n and lo <= hi else (0, 0, 0)


def idle_by_annotation(rows, shift_ns: int = 0) -> list[tuple]:
    """The first device's idle time between its operations, each part of a gap
    under the innermost ``tpusc.*`` event open on the host during it."""
    ops = sorted((r[3] + shift_ns, r[3] + r[4] + shift_ns) for r in device_rows(rows, OPS))
    marks = [(s, s + d, n) for p, _l, n, s, d, _x in rows
             if not p.startswith(DEVICE) and n.startswith(MARK)]
    parts, edge = [], ops[0][1] if ops else 0
    for s, e in ops:
        if s > edge:
            cuts = sorted({edge, s, *(t for m in marks for t in m[:2] if edge < t < s)})
            for a, b in zip(cuts, cuts[1:]):
                live = [m for m in marks if m[0] <= a and b <= m[1]]
                top = max(live, key=lambda m: (RANK.get(m[2], 0), m[0]), default=None)
                parts.append(((top[2] if top else "(none open)",), b - a))
        edge = max(edge, e)
    return totals(parts)


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    rows = load(argv[0])
    lo, hi, n = clock_shift_ns(rows)
    idle = (f"device idle seconds by innermost tpusc.* annotation (device clock + "
            f"{(lo + hi) / 2e6:.3f} ms; bounds {lo / 1e6:.3f}..{hi / 1e6:.3f} from {n} launches)")
    for title, table in (("device seconds by program", by_program(rows)),
                         ("device seconds by program | scope | operation (top 40)",
                          by_scope(rows)[:40]),
                         (idle, idle_by_annotation(rows, (lo + hi) // 2))):
        print(f"\n{title}\n   seconds   count")
        for *key, sec, count in table:
            print(f"  {sec:8.4f} {count:7d}  {' | '.join(key)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
