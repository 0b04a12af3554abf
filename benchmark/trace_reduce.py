"""From a profiler trace to numbers: busy and idle time, the operations that
took most of the device's time, and the longest idle gaps by what the host
was doing.

``load_xplane`` reads the ``.xplane.pb`` JAX's profiler writes with nothing
but JAX (``jax.profiler.ProfileData``) and flattens it to plain event rows
``(plane, line, name, start_ns, duration_ns)``; everything else works on such
rows, so the arithmetic is checked on a small recorded trace
(``tests/data/small_trace.json``) without a chip.

A device plane is one whose name starts with ``/device:TPU:``; the events of
its ``XLA Ops`` line are the operations that ran on the chip. Busy time is
the union of their intervals (operations of one line do not overlap, but
the union also holds if a later libtpu interleaves them). Control-flow
operations (``while``, ``conditional``, ``call``) span the operations inside
them and are left out of both the union's inputs' names and the top list:
their children are counted. An operation's name is the HLO instruction's
name without its numeric suffix, so a Pallas kernel appears under the name of
its ``pallas_call`` (``paged_decode_attention_kernel``).

The host's side comes from two places: a ``bench_wall_<ns>`` annotation the
benchmark writes into the trace (it ties the trace's clock to the wall
clock), and intervals on the wall clock that the caller passes in (engine
boundaries from the ring, requests in flight from the client's records).

Everything here is linear in the capture (a decode step is hundreds of small
operations, a 4 s span half a million): a name is parsed once a distinct
name, and idle gaps meet host states in one sweep in time order.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import sys
from typing import Iterable

Row = tuple[str, str, str, int, int]   # plane, line, name, start_ns, dur_ns

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:"             # TraceMe events: annotations live here
OPS_LINE = "XLA Ops"
WALL_MARK = "bench_wall_"
# operations that only wrap others: counting them would count the children twice
WRAPPERS = ("while", "conditional", "call")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str) -> list[Row]:
    """Device operation rows and the benchmark's own annotation: of a device
    plane the ``XLA Ops`` line, of the host's planes nothing but the first
    ``bench_wall_`` event (``run.py`` writes one a capture). Names are
    interned: a capture has a few hundred distinct ones, 200 bytes each."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    rows: list[Row] = []
    marked = False
    for plane in data.planes:
        pname = plane.name
        if pname.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    rows += [(pname, OPS_LINE, sys.intern(ev.name),
                              int(ev.start_ns), int(ev.duration_ns))
                             for ev in line.events]
        elif not marked and pname.startswith(HOST_PLANE):
            for line in plane.lines:
                mark = next((ev for ev in line.events
                             if ev.name.startswith(WALL_MARK)), None)
                if mark is not None:
                    rows.append((pname, line.name, mark.name,
                                 int(mark.start_ns), int(mark.duration_ns)))
                    marked = True
                    break
    return rows


def op_name(name: str) -> str:
    """An event's operation name. libtpu names an event by the whole HLO
    line (``%fusion.3 = bf16[...] fusion(...)``): keep what is left of
    `` = ``, without the ``%``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def is_wrapper(name: str) -> bool:
    return op_name(name).split(".")[0] in WRAPPERS


def device_ops(rows: Iterable[Row]) -> dict[str, list[tuple[str, int, int]]]:
    """{device plane: [(name, start_ns, dur_ns)]}, wrappers left out."""
    out: dict[str, list[tuple[str, int, int]]] = {}
    wrapper: dict[str, bool] = {}
    for plane, line, name, start, dur in rows:
        if line != OPS_LINE or not plane.startswith(DEVICE_PLANE):
            continue
        skip = wrapper.get(name)
        if skip is None:
            skip = wrapper[name] = is_wrapper(name)
        if not skip:
            out.setdefault(plane, []).append((name, start, dur))
    for ops in out.values():
        ops.sort(key=lambda e: e[1])
    return out


def union_ns(intervals: Iterable[tuple[int, int]]) -> int:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(ops: list[tuple[str, int, int]], lo: int, hi: int
         ) -> list[tuple[int, int]]:
    """Idle ``(start, end)`` intervals of one device inside ``[lo, hi]``."""
    out, edge = [], lo
    for _name, start, dur in ops:
        s, e = max(start, lo), min(start + dur, hi)
        if e <= s:
            continue
        if s > edge:
            out.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        out.append((edge, hi))
    return out


def kernel_name(name: str) -> str:
    """An operation's stable name: the HLO name without its numeric suffix
    (``fusion.123`` -> ``fusion``, ``%paged_decode.4`` -> ``paged_decode``)."""
    base = op_name(name)
    head, _, tail = base.rpartition(".")
    return head if head and tail.isdigit() else base


def wall_offset_ns(rows: Iterable[Row]) -> int | None:
    """``wall_ns - trace_ns`` from the benchmark's annotation, or None."""
    for _plane, _line, name, start, _dur in rows:
        if name.startswith(WALL_MARK):
            return int(name[len(WALL_MARK):]) - start
    return None


def name_gaps(spans: list[tuple[float, float]],
              host_states: list[tuple[str, float, float]]) -> list[str]:
    """The label of each idle span ``(wall_start_s, wall_end_s)``; the spans
    are in time order and do not overlap. A state names a span when it
    covers at least half of it; of several, the earliest entry of
    ``host_states`` wins; of none, ``"host: nothing recorded"``.

    One sweep: the states wait sorted by their start, and ``live`` holds the
    list positions, in rising order, of those that began before the span's
    end and had not ended by the start of an earlier one. Only they can
    overlap the span, so each is tried in list order with the arithmetic a
    loop over all states would use."""
    by_start = sorted(range(len(host_states)), key=lambda i: host_states[i][1])
    live: list[int] = []
    nxt = 0
    out = []
    for ws, we in spans:
        while nxt < len(by_start) and host_states[by_start[nxt]][1] < we:
            bisect.insort(live, by_start[nxt])
            nxt += 1
        label, ended = "host: nothing recorded", False
        for i in live:
            name, hs, he = host_states[i]
            if he <= ws:
                ended = True        # over before this span: before all later
                continue
            cover = min(we, he) - max(ws, hs)
            if cover > 0.0 and cover >= 0.5 * (we - ws):
                label = name
                break
        if ended:
            live = [i for i in live if host_states[i][2] > ws]
        out.append(label)
    return out


def reduce(rows: list[Row], host_states: list[tuple[str, float, float]] = (),
           top: int = 10) -> dict:
    """-> busy_s, window_s (averaged over the device planes), the top
    operations by summed time, per-kernel sums and call counts, and the
    longest idle gaps named by the host state that covers most of each.

    ``host_states`` are ``(label, wall_start_s, wall_end_s)``; earlier
    entries win where several cover a gap. A gap no state covers is
    ``"host: nothing recorded"``."""
    per_dev = device_ops(rows)
    if not per_dev:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0,
                "device_ops": [], "idle_gaps": [], "kernels": {}}
    lo = min(ops[0][1] for ops in per_dev.values())
    hi = max(max(s + d for _n, s, d in ops) for ops in per_dev.values())
    busy = [union_ns((s, s + d) for _n, s, d in ops) for ops in per_dev.values()]
    kernels: dict[str, list[float]] = {}
    by_event_name: dict[str, list[float]] = {}
    for ops in per_dev.values():
        for name, _s, d in ops:
            k = by_event_name.get(name)
            if k is None:
                k = by_event_name[name] = kernels.setdefault(
                    kernel_name(name), [0.0, 0])
            k[0] += d / 1e9 / len(per_dev)
            k[1] += 1
    offset = wall_offset_ns(rows)
    idle = gaps(next(iter(per_dev.values())), lo, hi)
    if offset is None:
        labels = ["host: nothing recorded"] * len(idle)
    else:
        labels = name_gaps([((s + offset) / 1e9, (e + offset) / 1e9)
                            for s, e in idle], list(host_states))
    labelled: dict[str, float] = {}
    longest: list[tuple[float, str]] = []
    for (s, e), label in zip(idle, labels):
        labelled[label] = labelled.get(label, 0.0) + (e - s) / 1e9
        longest.append(((e - s) / 1e9, label))
    longest.sort(reverse=True)
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(per_dev),
        "device_ops": sorted(([k, v[0]] for k, v in kernels.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in labelled.items()),
                            key=lambda kv: -kv[1])[:top],
        "longest_gaps": [[lab, sec] for sec, lab in longest[:top]],
        "kernels": {k: {"seconds": v[0], "calls": v[1]}
                    for k, v in kernels.items()},
    }
