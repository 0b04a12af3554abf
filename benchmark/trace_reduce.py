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
"""

from __future__ import annotations

import glob
import os
from typing import Iterable

Row = tuple[str, str, str, int, int]   # plane, line, name, start_ns, dur_ns

DEVICE_PLANE = "/device:TPU:"
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
    """Device operation rows and the benchmark's own annotations."""
    from jax.profiler import ProfileData

    rows: list[Row] = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(WALL_MARK):
                    rows.append((plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns)))
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
    for plane, line, name, start, dur in rows:
        if (plane.startswith(DEVICE_PLANE) and line == OPS_LINE
                and not is_wrapper(name)):
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
    for ops in per_dev.values():
        for name, _s, d in ops:
            k = kernels.setdefault(kernel_name(name), [0.0, 0])
            k[0] += d / 1e9 / len(per_dev)
            k[1] += 1
    offset = wall_offset_ns(rows)
    labelled: dict[str, float] = {}
    longest: list[tuple[float, str]] = []
    first = next(iter(per_dev.values()))
    for s, e in gaps(first, lo, hi):
        label = "host: nothing recorded"
        if offset is not None:
            ws, we = (s + offset) / 1e9, (e + offset) / 1e9
            best = 0.0
            for name, hs, he in host_states:
                cover = min(we, he) - max(ws, hs)
                if cover > best and cover >= 0.5 * (we - ws):
                    label, best = name, cover
                    break
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
