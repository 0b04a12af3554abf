"""A traced run's capture by PROGRAM EXECUTION: where the device stood still.

``trace_reduce.py`` sums the device's idle time under two words of the ring
(a boundary that admitted, one that did not); ``capture_scopes.py`` sums busy
time under a scope. Neither says whether the device waited INSIDE a program
(bubbles between its operations) or BETWEEN two programs (the host had not
launched the next one), and the second kind is the host's to shorten. The
capture knows: its ``XLA Modules`` line holds one event a program execution
(``jit__paged_decode_chunk_jit(<id>)``, with the launch's ``run_id``), the
``XLA Ops`` line the operations inside each, and the host planes the engine
thread's ``tpusc.*`` annotations (``utils/tracing.host_span``) beside the
runtime's ``DoEnqueueProgram`` / ``CompleteCallbacks`` events of the same
``run_id``.

``load`` reads a capture in ONE pass (events into arrays, a name parsed once
a distinct name; cached a process, as ``capture_scopes.load`` is) and hands
the rows to ``reduce``, which is plain arithmetic and is tested on rows built
by hand:

- every execution of the first device plane: program name without its id,
  start, end, ``run_id``, busy time (the union of the operations inside it,
  wrappers left out), idle time inside it, and the idle gap before it;
- the clock shift (device time + shift = host time), bounded from below by
  every ``DoEnqueueProgram`` (a program starts after it was enqueued) and from
  above by every ``CompleteCallbacks`` (it ended before its completion ran):
  ``tools/trace_scopes.clock_shift_ns``'s arithmetic;
- the decode chunks, each tied to its boundary ON THE HOST'S CLOCK (the
  ``DoEnqueueProgram`` of its ``run_id`` falls inside a ``tpusc.chunk_launch``
  event, or the ``tpusc.decode_chunk`` of a program older than that span; no
  shift needed). A boundary is *decode-only and back to back* when no
  admission program (a prefill, an insert, a prefill chunk, a speculation
  round: ``ADMISSION``) ran between its chunk and the one before, and the
  previous ``tpusc.boundary`` closed less than ``BACK_TO_BACK_NS`` before this
  one opened (the engine did not wait on its condition variable). Only such
  boundaries enter the gap metrics: "waiting for a request" never does;
- the span's idle time by cause, summing to the span's idle: inside a program
  (by program) or, between two, under the innermost annotation the engine's
  thread had open (device clock + the shift's middle).

The span is the first device's operations' (first start to last end), as
``trace_reduce.reduce`` has it; an execution that reaches past it is clipped.
"""

from __future__ import annotations

import bisect
import functools
import gzip
import statistics

import numpy as np

import capture_scopes
from capture_scopes import DEVICE, OPS, is_wrapper

MODULES = "XLA Modules"
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
MARK = "tpusc."
DECODE = capture_scopes.DECODE_PROGRAM
INSERT = "_paged_insert_jit"
# a program with one of these in its name is an admission's (or a speculation
# round's): a boundary that ran one between its chunk and the last is not
# decode-only
ADMISSION = ("prefill", "insert", "spec_round")
BACK_TO_BACK_NS = 0.5e6
DRIFT_NS = 0.5e6

LAUNCH, FETCH, CHUNK = "tpusc.chunk_launch", "tpusc.chunk_fetch", "tpusc.decode_chunk"
BOUNDARY, ADMIT, PREFILL = "tpusc.boundary", "tpusc.admit", "tpusc.prefill"
# causes of idle time between two programs, by the innermost annotation open
IN_LAUNCH = "launch path (under tpusc.chunk_launch)"
IN_FETCH = "a chunk's device end to tpusc.chunk_fetch's close"
IN_CHUNK = "under tpusc.decode_chunk outside its two child spans"
IN_BOUNDARY = "boundary host work (emit, ring, admit)"
IN_ADMISSION = "admission path (before prefill / insert / lane insert)"
NO_BOUNDARY = "no boundary open"
CAUSE = {LAUNCH: IN_LAUNCH, FETCH: IN_FETCH, CHUNK: IN_CHUNK,
         BOUNDARY: IN_BOUNDARY, "tpusc.emit": IN_BOUNDARY, ADMIT: IN_BOUNDARY,
         PREFILL: IN_ADMISSION, "tpusc.state_insert": IN_ADMISSION,
         None: NO_BOUNDARY}


def program_name(event: str) -> str:
    """``jit__paged_decode_chunk_jit(17441965134069376537)`` without its id."""
    return event.split("(", 1)[0]


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """One pass over the capture at ``path`` -> ``reduce``'s answer."""
    from jax.profiler import ProfileData

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    device = None
    programs: list[tuple] = []
    op_start: list[float] = []
    op_end: list[float] = []
    wrapper: dict[str, bool] = {}
    threads: list[list[tuple]] = []     # a host line's tpusc.* events
    enqueue: dict[int, float] = {}
    complete: dict[int, float] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE):
            if device not in (None, plane.name):
                continue                      # one device's executions
            device = plane.name
            for line in plane.lines:
                if line.name == MODULES:
                    for ev in line.events:
                        programs.append((
                            program_name(ev.name), ev.start_ns,
                            ev.start_ns + ev.duration_ns, run_id_of(ev)))
                elif line.name == OPS:
                    for ev in line.events:
                        name = ev.name
                        skip = wrapper.get(name)
                        if skip is None:
                            skip = wrapper[name] = is_wrapper(name)
                        if not skip:
                            start = ev.start_ns
                            op_start.append(start)
                            op_end.append(start + ev.duration_ns)
            continue
        for line in plane.lines:
            marks = []
            for ev in line.events:
                name = ev.name
                if name.startswith(MARK):
                    marks.append((name, ev.start_ns, ev.start_ns + ev.duration_ns))
                elif name == ENQUEUE:
                    enqueue[run_id_of(ev)] = ev.start_ns
                elif name == COMPLETE:
                    complete[run_id_of(ev)] = ev.start_ns
            if marks:
                threads.append(marks)
    # the engine's thread is the one that opens boundaries; request spans on
    # the serving threads (tpusc.serve, tpusc.rest ...) name no idle time
    engine = [m for marks in threads
              if any(n == BOUNDARY for n, _s, _e in marks) for m in marks]
    return dict(reduce(programs, np.asarray(op_start, np.float64),
                       np.asarray(op_end, np.float64), engine, enqueue, complete),
                device=device)


def run_id_of(ev) -> int:
    return next((int(v) for k, v in ev.stats if k == "run_id"), -1)


class Busy:
    """The union of the operations' intervals (``starts`` / ``ends``, sorted,
    disjoint) as a clock: ``busy(t)`` is the busy time before ``t`` (a number
    or an array)."""

    def __init__(self, op_start: np.ndarray, op_end: np.ndarray) -> None:
        order = np.argsort(op_start, kind="stable")
        s, e = op_start[order], op_end[order]
        top = np.maximum.accumulate(e)
        fresh = np.ones(len(s), bool)
        fresh[1:] = s[1:] > top[:-1]          # no earlier operation still runs
        self.starts = s[fresh]
        self.ends = top[np.append(fresh[1:], True)]
        self.before = np.concatenate([[0.0], np.cumsum(self.ends - self.starts)])

    def __call__(self, t):
        i = np.searchsorted(self.starts, t, side="right")
        over = np.maximum(self.ends[np.maximum(i, 1) - 1] - t, 0.0)
        return self.before[i] - np.where(i > 0, over, 0.0)

    def last_end(self, t: float) -> float:
        """Where the last operation that began before ``t`` ended (at most ``t``)."""
        i = int(np.searchsorted(self.starts, t, side="left")) - 1
        return min(float(self.ends[i]), t) if i >= 0 else t

    def first_start(self, t: float) -> float:
        """Where the first operation still to end after ``t`` begins (at least ``t``)."""
        i = int(np.searchsorted(self.ends, t, side="right"))
        return max(float(self.starts[i]), t) if i < len(self.starts) else t


def reduce(programs, op_start: np.ndarray, op_end: np.ndarray, engine,
           enqueue: dict, complete: dict) -> dict:
    """``programs``: ``[(name, start, end, run_id)]`` of one device's
    ``XLA Modules`` line; ``op_start`` / ``op_end``: its operations, wrappers
    left out; ``engine``: ``[(name, start, end)]`` of the engine thread's
    ``tpusc.*`` events; ``enqueue`` / ``complete``: ``{run_id: host time}``.
    Times are nanoseconds, the device's on its clock and the host's on its.

    -> ``span`` (lo, hi), ``busy_ns``, ``idle_ns``; ``programs``: a dict of
    equal-length columns (``name``, ``start``, ``end``, ``run_id``, ``busy``,
    ``idle`` inside, ``gap`` before: idle time since the execution before,
    NaN for the first); ``shift`` (low, high, launches); ``chunks``
    (``decode_chunks``); ``causes`` ``{cause: idle ns}``; ``marks``
    ``{annotation: (starts, ends)}``."""
    if not len(op_start) or not programs:
        return {"span": (0.0, 0.0), "busy_ns": 0.0, "idle_ns": 0.0,
                "programs": None, "shift": (0.0, 0.0, 0), "chunks": [],
                "causes": {}, "marks": {}}
    busy = Busy(op_start, op_end)
    lo, hi = float(busy.starts[0]), float(busy.ends[-1])
    shift = clock_shift(programs, enqueue, complete)
    programs = sorted((p for p in programs if p[2] > lo and p[1] < hi),
                      key=lambda p: p[1])
    start = np.clip(np.asarray([p[1] for p in programs], np.float64), lo, hi)
    end = np.clip(np.asarray([p[2] for p in programs], np.float64), lo, hi)
    inside = busy(end) - busy(start)
    gap = np.full(len(programs), np.nan)
    gap[1:] = (start[1:] - end[:-1]) - (busy(start[1:]) - busy(end[:-1]))
    table = {"name": [p[0] for p in programs], "start": start, "end": end,
             "run_id": [p[3] for p in programs], "busy": inside,
             "idle": (end - start) - inside, "gap": gap}
    spans: dict[str, list] = {}
    for name, s, e in sorted(engine, key=lambda m: m[1]):
        spans.setdefault(name, []).append((s, e))
    marks = {name: (np.asarray([s for s, _e in rows]), np.asarray([e for _s, e in rows]))
             for name, rows in spans.items()}
    out = {"span": (lo, hi), "busy_ns": float(busy(np.float64(hi))),
           "programs": table, "shift": shift, "marks": marks}
    out["idle_ns"] = (hi - lo) - out["busy_ns"]
    out["chunks"] = decode_chunks(table, busy, marks, enqueue)
    out["causes"] = idle_by_cause(table, engine, (shift[0] + shift[1]) / 2)
    return out


def clock_shift(programs, enqueue: dict, complete: dict) -> tuple:
    """(low, high, launches): device time + a shift in [low, high] = host time.
    The device's own (unclipped) start and end of every execution whose
    launch the capture holds. The two clocks DRIFT against each other, by up
    to 0.1 ms over a 4 s span on a v5e host, so over hundreds of launches the
    bounds often CROSS (low > high by 0.02–0.1 ms in four of eight spans
    read): the middle is still the shift, good to the crossing. Bounds more
    than ``DRIFT_NS`` the wrong way are two clocks that cannot be tied (a
    ``run_id`` matched wrongly), and read (0, 0, 0)."""
    low, high, n = -np.inf, np.inf, 0
    for _name, start, end, run_id in programs:
        if run_id in enqueue:
            low, n = max(low, enqueue[run_id] - start), n + 1
        if run_id in complete:
            high = min(high, complete[run_id] - end)
    if not n or not np.isfinite(high) or low - high > DRIFT_NS:
        return (0.0, 0.0, 0)
    return (float(low), float(high), n)


def decode_chunks(table: dict, busy, marks: dict, enqueue: dict) -> list[dict]:
    """One entry a decode chunk (row ``at`` of the table) that has a decode chunk
    before it in the span (row ``last``): ``gap_ns`` the device's idle time
    from the last one's last operation to this one's first, ``launches`` the
    executions from the last one's end to
    this one's end (this one included), ``between`` their names, ``counts``
    whether the boundary is decode-only and back to back (the module's
    docstring), and ``boundary`` (open, close on the host's clock) where the
    chunk's launch was found in one."""
    names, out = table["name"], []
    chunks = [i for i, n in enumerate(names) if DECODE in n]
    launch = marks.get(LAUNCH) or marks.get(CHUNK)
    bounds = marks.get(BOUNDARY)
    for last, this in zip(chunks, chunks[1:]):
        # from the last one's last operation to this one's first
        a = busy.last_end(float(table["end"][last]))
        b = busy.first_start(float(table["start"][this]))
        between = names[last + 1:this]
        entry = {"at": this, "last": last, "launches": this - last, "between": between,
                 "gap_ns": float((b - a) - (busy(b) - busy(a))),
                 "decode_only": not any(w in n for n in between for w in ADMISSION),
                 "boundary": None, "back_to_back": False}
        at = enqueue.get(table["run_id"][this])
        if at is not None and launch is not None and bounds is not None:
            if holds(launch, at):
                j = int(np.searchsorted(bounds[0], at, side="right")) - 1
                if j >= 0 and at <= bounds[1][j]:
                    entry["boundary"] = (float(bounds[0][j]), float(bounds[1][j]))
                    entry["back_to_back"] = bool(
                        j > 0 and bounds[0][j] - bounds[1][j - 1] < BACK_TO_BACK_NS)
        entry["counts"] = entry["decode_only"] and entry["back_to_back"]
        out.append(entry)
    return out


def holds(spans: tuple, t: float) -> bool:
    """Whether one of the sorted ``(starts, ends)`` spans holds ``t``."""
    i = int(np.searchsorted(spans[0], t, side="right")) - 1
    return i >= 0 and t <= spans[1][i]


def innermost(engine) -> tuple[list[float], list]:
    """The engine thread's annotations (they nest: one thread) as a step
    function -> (cut times, the innermost annotation open from each cut to
    the next; None where none is). An ``admit`` that holds a prefill is an
    admission's, and reads ``tpusc.prefill``."""
    prefills = sorted(s for n, s, _e in engine if n == PREFILL)
    cuts: list[float] = []
    names: list = []
    stack: list[tuple] = []

    def close(until: float) -> None:
        while stack and stack[-1][2] <= until:
            cuts.append(stack.pop()[2])
            names.append(stack[-1][0] if stack else None)

    for name, s, e in sorted(engine, key=lambda m: (m[1], -m[2])):
        close(s)
        if name == ADMIT:
            i = bisect.bisect_left(prefills, s)
            if i < len(prefills) and prefills[i] < e:
                name = PREFILL
        stack.append((name, s, e))
        cuts.append(s)
        names.append(name)
    close(float("inf"))
    return cuts, names


def idle_by_cause(table: dict, engine, shift_ns: float) -> dict[str, float]:
    """{cause: idle nanoseconds}: the idle time inside each program under its
    name, the idle time between two executions under what the engine's thread
    was doing meanwhile. Sums to the span's idle time."""
    out: dict[str, float] = {}
    for name, idle in zip(table["name"], table["idle"]):
        key = f"inside {name}"
        out[key] = out.get(key, 0.0) + float(idle)
    cuts, names = innermost(engine)
    for i in range(1, len(table["name"])):
        idle = float(table["gap"][i])
        a, b = table["end"][i - 1] + shift_ns, table["start"][i] + shift_ns
        if idle <= 0.0 or b <= a:
            continue
        scale = idle / (b - a)                # 1 unless something ran outside a program
        k = bisect.bisect_right(cuts, a) - 1
        while a < b:
            nxt = min(b, cuts[k + 1]) if k + 1 < len(cuts) else b
            cause = CAUSE.get(names[k] if k >= 0 else None, IN_BOUNDARY)
            out[cause] = out.get(cause, 0.0) + (nxt - a) * scale
            a, k = nxt, k + 1
    return out


def by_program(cap: dict) -> list[tuple]:
    """[(program, executions, device wall s, busy s, idle inside s, mean gap
    before s)], the largest wall first."""
    table, acc = cap["programs"], {}
    if table is None:
        return []
    for i, name in enumerate(table["name"]):
        row = acc.setdefault(name, [0, 0.0, 0.0, 0.0, []])
        row[0] += 1
        row[1] += (table["end"][i] - table["start"][i]) / 1e9
        row[2] += table["busy"][i] / 1e9
        row[3] += table["idle"][i] / 1e9
        if not np.isnan(table["gap"][i]):
            row[4].append(table["gap"][i] / 1e9)
    return sorted(((n, r[0], r[1], r[2], r[3],
                    statistics.fmean(r[4]) if r[4] else 0.0) for n, r in acc.items()),
                  key=lambda r: -r[2])


def counted(cap: dict) -> list[dict]:
    """The decode chunks whose boundary is decode-only and back to back."""
    return [c for c in cap["chunks"] if c["counts"]]


def report(cap: dict) -> list[str]:
    """What a traced run prints once: executions by program, the idle time by
    cause against the span's, the clock shift's bounds, and both sides of
    ``gap = fetch return + boundary work + launch`` on the capture's clocks."""
    lo, hi = cap["span"]
    low, high, n = cap["shift"]
    lines = [f"capture by program execution: span {(hi - lo) / 1e9:.4f} s, busy "
             f"{cap['busy_ns'] / 1e9:.4f}, idle {cap['idle_ns'] / 1e9:.4f}; device clock "
             f"+ {low / 1e6:.3f}..{high / 1e6:.3f} ms = host clock ({n} launches, bounds "
             f"{abs(high - low) / 1e6:.3f} ms apart{', crossed: the clocks drift' * (low > high)})",
             "  program: executions, device wall s, busy s, idle inside s, mean gap before ms"]
    for name, runs, wall, busy, idle, gap in by_program(cap):
        lines.append(f"    {name}: {runs}, {wall:.4f}, {busy:.4f}, {idle:.4f}, {gap * 1e3:.3f}")
    total = sum(cap["causes"].values())
    lines.append(f"  idle seconds by cause (sum {total / 1e9:.4f} of the span's "
                 f"{cap['idle_ns'] / 1e9:.4f}):")
    for cause, ns in sorted(cap["causes"].items(), key=lambda kv: -kv[1]):
        if ns > 0.0:
            lines.append(f"    {ns / 1e9:.4f}  {cause}")
    chunks, good = cap["chunks"], counted(cap)
    lines.append(f"  decode chunks after another: {len(chunks)}, of them decode-only and "
                 f"back to back: {len(good)}")
    if good:
        kinds: dict[tuple, int] = {}
        for c in good:
            key = tuple(c["between"])
            kinds[key] = kinds.get(key, 0) + 1
        for key, count in sorted(kinds.items(), key=lambda kv: -kv[1]):
            lines.append(f"    {count} x {len(key) + 1} launches: "
                         f"{', '.join(key) or '(none)'}, then the chunk")
        before: dict[str, list] = {}
        for c in good:
            for i in range(c["last"] + 1, c["at"] + 1):
                before.setdefault(cap["programs"]["name"][i], []).append(
                    cap["programs"]["gap"][i] / 1e6)
        lines.append("  idle gap before each of their launches, medians ms: " + ", ".join(
            f"{name} {statistics.median(gaps):.3f}" for name, gaps in before.items()))
        sides = identity(cap)
        if sides:
            lines.append(
                "  gap between two chunks, medians ms: device "
                f"{sides['gap']:.3f} = fetch return {sides['fetch']:.3f} + boundary "
                f"work {sides['boundary']:.3f} + launch to the device's start "
                f"{sides['launch']:.3f} (sum {sides['fetch'] + sides['boundary'] + sides['launch']:.3f}"
                f"; the launch span itself {sides['launch_span']:.3f})")
    return lines


def identity(cap: dict) -> dict | None:
    """Both sides of the gap's identity over the counted boundaries, medians
    in ms: the device's gap; and, on the host's clock with the shift's middle,
    the last chunk's device end to its fetch's close, from there to this
    chunk's launch span opening, from there to the device's start."""
    table, marks = cap["programs"], cap["marks"]
    launch, fetch = marks.get(LAUNCH), marks.get(FETCH) or marks.get(CHUNK)
    if launch is None or fetch is None:
        return None
    shift = (cap["shift"][0] + cap["shift"][1]) / 2
    rows = []
    for c in counted(cap):
        ended = table["end"][c["last"]] + shift
        began = table["start"][c["at"]] + shift
        f = int(np.searchsorted(fetch[1], ended, side="left"))      # closes after it
        o = int(np.searchsorted(launch[0], began, side="right")) - 1  # opened before it
        if f >= len(fetch[1]) or o < 0 or launch[0][o] < fetch[1][f]:
            continue
        rows.append((c["gap_ns"], fetch[1][f] - ended, launch[0][o] - fetch[1][f],
                     began - launch[0][o], launch[1][o] - launch[0][o]))
    if not rows:
        return None
    cols = [statistics.median(r[i] for r in rows) / 1e6 for i in range(5)]
    return dict(zip(("gap", "fetch", "boundary", "launch", "launch_span"), cols))


@functools.lru_cache(maxsize=2)
def load_and_report(path: str) -> dict:
    """``load`` with ``report`` printed, once a capture a process."""
    cap = load(path)
    if cap["programs"] is not None:
        print("\n".join(report(cap)), flush=True)
    return cap


def capture_of(run) -> dict | None:
    """This run's capture by program execution, found and held to the run as
    ``capture_scopes.find_capture`` does it; the first reader to ask prints
    ``report``. None where nothing was traced, no capture carries this run's
    mark, or it has no device plane."""
    if not run.trace_wall:
        return None
    path = capture_scopes.find_capture(run.trace_wall)
    cap = load_and_report(path) if path else None
    return cap if cap and cap["programs"] is not None else None


def on_chip(run) -> bool:
    return run.device.get("platform") == "tpu"


def over_counted(run, value):
    """A reader's answer over the span's counted chunks: ``(value(chunks),
    their number)`` on the chip; in a rehearsal 0.0 and the boundaries the
    ring says the span held (those that ran a chunk); None where there is
    nothing to read."""
    from measure import chunk_boundaries

    if not run.trace_wall:
        return None
    if not on_chip(run):
        held = len(chunk_boundaries(run))
        return (0.0, held) if held else None
    cap = capture_of(run)
    good = counted(cap) if cap else []
    return (value(good), len(good)) if good else None
