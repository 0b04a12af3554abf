"""The bring-up account: what a model's way onto the chip costs, kept by the
program where the work happens.

Three kinds of entry, all on the tracing the repo has (``TRACER`` spans,
``RECORDER`` records behind ``/monitoring/engine``, ``Metrics`` families); no
option switches it, like the engine's ring it is always on:

- **Every compiled program, by name.** ``install`` registers
  ``jax.monitoring`` listeners; jax calls them when it traces, lowers and
  compiles a ``jax.jit`` (on the CALLER's thread, trace then lower then
  compile) and when the persistent compilation cache hits or misses. Seconds go
  to ``tpusc_program_build_seconds_total{program, stage}``, a finished build to
  ``tpusc_program_builds_total{program, cache}``, to ``RECORDER.note_build``
  and, where a request's span is ambient on the thread, to a ``program_build``
  child of it. A trace nested in another build (a jitted helper called while
  its caller is traced, a kernel body's helpers while it is lowered) is part
  of that build and books nothing of its own. An event without a name (the
  cache's) goes to the build open on the thread, else to the program whose
  trace last ended there.
- **Every stage** (``stage`` / ``note_stage``; ``first_run`` for an engine
  program's first execution): a span under the ambient request where there is
  one (the profiler's ``tpusc.<stage>`` annotation either way),
  ``tpusc_cold_stage_seconds{stage}`` and a ``RECORDER.note_stage`` record. A stage's seconds are its wall LESS the
  build seconds booked on its thread meanwhile, so stages and builds add up
  without counting a second twice.
- **Device bytes at a stage's end** (``device_bytes``): one ``memory_stats()``
  of the fullest device into ``tpusc_device_bytes{stage, what}``, the record
  and the span. A backend without allocator statistics (the CPU) gives none.

What it costs: a listener call is a few microseconds and fires only while jax
builds (tests/test_bring_up.py holds it under 20 us an event); the engine's
call sites test ``BUILT.flag`` after their program call, one attribute read
(under 0.2 us); ``memory_stats()`` is 2-7 us a call on a v5e (PERF.md).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Any, Iterator

from tfservingcache_tpu.utils.flight_recorder import RECORDER
from tfservingcache_tpu.utils.tracing import TRACER, current_span, host_span

_BUILD = "/jax/core/compile/"
STAGE_OF = {
    _BUILD + "jaxpr_trace_duration": "trace",
    _BUILD + "jaxpr_to_mlir_module_duration": "lower",
    _BUILD + "backend_compile_duration": "compile",
}
_CACHE = "/jax/compilation_cache/"
# nameless events between a compile's start and its end: what the persistent
# cache did with the program ("off" where it was never asked)
CACHE_OF = {
    _CACHE + "compile_requests_use_cache": "miss",   # asked; a hit follows or not
    _CACHE + "cache_misses": "miss",                 # compiled and written
    _CACHE + "cache_hits": "hit",
}
MAX_PROGRAM_LABELS = 64
OTHER = "other"


class _Thread(threading.local):
    """One thread's open build. Class attributes are the defaults a thread
    sees before it writes its own."""

    flag = False        # a build was booked here since a call site last looked
    depth = 0           # jax build events open on this thread (they nest)
    program = ""        # whose trace last ended at depth 0
    build_s = 0.0       # every build second booked on this thread
    pending: dict | None = None     # the record being put together
    begun: tuple | None = None      # (monotonic, build_s) when the build opened
    last: tuple | None = None       # (program, *begun) of the last finished one


BUILT = _Thread()


def _plain(fun_name: str) -> str:
    """``jit(f)`` (the lowered module's name) -> ``f`` (the traced one's)."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


class Account:
    """The listeners' state that is not a thread's: the registry the counters
    are booked into and the bounded set of program labels."""

    def __init__(self) -> None:
        self.metrics: Any = None
        self.labels: set[str] = set()
        self.installed = False
        # the listeners' own cost (racy sums, a diagnostic): build events
        # seen to their end, nested ones included (they return at once), and
        # the seconds the others, which book, took
        self.events = 0
        self.listener_s = 0.0

    def label(self, program: str) -> str:
        labels = self.labels
        if program in labels:
            return program
        if len(labels) >= MAX_PROGRAM_LABELS:
            return OTHER
        labels.add(program)     # set.add is GIL-atomic; a race overshoots by one
        return program

    # -- the three listeners -------------------------------------------------
    def on_begin(self, event: str, _value: float, **_kw: Any) -> None:
        """Scalar listener: jax records a build event's start time under the
        event's name before it starts."""
        stage = STAGE_OF.get(event)
        if stage is None:
            return
        t = BUILT
        depth = t.depth
        t.depth = depth + 1
        if depth == 0 and (t.begun is None or stage == "trace"):
            # a build opens with its trace (one left without a compile, an
            # ``eval_shape``'s, is forgotten here)
            t.begun = (time.monotonic(), t.build_s)

    def on_event(self, event: str, **_kw: Any) -> None:
        cache = CACHE_OF.get(event)
        if cache is None:
            return
        t = BUILT
        pending = t.pending
        if pending is None:
            pending = t.pending = self._record(t.program or OTHER, 0.0)
        if cache == "hit" or pending["cache"] == "off":
            pending["cache"] = cache

    def on_end(self, event: str, duration: float, **kw: Any) -> None:
        stage = STAGE_OF.get(event)
        if stage is None:
            return
        self.events += 1
        t = BUILT
        depth = t.depth
        if depth > 1:
            # nested (a jitted helper traced inside its caller, most events
            # by far): these seconds are inside the open build's
            t.depth = depth - 1
            return
        t0 = time.perf_counter()
        t.depth = 0     # also where the event began before the listeners did
        self._book(t, stage, duration, kw.get("fun_name"))
        self.listener_s += time.perf_counter() - t0

    def _book(self, t: _Thread, stage: str, duration: float,
              fun_name: Any) -> None:
        program = _plain(str(fun_name or t.program or OTHER))
        pending = t.pending
        if stage == "trace":
            t.program = program
            pending = t.pending = self._record(program, duration)
        elif pending is None or pending["program"] != program:
            pending = t.pending = self._record(program, 0.0)
        label = self.label(program)
        metrics = self.metrics
        if stage == "lower":
            pending["lower_s"] += duration
        elif stage == "compile":
            pending["compile_s"] = duration
            if pending["cache"] == "hit":
                stage = "cache_load"
            self._finish(t, pending, label, metrics)
        if metrics is not None:
            metrics.program_build_seconds.labels(label, stage).inc(duration)
        t.build_s += duration
        t.flag = True

    @staticmethod
    def _record(program: str, trace_s: float) -> dict[str, Any]:
        return {"program": program, "t_wall": time.time() - trace_s,
                "thread": threading.current_thread().name,
                "trace_s": trace_s, "lower_s": 0.0, "compile_s": 0.0,
                "cache": "off"}

    def _finish(self, t: _Thread, rec: dict, label: str, metrics: Any) -> None:
        t.pending = None
        t.last = (rec["program"], *(t.begun or (time.monotonic(), t.build_s)))
        t.begun = None
        RECORDER.note_build(rec)
        if metrics is not None:
            metrics.program_builds.labels(label, rec["cache"]).inc()
        parent = current_span()
        if parent is not None:
            TRACER.attach(
                parent, "program_build",
                rec["trace_s"] + rec["lower_s"] + rec["compile_s"],
                start_s=rec["t_wall"], program=rec["program"],
                cache=rec["cache"])


ACCOUNT = Account()


def install(metrics: Any = None) -> None:
    """Register the listeners once a process (server start) and book into
    ``metrics`` from here on (the newest node's registry: one node a process
    outside tests)."""
    if metrics is not None:
        ACCOUNT.metrics = metrics
    if ACCOUNT.installed:
        return
    import jax.monitoring as monitoring

    ACCOUNT.installed = True
    monitoring.register_scalar_listener(ACCOUNT.on_begin)
    monitoring.register_event_listener(ACCOUNT.on_event)
    monitoring.register_event_duration_secs_listener(ACCOUNT.on_end)


def device_bytes(devices: Any) -> dict[str, int] | None:
    """The allocator's count on the fullest of ``devices``, or None where the
    backend keeps none (the CPU). ``reserved`` is the scratch the loaded
    program with the largest temporaries holds: on a v5e neither
    ``bytes_in_use`` nor its peak counts a program's temporaries (PERF.md)."""
    best = None
    for d in devices or ():
        stats = d.memory_stats()
        if stats and (best is None
                      or stats.get("bytes_in_use", 0) > best["bytes_in_use"]):
            best = {"bytes_in_use": int(stats.get("bytes_in_use", 0)),
                    "peak": int(stats.get("peak_bytes_in_use", 0)),
                    "reserved": int(stats.get("bytes_reserved", 0))}
    return best


def note_stage(
    name: str,
    wall_s: float,
    build_s0: float,
    metrics: Any = None,
    devices: Any = None,
    span: Any = None,
    bytes_as: str | None = None,
    **attrs: Any,
) -> dict[str, Any]:
    """Book one finished stage on this thread: ``wall_s`` less the build
    seconds booked here since ``build_s0`` (``BUILT.build_s`` when the stage
    began) into ``tpusc_cold_stage_seconds{name}``, the device's bytes (where
    ``devices`` are given and count them) into ``tpusc_device_bytes{bytes_as
    or name}``, the record into ``RECORDER``, the numbers onto ``span``. The
    builds inside are the stage's: the call sites' flag is cleared."""
    t = BUILT
    built = t.build_s - build_s0
    t.flag = False
    rec: dict[str, Any] = {
        "stage": name, "t_wall": time.time(), "wall_s": wall_s,
        "build_s": built, "seconds": max(0.0, wall_s - built), **attrs}
    mem = device_bytes(devices)
    if mem is not None:
        rec.update(mem)
        RECORDER.observe_watermark("device_bytes_peak", float(mem["peak"]))
    if metrics is not None:
        metrics.cold_stage_seconds.labels(name).observe(rec["seconds"])
        if mem is not None:
            gauge = metrics.device_bytes
            stage = bytes_as or name
            gauge.labels(stage, "in_use").set(mem["bytes_in_use"])
            gauge.labels(stage, "peak").set(mem["peak"])
            gauge.labels(stage, "reserved").set(mem["reserved"])
    if span is not None:
        span.attrs.update(build_s=round(built, 6), **(mem or {}))
    RECORDER.note_stage(rec)
    return rec


@contextmanager
def stage(name: str, metrics: Any = None, devices: Any = None,
          **attrs: Any) -> Iterator[dict[str, Any]]:
    """A stage booked by ``note_stage`` at its end: a child span where a
    request's trace is ambient, else the profiler's ``tpusc.<name>``
    annotation alone (a root of its own would enter the tracer's ring and,
    being seconds long, ask for an SLO-breach dump). Yields a dict the body
    may fill with what is only known inside: further attributes of the
    record."""
    late: dict[str, Any] = {}
    b0, t0 = BUILT.build_s, time.monotonic()
    sp = None
    if current_span() is not None:
        with TRACER.span(name, **attrs) as sp:
            yield late
    else:
        with host_span(name):
            yield late
    note_stage(name, time.monotonic() - t0, b0, metrics, devices, span=sp,
               **attrs, **late)


def first_run(programs: tuple[str, ...], outputs: Any, metrics: Any = None,
              devices: Any = None, **attrs: Any) -> dict[str, Any] | None:
    """The slow half of a call site's ``if BUILT.flag:`` after an engine
    program's call. Where the build that set the flag was one of ``programs``
    this was the program's first execution: wait for ``outputs`` once (the cold
    path), book ``first_run`` with the call's wall, taken from the build's
    start, less the build's seconds, and leave a ``tpusc.first_run`` marker on
    the profiler's clock. Any other build (a helper's, earlier on this thread)
    only clears the flag."""
    t = BUILT
    t.flag = False
    last, t.last = t.last, None
    if last is None or last[0] not in programs:
        return None
    import jax

    jax.block_until_ready(outputs)
    program, began, build_s0 = last
    wall = time.monotonic() - began
    parent = current_span()
    rec = note_stage("first_run", wall, build_s0, metrics, devices,
                     bytes_as=f"first_run:{ACCOUNT.label(program)}",
                     program=program, **attrs)
    if parent is not None:
        TRACER.attach(parent, "first_run", rec["seconds"], program=program,
                      **{k: rec[k] for k in ("bytes_in_use", "peak", "reserved")
                         if k in rec})
    with host_span(f"first_run#program={program},wall_ms={wall * 1e3:.1f}#"):
        pass
    return rec
