"""The arithmetic the metric readers share: which requests count, how a
percentile treats a miss, what a counter did inside the window.

A reader (``end_to_end/<name>.py``, ``layer_metrics/<name>.py``) is a module
with ``read(run)``; it returns ``None`` when it finds nothing to read (the
harness then leaves the metric out), a number, or ``(number, samples)``.
``run`` is the ``Run`` below: everything one run observed.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Any

import numpy as np

from client import find_spans, metric_sum


@dataclasses.dataclass
class Run:
    """What one run observed. Times are ``time.monotonic()`` seconds unless
    a name says wall."""

    cell: dict                      # the workload file
    config: dict                    # the configuration file
    program_config: dict            # the program's model config, as run
    server: dict                    # the server options, as run
    device: dict                    # platform, kind, count
    seconds: float                  # the window's length
    t0: float = 0.0                 # the window's start
    t_end: float = 0.0              # when the drain ended
    setup_s: float = 0.0
    setup_split: dict = dataclasses.field(default_factory=dict)
    records: list[dict] = dataclasses.field(default_factory=list)
    setup_records: list[dict] = dataclasses.field(default_factory=list)
    before: dict = dataclasses.field(default_factory=dict)   # Client.observe()
    after: dict = dataclasses.field(default_factory=dict)
    steps: list[dict] = dataclasses.field(default_factory=list)  # engine ring
    trace: dict | None = None       # trace_reduce.reduce(), traced runs only
    trace_wall: tuple[float, float] | None = None   # traced span, wall clock
    compiles_in_window: int = 0

    # -- requests -----------------------------------------------------------
    def due_in_window(self) -> list[dict]:
        return [r for r in self.records
                if self.t0 <= r["due"] < self.t0 + self.seconds]

    def window_steps(self) -> list[dict]:
        """Engine boundaries recorded inside the window (wall clock)."""
        lo = self.before.get("t_wall", 0.0)
        return [s for s in self.steps if lo <= s["t_wall"] <= lo + self.seconds]

    def counter(self, name: str, *labels: str) -> float:
        """What a Prometheus counter (or a histogram's _sum/_count) grew by
        between the window's start and its end."""
        return (metric_sum(self.after["prom"], name, *labels)
                - metric_sum(self.before["prom"], name, *labels))

    def ledger(self, dim: str) -> float:
        """A tenant-ledger dimension's growth over the window, all tenants."""
        def total(snap: dict) -> float:
            return sum(t["totals"].get(dim, 0.0)
                       for t in snap.get("tenants", {}).values())
        return total(self.after) - total(self.before)


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def percentile_with_misses(values: list[float], misses: list[float],
                           q: float) -> float:
    """A miss (failed, refused, unanswered) ranks above every value: it
    enters with the time it had already waited when the run gave up, or the
    largest value seen, whichever is larger."""
    top = max(values, default=0.0)
    return percentile(values + [max(m, top) for m in misses], q)


def ttft_s(rec: dict) -> float | None:
    """Due time -> first token frame at the client; None = a miss."""
    return rec["token_t"][0] - rec["due"] if rec["token_t"] else None


def ttft_percentile_ms(run: Run, q: float):
    """A percentile of the window's time to first token in milliseconds,
    misses ranked above every value -> ``(value, requests)`` or None."""
    reqs = run.due_in_window()
    if not reqs:
        return None
    got = [ttft_s(r) for r in reqs]
    values = [v * 1e3 for v in got if v is not None]
    misses = [(run.t_end - r["due"]) * 1e3 for r, v in zip(reqs, got) if v is None]
    return percentile_with_misses(values, misses, q), len(reqs)


def tpot_s(rec: dict) -> float | None:
    """(last token - first token) / (tokens - 1): frames arrive a chunk at
    a time, so single gaps say nothing."""
    t = rec["token_t"]
    return (t[-1] - t[0]) / (len(t) - 1) if len(t) >= 2 else None


def load_tiers(rec: dict) -> list[str]:
    """The tiers of the ``load`` spans the program put in this request's
    own trace: the program's word for "this request loaded a model"."""
    return [str(s.get("attrs", {}).get("tier", "?"))
            for s in find_spans(rec.get("span"), "load")]


def load_children(rec: dict, tier: str, child: str) -> list[float]:
    """Seconds of ``child`` spans under this request's ``load{tier}`` spans."""
    out = []
    for s in find_spans(rec.get("span"), "load"):
        if str(s.get("attrs", {}).get("tier")) == tier:
            out += [c["duration_s"] for c in s.get("children", ())
                    if c.get("name") == child]
    return out


# -- the paged decode kernel in a traced run

PAGED_DECODE_KERNEL = "paged_decode"


def kernel_time(run):
    """(device seconds, calls) of the paged decode kernel in the trace."""
    if not run.trace:
        return None
    hits = [v for k, v in run.trace["kernels"].items() if PAGED_DECODE_KERNEL in k]
    calls = sum(v["calls"] for v in hits)
    return (sum(v["seconds"] for v in hits), calls) if calls else None


def live_tokens(run, at: float) -> int:
    """Cached tokens the kernel attends to at monotonic time ``at``: for
    each request streaming then, its prompt plus the tokens the client had
    received (they trail the engine's by less than a chunk)."""
    tokens = 0
    for r in run.records:
        t = r["token_t"]
        if t and t[0] <= at and (len(t) < r["max_new"] or t[-1] >= at):
            tokens += r["prompt_len"] + bisect.bisect_right(t, at)
    return tokens


def chunk_boundaries(run):
    """The ring boundaries that ran a decode chunk and overlap the traced
    span -> ``[(boundary, wall middle, share of it inside the span)]``
    (``t_wall`` is a boundary's end, ``step_ms`` its length)."""
    lo, hi = run.trace_wall
    out = []
    for s in run.steps:
        if s["chunk"] <= 0:
            continue
        end = s["t_wall"]
        start = end - s["step_ms"] / 1e3
        inside = min(end, hi) - max(start, lo)
        if inside > 0:
            out.append((s, (start + end) / 2, inside / (end - start)))
    return out


def paged_decode_calls(run):
    """The decode calls the traced span held -> ``[(live tokens, lanes,
    calls)]``, one entry a boundary of ``chunk_boundaries``, or None where
    nothing was traced. The tokens are the records' at the boundary's
    middle, the lanes the ring's ``active``, the calls ``chunk x layers``
    weighted by the boundary's share inside the span (as ``kernel_costs_moe.
    traced_calls`` counts the expert kernel's)."""
    if not run.trace_wall:
        return None
    to_mono = run.before["t"] - run.before["t_wall"]
    layers = run.program_config["n_layers"]
    return [(live_tokens(run, mid + to_mono), s["active"],
             share * s["chunk"] * layers)
            for s, mid, share in chunk_boundaries(run)]


def samples(value: Any) -> tuple[float | None, int | None]:
    """Normalise a reader's answer to ``(value, samples)``."""
    if value is None:
        return None, None
    if isinstance(value, tuple):
        return float(value[0]), int(value[1])
    return float(value), None
