#!/usr/bin/env python3
"""Pretty-print a flight-recorder anomaly dump for postmortems.

The serving engine writes one JSON dump per anomaly (SLO breach,
page-exhaustion blocking, engine-thread crash) into
``observability.flight_dir`` — see utils/flight_recorder.py for the
format and OBSERVABILITY.md for the triggers. This tool renders the dump
the way an on-call reads it:

  - header: reason, model, trigger context (trace id / duration / error)
  - per-model window summary: steps, goodput, wasted steps, peak queue
  - stall spans: contiguous runs of steps with a non-empty admission
    queue (where requests sat waiting — page or lane starvation)
  - step timeline: the ring tail, one line per chunk boundary
  - phase notes: per-request queue/prefill/decode/respond attribution
  - watermarks captured at dump time

With ``--url`` the same rendering runs against a LIVE node: the tool
fetches ``<url>/monitoring/engine?reset=0`` (peek — it never consumes the
node's reset-on-scrape watermarks) and renders the response, so the
on-call can read the current engine state without waiting for an anomaly
dump. ``--model name@version`` narrows a busy multi-tenant node to one
model's rings.

Usage:
    python tools/engine_dump.py <dump.json> [--steps N]
    python tools/engine_dump.py --latest [<flight_dir>]
    python tools/engine_dump.py --url http://node:8501 [--model lm@1]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import urllib.parse
import urllib.request

DEFAULT_FLIGHT_DIR = "/tmp/tpusc_flight"


def fetch(url: str, steps: int, model: str | None = None, timeout: float = 5.0) -> dict:
    """GET <url>/monitoring/engine as a dump-shaped dict (reset=0: peeking
    must not consume the node's reset-on-scrape watermarks)."""
    query = {"n": str(steps), "reset": "0"}
    if model:
        query["model"] = model
    full = f"{url.rstrip('/')}/monitoring/engine?{urllib.parse.urlencode(query)}"
    with urllib.request.urlopen(full, timeout=timeout) as resp:
        return json.load(resp)


def _fmt_step(s: dict) -> str:
    used = s.get("pages_used", 0)
    shared = s.get("pages_shared", 0)
    # shared/private/free page split: `used` is physical occupancy
    # (arena - free), shared of those are multi-owner prefix pages
    pages = (
        f"pages={shared}s+{max(0, used - shared)}p"
        f"/{s.get('pages_free', 0)}f"
    )
    # the boundary's split (ISSUE 23); absent in dumps of 16-field rings
    split = ""
    if "chunk_ms" in s:   # the three fields come together
        pre, chunk, emit = s["prefill_ms"], s["chunk_ms"], s["emit_ms"]
        own = max(0.0, s.get("step_ms", 0) - pre - chunk - emit)
        # the chunk's launch path (ISSUE 35): the part of chunk= before
        # the device had the chunk; absent in dumps of rings up to 23 fields
        launch = f"launch={s['launch_ms']:.2f} " if "launch_ms" in s else ""
        # what that launch had to upload (ISSUE 36); rings up to 24 fields
        # have none
        if "uploads" in s:
            launch += f"uploads={s['uploads']} "
        split = (f"(prefill={pre:.2f} chunk={chunk:.2f} {launch}emit={emit:.2f} "
                 f"self={own:.2f}) ")
    # expert routing (ISSUE 25); absent in older dumps, 0 for dense models
    if s.get("experts_hit"):
        split += (f"experts={s['experts_hit']:.1f} "
                  f"rows_max={s.get('expert_rows_max', 0):.1f} ")
        if "expert_rows_local" in s:    # ISSUE 31: assignments that landed here
            split += f"rows_local={s['expert_rows_local']:.1f} "
    if s.get("write_lanes"):    # ISSUE 32: the lanes whose KV rows a step wrote
        split += f"write_lanes={s['write_lanes']} "
    if s.get("window_pages"):   # ISSUE 39: pages a window call read a live lane
        split += f"window_pages={s['window_pages']:.1f} "
    if s.get("shared_pages"):   # ISSUE 41: pages the calls over a shared layer read
        split += f"shared_pages={s['shared_pages']:.1f} "
    if s.get("state_lanes"):    # ISSUE 46: the lanes whose lane state a step wrote
        split += f"state_lanes={s['state_lanes']} "
    if s.get("ahead"):  # ISSUE 40: the chunk fetched here was launched ahead
        split += "ahead=1 "
    return (
        f"  {s.get('engine', '?'):<10} step={s.get('step_ms', 0):>8.2f}ms {split}"
        f"chunk={s.get('chunk', 0):>3} active={s.get('active', 0):>3} "
        f"+{s.get('admitted', 0)}/-{s.get('retired', 0)} "
        f"wasted={s.get('wasted', 0):>3} "
        f"{pages} "
        f"queue={s.get('queue_depth', 0):>3} "
        f"oldest={s.get('oldest_wait_ms', 0):>8.1f}ms"
    )


def _stall_spans(steps: list[dict]) -> list[tuple[int, int, int, float]]:
    """Contiguous runs of steps with queued requests:
    (start_idx, length, max_depth, max_wait_ms)."""
    spans = []
    start = None
    depth = 0
    wait = 0.0
    for i, s in enumerate(steps):
        if s.get("queue_depth", 0) > 0:
            if start is None:
                start, depth, wait = i, 0, 0.0
            depth = max(depth, s.get("queue_depth", 0))
            wait = max(wait, s.get("oldest_wait_ms", 0.0))
        elif start is not None:
            spans.append((start, i - start, depth, wait))
            start = None
    if start is not None:
        spans.append((start, len(steps) - start, depth, wait))
    return spans


def render(dump: dict, max_steps: int = 32, out=sys.stdout) -> None:
    w = out.write
    reason = dump.get("reason", "snapshot")
    w(f"=== flight dump: {reason} ===\n")
    if dump.get("model_filter") and not dump.get("model_found", True):
        # live snapshot narrowed to a model the node has never recorded:
        # say so explicitly instead of rendering an empty timeline the
        # on-call could mistake for "model exists but is idle"
        w(f"no such model: {dump['model_filter']} "
          f"(no engine rings or phase notes recorded under that name)\n")
        return
    if dump.get("model"):
        w(f"model:   {dump['model']}\n")
    ctx = dump.get("context") or {}
    for k in sorted(ctx):
        w(f"{k + ':':<9}{ctx[k]}\n")
    marks = dump.get("watermarks") or {}
    if marks:
        w("watermarks (high-water since last scrape):\n")
        for k in sorted(marks):
            w(f"  {k} = {marks[k]:.0f}\n")
    ckv = dump.get("conversation_kv") or {}
    if ckv.get("enabled"):
        # parked-conversation tier (serving.conversation_kv_bytes): how much
        # decode state is parked where, and how often resumes actually hit
        w(
            f"conversation KV: {ckv.get('host_conversations', 0)} host "
            f"({ckv.get('host_bytes', 0):,} B) + "
            f"{ckv.get('disk_conversations', 0)} disk "
            f"({ckv.get('disk_bytes', 0):,} B) parked, "
            f"hit rate={ckv.get('hit_rate', 0.0):.3f} "
            f"({ckv.get('hits', 0)} hit / {ckv.get('spilled_hits', 0)} "
            f"spilled / {ckv.get('misses', 0)} miss), "
            f"{ckv.get('spills', 0)} spills, "
            f"{ckv.get('migrations_in', 0)} migrations in\n"
        )
    for model, data in sorted((dump.get("models") or {}).items()):
        win = data.get("window") or {}
        steps = data.get("steps") or []
        w(f"\n--- {model} ({data.get('recorded_steps', 0)} steps recorded) ---\n")
        w(
            f"window: {win.get('steps', 0)} steps, "
            f"goodput={win.get('goodput', 1.0):.3f} "
            f"({win.get('wasted_steps', 0)}/{win.get('step_slots', 0)} "
            f"step-slots wasted), "
            f"max queue={win.get('max_queue_depth', 0)}, "
            f"max wait={win.get('max_oldest_wait_ms', 0.0):.1f}ms\n"
        )
        if win.get("admitted"):
            w(
                f"prefix sharing: {win.get('prefix_hits', 0)}"
                f"/{win['admitted']} admissions hit "
                f"(rate={win.get('prefix_hit_rate', 0.0):.3f}), "
                f"max shared pages={win.get('max_pages_shared', 0)}\n"
            )
        if win.get("drafted"):
            w(
                f"speculation: {win.get('accepted', 0)} tokens emitted / "
                f"{win['drafted']} drafted "
                f"(acceptance={win.get('spec_acceptance', 0.0):.3f} of "
                f"emission capacity)\n"
            )
        spans = _stall_spans(steps)
        if spans:
            w("stall spans (steps with a non-empty admission queue):\n")
            for start, length, depth, wait in spans:
                w(
                    f"  steps [{start}..{start + length - 1}]: "
                    f"{length} boundaries, depth<={depth}, "
                    f"oldest wait<={wait:.1f}ms\n"
                )
        shown = steps[-max_steps:]
        if shown:
            if len(steps) > len(shown):
                w(f"timeline (last {len(shown)} of {len(steps)}):\n")
            else:
                w("timeline:\n")
            for s in shown:
                w(_fmt_step(s) + "\n")
    phases = dump.get("phases") or {}
    for model, notes in sorted(phases.items()):
        if not notes:
            continue
        w(f"\n--- {model}: request phase attribution ---\n")
        for note in notes[-max_steps:]:
            ph = note.get("phases") or {}
            parts = " ".join(
                f"{k}={ph[k] * 1e3:.2f}ms"
                for k in ("queue", "prefill", "decode", "respond") if k in ph
            )
            tid = note.get("trace_id") or "-"
            w(f"  [{note.get('engine', '?')}] trace={tid[:16]} {parts}\n")


def _latest(flight_dir: str) -> str | None:
    try:
        names = sorted(
            f for f in os.listdir(flight_dir)
            if f.startswith("flight_") and f.endswith(".json")
        )
    except OSError:
        return None
    return os.path.join(flight_dir, names[-1]) if names else None


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", nargs="?", help="dump file (or flight dir with --latest)")
    ap.add_argument(
        "--latest", action="store_true",
        help=f"render the newest dump in the flight dir (default {DEFAULT_FLIGHT_DIR})",
    )
    ap.add_argument(
        "--steps", type=int, default=32,
        help="max timeline rows per model (default 32)",
    )
    ap.add_argument(
        "--url",
        help="render a live node's /monitoring/engine instead of a dump file "
             "(e.g. http://node:8501; peeks with reset=0)",
    )
    ap.add_argument(
        "--model",
        help="with --url: restrict to one model (name@version)",
    )
    args = ap.parse_args(argv)
    if args.url:
        dump = fetch(args.url, steps=args.steps, model=args.model)
        render(dump, max_steps=args.steps)
        return 0
    path = args.path
    if args.latest:
        path = _latest(path or DEFAULT_FLIGHT_DIR)
        if path is None:
            print("no flight dumps found", file=sys.stderr)
            return 1
    if not path:
        ap.error("dump file required (or --latest)")
    with open(path) as fh:
        dump = json.load(fh)
    render(dump, max_steps=args.steps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
