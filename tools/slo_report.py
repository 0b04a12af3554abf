#!/usr/bin/env python3
"""Terminal renderer for scenario-lab SLO scorecards.

Reads a JSON artifact carrying a ``scenario_lab`` section (or a bare
section dict: ``{"scenarios", "faults", "matrix": [scorecard rows of
lab.scenario.run_cell]}``; tests/test_scenario_lab.py builds one, the
committed BENCH_r11.json and BENCH_r13.json are two CPU-harness samples
from before bench.py went) and renders the scenario x fault matrix the way an on-call
reads a chaos drill: one row per scenario, one column per fault kind, the
chosen metric in each cell. A second table lists every cell's full
scorecard row (the SCORECARD_FIELDS schema from lab/scenario.py), with
lost requests and failed conservation censuses flagged loudly — a drill
that loses requests is the headline, not a footnote.

Usage:
    python tools/slo_report.py BENCH_r11.json
    python tools/slo_report.py BENCH_r11.json --metric tok_s
    python tools/slo_report.py BENCH_r11.json --cells   # full per-cell rows
"""

from __future__ import annotations

import argparse
import json
import sys

# matrix-cell metrics a reader can pivot on (must be numeric scorecard
# fields; lab/scenario.py SCORECARD_FIELDS is the authority)
METRICS = (
    "p50_ttft_ms", "p95_ttft_ms", "p99_ttft_ms", "tok_s", "wall_s",
    "completed", "lost", "recovered", "goodput", "cold_miss_rate",
    "fault_injections", "preemptions",
)

CELL_COLS = (
    ("scenario", 14), ("fault", 17), ("requests", 4), ("completed", 4),
    ("lost", 4), ("recovered", 4), ("p50_ttft_ms", 8), ("p95_ttft_ms", 8),
    ("p99_ttft_ms", 8), ("tok_s", 7), ("goodput", 7),
    ("cold_miss_rate", 6), ("fault_injections", 4), ("preemptions", 5),
    ("conservation_ok", 6),
)
CELL_HDRS = {
    "requests": "req", "completed": "done", "lost": "lost",
    "recovered": "rcvd", "p50_ttft_ms": "p50 ms", "p95_ttft_ms": "p95 ms",
    "p99_ttft_ms": "p99 ms", "tok_s": "tok/s", "goodput": "goodpt",
    "cold_miss_rate": "miss", "fault_injections": "inj",
    "preemptions": "prmpt", "conservation_ok": "census",
}

# render order for the per-priority-class pivot (ISSUE 19)
_CLASS_ORDER = {"high": 0, "normal": 1, "low": 2}


def _section(doc: dict) -> dict:
    """Accept a full artifact, its ``parsed`` envelope, or a bare
    scenario_lab section."""
    for key in ("parsed", "detail"):
        if isinstance(doc.get(key), dict):
            doc = doc[key]
    if isinstance(doc.get("scenario_lab"), dict):
        doc = doc["scenario_lab"]
    if "matrix" not in doc:
        raise SystemExit(
            "no scenario_lab matrix in this artifact "
            "(it wants lab.scenario.run_cell scorecard rows under "
            "scenario_lab.matrix)"
        )
    return doc


def _cell(row: dict | None, metric: str) -> str:
    if row is None:
        return "-"
    v = row.get(metric)
    if v is None:
        return "-"
    s = f"{v:.1f}" if isinstance(v, float) else str(v)
    # a lossy cell is flagged no matter which metric is displayed
    if row.get("lost"):
        s += f"!L{row['lost']}"
    if row.get("conservation_ok") is False:
        s += "!C"
    return s


def render(doc: dict, out=None, metric: str = "p95_ttft_ms",
           cells: bool = False) -> None:
    out = sys.stdout if out is None else out
    sec = _section(doc)
    rows = sec.get("matrix") or []
    w = out.write
    scenarios = sec.get("scenarios") or sorted({r["scenario"] for r in rows})
    faults = sec.get("faults") or sorted({r["fault"] for r in rows})
    by = {(r["scenario"], r["fault"]): r for r in rows}

    plat = {r.get("platform") for r in rows} - {None}
    kern = {bool(r.get("kernel_active")) for r in rows}
    w(f"scenario lab: {len(rows)} cells "
      f"({len(scenarios)} scenarios x {len(faults)} faults), "
      f"platform={'/'.join(sorted(plat)) or '?'} "
      f"kernel_active={'/'.join(str(k).lower() for k in sorted(kern))}\n")
    lost = sum(r.get("lost", 0) for r in rows)
    rec = sum(r.get("recovered", 0) for r in rows)
    bad_census = [r for r in rows if r.get("conservation_ok") is False]
    w(f"totals: lost={lost} recovered={rec} "
      f"census={'FAIL:' + str(len(bad_census)) if bad_census else 'green'}\n")

    w(f"\n{metric} by scenario x fault "
      f"(!Ln = n lost requests, !C = census failed):\n")
    fw = max(10, max((len(f) for f in faults), default=10) + 1)
    w(f"{'scenario':<16}" + "".join(f"{f:>{fw}}" for f in faults) + "\n")
    for s in scenarios:
        w(f"{s:<16}" + "".join(
            f"{_cell(by.get((s, f)), metric):>{fw}}" for f in faults
        ) + "\n")

    if cells:
        w("\nper-cell scorecards:\n")
        w(" ".join(
            f"{CELL_HDRS.get(k, k):>{n}}" if k not in ("scenario", "fault")
            else f"{k:<{n}}" for k, n in CELL_COLS
        ) + "\n")
        for r in rows:
            parts = []
            for k, n in CELL_COLS:
                v = r.get(k)
                if k == "conservation_ok":
                    v = {True: "ok", False: "FAIL", None: "-"}[v]
                elif isinstance(v, float):
                    v = f"{v:.1f}"
                elif v is None:
                    v = "-"
                parts.append(f"{v:<{n}}" if k in ("scenario", "fault")
                             else f"{v:>{n}}")
            w(" ".join(parts) + "\n")
            for err in (r.get("errors") or [])[:2]:
                w(f"    error: {err}\n")


def _unwrap(doc: dict) -> dict:
    for key in ("parsed", "detail"):
        if isinstance(doc.get(key), dict):
            doc = doc[key]
    return doc


def _classes_from_traces(traces: list) -> dict:
    """Per-class TTFT map from a ``/monitoring/traces`` dump: generate
    trace roots carry ``priority`` and ``ttft_ms`` attrs (stamped by the
    engine), so the live trace ring yields the same pivot a recorded
    artifact holds — the cross-check that the class-labeled
    ``tpusc_request_phase_seconds`` histogram and the traces agree."""
    samples: dict[str, list] = {}
    for t in traces:
        attrs = t.get("attrs") or {}
        pr, ttft = attrs.get("priority"), attrs.get("ttft_ms")
        if pr is not None and ttft is not None:
            samples.setdefault(str(pr), []).append(float(ttft))
    out = {}
    for cls, vals in samples.items():
        vals.sort()
        out[cls] = {
            "p50": vals[int(0.50 * (len(vals) - 1))],
            "p95": vals[int(0.95 * (len(vals) - 1))],
            "n": len(vals),
        }
    return out


def render_classes(doc: dict, out=None) -> None:
    """Per-priority-class TTFT pivot (ISSUE 19): one row per cell that
    recorded ``ttft_ms_by_class`` (an artifact's ``slo_engine`` arms, plus
    any scenario-lab cell that tagged its requests), one column per class.
    Each cell shows ``p95 (n=count)`` — the SLO the class actually got,
    not the population blend the headline p95 hides it in. A
    ``/monitoring/traces`` dump (``{"traces": [...]}``) works too: the
    pivot is derived from the roots' priority/ttft_ms attrs (ISSUE 20)."""
    out = sys.stdout if out is None else out
    d = _unwrap(doc)
    rows: list[tuple[str, dict]] = []
    se = d.get("slo_engine")
    if isinstance(se, dict):
        for arm in se.get("arms") or []:
            if arm.get("ttft_ms_by_class"):
                rows.append(
                    (f"slo_engine/{arm.get('name', '?')}",
                     arm["ttft_ms_by_class"])
                )
    sl = d.get("scenario_lab")
    if isinstance(sl, dict):
        for r in sl.get("matrix") or []:
            if r.get("ttft_ms_by_class"):
                rows.append(
                    (f"{r.get('scenario', '?')} x {r.get('fault', 'none')}",
                     r["ttft_ms_by_class"])
                )
    if isinstance(d.get("traces"), list):
        by_class = _classes_from_traces(d["traces"])
        if by_class:
            rows.append(("traces", by_class))
    if not rows:
        raise SystemExit(
            "no per-class TTFT data in this artifact "
            "(it wants ttft_ms_by_class rows, or a dump of "
            "/monitoring/traces)"
        )
    classes = sorted(
        {c for _, m in rows for c in m},
        key=lambda c: (_CLASS_ORDER.get(c, 9), c),
    )
    w = out.write
    lw = max(24, max(len(label) for label, _ in rows) + 2)
    w("p95 TTFT (ms) by priority class:\n")
    w(f"{'cell':<{lw}}" + "".join(f"{c:>16}" for c in classes) + "\n")
    for label, m in rows:
        parts = []
        for c in classes:
            v = m.get(c)
            parts.append(
                f"{v['p95']:.0f} (n={v.get('n', '?')})" if v else "-"
            )
        w(f"{label:<{lw}}" + "".join(f"{p:>16}" for p in parts) + "\n")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="render scenario-lab SLO scorecards from a JSON artifact"
    )
    ap.add_argument("artifact", help="BENCH_r*.json (or a bare section dump)")
    ap.add_argument("--metric", default="p95_ttft_ms", choices=METRICS,
                    help="matrix cell metric (default p95_ttft_ms)")
    ap.add_argument("--cells", action="store_true",
                    help="also print every cell's full scorecard row")
    ap.add_argument("--classes", action="store_true",
                    help="per-priority-class p95 TTFT pivot (slo_engine "
                         "arms + class-tagged lab cells)")
    args = ap.parse_args(argv)
    with open(args.artifact) as f:
        doc = json.load(f)
    if args.classes:
        render_classes(doc)
        return 0
    render(doc, metric=args.metric, cells=args.cells)
    return 0


if __name__ == "__main__":
    sys.exit(main())
