"""Scenario x fault cell runner: replay a compiled schedule, emit an SLO
scorecard row.

``run_cell`` is deliberately harness-agnostic: the caller
(tests/test_scenario_lab.py, or a drive script) supplies a
``generate_fn(ScheduledRequest) -> dict`` closure over whatever stack it
built, plus optional Metrics / census hooks. The runner owns only the
open-loop replay (one thread per request, sleeping to its compiled arrival
offset), fault arming, and the scorecard math — so the same cell definition
runs against an engine-only stub stack and a full manager+runtime stack.

Every scorecard row stamps ``kernel_active`` and ``platform``: a kernel
arm once ran interpret-mode on CPU in silence and its tok/s deltas were
non-evidence — a matrix row without the stamp can no longer exist.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable

from tfservingcache_tpu.lab import faults as lab_faults
from tfservingcache_tpu.lab.workload import ScheduledRequest, WorkloadSpec
from tfservingcache_tpu.utils.flight_recorder import RECORDER
from tfservingcache_tpu.utils.logging import get_logger

log = get_logger("lab.scenario")

__all__ = [
    "default_scenarios",
    "default_faults",
    "run_cell",
    "SCORECARD_FIELDS",
]

# the scorecard schema, in render order (tools/slo_report.py and the
# OBSERVABILITY.md "Scenario lab" section mirror this list)
SCORECARD_FIELDS = (
    "scenario", "fault", "requests", "completed", "lost", "recovered",
    "p50_ttft_ms", "p95_ttft_ms", "p99_ttft_ms", "tok_s", "wall_s",
    "tokens_out", "goodput", "cold_miss_rate", "fault_injections",
    "preemptions", "conservation_ok", "kernel_active", "platform",
)


def default_scenarios(
    tenants: tuple[str, ...] = ("lm",), requests: int = 16, max_new: int = 10,
) -> list[WorkloadSpec]:
    """The standard 4-scenario row set (the chaos suite's regression cells
    and the committed BENCH_r11.json sample are these workloads)."""
    multi = tenants if len(tenants) > 1 else tenants * 2
    return [
        WorkloadSpec(
            name="steady_poisson", tenants=tenants[:1], arrival="poisson",
            rate_rps=24.0, requests=requests, max_new=max_new,
            prompt_lens=(6, 12, 24),
        ),
        WorkloadSpec(
            name="zipf_burst", tenants=multi, zipf_s=1.1, arrival="burst",
            burst_size=max(2, requests // 4), burst_gap_s=0.3,
            requests=requests, max_new=max_new, prompt_lens=(8, 16),
        ),
        WorkloadSpec(
            name="flash_crowd", tenants=multi, zipf_s=0.8,
            arrival="flash_crowd", rate_rps=12.0, flash_at_s=0.4,
            flash_width_s=0.05, flash_share=0.6, requests=requests,
            max_new=max_new, prompt_lens=(6, 12),
        ),
        WorkloadSpec(
            name="multi_turn", tenants=tenants[:1], arrival="poisson",
            rate_rps=16.0, requests=requests, max_new=max_new, turns=4,
            turn_gap_s=0.15, prompt_lens=(8,), turn_suffix_tokens=8,
        ),
    ]


def default_faults(duration_s: float = 0.4) -> list[lab_faults.FaultSpec | None]:
    """The standard fault column set: a no-fault baseline plus one spec per
    armed kind. ``after`` offsets put the firing mid-run, not at t=0 — a
    kill before any admission exercises nothing."""
    return [
        None,
        lab_faults.FaultSpec(kind="kill_engine", after=3, count=1),
        lab_faults.FaultSpec(
            kind="freeze_scheduler", after=2, count=1, duration_s=duration_s
        ),
        lab_faults.FaultSpec(
            kind="stall_store", after=0, count=1, duration_s=duration_s
        ),
        lab_faults.FaultSpec(kind="drop_peer", after=0, count=0),
    ]


def _family_sum(metrics: Any, family: str) -> float:
    """Sum a family's samples across all label sets (counters expose
    ``<family>_total`` samples; gauges expose the bare name)."""
    if metrics is None:
        return 0.0
    total = 0.0
    for mf in metrics.registry.collect():
        if mf.name != family:
            continue
        for s in mf.samples:
            if s.name in (family, family + "_total"):
                total += s.value
    return total


def _pct(sorted_ms: list[float], q: float) -> float:
    if not sorted_ms:
        return 0.0
    i = min(len(sorted_ms) - 1, int(round(q * (len(sorted_ms) - 1))))
    return sorted_ms[i]


def run_cell(
    schedule: list[ScheduledRequest],
    generate_fn: Callable[[ScheduledRequest], dict],
    *,
    scenario_name: str = "",
    fault: "lab_faults.FaultSpec | None" = None,
    metrics: Any = None,
    census_fn: Callable[[], bool] | None = None,
    kernel_active: bool = False,
    platform: str | None = None,
) -> dict[str, Any]:
    """Run one scenario x fault cell and return its scorecard row.

    ``generate_fn`` must return ``{"ok": bool, "ttft_s": float | None,
    "tokens": int, "error": str | None}`` per request and never raise (wrap
    and report — a lost request is a *measurement*, not a harness crash).
    ``census_fn`` returns the page-conservation verdict after the replay
    (None entry in the row when the stack has no paged state to census).
    """
    if platform is None:
        try:
            import jax

            platform = jax.default_backend()
        except Exception:  # noqa: BLE001 - stub stacks without jax
            platform = "unknown"

    base_recovered = _family_sum(metrics, "tpusc_requests_recovered")
    base_preempted = _family_sum(metrics, "tpusc_gen_preemptions")
    base_injected = _family_sum(metrics, "tpusc_fault_injected")
    base_lookups = _family_sum(metrics, "tfservingcache_cache")
    base_misses = _family_sum(metrics, "tfservingcache_cache_misses")
    base_faults = RECORDER.fault_counts()

    results: list[dict | None] = [None] * len(schedule)

    def _one(i: int, sr: ScheduledRequest, t0: float) -> None:
        delay = t0 + sr.at_s - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        try:
            results[i] = generate_fn(sr)
        except BaseException as e:  # noqa: BLE001 - a lost request is data
            results[i] = {"ok": False, "ttft_s": None, "tokens": 0,
                          "error": repr(e)}

    if fault is not None:
        # arm a FRESH copy: a FaultSpec's visits/fired tallies are runtime
        # state, and a spec list reused across a matrix must fire in every
        # cell, not just the first one that exhausts its count
        lab_faults.arm(
            [dataclasses.replace(fault, visits=0, fired=0)], metrics=metrics
        )
    try:
        t0 = time.monotonic()
        threads: list[threading.Thread] = []
        for i, sr in enumerate(schedule):
            t = threading.Thread(target=_one, args=(i, sr, t0), daemon=True)
            threads.append(t)
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
    finally:
        if fault is not None:
            lab_faults.disarm()

    rows = [r if r is not None else
            {"ok": False, "ttft_s": None, "tokens": 0, "error": "no result"}
            for r in results]
    ok_rows = [r for r in rows if r.get("ok")]
    ttfts = sorted(
        r["ttft_s"] * 1e3 for r in ok_rows if r.get("ttft_s") is not None
    )
    tokens_out = sum(int(r.get("tokens", 0)) for r in ok_rows)
    lookups = _family_sum(metrics, "tfservingcache_cache") - base_lookups
    misses = _family_sum(metrics, "tfservingcache_cache_misses") - base_misses
    injected_now = RECORDER.fault_counts()
    injected = sum(injected_now.values()) - sum(base_faults.values())
    if metrics is not None:
        # prefer the counter when a registry is in play (it survives a
        # recorder shared across concurrent cells)
        injected = int(
            _family_sum(metrics, "tpusc_fault_injected") - base_injected
        ) or injected
    engine = RECORDER.engine_stats()
    row = {
        "scenario": scenario_name,
        "fault": fault.kind if fault is not None else "none",
        "requests": len(schedule),
        "completed": len(ok_rows),
        "lost": len(rows) - len(ok_rows),
        "recovered": int(
            _family_sum(metrics, "tpusc_requests_recovered") - base_recovered
        ),
        "p50_ttft_ms": round(_pct(ttfts, 0.50), 1),
        "p95_ttft_ms": round(_pct(ttfts, 0.95), 1),
        "p99_ttft_ms": round(_pct(ttfts, 0.99), 1),
        "tok_s": round(tokens_out / wall, 1) if wall > 0 else 0.0,
        "wall_s": round(wall, 2),
        "tokens_out": tokens_out,
        "goodput": round(float(engine.get("goodput", 1.0)), 4),
        "cold_miss_rate": round(misses / lookups, 4) if lookups else 0.0,
        "fault_injections": int(injected),
        "preemptions": int(
            _family_sum(metrics, "tpusc_gen_preemptions") - base_preempted
        ),
        "conservation_ok": census_fn() if census_fn is not None else None,
        "kernel_active": bool(kernel_active),
        "platform": platform,
    }
    errs = sorted({str(r.get("error")) for r in rows if not r.get("ok")})
    if errs:
        row["errors"] = errs[:4]
    log.info(
        "cell %s x %s: %d/%d ok, p95 ttft %.0f ms, %d recovered",
        row["scenario"], row["fault"], row["completed"], row["requests"],
        row["p95_ttft_ms"], row["recovered"],
    )
    return row
