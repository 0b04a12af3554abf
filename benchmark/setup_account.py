"""What the set-up readers share: the program's bring-up account
(``tfservingcache_tpu/utils/bring_up.py``) as an operator reads it, from the
Prometheus snapshot the harness takes at the window's START
(``run.before["prom"]``). Set-up is over by then, so the readers cost a run
nothing after the window and a traced run no second pass.

Three families and one histogram: ``tpusc_program_build_seconds_total{program,
stage}`` and ``tpusc_program_builds_total{program, cache}`` (what jax traced,
lowered and compiled, by program), ``tpusc_device_bytes{stage, what}`` (the
allocator's count at a stage's end) and ``tpusc_cold_stage_seconds{stage}``,
whose stages ``server_start`` / ``load`` / ``engine_build`` / ``first_run``
are the account's. A program older than the account has none of them: every
function here then gives None and its reader is left out.

The harness's own reference runs in the server's process, so the program's
listeners book ITS programs too. They are told apart by when a counter's label
set was first written (the ``_created`` sample): inside the harness's checks
(the last ``checks_s`` before the snapshot) the time is already in
``checks_s``.
"""

from __future__ import annotations

import re

BUILD_SECONDS = "tpusc_program_build_seconds_total"
BUILD_CREATED = "tpusc_program_build_seconds_created"
BUILDS = "tpusc_program_builds_total"
STAGE_SECONDS = "tpusc_cold_stage_seconds_sum"
DEVICE_BYTES = "tpusc_device_bytes"
# a load's own stages (runtime/model_runtime.py, cache/manager.py) but
# ``compile_warmup``, whose seconds are a program's build
LOAD_STAGES = ("provider_fetch", "artifact_read", "host_dequant",
               "device_transfer", "device_dequant", "transfer_sync")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def samples(prom: dict, name: str) -> list[tuple[dict, float]]:
    """[(labels, value)] of one sample name (up to ``{`` or the key's end)."""
    return [(dict(_LABEL.findall(key)), value) for key, value in prom.items()
            if key == name or key.startswith(name + "{")]


def snapshot(run) -> dict:
    return (run.before or {}).get("prom") or {}


def stage_seconds(run) -> dict[str, float] | None:
    """{stage: summed seconds} of ``tpusc_cold_stage_seconds``, or None where
    the account's own stages are not among them."""
    out = {lab.get("stage", ""): v for lab, v in samples(snapshot(run), STAGE_SECONDS)}
    return out if "server_start" in out else None


def checks_began(run) -> float:
    """Wall time at which the harness's checks (its reference, in this
    process) began: ``checks_s`` before the snapshot."""
    return run.before.get("t_wall", 0.0) - float(run.setup_split.get("checks_s", 0.0))


def build_seconds(run, inside_checks: bool = False) -> dict[str, dict[str, float]] | None:
    """{program: {stage: seconds}} booked before the window, the label sets
    first written during the harness's checks left out (or, with
    ``inside_checks``, those alone); None where the family is absent."""
    prom = snapshot(run)
    rows = samples(prom, BUILD_SECONDS)
    if not rows:
        return None
    born = {(lab.get("program"), lab.get("stage")): v
            for lab, v in samples(prom, BUILD_CREATED)}
    cut = checks_began(run)
    out: dict[str, dict[str, float]] = {}
    for lab, seconds in rows:
        key = (lab.get("program"), lab.get("stage"))
        if (born.get(key, 0.0) >= cut) == inside_checks:
            out.setdefault(key[0], {})[key[1]] = seconds
    return out


def total(builds: dict[str, dict[str, float]], *stages: str) -> float:
    return sum(v for by_stage in builds.values()
               for stage, v in by_stage.items() if stage in stages)


def dearest(builds: dict[str, dict[str, float]], *stages: str, n: int = 5) -> str:
    rows = sorted(((sum(v for s, v in by.items() if s in stages), name)
                   for name, by in builds.items()), reverse=True)[:n]
    return ", ".join(f"{name} {sec:.2f}" for sec, name in rows if sec > 0)


def setup_parts(run) -> dict[str, float] | None:
    """The whole table: every part of ``run.setup_s`` the harness or the
    program names, and ``unexplained``, what no span covers. The parts count a
    second once: a stage's seconds are already less the builds on its thread,
    ``compile_warmup`` is left to the builds, what a load's stages ran beside
    one another is taken off again, and a build inside the harness's checks
    is left to ``checks_s``."""
    stages, builds = stage_seconds(run), build_seconds(run)
    if stages is None or builds is None:
        return None
    split = run.setup_split
    parts = {
        # imports, the device, the weights: all before the server's start
        "harness_before_server_s": float(split.get("server_up_s", 0.0))
        - stages["server_start"],
        "server_start_s": stages["server_start"],
        "load_s": sum(stages.get(s, 0.0) for s in LOAD_STAGES),
        # a pipelined load compiles beside its transfer: those seconds are
        # under the builds below AND inside the transfer's wall
        "load_overlap_s": -stages.get("load_overlap", 0.0),
        "engine_build_s": stages.get("engine_build", 0.0),
        "trace_s": total(builds, "trace"),
        "lower_s": total(builds, "lower"),
        "compile_s": total(builds, "compile"),
        "cache_load_s": total(builds, "cache_load"),
        "first_run_s": stages.get("first_run", 0.0),
        "harness_checks_s": float(split.get("checks_s", 0.0)),
    }
    parts["unexplained_s"] = run.setup_s - sum(parts.values())
    return parts
