"""Arena (SlotDecodeState): seconds the engines' slot states took to build
before the window (the arenas, the lane state, the window rings, waited for
once), ``tpusc_cold_stage_seconds_sum{stage="engine_build"}``, less the
builds on that thread."""

from setup_account import stage_seconds


def read(run):
    stages = stage_seconds(run)
    if stages is None or "engine_build" not in stages:
        return None
    return stages["engine_build"]
