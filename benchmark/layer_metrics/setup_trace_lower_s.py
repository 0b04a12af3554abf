"""Model step (models/generation.py): seconds Python spent tracing and
lowering the programs set-up built, ``tpusc_program_build_seconds_total
{stage="trace"}`` + ``{stage="lower"}`` at the window's start. A warm
compilation cache does not take this share away: a kernel's body is traced and
lowered on every start."""

from setup_account import build_seconds, dearest, total


def read(run):
    builds = build_seconds(run)
    if builds is None:
        return None
    print(f"setup trace + lower: trace {total(builds, 'trace'):.2f} s, lower "
          f"{total(builds, 'lower'):.2f} s over {len(builds)} programs; the "
          f"dearest: {dearest(builds, 'trace', 'lower')}", flush=True)
    return total(builds, "trace", "lower"), len(builds)
