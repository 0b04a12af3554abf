"""Runtime load (runtime/model_runtime.py): seconds of the loads' own stages
before the window, ``tpusc_cold_stage_seconds_sum`` over the provider's fetch,
the artifact's read, the transfer and its sync (and the dequant stages of a
quantized artifact) but NOT ``compile_warmup``, whose seconds
``setup_trace_lower_s`` and ``setup_compile_s`` hold. Prints every stage, and
beside them ``load``: a load's wall less the builds on its thread."""

from setup_account import LOAD_STAGES, stage_seconds


def read(run):
    stages = stage_seconds(run)
    if stages is None or "load" not in stages:
        return None
    own = {s: stages[s] for s in LOAD_STAGES if s in stages}
    print("setup load: " + ", ".join(f"{s} {v:.2f}" for s, v in own.items())
          + f"; compile_warmup {stages.get('compile_warmup', 0.0):.2f} (left "
          f"to the builds); load (wall less builds) {stages['load']:.2f}",
          flush=True)
    return sum(own.values()), len(own)
