"""Arena (SlotDecodeState): the device bytes nobody owns: the highest
``tpusc_device_bytes{what="peak"}`` the program read at the end of its own
stages (``load``, ``engine_build``, each ``first_run``) less what it answers
for (``tpusc_hbm_bytes_in_use``: the weights; ``tpusc_kv_arena_bytes``;
``tpusc_lane_state_bytes``), all at the window's start. Bytes nobody owns are
pages the arena cannot have. None on a backend without allocator statistics.

Prints what is owned by owner, the allocator's bytes by stage, and the
harness's end-of-run ``peak_bytes_in_use`` beside them: the difference is what
the float32 reference (same process) adds. ``reserved`` is the scratch of the
loaded program with the largest temporaries, which neither ``in_use`` nor
``peak`` ever counts on a v5e (PERF.md section 6, PR 51)."""

from client import metric_sum
from setup_account import DEVICE_BYTES, samples, snapshot


def read(run):
    prom = snapshot(run)
    by_stage: dict[str, dict[str, float]] = {}
    for lab, v in samples(prom, DEVICE_BYTES):
        by_stage.setdefault(lab.get("stage", "?"), {})[lab.get("what", "?")] = v
    peaks = {s: w["peak"] for s, w in by_stage.items() if "peak" in w}
    if not peaks:
        return None
    owned = {"weights": metric_sum(prom, "tpusc_hbm_bytes_in_use"),
             "arenas": metric_sum(prom, "tpusc_kv_arena_bytes"),
             "lane_state": metric_sum(prom, "tpusc_lane_state_bytes")}
    import jax

    end = max((int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices()), default=0)
    print("device bytes: owned " + ", ".join(f"{k} {int(v)}" for k, v in owned.items())
          + f" = {int(sum(owned.values()))}; by stage (in_use / peak / reserved): "
          + "; ".join(f"{s} {int(w.get('in_use', 0))} / {int(w.get('peak', 0))} / "
                      f"{int(w.get('reserved', 0))}" for s, w in sorted(by_stage.items()))
          + f"; the harness's end-of-run peak_bytes_in_use {end}", flush=True)
    return max(peaks.values()) - sum(owned.values()), len(peaks)
