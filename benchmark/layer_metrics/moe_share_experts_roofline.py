"""Expert layer (ops/moe.py): the grouped expert kernel's share of its
roofline over the traced span where the chip holds a SHARE of each layer's
experts. ``moe_experts_roofline`` reckons ``active x top_k`` rows a decode
call and every expert reachable; a quarter share receives a quarter of those
rows and can hit only the experts it holds, so that reader would read four
times too high. This one takes each call's rows from the program's own count
(ring ``expert_rows_local``) and its experts hit among those held
(``kernel_costs_mla.share_calls``), the costs from ``kernel_costs_moe.
grouped_experts`` unchanged, over the device time of the kernel's events.

A program without the share gives nothing; a rehearsal shows a count only."""

import kernel_costs_mla


def read(run):
    calls = kernel_costs_mla.share_calls(run)
    if calls is None:
        return None
    expected = sum(c for _r, _e, c in calls)
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(expected))
    found = kernel_costs_mla.experts_kernel_time(run)
    if found is None or not calls:
        return None
    seconds, n = found
    mc = run.program_config
    peak = kernel_costs_mla.peaks(run.device["kind"])
    least = {"memory": 0.0, "compute": 0.0}
    for rows, hit, count in calls:
        best = kernel_costs_mla.roofline(kernel_costs_mla.grouped_experts(
            rows, hit, mc["d_model"], mc["d_ff"]), peak)
        least[best["bound"]] += count * best["seconds"]
    total = least["memory"] + least["compute"]
    print(f"moe share experts roofline: {expected:.0f} calls expected from the "
          f"ring and the records, {n:.0f} in the trace; least {total * 1e3:.2f} ms "
          f"({least['memory'] * 1e3:.2f} memory-bound, "
          f"{least['compute'] * 1e3:.2f} compute-bound) against "
          f"{seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, round(n)
