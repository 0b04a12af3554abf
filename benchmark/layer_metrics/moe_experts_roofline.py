"""Kernels (ops/moe.py): the grouped expert kernel's share of its roofline
over the traced span. For every call the span held (``kernel_costs_moe.
traced_calls``: decode steps from the ring with their live rows and experts
hit, prefills from the client's records) the least time the chip could take
(the hit experts' weights and the rows over the HBM peak, or the FLOPs over
the bf16 peak, whichever is larger; ``kernel_costs_moe.py``, ``peaks.json``),
summed, over the device time of the kernel's events in the trace.

A program without the kernel gives nothing; a rehearsal shows a count only
(see ``moe_experts_ms_per_call.py``)."""

import kernel_costs_moe


def read(run):
    calls = kernel_costs_moe.traced_calls(run)
    if calls is None:
        return None
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(sum(c for _r, _e, c in calls)))
    found = kernel_costs_moe.kernel_time(run)
    if found is None or not calls:
        return None
    seconds, n = found
    mc = run.program_config
    peak = kernel_costs_moe.peaks(run.device["kind"])
    least = {"memory": 0.0, "compute": 0.0}
    for rows, hit, count in calls:
        best = kernel_costs_moe.roofline(kernel_costs_moe.grouped_experts(
            rows, hit, mc["d_model"], mc["d_ff"]), peak)
        least[best["bound"]] += count * best["seconds"]
    total = least["memory"] + least["compute"]
    bound = max(least, key=least.get)
    expected = sum(c for _r, _e, c in calls)
    print(f"moe experts roofline: {expected:.0f} calls expected from the ring "
          f"and the records, {n:.0f} in the trace; least {total * 1e3:.2f} ms "
          f"({least['memory'] * 1e3:.2f} memory-bound, "
          f"{least['compute'] * 1e3:.2f} compute-bound: {bound}-bound) "
          f"against {seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, round(n)
