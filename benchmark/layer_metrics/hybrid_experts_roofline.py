"""Expert layer (ops/moe.py): the grouped expert kernel's share of its
roofline over the traced span, for a model whose layers are of several kinds:
``moe_experts_roofline.py``'s arithmetic with the calls a step counted over
the layers that HOLD experts (``kernel_costs_hybrid.traced_calls``: 12 of 14
in ``lfm2-longgen-steady``), not ``n_layers``.

A program without the kernel, or a model that does not say what its layers
are, gives nothing; a rehearsal shows a count only."""

import kernel_costs_hybrid


def read(run):
    calls = kernel_costs_hybrid.traced_calls(run)
    if calls is None:
        return None
    expected = sum(c for _r, _e, c in calls)
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(expected))
    found = kernel_costs_hybrid.kernel_time(run)
    if found is None or not calls:
        return None
    seconds, n = found
    mc = run.program_config
    peak = kernel_costs_hybrid.peaks(run.device["kind"])
    least = {"memory": 0.0, "compute": 0.0}
    for rows, hit, count in calls:
        best = kernel_costs_hybrid.roofline(kernel_costs_hybrid.grouped_experts(
            rows, hit, mc["d_model"], mc["d_ff"]), peak)
        least[best["bound"]] += count * best["seconds"]
    total = least["memory"] + least["compute"]
    kinds = kernel_costs_hybrid.layer_counts(mc)
    print(f"hybrid experts roofline: {kinds['moe']} of {len(mc['layer_types'])} "
          f"layers hold experts; {expected:.0f} calls expected from the ring "
          f"and the records, {n:.0f} in the trace; least {total * 1e3:.2f} ms "
          f"({least['memory'] * 1e3:.2f} memory-bound, "
          f"{least['compute'] * 1e3:.2f} compute-bound) against "
          f"{seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, round(n)
