"""Expert layer (ops/moe.py): the grouped expert kernel's share of its
roofline over the traced span where the chip holds a SHARE of each layer's
experts and NOT every layer holds experts: ``moe_share_experts_roofline.py``'s
arithmetic (each call's rows from the program's own count, ring
``expert_rows_local``, its experts hit among those held, the costs from
``kernel_costs_moe.grouped_experts``) with the calls a step counted over the
layers that hold experts (``kernel_costs_heads.share_calls``: 8 of 9 in
``laguna-repoctx-steady``, whose layer 0 is dense; ``n_layers`` would read
9 / 8 of the truth).

A program without the share gives nothing; a rehearsal shows a count only."""

import kernel_costs_heads
import kernel_costs_mla


def read(run):
    if "n_experts_held" not in run.program_config:
        return None
    calls = kernel_costs_heads.share_calls(run)
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
    least = kernel_costs_heads.least_seconds(
        ((kernel_costs_mla.grouped_experts(rows, hit, mc["d_model"], mc["d_ff"]),
          count) for rows, hit, count in calls), peak)
    total = least["memory"] + least["compute"]
    print(f"share sparse experts roofline: "
          f"{kernel_costs_heads.expert_layers(mc)} of {mc['n_layers']} layers "
          f"hold experts; {expected:.0f} calls expected from the ring and the "
          f"records, {n:.0f} in the trace; least {total * 1e3:.2f} ms "
          f"({least['memory'] * 1e3:.2f} memory-bound, "
          f"{least['compute'] * 1e3:.2f} compute-bound) against "
          f"{seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, round(n)
