"""Model step (models/generation.py): the chunked delta rule's kernel's share
of its roofline over the traced span. For every prefill the span held
(``kernel_costs_gdn_chunk.prefills``: the client's records matched to the
ring's admitting boundaries, at their TRUE prompt lengths, so a bucket's pad
chunks count against the number, weighted by the prefill's share inside the
span) the least time the chip could take for one layer's rule (the lane's
state once in and once out and a token's operands and output over the HBM
peak, or the chunk products' FLOPs over the bf16 peak, whichever is larger;
``kernel_costs_gdn_chunk.chunk_rule``, ``peaks.json``), times the model's
linear-attention layers, summed, over the device time of the kernel's events
in the trace (``delta_chunk_kernel``, by name). It prints the calls expected
against the events in the trace: that ratio is the engagement count, 1.0 where
every prefill's linear layers took the kernel.

A model with no such layer, a span that held no prefill, or a program without
the kernel (every program before PR 47) gives nothing; a rehearsal shows the
calls expected as a count."""

import kernel_costs_gdn_chunk as costs


def read(run):
    held = costs.prefills(run)
    if not held:
        return None
    mc = run.program_config
    layers = costs.layer_counts(mc)["linear"]
    expected = layers * sum(share for _tokens, share in held)
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(expected))
    found = costs.kernel_time(run)
    if found is None:
        return None
    seconds, events = found
    if seconds <= 0:
        return None
    peak = costs.peaks(run.device["kind"])
    least = {"memory": 0.0, "compute": 0.0}
    for tokens, share in held:
        best = costs.roofline(costs.chunk_rule(
            tokens, mc["linear_heads"], mc["linear_key_dim"],
            mc["linear_value_dim"]), peak)
        least[best["bound"]] += layers * share * best["seconds"]
    total = least["memory"] + least["compute"]
    print(f"gdn chunk roofline: {expected:.1f} calls expected from the ring and "
          f"the records ({len(held)} prefills of "
          f"{sum(t * s for t, s in held) / sum(s for _t, s in held):.0f} true "
          f"tokens x {layers} layers), {events} in the trace; least "
          f"{total * 1e3:.2f} ms ({least['memory'] * 1e3:.2f} memory-bound, "
          f"{least['compute'] * 1e3:.2f} compute-bound) against "
          f"{seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, events
