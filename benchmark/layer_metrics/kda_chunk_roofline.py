"""Model step (models/generation.py): the chunked delta rule's kernel for a
decay a CHANNEL (Kimi delta attention), its share of its roofline over the
traced span: ``gdn_chunk_roofline``'s twin. For every prefill the span held
(``kernel_costs_kda_chunk.prefills``: the client's records matched to the
ring's admitting boundaries, at their TRUE prompt lengths, so a bucket's pad
chunks count against the number, weighted by the prefill's share inside the
span) the least time the chip could take for one layer's rule (the lane's
state once in and once out and a token's operands, log-decay and output over
the HBM peak, or the chunk products' FLOPs over the bf16 peak, whichever is
larger; ``kernel_costs_kda_chunk.chunk_rule``, ``peaks.json``), times the
model's linear-attention layers, summed, over the device time of the kernel's
events in the trace (``delta_channel_chunk_kernel``, by name; they lie in
``_slot_prefill_jit``). It prints the calls expected (a layer of a prefill)
against the events in the trace: a prompt whose mixer runs a block of tokens
at a time makes an event a block, so the events are the calls or more, and
none where a prefill's layers took the block form.

A model whose decay is not a channel's (``kernel_costs_kda.is_kda``), a span
that held no prefill, or a program without the kernel (every program before
PR 50) gives nothing; a rehearsal shows the calls expected as a count."""

import kernel_costs_kda_chunk as costs


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
    print(f"kda chunk roofline: {expected:.1f} calls expected from the ring and "
          f"the records ({len(held)} prefills of "
          f"{sum(t * s for t, s in held) / sum(s for _t, s in held):.0f} true "
          f"tokens x {layers} layers), {events} events in the trace; least "
          f"{total * 1e3:.2f} ms ({least['memory'] * 1e3:.2f} memory-bound, "
          f"{least['compute'] * 1e3:.2f} compute-bound) against "
          f"{seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, events
