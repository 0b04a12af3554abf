"""Kernels (ops/attention.py): the paged decode kernel's share of its roofline
over the traced span. For every call the span held (``measure.
paged_decode_calls``: a ring boundary that ran a chunk stands for ``chunk x
layers`` calls, weighted by its share inside the span, at the live tokens the
client's records show at the boundary's middle and the ring's live lanes) the
least time the chip could take (its bytes over the HBM peak or its FLOPs over
the bf16 peak, whichever is larger; ``kernel_costs.py``, ``peaks.json``),
summed, over the device time of the kernel's events in the trace.

A mean over the span, not one instant: at two live lanes one long request
entering or leaving doubles the tokens of any single moment."""

import kernel_costs
from measure import kernel_time, paged_decode_calls


def read(run):
    found = kernel_time(run)
    calls = paged_decode_calls(run)
    if found is None or not calls:
        return None
    seconds, n = found
    mc = run.program_config
    peak = kernel_costs.peaks(run.device["kind"])
    least = {"memory": 0.0, "compute": 0.0}
    for tokens, lanes, count in calls:
        best = kernel_costs.roofline(kernel_costs.paged_decode(
            tokens, lanes, mc["n_heads"], mc["n_kv_heads"],
            mc["d_model"] // mc["n_heads"]), peak)
        least[best["bound"]] += count * best["seconds"]
    total = least["memory"] + least["compute"]
    expected = sum(c for _t, _l, c in calls)
    print(f"paged decode roofline: {expected:.0f} calls expected from the ring, "
          f"{n} in the trace; {sum(t * c for t, _l, c in calls) / expected:.0f} "
          f"live tokens over {sum(ln * c for _t, ln, c in calls) / expected:.2f} "
          f"lanes a call; least {total * 1e3:.2f} ms "
          f"({least['memory'] * 1e3:.2f} memory-bound, "
          f"{least['compute'] * 1e3:.2f} compute-bound) against "
          f"{seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, n
