"""Kernels (ops/attention.py): the latent (MLA) decode kernel's share of its
roofline over the traced span. For every call the span held (``measure.
paged_decode_calls``: a ring boundary that ran a chunk stands for ``chunk x
layers`` calls, weighted by its share inside the span, at the live tokens the
client's records show at the boundary's middle and the ring's live lanes) the
least time the chip could take (``kernel_costs_mla.latent_decode``: 640 B a
live token plus queries and outputs over the HBM peak, or its FLOPs over the
bf16 peak, whichever is larger), summed, over the device time of the kernel's
events in the trace.

A program without the kernel gives nothing; a rehearsal shows a count only
(see ``latent_decode_ms_per_call.py``)."""

import kernel_costs_mla
from measure import paged_decode_calls


def read(run):
    if not kernel_costs_mla.is_latent(run):
        return None
    calls = paged_decode_calls(run)
    if calls is None:
        return None
    expected = sum(c for _t, _l, c in calls)
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(expected))
    found = kernel_costs_mla.kernel_time(run)
    if found is None or not calls:
        return None
    seconds, n = found
    mc = run.program_config
    peak = kernel_costs_mla.peaks(run.device["kind"])
    least = {"memory": 0.0, "compute": 0.0}
    for tokens, lanes, count in calls:
        best = kernel_costs_mla.roofline(kernel_costs_mla.latent_decode(
            tokens, lanes, mc["n_heads"], mc["kv_lora_rank"],
            mc["qk_rope_head_dim"]), peak)
        least[best["bound"]] += count * best["seconds"]
    total = least["memory"] + least["compute"]
    print(f"latent decode roofline: {expected:.0f} calls expected from the ring, "
          f"{n} in the trace; {sum(t * c for t, _l, c in calls) / expected:.0f} "
          f"live tokens over {sum(ln * c for _t, ln, c in calls) / expected:.2f} "
          f"lanes a call; least {total * 1e3:.2f} ms "
          f"({least['memory'] * 1e3:.2f} memory-bound, "
          f"{least['compute'] * 1e3:.2f} compute-bound) against "
          f"{seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, n
