"""Kernels (ops/attention.py): the paged decode kernel's share of its roofline
over the traced span in the GLOBAL layers of a model whose query heads go by
layer: ``global_decode_roofline.py``'s arithmetic (the calls over the layers
that keep every row, ``kernel_costs_window.global_decode_calls``; the kernel's
events by ``measure.kernel_time``) with each call's costs at the global
layers' OWN head count (``kernel_costs_heads.global_decode``: 48 over 8 KV
heads in ``laguna-repoctx-steady``, group 6, counted as 6 whatever a kernel
pads).

A program without heads a layer or without the kernel gives nothing; a
rehearsal shows a count only."""

import kernel_costs_heads as costs
import kernel_costs_window as window
from measure import kernel_time


def read(run):
    if not costs.has_heads_a_layer(run.program_config):
        return None
    calls = window.global_decode_calls(run)
    if calls is None:
        return None
    expected = sum(c for _t, _l, c in calls)
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(expected))
    found = kernel_time(run)
    if found is None or not calls:
        return None
    seconds, n = found
    mc = run.program_config
    peak = costs.peaks(run.device["kind"])
    least = costs.least_seconds(
        ((costs.global_decode(tokens, lanes, mc), count)
         for tokens, lanes, count in calls), peak)
    total = least["memory"] + least["compute"]
    print(f"heads global decode roofline: {costs.kind_heads(mc, costs.FULL)} "
          f"query heads over {mc['n_kv_heads']} KV heads; {expected:.0f} calls "
          f"expected from the ring, {n} in the trace; "
          f"{sum(t * c for t, _l, c in calls) / max(expected, 1e-9):.0f} live "
          f"tokens a call; least {total * 1e3:.2f} ms "
          f"({least['memory'] * 1e3:.2f} memory-bound, "
          f"{least['compute'] * 1e3:.2f} compute-bound) against "
          f"{seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, n
