"""Kernels (ops/attention.py): the paged decode kernel's share of its roofline
over the traced span in a model that has WINDOW layers beside its global
ones: ``paged_decode_roofline.py``'s arithmetic with the calls a step counted
over the layers that keep EVERY row (``kernel_costs_window.
global_decode_calls``: 2 of 8 in ``mellum2-codectx-mixed``; ``n_layers`` would
read four times the truth) and the head width the configuration states
(``head_dim``: the hidden size over the heads gives 72 there, the model's is
128). The window layers' calls run under another name in the trace
(``window_decode_roofline.py``).

A model with no window layer gives nothing (``paged_decode_roofline`` serves
it); a rehearsal shows a count only."""

import kernel_costs
import kernel_costs_window as costs
from measure import kernel_time


def read(run):
    calls = costs.global_decode_calls(run)
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
    least = {"memory": 0.0, "compute": 0.0}
    for tokens, lanes, count in calls:
        best = costs.roofline(kernel_costs.paged_decode(
            tokens, lanes, mc["n_heads"], mc["n_kv_heads"], mc["head_dim"]),
            peak)
        least[best["bound"]] += count * best["seconds"]
    total = least["memory"] + least["compute"]
    print(f"global decode roofline: {costs.global_layers(mc)} of "
          f"{len(mc['layer_types'])} layers keep every row; {expected:.0f} "
          f"calls expected from the ring, {n} in the trace; "
          f"{sum(t * c for t, _l, c in calls) / max(expected, 1e-9):.0f} live "
          f"tokens a call; least {total * 1e3:.2f} ms "
          f"({least['memory'] * 1e3:.2f} memory-bound, "
          f"{least['compute'] * 1e3:.2f} compute-bound) against "
          f"{seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, n
