"""Kernels (ops/attention.py): the paged decode kernel's share of its roofline
over the traced span in a model whose ONE full-attention layer's rows several
layers read: ``global_decode_roofline.py``'s arithmetic with ``chunk x
readers`` calls a boundary (8 in ``phi4flash-reasoning-steady``: the layer that
writes the rows and the seven cross-attention layers over it; ``n_layers``
would read four times the truth) on ONE arena layer whose row holds a pair of
KV heads, 128 lanes (``kernel_costs_sambay.shared_decode``). The window
layers' calls run under another name in the trace.

A model with no such layer gives nothing; a rehearsal shows a count only."""

import kernel_costs_sambay as costs
from measure import kernel_time


def read(run):
    calls = costs.shared_decode_calls(run)
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
    head = mc["d_model"] // mc["n_heads"]
    peak = costs.peaks(run.device["kind"])
    least = {"memory": 0.0, "compute": 0.0}
    for tokens, lanes, count in calls:
        best = costs.roofline(costs.shared_decode(
            tokens, lanes, mc["n_heads"], mc["n_kv_heads"], head), peak)
        least[best["bound"]] += count * best["seconds"]
    total = least["memory"] + least["compute"]
    print(f"shared kv decode roofline: "
          f"{costs.layer_counts(mc)['readers']} layers read one arena layer; "
          f"{expected:.0f} calls expected from the ring, {n} in the trace; "
          f"{sum(t * c for t, _l, c in calls) / max(expected, 1e-9):.0f} live "
          f"tokens a call; least {total * 1e3:.2f} ms "
          f"({least['memory'] * 1e3:.2f} memory-bound, "
          f"{least['compute'] * 1e3:.2f} compute-bound) against "
          f"{seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, n
