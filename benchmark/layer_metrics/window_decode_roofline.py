"""Kernels (ops/attention.py): a WINDOW layer's paged decode kernel's share of
its roofline over the traced span. For every call the span held
(``kernel_costs_window.decode_calls``: a ring boundary that ran a chunk stands
for ``chunk x window layers`` calls, weighted by its share inside the span, at
the tokens each live lane holds at the boundary's middle) the least time the
chip could take (``kernel_costs_window.window_decode``: the pages that hold a
lane's last ``min(tokens, window)`` tokens at 2 KiB a token, plus queries and
outputs, over the HBM peak, or its FLOPs over the bf16 peak, whichever is
larger), summed, over the device time of the kernel's events in the trace.

A program without the kernel gives nothing; a rehearsal shows a count only
(see ``window_decode_ms_per_call.py``)."""

import kernel_costs_window as costs


def read(run):
    calls = costs.decode_calls(run)
    if calls is None:
        return None
    expected = sum(c for _t, c in calls)
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(expected))
    found = costs.kernel_time(run, costs.DECODE_KERNEL)
    if found is None or not calls:
        return None
    seconds, n = found
    mc = run.program_config
    peak = costs.peaks(run.device["kind"])
    page = int(run.server.get("serving", {}).get("kv_page_tokens", 16))
    least = {"memory": 0.0, "compute": 0.0}
    for tokens, count in calls:
        best = costs.roofline(costs.window_decode(
            tokens, mc["sliding_window"], page, mc["n_heads"],
            mc["n_kv_heads"], mc["head_dim"]), peak)
        least[best["bound"]] += count * best["seconds"]
    total = least["memory"] + least["compute"]
    lanes = sum(len(t) * c for t, c in calls) / max(expected, 1e-9)
    print(f"window decode roofline: {expected:.0f} calls expected from the "
          f"ring, {n} in the trace; {lanes:.2f} lanes a call; least "
          f"{total * 1e3:.2f} ms ({least['memory'] * 1e3:.2f} memory-bound, "
          f"{least['compute'] * 1e3:.2f} compute-bound) against "
          f"{seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, n
