"""Kernels (ops/attention.py): the WINDOW flash kernel's share of its roofline
over the traced span: a window layer's attention over a fresh prompt
(``flash_window_kernel``). For every prefill the span held
(``kernel_costs_window.flash_calls``: a request is matched by its first
token's time to the ring boundary that admitted it and gives ``window
layers`` calls at its prompt's length, weighted by the share of that
boundary's prefill time inside the span) the least time the chip could take
(``kernel_costs_window.window_flash``: ``4 x heads x head_dim x sum_i min(i +
1, window)`` FLOPs over the bf16 peak, or its q, k, v and output over the HBM
peak, whichever is larger), summed, over the device time of the kernel's
events in the trace. Nothing is scaled to the events the trace holds: the
line it prints shows the calls expected against the calls traced, and a
miscount shows in the share.

The kernel runs on the prompt's power-of-two bucket; the algorithm needs the
prompt. A program without the kernel, or a span without a prefill, gives
nothing; a rehearsal shows the calls as a count."""

import kernel_costs_window as costs


def read(run):
    calls = costs.flash_calls(run)
    if not calls:
        return None
    expected = sum(c for _s, c in calls)
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(expected))
    found = costs.kernel_time(run, costs.FLASH_KERNEL)
    if found is None:
        return None
    seconds, n = found
    mc = run.program_config
    peak = costs.peaks(run.device["kind"])
    least = {"memory": 0.0, "compute": 0.0}
    for s, count in calls:
        best = costs.roofline(costs.window_flash(
            s, mc["sliding_window"], mc["n_heads"], mc["n_kv_heads"],
            mc["head_dim"]), peak)
        least[best["bound"]] += count * best["seconds"]
    total = least["memory"] + least["compute"]
    print(f"window prefill roofline: {expected:.1f} calls expected from the "
          f"ring and the records ({len(calls)} prefills), {n} in the trace; "
          f"least {total * 1e3:.2f} ms ({least['memory'] * 1e3:.2f} "
          f"memory-bound, {least['compute'] * 1e3:.2f} compute-bound) "
          f"against {seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, n
