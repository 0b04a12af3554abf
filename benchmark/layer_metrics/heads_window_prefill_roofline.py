"""Kernels (ops/attention.py): the WINDOW flash kernel's share of its roofline
over the traced span in a model whose query heads go by layer:
``window_prefill_roofline.py``'s arithmetic (the prefills the span held and
their share inside it, ``kernel_costs_window.flash_calls``; the kernel's
events by its name) with each call's FLOPs and bytes at the window layers' OWN
head count (``kernel_costs_heads.window_flash``: 72 heads over a window of 512
in ``laguna-repoctx-steady``).

A program without heads a layer or without the kernel, or a span without a
prefill, gives nothing; a rehearsal shows the calls as a count."""

import kernel_costs_heads as costs
import kernel_costs_window as window


def read(run):
    if not costs.has_heads_a_layer(run.program_config):
        return None
    calls = window.flash_calls(run)
    if not calls:
        return None
    expected = sum(c for _s, c in calls)
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(expected))
    found = window.kernel_time(run, window.FLASH_KERNEL)
    if found is None:
        return None
    seconds, n = found
    mc = run.program_config
    peak = costs.peaks(run.device["kind"])
    least = costs.least_seconds(
        ((costs.window_flash(s, mc), count) for s, count in calls), peak)
    total = least["memory"] + least["compute"]
    print(f"heads window prefill roofline: {expected:.1f} calls expected from "
          f"the ring and the records ({len(calls)} prefills), {n} in the "
          f"trace; least {total * 1e3:.2f} ms ({least['memory'] * 1e3:.2f} "
          f"memory-bound, {least['compute'] * 1e3:.2f} compute-bound) against "
          f"{seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, n
