"""Kernels (ops/attention.py): a WINDOW layer's paged decode kernel's share of
its roofline over the traced span in a model whose query heads go by layer:
``window_decode_roofline.py``'s arithmetic (the calls and their lanes' tokens
from ``kernel_costs_window.decode_calls``, the kernel's events by its name)
with each call's costs reckoned at the window layers' OWN head count
(``kernel_costs_heads.window_decode``: 72 over 8 KV heads in
``laguna-repoctx-steady``, where the program's one ``n_heads`` says 48). A
padded GQA group is an implementation's choice: the roofline counts 9.

A program without heads a layer or without the kernel gives nothing; a
rehearsal shows a count only."""

import kernel_costs_heads as costs
import kernel_costs_window as window


def read(run):
    if not costs.has_heads_a_layer(run.program_config):
        return None
    calls = window.decode_calls(run)
    if calls is None:
        return None
    expected = sum(c for _t, c in calls)
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(expected))
    found = window.kernel_time(run, window.DECODE_KERNEL)
    if found is None or not calls:
        return None
    seconds, n = found
    mc = run.program_config
    peak = costs.peaks(run.device["kind"])
    page = int(run.server.get("serving", {}).get("kv_page_tokens", 16))
    least = costs.least_seconds(
        ((costs.window_decode(tokens, mc, page), count)
         for tokens, count in calls), peak)
    total = least["memory"] + least["compute"]
    lanes = sum(len(t) * c for t, c in calls) / max(expected, 1e-9)
    print(f"heads window decode roofline: {costs.kind_heads(mc, costs.SLIDING)} "
          f"query heads over {mc['n_kv_heads']} KV heads; {expected:.0f} calls "
          f"expected from the ring, {n} in the trace; {lanes:.2f} lanes a "
          f"call; least {total * 1e3:.2f} ms ({least['memory'] * 1e3:.2f} "
          f"memory-bound, {least['compute'] * 1e3:.2f} compute-bound) against "
          f"{seconds * 1e3:.2f} ms measured", flush=True)
    return 100.0 * total / seconds, n
