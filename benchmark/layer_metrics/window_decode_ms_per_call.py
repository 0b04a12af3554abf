"""Kernels (ops/attention.py): device milliseconds per call of a WINDOW
layer's paged decode kernel, from the device trace, by its own name
(``paged_window_decode_kernel``; one call = one window layer of one decode
step for all lanes; the global layers' calls are ``paged_decode_ms_per_call``).

A program without the kernel, or a model with no window layer, gives nothing.
A rehearsal has no device plane: there the sample count is the number of
calls the ring says the traced span held, and no value is shown."""

import kernel_costs_window as costs


def read(run):
    calls = costs.decode_calls(run)
    if calls is None:
        return None
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(sum(c for _t, c in calls)))
    found = costs.kernel_time(run, costs.DECODE_KERNEL)
    if found is None:
        return None
    seconds, n = found
    return seconds / n * 1e3, n
