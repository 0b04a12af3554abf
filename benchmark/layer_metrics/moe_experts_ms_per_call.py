"""Kernels (ops/moe.py): device milliseconds per call of the grouped expert
kernel, from the device trace. One call = one layer's experts for one batch
of rows (one layer of one decode step, or of one prefill): two events of
``moe_grouped_matmul_kernel``, the SwiGLU product and the down product.

A program without the kernel gives nothing. A rehearsal has no device plane:
there the sample count is the number of calls the ring and the client's
records say the traced span held, and no value is shown."""

import kernel_costs_moe


def read(run):
    calls = kernel_costs_moe.traced_calls(run)
    if calls is None:
        return None
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(sum(c for _r, _e, c in calls)))
    found = kernel_costs_moe.kernel_time(run)
    if found is None:
        return None
    seconds, n = found
    return seconds / n * 1e3, round(n)
