"""Kernels (ops/attention.py): device milliseconds per call of the latent
(MLA) decode kernel, from the device trace, by its own name
(``paged_latent_decode_kernel``; one call = one layer of one decode step for
all lanes).

A program without the kernel, or a model that is not a latent one, gives
nothing. A rehearsal has no device plane: there the sample count is the
number of calls the ring says the traced span held, and no value is shown."""

import kernel_costs_mla
from measure import paged_decode_calls


def read(run):
    if not kernel_costs_mla.is_latent(run):
        return None
    if run.device.get("platform") != "tpu":
        calls = paged_decode_calls(run)
        if calls is None:
            return None
        return 0.0, max(1, round(sum(c for _t, _l, c in calls)))
    found = kernel_costs_mla.kernel_time(run)
    if found is None:
        return None
    seconds, calls = found
    return seconds / calls * 1e3, calls
