"""Kernels: the paged decode kernel's share of its roofline. The least time
the chip could take for a call (its bytes over the HBM peak or its FLOPs over
the bf16 peak, whichever is larger; ``kernel_costs.py``, ``peaks.json``) over
the time a call took in the device trace.

The call's live tokens are not in the trace: they are taken from the
client's records as, for each request streaming during the traced span, its
prompt plus the tokens it had received by the span's middle."""

import kernel_costs
from measure import kernel_time, live_tokens


def read(run):
    found = kernel_time(run)
    live = live_tokens(run)
    if found is None or live is None:
        return None
    seconds, calls = found
    tokens, lanes = live
    mc = run.program_config
    cost = kernel_costs.paged_decode(
        tokens, lanes, mc["n_heads"], mc["n_kv_heads"],
        mc["d_model"] // mc["n_heads"])
    best = kernel_costs.roofline(cost, kernel_costs.peaks(run.device["kind"]))
    print(f"paged decode roofline: {tokens:.0f} live tokens over {lanes} "
          f"lanes, {cost['bytes']:.0f} bytes and {cost['flops']:.0f} FLOPs a "
          f"call, {best['bound']}-bound, least {best['seconds'] * 1e6:.2f} us "
          f"against {seconds / calls * 1e6:.2f} us measured", flush=True)
    return 100.0 * best["seconds"] / (seconds / calls), calls
