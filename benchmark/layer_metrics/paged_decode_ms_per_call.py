"""Kernels (ops/attention.py): device milliseconds per call of the paged
decode kernel, from the device trace (one call = one layer of one decode
step for all lanes)."""

from measure import kernel_time


def read(run):
    found = kernel_time(run)
    if found is None:
        return None
    seconds, calls = found
    return seconds / calls * 1e3, calls
