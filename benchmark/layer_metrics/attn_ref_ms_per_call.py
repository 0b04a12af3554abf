"""Kernels (ops/attention.py): device milliseconds a call of an attention
layer in a decode step of a model whose heads are 64 wide: the paged decode
kernel's gate wants a head of 128, so these layers take the gather + einsum
reference (``dispatch_tally``: ``paged_attention``, ``reference``). What is
summed is everything the decode chunk's program runs under ``layer/attn`` (the
q, k, v projections, the per-head norms, the rotation, the gather of the
lane's pages, scores, softmax, values, the output projection), over the
traced span; a call is one attention layer of one decode step (3 of 14 layers
in ``lfm2-longgen-steady``). It is the price a head-64 paged kernel would
have to beat.

A model that does not say what its layers are, a program without the scope,
or a capture that cannot be found gives nothing; a rehearsal shows a count
only."""

import kernel_costs_hybrid
import capture_scopes


def read(run):
    kinds = kernel_costs_hybrid.layer_counts(run.program_config)
    if kinds is None or not kinds["attn"]:
        return None
    return capture_scopes.decode_scope_ms(run, "layer/attn", kinds["attn"])
