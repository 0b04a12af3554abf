"""Model step (models/generation.py): device milliseconds a decode step spends
in its gated short-convolution operators, all of them together (11 of 14
layers in ``lfm2-longgen-steady``): the operations of the decode chunk's
program whose ``jax.named_scope`` path runs through ``layer/conv`` (the two
projections, the gates, the three taps, the lane state's read and write) and
the waits for the operators' own weights (the compiler fetches them into fast
memory asynchronously, in operations of its own that carry no path:
``capture_scopes.consumer_scopes`` gives each its user's), summed over the
traced span, over the decode steps the ring says the span held. A fetch that
ends under another layer's work costs this layer nothing and shows nothing
here: the number is what the operators add to a step, not their bytes over the
memory's peak.

A model with no such layer, a program without the scope, or a capture that
cannot be found gives nothing; a rehearsal shows a count only."""

import kernel_costs_hybrid
import capture_scopes


def read(run):
    kinds = kernel_costs_hybrid.layer_counts(run.program_config)
    if kinds is None or not kinds["conv"]:
        return None
    return capture_scopes.decode_scope_ms(run, "layer/conv", 1)
