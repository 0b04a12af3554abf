"""Model step (models/generation.py): device milliseconds a decode step spends
in its linear-attention layers, all of them together (6 of 8 layers in
``olmohybrid-longdoc-steady``): the operations of the decode chunk's program
whose ``jax.named_scope`` path runs through ``layer/gdn`` (the projections, the
4 taps, the gates, the one-token delta-rule step, the two-part lane state's
read and write, the norm that follows the mixer) and the waits for the layers'
own weights (``capture_scopes.consumer_scopes`` gives a fetch its user's
path), summed over the traced span, over the decode steps the ring says the
span held; as ``ssm_layers_ms_per_step`` reads a Mamba layer's.

A model with no such layer, a program without the scope, or a capture that
cannot be found gives nothing; a rehearsal shows a count only."""

import capture_scopes
import kernel_costs_gdn


def read(run):
    if kernel_costs_gdn.layer_counts(run.program_config) is None:
        return None
    return capture_scopes.decode_scope_ms(run, "layer/gdn", 1)
