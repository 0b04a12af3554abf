"""Model step (models/generation.py): device milliseconds a decode step spends
in its Mamba layers, all of them together (9 of 32 layers in
``phi4flash-reasoning-steady``): the operations of the decode chunk's program
whose ``jax.named_scope`` path runs through ``layer/ssm`` (the input and
output projections, the 4 taps, ``dt``'s softplus, the one-token selective
step, the two-part lane state's read and write) and the waits for the layers'
own weights (``capture_scopes.consumer_scopes`` gives a fetch its user's
path), summed over the traced span, over the decode steps the ring says the
span held; as ``conv_layers_ms_per_step`` reads a convolution layer's.

A model with no such layer, a program without the scope, or a capture that
cannot be found gives nothing; a rehearsal shows a count only."""

import capture_scopes
import kernel_costs_sambay


def read(run):
    kinds = kernel_costs_sambay.layer_counts(run.program_config)
    if kinds is None or not kinds["mamba"]:
        return None
    return capture_scopes.decode_scope_ms(run, "layer/ssm", 1)
