"""Model step (models/generation.py): device milliseconds a decode step spends
in its Kimi-delta-attention layers, all of them together (3 of 4 layers in
``solaropen2-docreport-steady``): the operations of the decode chunk's program
whose ``jax.named_scope`` path runs through ``layer/kda`` (the norm, the
projections, the low-rank gates, the 4 taps, the one-token delta-rule step
with its decay a channel, the two-part lane state's read and write) and the
waits for the layers' own weights (``capture_scopes.consumer_scopes`` gives a
fetch its user's path), summed over the traced span, over the decode steps the
ring says the span held; as ``gdn_layers_ms_per_step`` reads Olmo-Hybrid's.

A model with no such layer, a program without the scope, or a capture that
cannot be found gives nothing; a rehearsal shows a count only."""

import capture_scopes
import kernel_costs_kda


def read(run):
    if not kernel_costs_kda.is_kda(run.program_config):
        return None
    return capture_scopes.decode_scope_ms(run, "layer/kda", 1)
