"""Model step (models/generation.py): device milliseconds a decode step spends
in its gated memory units, all of them together (7 of 32 layers in
``phi4flash-reasoning-steady``): the operations of the decode chunk's program
whose ``jax.named_scope`` path runs through ``layer/gmu`` (two projections and
the gate over the last Mamba layer's scan output of the same token; a unit
keeps nothing and reads no cache) and the waits for their weights, over the
decode steps the ring says the traced span held; as
``ssm_layers_ms_per_step``.

A model with no such layer, a program without the scope, or a capture that
cannot be found gives nothing; a rehearsal shows a count only."""

import capture_scopes
import kernel_costs_sambay


def read(run):
    kinds = kernel_costs_sambay.layer_counts(run.program_config)
    if kinds is None or not kinds["gmu"]:
        return None
    return capture_scopes.decode_scope_ms(run, "layer/gmu", 1)
