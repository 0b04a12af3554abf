"""Model step (models/generation.py): device milliseconds the linear-attention
layers of a prefill take a thousand prompt tokens: the operations of
``_slot_prefill_jit`` whose ``jax.named_scope`` path runs through ``layer/gdn``
(the projections, the taps, the gates and, under ``chunk`` inside it, the
chunked delta rule over the prompt's bucket, all 6 layers), summed over the
traced span, over the prompt tokens whose prefill the span held
(``kernel_costs_gdn.prefill_tokens``: true lengths, not buckets, so padding
counts against the number). This is the number a kernel for the chunked rule
would have to beat.

A model with no such layer, a span that held no prefill, a program without the
scope, or a capture that cannot be found gives nothing; a rehearsal shows the
prefills' tokens as a count."""

import capture_scopes
import kernel_costs_gdn

PREFILL_PROGRAM = "_slot_prefill_jit"


def read(run):
    tokens = kernel_costs_gdn.prefill_tokens(run)
    if not tokens:
        return None
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(tokens))
    capture = capture_scopes.capture_of(run)
    if capture is None or capture["device"] is None:
        return None
    seconds, events = capture_scopes.scope_seconds(
        capture["ops"], PREFILL_PROGRAM, "layer/gdn")
    if not events:
        return None
    return seconds * 1e3 / (tokens / 1e3), max(1, round(tokens))
