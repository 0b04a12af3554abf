"""Model step (models/generation.py): device milliseconds the Mamba layers of
a prefill take a thousand prompt tokens: the operations of
``_slot_prefill_jit`` whose ``jax.named_scope`` path runs through
``layer/ssm`` (the projections, the taps and, under ``scan`` inside it, the
selective scan over the prompt's bucket, all 9 layers), summed over the traced
span, over the prompt tokens whose prefill the span held
(``kernel_costs_sambay.prefill_tokens``: true lengths, not buckets, so padding
counts against the number). The scan is sequential in the tokens: this is the
number a scan kernel would have to beat.

A model with no such layer, a span that held no prefill, a program without the
scope, or a capture that cannot be found gives nothing; a rehearsal shows the
prefills' tokens as a count."""

import capture_scopes
import kernel_costs_sambay

PREFILL_PROGRAM = "_slot_prefill_jit"


def read(run):
    tokens = kernel_costs_sambay.prefill_tokens(run)
    if not tokens:
        return None
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(tokens))
    capture = capture_scopes.capture_of(run)
    if capture is None or capture["device"] is None:
        return None
    seconds, events = capture_scopes.scope_seconds(
        capture["ops"], PREFILL_PROGRAM, "layer/ssm")
    if not events:
        return None
    return seconds * 1e3 / (tokens / 1e3), max(1, round(tokens))
