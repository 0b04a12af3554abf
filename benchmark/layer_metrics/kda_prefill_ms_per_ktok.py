"""Model step (models/generation.py): device milliseconds the
Kimi-delta-attention layers of a prefill take a thousand prompt tokens: the
operations of ``_slot_prefill_jit`` whose ``jax.named_scope`` path runs through
``layer/kda`` (``proj``, ``conv``, ``gate`` and, under ``chunk``, the chunked
delta rule with its decay a channel over the prompt's bucket, all 3 layers),
summed over the traced span, over the prompt tokens whose prefill the span
held (``kernel_costs_kda.prefill_tokens``: true lengths, not buckets, so
padding counts against the number). The chunked form is plain ``jax.numpy``
(``ops.delta_rule._chunked_block_channel``): this is the number a kernel for
it starts from.

A model with no such layer, a span that held no prefill, a program without the
scope, or a capture that cannot be found gives nothing; a rehearsal shows the
prefills' tokens as a count."""

import capture_scopes
import kernel_costs_kda

PREFILL_PROGRAM = "_slot_prefill_jit"


def read(run):
    tokens = kernel_costs_kda.prefill_tokens(run)
    if not tokens:
        return None
    if run.device.get("platform") != "tpu":
        return 0.0, max(1, round(tokens))
    capture = capture_scopes.capture_of(run)
    if capture is None or capture["device"] is None:
        return None
    seconds, events = capture_scopes.scope_seconds(
        capture["ops"], PREFILL_PROGRAM, "layer/kda")
    if not events:
        return None
    return seconds * 1e3 / (tokens / 1e3), max(1, round(tokens))
