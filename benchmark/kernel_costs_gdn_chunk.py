"""Operations and bytes of the gated delta rule over a PROMPT (its chunked
form), from shapes alone, and the prefills a traced span held, for a model
with linear-attention layers (``kernel_costs_gdn.py`` holds the one-token
step's and tells such a model from every other; ``kernel_costs.py`` the peaks
and ``roofline``: both reused by import).

The algorithm's needs, not an implementation's. One CALL is one
linear-attention layer of one prefill over the prompt's TRUE tokens (a
program that also multiplies its bucket's pad tokens does work nobody asked
for, and the share says so):

* bytes: the lane's matrix state ``S (heads, d_k, d_v)`` float32 read once
  and written once (2,211,840 B each way at 30 heads of 96 x 192), and a
  token: ``q``, ``k`` (``heads x d_k``) and ``v`` (``heads x d_v``) read in the
  model's dtype, its two gates (``heads`` float32 each) read and its float32
  output (``heads x d_v``) written: 46,320 B a token at those widths.
* FLOPs: the eight products of a chunk of ``CHUNK`` tokens a head, two a
  multiply-add: ``K K^T`` and ``Q K^T`` (``CHUNK d_k`` a token each), the
  triangular system's two right-hand sides (``CHUNK (d_k + d_v)``), ``W S``,
  ``Q S`` and ``K^T U`` (``d_k d_v`` each) and ``(Q K^T * D) U`` (``CHUNK
  d_v``): ``6 CHUNK d_k + 4 CHUNK d_v + 6 d_k d_v`` = 196,608 a token a head
  at 64 / 96 / 192. Forming the system's inverse is an implementation's choice
  and is not counted.

At 30 heads that is 127 FLOP a byte against the v5e's ridge of 240: the rule
over a prompt is memory-bound by what it must read and write.
"""

from __future__ import annotations

import bisect

from kernel_costs_gdn import layer_counts, peaks, roofline  # noqa: F401

KERNEL = "delta_chunk_kernel"    # the pallas_call's name in the device trace
CHUNK = 64                       # tokens a chunk (``ops.delta_rule.CHUNK``)


def chunk_rule(tokens: float, heads: int, d_k: int, d_v: int,
               itemsize: int = 2, chunk: int = CHUNK) -> dict:
    """One layer's rule over a prompt of ``tokens`` true tokens."""
    state = 2 * heads * d_k * d_v * 4                       # read and written
    token = (heads * (2 * d_k + d_v) * itemsize + 2 * heads * 4
             + heads * d_v * 4)
    flops = heads * (6 * chunk * d_k + 4 * chunk * d_v + 6 * d_k * d_v)
    return {"bytes": state + tokens * token, "flops": tokens * flops}


def prefills(run):
    """The prefills the traced span held -> ``[(true prompt tokens, share of
    the prefill inside the span)]``, matched as ``kernel_costs_gdn.
    prefill_tokens`` matches them: a prefill runs at the start of the ring
    boundary that admitted its request (``t_wall`` is the boundary's end,
    ``step_ms`` its length, ``prefill_ms`` its admissions' prefills, which
    come first), and each request belongs to the last such boundary that began
    before its first token. None where the model has no linear-attention
    layer, nothing was traced or the span held no prefill."""
    if layer_counts(run.program_config) is None or not run.trace_wall:
        return None
    lo, hi = run.trace_wall
    to_wall = run.before["t_wall"] - run.before["t"]
    admits = sorted(
        (s["t_wall"] - s["step_ms"] / 1e3, s.get("prefill_ms", 0.0) / 1e3)
        for s in run.steps if s.get("admitted") and s.get("prefill_ms"))
    starts = [start for start, _len in admits]
    held = []
    for r in run.records:
        if not r["token_t"]:
            continue
        at = bisect.bisect_right(starts, r["token_t"][0] + to_wall) - 1
        if at < 0:
            continue
        start, length = admits[at]
        inside = min(start + length, hi) - max(start, lo)
        if inside > 0:
            held.append((r["prompt_len"], inside / length))
    return held or None


def kernel_time(run):
    """(device seconds, events) of the chunked rule's kernel in the trace: an
    event is one layer of one prefill. None for a program without it."""
    if not run.trace:
        return None
    hits = [v for k, v in run.trace["kernels"].items() if KERNEL in k]
    events = sum(v["calls"] for v in hits)
    return (sum(v["seconds"] for v in hits), events) if events else None
