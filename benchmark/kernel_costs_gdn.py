"""Operations and bytes of the gated delta rule's one-token step, from shapes
alone, and the steps and prefills a traced span held, for a model with
linear-attention layers (``families/olmo_hybrid.py``; ``kernel_costs.py`` holds
the peaks and ``roofline``, reused by import).

The algorithm's needs, not an implementation's:

* one STEP call is one linear-attention layer of one decode step: each LIVE
  lane's matrix state ``S (heads, d_k, d_v)`` float32 read once and written
  once (2,211,840 B each way a lane at 30 heads of 96 x 192), its ``q``, ``k``
  (``heads x d_k``) and ``v`` (``heads x d_v``) read in the model's dtype, its
  two gates (``heads`` float32 each) read and its float32 output (``heads x
  d_v``) written. A lane that is not live needs nothing: a program that reads
  and writes every lane's slice moves ``slots / live`` times these bytes, and
  the share says so. FLOPs: the decay (``d_k d_v``), ``k S`` and ``q S`` (``2
  d_k d_v`` each) and the rank-one write (``2 d_k d_v``) a head: ``7 heads d_k
  d_v`` a live lane. 0.6 FLOP a byte: memory-bound at every size.

What tells this file's model from every other is what its program config says
of its layers (``layer_types`` with ``linear_attention`` entries): a config
without them gives None everywhere, and every reader built on this file then
gives nothing.
"""

from __future__ import annotations

import bisect

from kernel_costs import peaks, roofline  # noqa: F401  (one table, one rule)
from measure import chunk_boundaries

LINEAR, FULL = "linear_attention", "full_attention"


def layer_counts(mc: dict) -> dict[str, int] | None:
    """Layers by kind -> {"linear", "full"}, or None for a program config
    with no linear-attention layer."""
    types = list(mc.get("layer_types") or ())
    if LINEAR not in types:
        return None
    return {"linear": types.count(LINEAR), "full": types.count(FULL)}


def step(live_lanes: float, heads: int, d_k: int, d_v: int,
         itemsize: int = 2) -> dict:
    """One step call of one linear-attention layer over ``live_lanes`` lanes."""
    state = 2 * heads * d_k * d_v * 4                       # read and written
    operands = heads * (2 * d_k + d_v) * itemsize + 2 * heads * 4
    out = heads * d_v * 4
    return {"bytes": live_lanes * (state + operands + out),
            "flops": live_lanes * 7 * heads * d_k * d_v}


def step_calls(run):
    """The step calls the traced span held -> ``[(live lanes, calls)]``, one
    entry a ring boundary that ran a chunk (``measure.chunk_boundaries``):
    ``chunk x linear layers`` calls weighted by the boundary's share inside
    the span, at the ring's live lanes. None where the model has no such
    layer or nothing was traced."""
    kinds = layer_counts(run.program_config)
    if kinds is None or not run.trace_wall:
        return None
    return [(s["active"], share * s["chunk"] * kinds["linear"])
            for s, _mid, share in chunk_boundaries(run)]


def prefill_tokens(run) -> float | None:
    """Prompt tokens whose prefill the traced span held (true lengths, not
    buckets). A prefill runs at the start of the ring boundary that admitted
    its request (``admitted`` > 0; ``t_wall`` is the boundary's end,
    ``step_ms`` its length, ``prefill_ms`` its admissions' prefills, which
    come first in a boundary), and the request's first token leaves right
    after it: each request is matched BY TIMESTAMP to the last such boundary
    that began before its first token and counts for the share of that
    boundary's prefill time that lies inside the span (the matching
    ``kernel_costs_window.flash_calls`` makes for a window model). None where
    the model has no linear-attention layer, nothing was traced or the span
    held no prefill."""
    if layer_counts(run.program_config) is None or not run.trace_wall:
        return None
    lo, hi = run.trace_wall
    to_wall = run.before["t_wall"] - run.before["t"]
    admits = sorted(
        (s["t_wall"] - s["step_ms"] / 1e3, s.get("prefill_ms", 0.0) / 1e3)
        for s in run.steps if s.get("admitted") and s.get("prefill_ms"))
    starts = [start for start, _len in admits]
    tokens = 0.0
    for r in run.records:
        if not r["token_t"]:
            continue
        at = bisect.bisect_right(starts, r["token_t"][0] + to_wall) - 1
        if at < 0:
            continue
        start, length = admits[at]
        inside = min(start + length, hi) - max(start, lo)
        if inside > 0:
            tokens += r["prompt_len"] * inside / length
    return tokens or None
