"""Operations and bytes of the delta rule's one-token step where the decay is
a CHANNEL's (Kimi Delta Attention, ``families/kda_moe.py``), from shapes
alone. ``kernel_costs_gdn.py`` holds the step calls and the prefilled tokens a
traced span held (config-generic: any program config whose ``layer_types``
name ``linear_attention`` layers), ``kernel_costs.py`` the peaks and
``roofline``; all reused by import.

The algorithm's needs, not an implementation's:

* one STEP call is one linear-attention layer of one decode step: each LIVE
  lane's matrix state ``S (heads, d_k, d_v)`` float32 read once and written
  once (4,194,304 B each way a lane at 64 heads of 128 x 128), its ``q``, ``k``
  (``heads x d_k``) and ``v`` (``heads x d_v``) read in the model's dtype
  (49,152 B), its decays (``heads x d_k`` float32, a value a channel) and its
  write strengths (``heads`` float32) read (33,024 B) and its float32 output
  (``heads x d_v``, 32,768 B) written. A lane that is not live needs nothing.
  FLOPs: the decay (``d_k d_v``), ``k S`` and ``q S`` (``2 d_k d_v`` each) and
  the rank-one write (``2 d_k d_v``) a head: ``7 heads d_k d_v`` a live lane.
  0.9 FLOP a byte: memory-bound at every size.

What tells this file's model from Olmo-Hybrid's (a decay a head) is the
low-rank gates' ``linear_gate_rank`` in its program config: a config without
it gives None everywhere, and every reader built on this file then gives
nothing.
"""

from __future__ import annotations

from kernel_costs import peaks, roofline  # noqa: F401  (one table, one rule)
from kernel_costs_gdn import prefill_tokens as _prefill_tokens
from kernel_costs_gdn import step_calls as _step_calls


def is_kda(mc: dict) -> bool:
    """Whether the program's config, as run, is a model's whose linear layers
    decay a channel."""
    return "linear_gate_rank" in mc and "linear_attention" in (
        mc.get("layer_types") or ())


def step(live_lanes: float, heads: int, d_k: int, d_v: int,
         itemsize: int = 2) -> dict:
    """One step call of one linear-attention layer over ``live_lanes`` lanes."""
    state = 2 * heads * d_k * d_v * 4                       # read and written
    operands = heads * (2 * d_k + d_v) * itemsize
    decays = heads * d_k * 4 + heads * 4
    out = heads * d_v * 4
    return {"bytes": live_lanes * (state + operands + decays + out),
            "flops": live_lanes * 7 * heads * d_k * d_v}


def step_calls(run):
    """``kernel_costs_gdn.step_calls`` for a model whose decay is a channel's;
    None for every other."""
    return _step_calls(run) if is_kda(run.program_config) else None


def prefill_tokens(run):
    """``kernel_costs_gdn.prefill_tokens`` for a model whose decay is a
    channel's; None for every other."""
    return _prefill_tokens(run) if is_kda(run.program_config) else None
