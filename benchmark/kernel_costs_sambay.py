"""Operations and bytes of the decode calls over a SHARED global layer, from
shapes alone, and the calls and prefills a traced span held, for a model
whose ONE full-attention layer's rows several layers read
(``families/sambay.py``: the full-attention layer and every cross-attention
layer over it; ``kernel_costs.py`` holds the peaks and ``roofline``, reused by
import).

Attention there is differential: a row of the arena holds a PAIR of KV heads,
``[k(2j) | k(2j + 1)]`` and ``[v(2j) | v(2j + 1)]``, ``2 x head`` wide (128
lanes at a head of 64). The algorithm's needs, not an implementation's:

* one DECODE call is one reading layer of one decode step for all lanes: every
  live token's K and V row of every KV pair read once (``n_kv_heads x head``
  on two sides: 5120 B a token in the accepted configuration, the same bytes
  whichever layer reads them), each lane's queries (``n_heads x head``, in the
  cache's dtype) read and its float32 output written (``n_heads / 2`` pairs x
  ``2 x head``: the difference of the two softmax terms, which is all the
  layer needs). FLOPs: a score
  is a product over the head (``2 x head`` a token a query head), a value
  product over the pair's ``2 x head`` (``4 x head``): ``6 x head x n_heads``
  a live token. Memory-bound at every size that occurs. A kernel that scores
  zero-padded queries over the whole row does ``8 x head`` and reads no byte
  more.

What tells this file's model from every other is what its program config
says of its layers (``layer_types`` with ``cross_attention`` entries): a
config without them gives None everywhere, and every reader built on this
file then gives nothing.
"""

from __future__ import annotations

from kernel_costs import peaks, roofline  # noqa: F401  (one table, one rule)
from kernel_costs_window import flash_calls, lane_tokens
from measure import chunk_boundaries

MAMBA, FULL, GMU, CROSS = "mamba", "full_attention", "gmu", "cross_attention"


def layer_counts(mc: dict) -> dict[str, int] | None:
    """Layers by kind -> {"mamba", "gmu", "readers"} (``readers``: the layers
    whose decode call reads the shared global layer, the one that writes it
    among them), or None for a program config with no cross-attention
    layer."""
    types = list(mc.get("layer_types") or ())
    if CROSS not in types:
        return None
    return {"mamba": types.count(MAMBA), "gmu": types.count(GMU),
            "readers": types.count(FULL) + types.count(CROSS)}


def shared_decode(live_tokens: float, lanes: int, n_heads: int,
                  n_kv_heads: int, head_dim: int, itemsize: int = 2) -> dict:
    """One decode call of one reading layer over ``live_tokens`` cached tokens
    summed over ``lanes`` lanes."""
    kv = 2 * live_tokens * n_kv_heads * head_dim * itemsize
    q_out = lanes * n_heads * head_dim * (itemsize + 4)
    return {"bytes": kv + q_out, "flops": 6 * live_tokens * n_heads * head_dim}


def shared_decode_calls(run):
    """The decode calls over the shared layer that the traced span held ->
    ``[(live tokens, lanes, calls)]``: ``chunk x readers`` calls a ring
    boundary, weighted by its share inside the span, at the tokens the
    client's records show the live lanes holding at its middle (as
    ``kernel_costs_window.global_decode_calls``). None where the model has no
    such layer or nothing was traced."""
    kinds = layer_counts(run.program_config)
    if kinds is None or not run.trace_wall:
        return None
    to_mono = run.before["t"] - run.before["t_wall"]
    return [(sum(lane_tokens(run, mid + to_mono)), s["active"],
             share * s["chunk"] * kinds["readers"])
            for s, mid, share in chunk_boundaries(run)]


def prefill_tokens(run) -> float | None:
    """Prompt tokens whose prefill the traced span held (a prefill that ran
    partly inside it counts for that part): ``kernel_costs_window.
    flash_calls``' matching of requests to the ring's admitting boundaries,
    a call a window layer there, so the calls over the window layers are the
    prefills."""
    if layer_counts(run.program_config) is None:
        return None
    calls = flash_calls(run)
    if not calls:
        return None
    windows = sum(t == "sliding_attention"
                  for t in run.program_config["layer_types"])
    return sum(tokens * count / windows for tokens, count in calls)
