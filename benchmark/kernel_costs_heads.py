"""Operations and bytes of the attention calls of a model whose QUERY heads go
by layer (Laguna-S-2.1: 72 in a window layer, 48 in a global one, over the
same 8 KV heads), and the expert calls of a model whose layers do not all hold
experts. ``kernel_costs.py`` holds the peaks, ``roofline`` and the global
decode call's costs, ``kernel_costs_window.py`` the window calls' and the
calls a traced span held, ``kernel_costs_mla.py`` the share's; all are reused
from there by import, with a layer's OWN head count put where they take one.

The algorithm's needs, never what an implementation pads: a decode call reads
each live token's K and V row ONCE for all the query heads of its KV group
(4 KiB a token at 8 KV heads of 128, whatever the group), so the group enters
through the queries read, the float32 outputs written and the FLOPs alone; a
kernel that pads a group of 6 to 8 or of 9 to 16 rows is counted at 6 and 9.
A call's cost is the mean over the layers of its kind (every layer of a kind
has the same count in the accepted configuration).
"""

from __future__ import annotations

import copy

import kernel_costs
import kernel_costs_mla
import kernel_costs_window as window_costs
from kernel_costs import peaks, roofline  # noqa: F401  (one table, one rule)

SLIDING, FULL = "sliding_attention", "full_attention"


def has_heads_a_layer(mc: dict) -> bool:
    """Whether the program's config, as run, states its query heads a layer
    (and its layers' kinds): the readers built on this file have nothing to
    read elsewhere."""
    return bool(mc.get("n_heads_per_layer")) and bool(mc.get("layer_types"))


def kind_heads(mc: dict, kind: str) -> list[int]:
    """The query heads of each layer of ``kind``, in the model's order."""
    return [int(h) for h, t in zip(mc["n_heads_per_layer"], mc["layer_types"])
            if t == kind]


def _mean(costs: list[dict]) -> dict:
    return {k: sum(c[k] for c in costs) / len(costs) for k in ("bytes", "flops")}


def window_decode(lane_tokens, mc: dict, page_tokens: int) -> dict:
    """One window layer's decode call over lanes holding ``lane_tokens``
    cached tokens each, at the window layers' own head count."""
    return _mean([window_costs.window_decode(
        lane_tokens, mc["sliding_window"], page_tokens, h, mc["n_kv_heads"],
        mc["head_dim"]) for h in kind_heads(mc, SLIDING)])


def global_decode(live_tokens: float, lanes: int, mc: dict) -> dict:
    """One global layer's decode call over ``live_tokens`` cached tokens in
    ``lanes`` lanes, at the global layers' own head count."""
    return _mean([kernel_costs.paged_decode(
        live_tokens, lanes, h, mc["n_kv_heads"], mc["head_dim"])
        for h in kind_heads(mc, FULL)])


def window_flash(s: int, mc: dict) -> dict:
    """One window layer's fresh-prefill call over ``s`` tokens, at the window
    layers' own head count."""
    return _mean([window_costs.window_flash(
        s, mc["sliding_window"], h, mc["n_kv_heads"], mc["head_dim"])
        for h in kind_heads(mc, SLIDING)])


def global_flash(s: int, mc: dict) -> dict:
    """One global layer's fresh-prefill call over ``s`` tokens (the whole
    causal triangle: ``window_flash`` with a window as long as the prompt)."""
    return _mean([window_costs.window_flash(
        s, s, h, mc["n_kv_heads"], mc["head_dim"])
        for h in kind_heads(mc, FULL)])


def least_seconds(weighted_costs, peak: dict) -> dict:
    """The least seconds the chip could take for ``[(cost, calls)]``, summed
    by the peak that bounds each call: ``{"memory": s, "compute": s}``."""
    least = {"memory": 0.0, "compute": 0.0}
    for cost, calls in weighted_costs:
        best = roofline(cost, peak)
        least[best["bound"]] += calls * best["seconds"]
    return least


# -- layers that hold experts ---------------------------------------------------

def expert_layers(mc: dict) -> int:
    """How many of the program's layers, as run, hold experts: all of them but
    the dense ones the config names (``mlp_only_layers``)."""
    return int(mc["n_layers"]) - len(mc.get("mlp_only_layers") or ())


def share_calls(run):
    """``kernel_costs_mla.share_calls`` with the calls a step counted over the
    layers that HOLD experts: that function reckons ``chunk x n_layers`` calls
    a boundary and ``n_layers`` a prefill, which reads 9 / 8 of the truth
    where layer 0 of 9 is dense. Same rows, same experts hit, same weights."""
    seen = copy.copy(run)
    seen.program_config = dict(run.program_config,
                               n_layers=expert_layers(run.program_config))
    return kernel_costs_mla.share_calls(seen)
