"""The grouped expert kernel's calls in a model whose layers are of several
kinds (``families/lfm2_moe.py``: the first ``n_dense_layers`` layers hold a
dense SwiGLU and no experts, and one layer in four attends).

``kernel_costs_moe.traced_calls`` counts ``n_layers`` calls of the expert
kernel a decode step, which is right for a model whose every layer holds
experts and 14/12 of the truth here. This file
counts calls by the layers that HOLD experts, from the program's config as
run (``layer_types``, ``n_dense_layers``). The bytes and FLOPs of a
call, the peaks and the roofline rule are ``kernel_costs_moe``'s and
``kernel_costs``', by import.
"""

from __future__ import annotations

import kernel_costs_moe
from kernel_costs_moe import (  # noqa: F401  (one table, one rule)
    grouped_experts,
    kernel_time,
    peaks,
    roofline,
)


def layer_counts(mc: dict) -> dict[str, int] | None:
    """Layers by kind -> {"conv", "attn", "dense", "moe"}, or None for a
    program config that does not say what its layers are."""
    types = mc.get("layer_types")
    if not types or "n_dense_layers" not in mc:
        return None
    conv = sum(t == "conv" for t in types)
    dense = min(int(mc["n_dense_layers"]), len(types))
    return {"conv": conv, "attn": len(types) - conv, "dense": dense,
            "moe": len(types) - dense}


def traced_calls(run):
    """``kernel_costs_moe.traced_calls`` with the calls a step (and a prefill)
    counted over the layers that hold experts, not ``n_layers`` ->
    ``[(rows, experts_hit, calls)]``, or None where the model is not of this
    kind, the ring has no routing fields, or nothing was traced."""
    kinds = layer_counts(run.program_config)
    calls = kernel_costs_moe.traced_calls(run) if kinds else None
    if calls is None:
        return None
    share = kinds["moe"] / run.program_config["n_layers"]
    return [(rows, hit, count * share) for rows, hit, count in calls]
