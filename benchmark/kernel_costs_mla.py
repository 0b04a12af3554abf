"""Operations and bytes of latent (MLA) decode attention, and the calls of
the expert kernel on one chip's share of a layer, from shapes alone
(``kernel_costs.py`` holds the peaks and ``roofline``, ``kernel_costs_moe.py``
the grouped product's costs; both are reused from there by import).

One call of latent decode attention is one layer of one decode step for all
lanes, in the absorbed form: every head of a lane scores its query against
the lane's latent rows ``[c_kv | rope(k_r)]`` and sums their ``c_kv`` part.
The algorithm's needs, not an implementation's: a live token's row is
``kv_lora_rank + qk_rope_head_dim`` columns read ONCE for all heads (640 B at
256 + 64 in bf16), whatever padding an arena stores it with; a lane's queries
are read in the cache's dtype and its float32 output written.
"""

from __future__ import annotations

from kernel_costs import peaks, roofline  # noqa: F401  (one table, one rule)
from kernel_costs_moe import grouped_experts, kernel_time as experts_kernel_time  # noqa: F401
from measure import chunk_boundaries


def latent_decode(live_tokens: float, lanes: int, n_heads: int, kv_rank: int,
                  rope_dim: int, itemsize: int = 2) -> dict:
    """Bytes: every live token's ``kv_rank + rope_dim`` row once, each lane's
    ``n_heads`` queries of that width and its ``n_heads x kv_rank`` float32
    output. FLOPs: a score over ``kv_rank + rope_dim`` and a weighted sum over
    ``kv_rank`` for every head and live token, two a multiply-add."""
    row = kv_rank + rope_dim
    q_out = lanes * n_heads * (row * itemsize + kv_rank * 4)
    return {"bytes": live_tokens * row * itemsize + q_out,
            "flops": 2 * n_heads * (row + kv_rank) * live_tokens}


# -- the latent kernel in a traced run ----------------------------------------

KERNEL = "paged_latent_decode"   # the pallas_call's name in the device trace


def kernel_time(run):
    """(device seconds, calls) of the latent decode kernel in the trace, by
    its own name (``measure.kernel_time`` looks for the K/V kernel)."""
    if not run.trace:
        return None
    hits = [v for k, v in run.trace["kernels"].items() if KERNEL in k]
    calls = sum(v["calls"] for v in hits)
    return (sum(v["seconds"] for v in hits), calls) if calls else None


def is_latent(run) -> bool:
    """Whether the program's config, as run, is a latent-attention model's."""
    return "kv_lora_rank" in run.program_config


# -- the expert kernel on a share of a layer -----------------------------------

def share_calls(run):
    """The expert kernel's calls the traced span held where the chip holds a
    SHARE of a layer's experts -> ``[(rows, experts_hit, calls)]``, or None
    where the ring has no ``expert_rows_local`` (a program older than the
    share) or nothing was traced.

    Decode: a ring boundary that ran a chunk gives ``chunk x layers`` calls of
    ``expert_rows_local`` rows (the assignments that landed on a held expert,
    counted by the program) over ``experts_hit`` of the held experts, weighted
    by the boundary's share inside the span. Prefill: a request whose first
    token arrived inside the span gives ``layers`` calls of the held fraction
    of ``prompt_len x top_k`` rows over the held experts that many uniform
    assignments are expected to hit."""
    if not run.trace_wall or not any("expert_rows_local" in s for s in run.steps):
        return None
    lo, hi = run.trace_wall
    mc = run.program_config
    layers, top_k, n_exp = mc["n_layers"], mc["top_k"], mc["n_experts"]
    held = mc.get("n_experts_held", n_exp)
    calls = [(s["expert_rows_local"], s["experts_hit"], share * s["chunk"] * layers)
             for s, _mid, share in chunk_boundaries(run)
             if s.get("expert_rows_local")]
    to_wall = run.before["t_wall"] - run.before["t"]
    for r in run.records:
        if r["token_t"] and lo <= r["token_t"][0] + to_wall <= hi:
            rows = r["prompt_len"] * top_k
            calls.append((rows * held / n_exp,
                          held * (1.0 - (1.0 - 1.0 / n_exp) ** rows), layers))
    return calls
