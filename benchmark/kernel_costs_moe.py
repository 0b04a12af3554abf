"""Operations and bytes of the expert layer's grouped product, from its
shapes alone (``kernel_costs.py`` holds the paged kernel's, the peaks and
``roofline``; they are reused from there by import).

One call is one layer's experts for one batch of rows: the SwiGLU product
(``silu(x W1_e) * (x W3_e)``) and the down product (``h W2_e``) of every
row with the expert it was routed to. ``rows`` counts assignments (tokens x
experts a token), ``experts_hit`` the distinct experts that got a row. The
algorithm's needs, not an implementation's: an expert's three matrices are
read once if it was hit and never if it was not, each row is read once and
written once in the compute dtype, and the intermediate ``h`` stays on the
chip. A formulation that reads every expert, or each expert once a row, does
worse than this by construction and its roofline share says by how much.
"""

from __future__ import annotations

from kernel_costs import peaks, roofline  # noqa: F401  (one table, one rule)
from measure import chunk_boundaries


def grouped_experts(rows: float, experts_hit: float, hidden: int, width: int,
                    itemsize: int = 2) -> dict:
    """Bytes: the hit experts' ``3 x hidden x width`` weights plus the rows in
    and out. FLOPs: three products of ``hidden x width`` a row, two a
    multiply-add."""
    weights = experts_hit * 3 * hidden * width * itemsize
    io = 2 * rows * hidden * itemsize
    return {"bytes": weights + io, "flops": rows * 3 * 2 * hidden * width}


# -- the grouped kernel in a traced run ---------------------------------------

KERNEL = "moe_grouped_matmul"    # the pallas_call's name in the device trace
KERNELS_A_CALL = 2               # the SwiGLU product, then the down product


def kernel_time(run):
    """(device seconds, calls) of the grouped expert kernel in the trace; a
    call is one layer's experts for one batch of rows, two kernel events."""
    if not run.trace:
        return None
    hits = [v for k, v in run.trace["kernels"].items() if KERNEL in k]
    events = sum(v["calls"] for v in hits)
    if not events:
        return None
    return sum(v["seconds"] for v in hits), events / KERNELS_A_CALL


def traced_calls(run):
    """The calls the traced span held, from the program's own records ->
    ``[(rows, experts_hit, calls)]``, or None where the ring has no routing
    fields (a program without the expert layer) or nothing was traced.

    Decode: every ring boundary that ran a chunk gives ``chunk x layers``
    calls of ``active x top_k`` rows over the boundary's ``experts_hit``,
    weighted by the share of the boundary that lies inside the span.
    Prefill: every request whose first token arrived inside the span gives
    ``layers`` calls of ``prompt_len x top_k`` rows over the experts that
    many uniform assignments are expected to hit."""
    if not run.trace_wall or not any("experts_hit" in s for s in run.steps):
        return None
    lo, hi = run.trace_wall
    mc = run.program_config
    layers, top_k, n_exp = mc["n_layers"], mc["top_k"], mc["n_experts"]
    calls = [(s["active"] * top_k, s["experts_hit"], share * s["chunk"] * layers)
             for s, _mid, share in chunk_boundaries(run) if s.get("experts_hit")]
    to_wall = run.before["t_wall"] - run.before["t"]
    for r in run.records:
        if r["token_t"] and lo <= r["token_t"][0] + to_wall <= hi:
            rows = r["prompt_len"] * top_k
            calls.append((rows, n_exp * (1.0 - (1.0 - 1.0 / n_exp) ** rows),
                          layers))
    return calls
