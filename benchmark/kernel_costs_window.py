"""Operations and bytes of a WINDOW layer's two attention calls, from shapes
alone, and the calls of each that a traced span held (``kernel_costs.py``
holds the peaks and ``roofline``; they are reused from there by import).

A window layer's query at position ``i`` reads keys ``(i - window, i]``. The
algorithm's needs, not an implementation's:

* one DECODE call is one window layer of one decode step for all lanes: a
  lane with ``t`` cached tokens reads its last ``min(t, window)`` tokens' K and
  V rows, rounded up to the whole pages that hold them (a page is the unit a
  paged arena can move), each row ``n_kv_heads x head_dim`` on two sides (2 KiB
  in the accepted configuration), its queries in the cache's dtype, and writes
  its float32 output. Memory-bound at every size that occurs;
* one FLASH call is one window layer's attention over a fresh prompt of ``s``
  tokens: q.k and p.v, two a multiply-add, for every query head over the
  ``min(i + 1, window)`` keys query ``i`` reads, ``4 x heads x head_dim x
  sum_i min(i + 1, window)`` FLOPs; q, k, v read and the output written once.
  Compute-bound. A kernel that does the whole causal triangle does 0.55
  TFLOP at 8192 tokens where this is 0.14.
"""

from __future__ import annotations

import bisect

from kernel_costs import peaks, roofline  # noqa: F401  (one table, one rule)
from measure import chunk_boundaries

DECODE_KERNEL = "paged_window_decode"   # the decode call's name in the trace
FLASH_KERNEL = "flash_window"           # the prefill call's name in the trace


def window_decode(lane_tokens, window: int, page_tokens: int, n_heads: int,
                  n_kv_heads: int, head_dim: int, itemsize: int = 2) -> dict:
    """One decode call over lanes holding ``lane_tokens`` cached tokens each
    (the new token's row included)."""
    row = 2 * n_kv_heads * head_dim * itemsize
    read = flops = 0
    for t in lane_tokens:
        kept = min(int(t), window)
        if kept <= 0:
            continue
        pages = (t - 1) // page_tokens - (t - kept) // page_tokens + 1
        read += pages * page_tokens * row
        flops += 2 * 2 * kept * n_heads * head_dim
    q_out = len(lane_tokens) * n_heads * head_dim * (itemsize + 4)
    return {"bytes": read + q_out, "flops": flops}


def window_flash(s: int, window: int, n_heads: int, n_kv_heads: int,
                 head_dim: int, itemsize: int = 2) -> dict:
    """One fresh-prefill call over ``s`` tokens."""
    full = min(s, window)
    # sum_i min(i + 1, window), i = 0 .. s - 1
    pairs = full * (full + 1) // 2 + (s - full) * window
    io = (2 * n_heads + 2 * n_kv_heads) * s * head_dim * itemsize
    return {"bytes": io, "flops": 4 * n_heads * head_dim * pairs}


# -- a window model's calls in a traced run ------------------------------------

def window_layers(mc: dict) -> int:
    """How many of the program's layers, as run, keep a window (0 for a
    model that has none or does not say)."""
    if not mc.get("sliding_window"):
        return 0
    return sum(t == "sliding_attention" for t in mc.get("layer_types") or ())


def kernel_time(run, kernel: str):
    """(device seconds, calls) of the events whose name holds ``kernel``."""
    if not run.trace:
        return None
    hits = [v for k, v in run.trace["kernels"].items() if kernel in k]
    calls = sum(v["calls"] for v in hits)
    return (sum(v["seconds"] for v in hits), calls) if calls else None


def lane_tokens(run, at: float) -> list[int]:
    """Cached tokens of each request streaming at monotonic time ``at``: its
    prompt plus the tokens the client had received (``measure.live_tokens``
    gives their sum)."""
    out = []
    for r in run.records:
        t = r["token_t"]
        if t and t[0] <= at and (len(t) < r["max_new"] or t[-1] >= at):
            out.append(r["prompt_len"] + bisect.bisect_right(t, at))
    return out


def decode_calls(run):
    """The window decode calls the traced span held -> ``[(lane tokens,
    calls)]``, one entry a ring boundary that ran a chunk
    (``measure.chunk_boundaries``): ``chunk x window layers`` calls weighted
    by the boundary's share inside the span, at the tokens the client's
    records show each live lane holding at the boundary's middle (as
    ``paged_decode_roofline`` counts the global calls). None where the model
    has no window layer or nothing was traced."""
    layers = window_layers(run.program_config)
    if not layers or not run.trace_wall:
        return None
    to_mono = run.before["t"] - run.before["t_wall"]
    return [(lane_tokens(run, mid + to_mono), share * s["chunk"] * layers)
            for s, mid, share in chunk_boundaries(run)]


def flash_calls(run):
    """The window flash calls the traced span held -> ``[(prompt tokens,
    calls)]``. A prefill runs at the start of the ring boundary that admitted
    its request (``admitted`` > 0; ``t_wall`` is the boundary's end,
    ``step_ms`` its length, ``prefill_ms`` its admissions' prefills, which
    come first in a boundary), and the request's first token leaves right
    after it: so each request is matched BY TIMESTAMP to the last such
    boundary that began before its first token, and gives ``window layers``
    calls at its prompt's length weighted by the share of that boundary's
    prefill time that lies inside the span (as ``measure.chunk_boundaries``
    weighs a decode boundary). A prefill that ran before the span and
    answered inside it counts for what the span held of it, and no more. None
    where the model has no window layer or nothing was traced."""
    layers = window_layers(run.program_config)
    if not layers or not run.trace_wall:
        return None
    lo, hi = run.trace_wall
    to_wall = run.before["t_wall"] - run.before["t"]
    admits = sorted(
        (s["t_wall"] - s["step_ms"] / 1e3, s.get("prefill_ms", 0.0) / 1e3)
        for s in run.steps if s.get("admitted") and s.get("prefill_ms"))
    starts = [start for start, _len in admits]
    calls = []
    for r in run.records:
        if not r["token_t"]:
            continue
        at = bisect.bisect_right(starts, r["token_t"][0] + to_wall) - 1
        if at < 0:
            continue
        start, length = admits[at]
        inside = min(start + length, hi) - max(start, lo)
        if inside > 0:
            calls.append((r["prompt_len"], layers * inside / length))
    return calls


def global_layers(mc: dict) -> int:
    """How many of a window model's layers, as run, keep every row (0 for a
    model with no window layer: ``paged_decode_roofline`` serves it)."""
    if not window_layers(mc):
        return 0
    return len(mc["layer_types"]) - window_layers(mc)


def global_decode_calls(run):
    """The GLOBAL layers' decode calls the traced span held -> ``[(live
    tokens, lanes, calls)]``: ``measure.paged_decode_calls`` with ``chunk x
    global layers`` calls a boundary where that reckons ``chunk x n_layers``
    (which would read 4 times the truth at one global layer in four). None
    where the model has no window layer or nothing was traced."""
    layers = global_layers(run.program_config)
    if not layers or not run.trace_wall:
        return None
    to_mono = run.before["t"] - run.before["t_wall"]
    return [(sum(lane_tokens(run, mid + to_mono)), s["active"],
             share * s["chunk"] * layers)
            for s, mid, share in chunk_boundaries(run)]
