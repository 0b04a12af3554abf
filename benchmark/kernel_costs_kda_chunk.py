"""Operations and bytes of the delta rule over a PROMPT (its chunked form)
where the decay is a CHANNEL's (Kimi Delta Attention), from shapes alone.
``kernel_costs_kda.py`` tells such a model from every other and holds the
prefilled tokens a traced span held, ``kernel_costs_gdn_chunk.py`` the prefills
themselves (config-generic), ``kernel_costs.py`` the peaks and ``roofline``:
all reused by import.

The algorithm's needs, not an implementation's. One CALL is one
linear-attention layer of one prefill over the prompt's TRUE tokens (a program
that also multiplies its bucket's pad tokens, or that reads and writes the
state once a block of a long prompt, does work nobody asked for, and the share
says so):

* bytes: the lane's matrix state ``S (heads, d_k, d_v)`` float32 read once
  and written once (4,194,304 B each way at 64 heads of 128 x 128), and a
  token: ``q``, ``k`` (``heads x d_k``) and ``v`` (``heads x d_v``) read in the
  model's dtype, its log-decay (``heads x d_k`` float32, a value a channel)
  and its write strength (``heads`` float32) read and its float32 output
  (``heads x d_v``) written: 114,944 B a token at those widths.
* FLOPs a token a head, a chunk of ``CHUNK`` tokens: ``K K^T`` and ``Q K^T``
  with the decay between the two tokens INSIDE the sum over the channel (two
  multiplies and an add a term: ``3 CHUNK d_k`` each, where a decay a head
  factors out and leaves ``2 CHUNK d_k``), the triangular system's two
  right-hand sides (``2 CHUNK (d_k + d_v)``), ``W S``, ``Q S`` and ``K^T U``
  (``2 d_k d_v`` each), ``(Q K^T) U`` (``2 CHUNK d_v``), the three decayed
  operands ``K exp(G)``, ``Q exp(G)``, ``K exp(G_C - G)`` (``d_k`` each) and
  the state's rows decayed once a chunk (``d_k d_v / CHUNK``): ``8 CHUNK d_k +
  4 CHUNK d_v + 6 d_k d_v + 3 d_k + d_k d_v / CHUNK`` = 197,248 at 64 / 128 /
  128. The exponentials are no FLOPs and forming the system's inverse is an
  implementation's choice: neither is counted.

At 64 heads that is 110 FLOP a byte against the v5e's ridge of 240: the rule
over a prompt is memory-bound by what it must read and write.
"""

from __future__ import annotations

from kernel_costs_gdn import layer_counts  # noqa: F401
from kernel_costs_gdn_chunk import CHUNK
from kernel_costs_gdn_chunk import prefills as _prefills
from kernel_costs_kda import is_kda, peaks, roofline  # noqa: F401

# the pallas_call's name in the device trace (NOT a part of the head-decay
# kernel's ``delta_chunk_kernel``, which ``gdn_chunk_roofline`` finds by name)
KERNEL = "delta_channel_chunk_kernel"


def chunk_rule(tokens: float, heads: int, d_k: int, d_v: int,
               itemsize: int = 2, chunk: int = CHUNK) -> dict:
    """One layer's rule over a prompt of ``tokens`` true tokens."""
    state = 2 * heads * d_k * d_v * 4                       # read and written
    token = (heads * (2 * d_k + d_v) * itemsize + heads * d_k * 4 + heads * 4
             + heads * d_v * 4)
    flops = heads * (8 * chunk * d_k + 4 * chunk * d_v + 6 * d_k * d_v
                     + 3 * d_k + d_k * d_v / chunk)
    return {"bytes": state + tokens * token, "flops": tokens * flops}


def prefills(run):
    """``kernel_costs_gdn_chunk.prefills`` for a model whose decay is a
    channel's; None for every other."""
    return _prefills(run) if is_kda(run.program_config) else None


def kernel_time(run):
    """(device seconds, events) of the channel-decay kernel in the trace: an
    event is one call of it, a layer of a prefill or, in a long prompt whose
    mixer runs a block of tokens at a time, of one block. None for a program
    without it."""
    if not run.trace:
        return None
    hits = [v for k, v in run.trace["kernels"].items() if KERNEL in k]
    events = sum(v["calls"] for v in hits)
    return (sum(v["seconds"] for v in hits), events) if events else None
