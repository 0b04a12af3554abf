"""Operations and bytes a kernel's call needs, from its shapes alone.

These are the algorithm's needs, not what an implementation happens to
move: a roofline share computed from them says how far a kernel is from the
best the chip could do for that call.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind`` from ``peaks.json``. A device
    that is not in the table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in benchmark/peaks.json (has: {sorted(table)})")
    return table[device_kind]


def paged_decode(live_tokens: float, lanes: int, n_heads: int, n_kv_heads: int,
                 head_dim: int, kv_itemsize: int = 2) -> dict:
    """One call of paged decode attention (one layer, one new token a lane).

    ``live_tokens`` is the number of cached tokens the call attends to,
    summed over lanes. Bytes: every live token's K and V row of every KV head
    read once, each lane's queries read (in the cache's dtype) and its
    float32 output written. FLOPs: q.k and p.v, two per multiply-add, for
    every query head over every live token."""
    kv = 2 * live_tokens * n_kv_heads * head_dim * kv_itemsize
    q_out = lanes * n_heads * head_dim * (kv_itemsize + 4)
    return {"bytes": kv + q_out,
            "flops": 2 * 2 * live_tokens * n_heads * head_dim}


def roofline(cost: dict, peak: dict) -> dict:
    """The least seconds the chip could take for ``cost`` and which peak
    bounds it."""
    t_mem = cost["bytes"] / peak["hbm_bytes_per_s"]
    t_flop = cost["flops"] / peak["bf16_flops_per_s"]
    return {"seconds": max(t_mem, t_flop),
            "bound": "memory" if t_mem >= t_flop else "compute"}
