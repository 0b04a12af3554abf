"""Dropless top-k expert layer: router, grouping, grouped product, combine.

One function, ``moe_experts``, serves prefill (hundreds to thousands of
tokens, every expert hit) and decode (at most a row a lane). Nothing in it
depends on who else is in the batch: there is no capacity and no
``(tokens, experts, capacity)`` tensor, every token reaches each of its
``top_k`` experts, and a row's answer is computed from that row alone (its
own router logits, its own ``top_k`` products summed in rank order). A row
can therefore share a decode step with strangers.

  route    router product in float32 at HIGHEST precision (a float32 matmul
           on the TPU is one bf16 pass otherwise), scores over all experts
           (``softmax``, or ``sigmoid`` with an optional selection ``bias``
           that picks the experts and does not enter their weight),
           ``jax.lax.top_k`` (ties as it breaks them), gates = the scores
           themselves unless ``norm_topk`` (over their sum plus
           ``norm_eps``, 0 unless a model states one), times ``scale``; the
           ``tokens x top_k`` assignments sorted by expert (stable), group
           sizes counted.
  experts  the sorted rows gathered, ``silu(x W1_e) * (x W3_e)`` then
           ``h W2_e`` as two grouped products (bf16 operands, float32
           accumulation), unsorted, weighted by the gates and summed.

The grouped product is ``moe_grouped_matmul_kernel`` on the TPU: a Pallas
kernel after ``jax.experimental.pallas.ops.tpu.megablox.gmm`` (whose group
metadata it uses) whose grid visits only (row tile, expert) pairs that hold
rows, so an expert no token chose is never read from HBM, with the SwiGLU
folded into the first product. Elsewhere (the CPU, a partitioned
program) ``jax.lax.ragged_dot`` computes the same arithmetic. The gate records
which was traced under ``('moe_experts', 'kernel'|'reference', reason)`` in
``ops.attention.dispatch_tally()``; the kernel's reason carries the row tile
the program took (``'pallas tm=256'``), which ``row_tile`` reads off the
call's shapes: 128 rows for a decode step and for a call whose experts draw
few rows each, the chip's ridge (256 on a v5e) for anything larger.

``row_mask`` marks rows whose answer nobody reads (the engine's inactive
lanes): their assignments go to no expert, so they hit none and read none,
and their output is zero.

``held = (first, count)`` says which experts this chip holds of a layer that
several chips share (expert parallelism): the weights are ``(count, d, ff)``,
the router keeps its full width, and an assignment to an expert held
elsewhere takes the road a masked row takes, to no expert. What comes back is
the held experts' part of the layer's answer; on one chip there is no
exchange, and nothing here stands in for the absent chips.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tfservingcache_tpu.ops.attention import _record_dispatch

# Tests flip this to run the Pallas kernel through its interpreter on the CPU
# (trace-time only, like ops.attention.PAGED_KERNEL_INTERPRET).
MOE_KERNEL_INTERPRET = False

# Row tiles. A visit multiplies a WHOLE tile by one expert's blocks and keeps
# that expert's rows, so it costs the larger of the expert's weights' read and
# the tile's product, and an expert that draws R rows takes about R / tm + 1
# visits. The widest tile worth taking is the one at the chip's RIDGE, where a
# visit's product takes as long as its read whatever the widths (rows x 6kn /
# 197 TFLOP/s = 6kn B / 819 GB/s: 240 rows on a v5e, so 256): past it every
# visit is compute-bound on rows that are mostly another expert's (512 rows
# cost 0.9-2.0 ms a call more at the benchmark's widths). A call whose experts
# draw well under a tile each (at most NARROW_ROWS on average over the router's
# width; a decode step, at most 32 lanes x top_k rows with most of them masked
# at low load, always) keeps the narrow tile, whose visits are bound by the
# read alone: on the chip 2-11 % ahead of 256 at 16-32 rows an expert, level
# at 64, behind from 128 on (PERF.md section 6, PR 44).
DECODE_ROWS, DECODE_TM, PREFILL_TM = 1024, 128, 256
NARROW_ROWS = 64
TN = 512
VMEM_LIMIT = 64 << 20


def row_tile(a: int, n_experts: int) -> int:
    """The row tile of a call with ``a`` assignments (``tokens x top_k``) over
    a router ``n_experts`` wide: a function of the call's shapes alone, fixed
    when the program is traced. The router's FULL width counts, not the
    experts held here: a chip's share draws the same rows an expert."""
    narrow = a <= max(DECODE_ROWS, NARROW_ROWS * n_experts)
    return DECODE_TM if narrow else PREFILL_TM


def route(x: jax.Array, router: jax.Array, top_k: int, norm_topk: bool = False,
          score: str = "softmax", bias: jax.Array | None = None,
          scale: float = 1.0, norm_eps: float = 0.0):
    """``x (t, d)`` -> (gates ``(t, k)`` float32, experts ``(t, k)`` int32,
    scores ``(t, e)`` float32). ``score`` is the function over the router's
    logits, ``softmax`` or ``sigmoid``; ``bias (e,)`` is added to the scores
    for the SELECTION only (a gate is the unbiased score); ``scale``
    multiplies the gates after ``norm_topk``, whose denominator is the sum of
    the chosen scores plus ``norm_eps`` (0, the bare sum, unless a model's
    published router states a term)."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"route score {score!r}: softmax or sigmoid")
    probs = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
             else jax.nn.sigmoid(logits))
    if bias is None:
        gates, idx = jax.lax.top_k(probs, top_k)
    else:
        _, idx = jax.lax.top_k(probs + bias.astype(jnp.float32), top_k)
        gates = jnp.take_along_axis(probs, idx, axis=-1)
    if norm_topk:
        total = jnp.sum(gates, axis=-1, keepdims=True)
        gates = gates / (total + norm_eps if norm_eps else total)
    if scale != 1.0:
        gates = gates * scale
    return gates, idx.astype(jnp.int32), probs


def _kernel_refusal(w1: jax.Array, partitioned: bool) -> str | None:
    if MOE_KERNEL_INTERPRET:
        return None
    if jax.default_backend() != "tpu":
        return f"backend={jax.default_backend()}"
    if partitioned:
        return "partitioned program (kernel is single-chip)"
    d, ff = w1.shape[1], w1.shape[2]
    if d % 128 or ff % 128:
        return f"d_model={d} or d_ff={ff} not a multiple of 128"
    return None


def grouped_matmul_reference(lhs, rhs, group_sizes, rhs_up=None,
                             out_dtype=jnp.float32):
    """``lhs[rows of group g] @ rhs[g]`` for rows sorted by group (rows past
    the groups' total are zero), as ``jax.lax.ragged_dot``; with ``rhs_up``
    the SwiGLU ``silu(lhs @ rhs) * (lhs @ rhs_up)``."""
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes,
                            preferred_element_type=jnp.float32)
    out = dot(lhs, rhs)
    if rhs_up is not None:
        out = jax.nn.silu(out) * dot(lhs, rhs_up)
    return out.astype(out_dtype)


def moe_grouped_matmul(lhs, rhs, group_sizes, rhs_up=None, *, tm: int,
                       out_dtype=jnp.float32, interpret: bool = False):
    """The Pallas grouped product. ``lhs (m, k)`` rows sorted by group, ``m``
    a multiple of ``tm``; ``rhs (groups, k, n)``; rows past the groups' total
    are left UNWRITTEN (the caller masks them). Grid ``(n tiles, visits)``:
    a visit is one (row tile, group) pair that holds rows, so the weights of
    a group without rows are never copied; a tile several groups share is
    visited once a group and each visit stores its own rows only."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

    m, k = lhs.shape
    n = rhs.shape[2]
    # the widest column tile of whole 128-lane tiles up to TN that divides n
    # (1792 = 7 x 256 takes 256; 1024, 2048 and 4096 take TN as they did)
    tn = next((t for t in range(min(TN, n), 0, -128) if n % t == 0), min(TN, n))
    if m % tm or n % tn:
        raise ValueError(f"rows {m} / columns {n} not a multiple of the tile "
                         f"({tm}, {tn})")
    swiglu = rhs_up is not None
    (group_offsets, group_ids, m_tile_ids), visits = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=rhs.shape[0],
        visit_empty_groups=False)

    def moe_grouped_matmul_kernel(offsets, gids, tiles, lhs_ref, *refs):
        out_ref = refs[-1]
        v = pl.program_id(1)
        x = lhs_ref[...]
        acc = jnp.dot(x, refs[0][...], preferred_element_type=jnp.float32)
        if swiglu:
            acc = jax.nn.silu(acc) * jnp.dot(
                x, refs[1][...], preferred_element_type=jnp.float32)
        g = gids[v]
        rows = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0) + tiles[v] * tm
        mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
        out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])

    rhs_spec = pl.BlockSpec((None, k, tn), lambda j, v, o, g, t: (g[v], 0, j))
    call = pl.pallas_call(
        moe_grouped_matmul_kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, k), lambda j, v, o, g, t: (t[v], 0)),
                      rhs_spec] + ([rhs_spec] if swiglu else []),
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, o, g, t: (t[v], j)),
            grid=(n // tn, visits),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_grouped_matmul_kernel",
    )
    operands = (lhs, rhs, rhs_up) if swiglu else (lhs, rhs)
    return call(group_offsets, group_ids, m_tile_ids, *operands)


def moe_experts(x: jax.Array, moe: dict, top_k: int, *, norm_topk: bool = False,
                row_mask: jax.Array | None = None, partitioned: bool = False,
                score: str = "softmax", route_scale: float = 1.0,
                held: tuple[int, int] | None = None, norm_eps: float = 0.0):
    """``x (t, d)`` in the compute dtype, ``moe`` = ``router (d, E)`` float32
    (and ``bias (E,)`` where the selection has one) and ``w1``, ``w3``
    ``(e, d, ff)``, ``w2`` ``(e, ff, d)`` in the compute dtype -> (``y (t,
    d)``, stats). ``e`` is the experts held here: all ``E`` of them, or
    ``held = (first, count)`` of a layer shared between chips. ``stats`` holds
    ``experts_hit``, ``expert_rows_max`` and ``expert_rows_local`` (float32
    scalars: distinct held experts with rows, the most rows one of them took,
    and the assignments that landed on a held expert) and ``probs`` /
    ``experts`` (over all ``E``; a ``row_mask``ed row's are ``E``, no expert)
    for a training loss."""
    t, d = x.shape
    e = moe["w1"].shape[0]
    with jax.named_scope("route"):
        gates, routed, probs = route(x, moe["router"], top_k, norm_topk, score,
                                     moe.get("bias"), route_scale, norm_eps)
        if row_mask is not None:   # to no expert: one past the router's width
            routed = jnp.where(row_mask[:, None], routed, probs.shape[-1])
        idx = routed
        if held is not None:
            first, count = held
            if count != e:
                raise ValueError(f"held {held}: the weights hold {e} experts")
            idx = idx - first
            idx = jnp.where((idx >= 0) & (idx < e), idx, e)   # held elsewhere
        a = t * top_k
        why = _kernel_refusal(moe["w1"], partitioned)
        tm = row_tile(a, probs.shape[-1])
        rows = -(-a // tm) * tm if why is None else a
        flat = jnp.pad(idx.reshape(a), (0, rows - a), constant_values=e)
        order = jnp.argsort(flat, stable=True)
        group_sizes = jnp.sum(
            flat[:, None] == jnp.arange(e, dtype=jnp.int32)[None, :], axis=0,
            dtype=jnp.int32)
    with jax.named_scope("experts"):
        xs = x[jnp.minimum(order // top_k, t - 1)]
        shapes = (x.shape, moe["w1"].shape, (top_k,))
        if why is None:
            _record_dispatch(
                "moe_experts", "kernel",
                f"{'interpret' if MOE_KERNEL_INTERPRET else 'pallas'} tm={tm}",
                *shapes)
            gmm = functools.partial(moe_grouped_matmul, tm=tm,
                                    interpret=MOE_KERNEL_INTERPRET)
        else:
            _record_dispatch("moe_experts", "reference", why, *shapes)
            gmm = grouped_matmul_reference
        h = gmm(xs, moe["w1"], group_sizes, moe["w3"], out_dtype=x.dtype)
        ys = gmm(h, moe["w2"], group_sizes)
        # back to (token, rank) order; a masked or padded row was written by
        # no visit, so it is taken out by select, never by a product
        back = jnp.argsort(order)[:a]
        ys = ys[back].reshape(t, top_k, d)
        live = (idx < e)[..., None]
        y = jnp.sum(jnp.where(live, ys * gates[..., None], 0.0), axis=1)
    stats = {
        "experts_hit": jnp.sum(group_sizes > 0).astype(jnp.float32),
        "expert_rows_max": jnp.max(group_sizes).astype(jnp.float32),
        "expert_rows_local": jnp.sum(group_sizes).astype(jnp.float32),
        "probs": probs, "experts": routed,
    }
    return y.astype(x.dtype), stats
