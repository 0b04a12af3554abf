"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

No reference counterpart (SURVEY.md §2: the reference implements no
parallelism) — this exists so models deeper than one chip group's HBM can
span stages. TPU-first design: the schedule is a single jitted program under
``shard_map`` — each device holds one stage's weights (leading-dim sharded
over the ``stage`` axis), activations hop stage-to-stage with ``ppermute``
(nearest-neighbor ICI), and the whole T = M + P - 1 tick loop is a
``lax.fori_loop`` so XLA sees static control flow.

Bubble fraction is (P-1)/(M+P-1): callers pick n_microbatches >> n_stages to
amortize. Inter-stage activations must have one shape (the usual transformer
block contract).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(stage_params: list[Any]) -> Any:
    """Stack per-stage pytrees into one pytree with leading dim n_stages
    (the dim ``pipeline_apply`` shards over the stage axis)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stage_params)


def _pipeline_shard_fn(
    params: Any,
    x: jax.Array,
    *,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    axis: str,
    n_stages: int,
    n_micro: int,
):
    """Per-device body: runs this device's stage for every tick."""
    idx = jax.lax.axis_index(axis)
    # shard_map hands each stage params with leading dim 1 — drop it
    local = jax.tree_util.tree_map(lambda a: a[0], params)
    mb_shape = x.shape[1:]

    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]
    n_ticks = n_micro + n_stages - 1

    def tick(t, carry):
        prev_y, out_buf = carry
        # activation from the previous stage (stage 0 receives zeros)
        recv = jax.lax.ppermute(prev_y, axis, fwd_perm) if n_stages > 1 else prev_y
        mb_ix = jnp.clip(t, 0, n_micro - 1)
        fresh = jax.lax.dynamic_index_in_dim(x, mb_ix, axis=0, keepdims=False)
        inp = jnp.where(idx == 0, fresh, recv)
        y = stage_fn(local, inp)
        # the last stage banks microbatch t-(P-1) once it emerges
        slot = t - (n_stages - 1)
        valid = jnp.logical_and(slot >= 0, idx == n_stages - 1)
        out_buf = jax.lax.cond(
            valid,
            lambda b: jax.lax.dynamic_update_index_in_dim(
                b, y, jnp.clip(slot, 0, n_micro - 1), axis=0
            ),
            lambda b: b,
            out_buf,
        )
        return y, out_buf

    init = (
        jnp.zeros(mb_shape, x.dtype),
        jnp.zeros((n_micro,) + mb_shape, x.dtype),
    )
    _, out_buf = jax.lax.fori_loop(0, n_ticks, tick, init)
    # only the last stage holds real outputs; psum over the stage axis
    # replicates them everywhere (all other stages contribute zeros)
    out_buf = jnp.where(idx == n_stages - 1, out_buf, jnp.zeros_like(out_buf))
    return jax.lax.psum(out_buf, axis)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    axis: str = "stage",
    n_microbatches: int | None = None,
) -> jax.Array:
    """Run ``x`` through ``n_stages`` chained applications of ``stage_fn``,
    pipelined over ``mesh[axis]``.

    ``stage_params``: pytree with leading dim n_stages (see
    ``stack_stage_params``), sharded one stage per mesh slot.
    ``x``: (batch, ...) — split into microbatches along dim 0.
    Returns exactly ``stage_fn(p[P-1], ... stage_fn(p[0], x))``.
    """
    n_stages = mesh.shape[axis]
    leading = {a.shape[0] for a in jax.tree_util.tree_leaves(stage_params)}
    if leading != {n_stages}:
        # a mismatch would otherwise be silently block-sharded (each device
        # getting >1 stage and running only the first) — wrong answer, no error
        raise ValueError(
            f"stage_params leading dim(s) {sorted(leading)} != {n_stages} mesh stages"
        )
    n_micro = n_stages if n_microbatches is None else n_microbatches
    if n_micro < 1:
        raise ValueError(f"n_microbatches must be >= 1, got {n_micro}")
    if x.shape[0] % n_micro:
        raise ValueError(f"batch {x.shape[0]} not divisible into {n_micro} microbatches")
    mb = x.shape[0] // n_micro
    xm = x.reshape((n_micro, mb) + x.shape[1:])

    fn = jax.shard_map(
        functools.partial(
            _pipeline_shard_fn,
            stage_fn=stage_fn,
            axis=axis,
            n_stages=n_stages,
            n_micro=n_micro,
        ),
        mesh=mesh,
        in_specs=(P(axis), P()),   # params stage-sharded; input replicated
        out_specs=P(),
        check_vma=False,
    )
    out = fn(stage_params, xm)
    return out.reshape((n_micro * mb,) + out.shape[2:])
