"""A prefill's token-wise stages over the row blocks that hold REAL rows.

An admission pads its prompt to a power-of-two bucket, and a stage that maps
each row to a row (a projection, an MLP, a norm, a gate) costs a pad row what
it costs a real one. ``over_real_rows`` runs such a stage a block of rows at a
time, for the blocks that hold a real row and no other; the rows past them
stay zero, and nothing reads them. The trip count is the device's (a traced
bound), the block a function of the bucket alone: no program is added, and a
forward that is not a long prefill (one token a lane, no ``took``, a short
bucket) traces the stage as it always was.

A leaf module: the families' files and ``models/generation.py`` both call it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# A bucket is cut into this many blocks (a prompt fills more than half of its
# bucket, so five to eight of them hold real rows) ...
ROW_BLOCKS = 8
# ... of never fewer rows than this, or the bucket runs whole: a block's
# product then stays at twice the v5e's ridge (240 rows: where a product takes
# as long as its weights' read), so re-reading a stage's weights a block hides
# under the block's product; and a program pays for its loops at every start
# (tracing and lowering them: about a second a bucket of an 8-layer model on
# the v5e's host, PR 48), which a 2048-row bucket's prompts do not earn back.
MIN_BLOCK_ROWS = 512


def row_block(t: int) -> int:
    """Rows a block of a ``t``-row bucket, 0 where its stages run whole: a
    function of the bucket alone, fixed at trace time (4096 -> 512, 8192 ->
    1024, 16384 -> 2048; nothing under 4096, and no length that is not a
    whole number of blocks)."""
    block = t // ROW_BLOCKS
    return block if t % ROW_BLOCKS == 0 and block >= MIN_BLOCK_ROWS else 0


def real_blocks(longest, block: int):
    """Blocks that hold a real row where the longest example has ``longest``.
    Operators only: the device (a traced scalar) and the host
    (``rows_computed``) run this line."""
    return (longest + block - 1) // block


def rows_computed(real: int, bucket: int) -> int:
    """Rows a looped stage computes of a ``bucket``-row prefill whose longest
    prompt has ``real`` tokens, worked out on the host as the device works out
    its trip count (``tpusc_prefill_rows_total{kind="computed"}``)."""
    block = row_block(bucket)
    return int(real_blocks(int(real), block)) * block if block else int(bucket)


def over_real_rows(fn, xs: tuple, took, *, in_axis: int = 1,
                   out_axis: int = 1, halo: int = 0):
    """``fn(*xs)`` for a stage that maps each row of its operands to the same
    row of its outputs and looks at no other row, computed for the row blocks
    that hold a real row: ``took (B,)`` says how many of each example's rows
    are real (None = all). Rows lie along ``in_axis`` of every operand and
    ``out_axis`` of every output (one array or a tuple of them); rows past
    the last block come back ZERO. A stage whose row ``t`` reads operand rows
    ``t .. t + halo`` (a causal convolution whose operand carries its
    ``halo`` leading rows) hands operands ``halo`` rows longer than its
    outputs.

    A ``lax.fori_loop`` whose bound the device computes (``real_blocks`` of the
    longest example): each trip slices a block of every operand, runs ``fn``
    on it and sets the block of every output, in place on the carry; ``fn`` is
    traced once for the body. Whatever ``fn`` closes over (weights, already
    cast) is the loop's invariant. With ``took`` None or a bucket
    ``row_block`` leaves whole this IS ``fn(*xs)``."""
    t = xs[0].shape[in_axis] - halo
    block = row_block(t)
    if took is None or not block:
        return fn(*xs)

    def take(start):
        return [jax.lax.dynamic_slice_in_dim(x, start, block + halo, in_axis)
                for x in xs]

    def zeros(part):
        shape = list(part.shape)
        shape[out_axis] = t
        return jnp.zeros(shape, part.dtype)

    outs = jax.tree_util.tree_map(
        zeros, jax.eval_shape(lambda: fn(*take(0))))

    def trip(i, outs):
        start = i * block
        return jax.tree_util.tree_map(
            lambda out, part: jax.lax.dynamic_update_slice_in_dim(
                out, part.astype(out.dtype), start, out_axis),
            outs, fn(*take(start)))

    return jax.lax.fori_loop(0, real_blocks(jnp.max(took), block), trip, outs)
