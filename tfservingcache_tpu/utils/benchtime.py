"""Trustworthy on-device timing for jittable array functions.

Naive loops (call N times, ``block_until_ready``) time the host as much as
the device: async dispatch, per-call launch overhead and transfer-queue
backpressure all land in the measurement, and for a kernel of a few
microseconds they ARE the measurement. The fix: chain the N executions
*inside one compiled program* with a data dependency between iterations, so
the device must genuinely run every iteration, and subtract a 1-iteration
run to cancel dispatch/transfer overhead.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Sequence


def chained_device_time(
    fn: Callable[..., Any],
    args: Sequence[Any],
    iters: int = 16,
    repeats: int = 3,
    max_iters: int = 1024,
    return_valid: bool = False,
) -> float | tuple[float, bool]:
    """Seconds per call of ``fn(*args)`` measured on device.

    ``fn`` must be traceable and return an array (or pytree; the first leaf
    feeds the inter-iteration dependency). ``args[0]`` must be a float array:
    iteration i+1 perturbs it by ``1e-6 * out[0]`` so no two iterations are
    identical and the chain cannot be hoisted, cached, or reordered.

    Every *timed* call also gets a freshly perturbed ``args[0]``, so no
    timed run repeats an (executable, inputs) pair the warmup already
    executed. The per-iter estimate is the median over ``repeats``
    independent (1-iter, n-iter) pairs.

    ``iters`` is a STARTING chain length, not a fixed one: if the n-iter run
    does not take at least 2x the 1-iter run (median over the round), the
    subtraction is dispatch noise and the chain grows 4x — up to
    ``max_iters`` — re-compiling the longer chain each time. Budget
    accordingly for very cheap ``fn``: worst case ~4 extra compiles and a
    ``max_iters``-long chain per call. If dominance is never reached even at
    ``max_iters``, the (noisy) max_iters estimate is returned rather than
    failing — callers that publish the number should pass
    ``return_valid=True`` to get ``(estimate, dominated)`` back and mark the
    row noisy when ``dominated`` is False, instead of printing dispatch
    noise as if it were kernel time.
    """
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames="n")
    def loop(args, n):
        def body(carry, _):
            a, acc = carry
            out = fn(*a)
            first = jnp.ravel(jax.tree_util.tree_leaves(out)[0])[0]
            a = (a[0] + first.astype(a[0].dtype) * 1e-6,) + tuple(a[1:])
            return (a, acc + first.astype(jnp.float32)), None
        (a, acc), _ = jax.lax.scan(body, (tuple(args), jnp.float32(0)), None, length=n)
        return acc

    args = tuple(args)

    salt = [0]
    # step must survive rounding in args[0]'s dtype AT ITS MAGNITUDE: eps is
    # the spacing at 1.0, so an absolute step washes out for inputs of
    # magnitude >~ 8 (and bf16 eps ~8e-3 already needs it at magnitude 1) —
    # scale by max|args[0]| so at least the largest elements change
    scale = max(1.0, float(jnp.max(jnp.abs(args[0].astype(jnp.float32)))))
    step = 8 * float(jnp.finfo(args[0].dtype).eps) * scale

    def fresh() -> tuple:
        salt[0] += 1
        a0 = args[0] + jnp.asarray(salt[0] * step, args[0].dtype)
        jax.block_until_ready(a0)
        return (a0,) + args[1:]

    def measure(n: int) -> list[tuple[float, float]]:
        float(loop(args, 1))    # compile the 1-iter program
        float(loop(args, n))    # compile the n-iter program
        pairs = []
        for _ in range(repeats):
            a_short, a_long = fresh(), fresh()
            t0 = time.perf_counter()
            float(loop(a_short, 1))
            t1 = time.perf_counter()
            float(loop(a_long, n))
            t2 = time.perf_counter()
            pairs.append((t1 - t0, t2 - t1))
        return pairs

    # A fast kernel at small iters can vanish under dispatch overhead: the
    # n-iter run takes barely longer than the 1-iter run, the subtraction
    # lands at (or below) zero, and the caller would report a nonsense
    # "0.000 ms" (the r5 kernel-check small-shape artifact). Grow the chain
    # until the long run clearly dominates the short one, so the subtraction
    # carries signal, not noise.
    dominated = False
    while True:
        pairs = measure(iters)
        shorts = sorted(s for s, _ in pairs)
        longs = sorted(l for _, l in pairs)
        if longs[len(longs) // 2] >= 2.0 * shorts[len(shorts) // 2]:
            dominated = True
            break
        if iters >= max_iters:
            break
        iters = min(iters * 4, max_iters)
    estimates = sorted(
        max(l - s, 1e-9) / (iters - 1) for s, l in pairs
    )
    est = estimates[len(estimates) // 2]
    if return_valid:
        return est, dominated
    return est
