"""The selective scan of a Mamba layer (arXiv:2312.00752), in plain ``lax``.

For channel ``e`` of ``E`` and state index ``n`` of ``N``, token ``t``::

    H_t[n, e] = exp(dt_t[e] A[n, e]) H_{t-1}[n, e] + dt_t[e] x_t[e] B_t[n]
    y_t[e]    = sum_n H_t[n, e] C_t[n] + D[e] x_t[e]

``H`` is float32 and laid out ``(N, E)``: the channels are the minor
dimension, so a state fills whole 128-lane rows (``(E, N)`` with ``N`` = 16
would fill an eighth of each). ``A`` comes in the same layout and is negative,
``dt`` is positive (after its softplus), so ``exp(dt A)`` is in ``(0, 1]``.

Two forms of the same recurrence, both taking the state in and handing the
state out, which is what lets a request's state live beside the paged arena
(``registry.LaneState``) between the programs that advance it:

* ``selective_step``: ONE token a row (a decode step over every lane). A row
  with ``took`` false keeps its state bit for bit (a select, not a product
  with one: ``-0.0 * 1 + 0`` is ``+0.0``).
* ``selective_scan``: ``T`` tokens a row (a prompt bucket). The state
  returned is the one after ``real_len`` tokens: ``dt_t = 0`` past it makes
  the step ``H_t = 1 * H_{t-1} + 0``. A ``lax.scan`` over the tokens whose body
  is unrolled ``SCAN_UNROLL`` tokens a trip: the carry is the ``(B, N, E)``
  state and ``exp(dt_t A)`` is formed inside the body, so no ``(T, N, E)``
  array exists (1024 x 16 x 5120 x 4 B = 336 MB a layer at the benchmark's
  width), only the ``(T, E)`` and ``(T, N)`` operands.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Tokens a trip of the scan's loop: the body of one trip is straight-line code
# XLA fuses across tokens, so the loop's own cost is paid once a trip.
SCAN_UNROLL = 8


def _advance(h, dt, x, a, b, c, d_skip):
    """One token: ``h (B, N, E)`` float32, ``dt`` / ``x (B, E)``, ``b`` /
    ``c (B, N)``, ``a (N, E)``, ``d_skip (E,)`` -> (``h'``, ``y (B, E)``),
    all float32."""
    decay = jnp.exp(dt[:, None, :] * a[None])
    h = decay * h + (dt * x)[:, None, :] * b[:, :, None]
    y = jnp.sum(h * c[:, :, None], axis=1) + d_skip[None] * x
    return h, y


def _f32(*arrays):
    return tuple(t.astype(jnp.float32) for t in arrays)


@jax.named_scope("step")
def selective_step(h, dt, x, a, b, c, d_skip, took=None):
    """One token a row: ``h (B, N, E)`` float32 in, ``dt`` / ``x (B, E)``,
    ``b`` / ``c (B, N)`` -> (``y (B, E)`` float32, the state after). ``took
    (B,)`` (None = every row) names the rows whose token is real: the others
    keep ``h`` bit for bit (their ``y`` is junk nobody reads)."""
    new, y = _advance(h, *_f32(dt, x, a, b, c, d_skip))
    if took is not None:
        new = jnp.where(took.astype(bool)[:, None, None], new, h)
    return y, new


@jax.named_scope("scan")
def selective_scan(h, dt, x, a, b, c, d_skip, real_len=None):
    """``T`` tokens a row: ``h (B, N, E)`` float32 in, ``dt`` / ``x (B, T,
    E)``, ``b`` / ``c (B, T, N)`` -> (``y (B, T, E)`` float32, the state after
    ``real_len (B,)`` of the tokens; None = all ``T``). ``y`` past
    ``real_len`` is junk nobody reads."""
    dt, x, a, b, c, d_skip = _f32(dt, x, a, b, c, d_skip)
    t_len = dt.shape[1]
    if real_len is not None:
        real = jnp.arange(t_len)[None, :] < real_len.astype(jnp.int32)[:, None]
        dt = jnp.where(real[:, :, None], dt, 0.0)

    def token(h, row):
        dt_t, x_t, b_t, c_t = row
        return _advance(h, dt_t, x_t, a, b_t, c_t, d_skip)

    rows = tuple(jnp.moveaxis(t, 1, 0) for t in (dt, x, b, c))
    h, y = jax.lax.scan(token, h, rows,
                        unroll=max(1, min(SCAN_UNROLL, t_len)))
    return jnp.moveaxis(y, 0, 1), h
