"""The gated delta rule of a linear-attention layer (Gated DeltaNet,
arXiv:2412.06464), in plain ``jax.numpy``.

A head keeps a MATRIX state ``S (d_k, d_v)`` in float32. For token ``t`` with
key ``k_t`` and query ``q_t`` (``d_k``, both already L2-normalised, the query
also scaled), value ``v_t`` (``d_v``), decay ``alpha_t`` in (0, 1) and write
strength ``beta_t`` in (0, 2)::

    S   <-  alpha_t S
    S   <-  S + k_t^T ( beta_t (v_t - k_t S) )        the delta rule
    o_t  =  q_t S

The state of every head of a lane is ONE array ``(d_k, H x d_v)``, heads side
by side in the minor dimension (30 heads of 192 are 5760 columns, 45 whole
128-lane rows, where a head's own 192 would fill one and a half): that is the
layout ``registry.LaneState`` stores, and both forms below take it in and hand
it out, which is what lets a request's state live beside the paged arena
between the programs that advance it.

Two forms of the one recurrence:

* ``delta_step``: ONE token a row. A row with ``took`` false keeps its state
  bit for bit (a select, as ``ops.ssm.selective_step``). Products with the
  state are multiply-and-sum in float32, not matrix products: on a TPU a
  float32 ``dot`` rounds its operands to bfloat16, and a step is bound by the
  state's bytes, not by its arithmetic. ``delta_step_live`` is the same step
  on a layer's slice of the whole state array, in place, for the lanes that
  took a token and no other: the decode step's form.
* ``delta_chunked``: ``T`` tokens a row (a prompt bucket) in chunks of
  ``CHUNK`` tokens, the WY / UT-transform form of arXiv:2412.06464. With
  ``g_t`` the running product of ``alpha`` inside a chunk and ``S_0`` the
  state the chunk starts from, the pseudo-values ``u_t = beta_t (v_t -
  alpha_t k_t S_{t-1})`` solve a unit lower-triangular system::

      (I + A) U = diag(beta) (V - diag(g) K S_0),
      A[t, i] = beta_t (g_t / g_i) (k_t . k_i)   for i < t

  which is solved ONCE a chunk for both right-hand sides, in parallel over
  the chunks (``W = (I + A)^-1 diag(beta g) K``, ``U_0 = (I + A)^-1
  diag(beta) V``, the inverse formed by ``_unit_lower_inverse``); what is
  left for the sequential pass over the chunks is three matrix products a
  chunk::

      U = U_0 - W S_0
      O = diag(g) Q S_0 + ((Q K^T) * D) U,   D[t, i] = g_t / g_i  for i <= t
      S_C = g_C S_0 + (diag(g_C / g) K)^T U

  The state returned is the one after ``real_len`` tokens: past it ``alpha =
  1`` and ``beta = 0`` make a token the identity. ``g_t / g_i`` is formed as
  ``exp(log g_t - log g_i)`` with ``i <= t`` only, so it never exceeds 1. A
  prompt longer than ``BLOCK`` tokens is taken a block at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Tokens a chunk of ``delta_chunked``: inside one the updates are matrix
# products, between two the state is carried (the published kernels' size).
CHUNK = 64
# Tokens ``delta_chunked`` takes at a time: the systems of one block's chunks
# are solved together (32 chunks x 30 heads are 960 of them), and what they
# leave in float32 (a token's 288 right-hand-side columns and as many solved)
# is a block's, 0.3 GB at 30 heads, not a 16384-token bucket's 2.4 GB.
BLOCK = 2048


def _heads(state, n_heads: int):
    """``(B, d_k, H x d_v)`` -> ``(B, H, d_k, d_v)``."""
    b, d_k, width = state.shape
    return state.reshape(b, d_k, n_heads, width // n_heads).transpose(0, 2, 1, 3)


def _flat(state):
    """``(B, H, d_k, d_v)`` -> ``(B, d_k, H x d_v)``."""
    b, h, d_k, d_v = state.shape
    return state.transpose(0, 2, 1, 3).reshape(b, d_k, h * d_v)


# Lanes a trip of ``delta_step_live``'s loop: a trip takes their states out
# of the array (2.2 MB each at 30 heads of 96 x 192), advances them and puts
# them back; the engine runs 2 to 6 live lanes of 16: one or two trips.
STEP_GROUP = 4


def _over_columns(x, d_v: int):
    """A value a head, ``(..., H)``, over that head's ``d_v`` columns of the
    state's minor dimension -> ``(..., H x d_v)``: a product with a 0 / 1
    matrix, exact (one term a column), which the compiler fuses into the
    multiply that uses it. A ``repeat`` says the same and costs a
    ``(..., H, d_v)`` array re-laid into ``H x d_v`` columns: 192 is one and a
    half 128-lane rows (on the v5e 10.7 ms a step of 9 layers at 16 lanes
    against 4.9: my chip run, PR 46). bfloat16 values are exact in one pass of
    the MXU; float32 ones take the six passes that keep them."""
    spread = jnp.repeat(jnp.eye(x.shape[-1], dtype=x.dtype), d_v, axis=1)
    return jnp.einsum(
        "...h,hc->...c", x, spread, preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                   else jax.lax.Precision.DEFAULT))


def _advance(state, q, k, v, alpha, beta):
    """One token a row, every row real: ``state (B, d_k, H x d_v)`` float32,
    ``q`` / ``k (B, H, d_k)``, ``v (B, H, d_v)``, ``alpha`` / ``beta (B, H)``
    -> (``o (B, H x d_v)`` float32, the state after), all in the state's own
    layout: nothing of the state's size is reshaped."""
    f32 = jnp.float32
    d_v = v.shape[-1]
    k_col = _over_columns(k.transpose(0, 2, 1), d_v)            # (B, d_k, H d_v)
    q_col = _over_columns(q.transpose(0, 2, 1), d_v)
    state = _over_columns(alpha.astype(f32), d_v)[:, None, :] * state
    u = _over_columns(beta.astype(f32), d_v) * (
        v.astype(f32).reshape(v.shape[0], -1) - jnp.sum(k_col * state, axis=1))
    state = state + k_col * u[:, None, :]
    return jnp.sum(q_col * state, axis=1), state


@jax.named_scope("step")
def delta_step(state, q, k, v, alpha, beta, took=None):
    """One token a row: ``state (B, d_k, H x d_v)`` float32 in, ``q`` / ``k
    (B, H, d_k)``, ``v (B, H, d_v)``, ``alpha`` / ``beta (B, H)`` -> (``o (B,
    H, d_v)`` float32, the state after). ``took (B,)`` (None = every row)
    names the rows whose token is real: the others keep ``state`` bit for bit
    (their ``o`` is junk nobody reads). Every row's state is read and
    written: ``delta_step_live`` is the form for a state array of which few
    lanes are live."""
    o, new = _advance(state, q, k, v, alpha, beta)
    if took is not None:
        new = jnp.where(took.astype(bool)[:, None, None], new, state)
    return o.reshape(v.shape), new


@jax.named_scope("step")
def delta_step_live(states, layer: int, q, k, v, alpha, beta, took=None,
                    live=None, group: int = STEP_GROUP):
    """``delta_step`` on layer ``layer``'s slice of the WHOLE state array
    ``states (layers, S, d_k, H x d_v)`` float32, in place on it, touching
    the lanes that took a token and no other -> (``o (S, H, d_v)`` float32,
    zeros for a lane that took none; the array after). The array is a decode
    chunk's carry (donated): a trip of the loop takes ``group`` lanes'
    states out of it (a dynamic slice a lane, no slice of a layer), advances
    them and puts them back (a dynamic update a lane), so a step moves the
    live lanes' states and the array is never copied, where setting a
    layer's slice whole reads and writes every lane's. ``live`` is ``(order,
    count)``, the lanes with those that took a token first and how many they
    are (None: worked out from ``took``; ``took`` None = every lane). The last trip's
    lanes past ``count`` took nothing and keep their states bit for bit (a
    select)."""
    lanes = states.shape[1]
    q, k, v, alpha, beta = map(jnp.asarray, (q, k, v, alpha, beta))
    took = (jnp.ones((lanes,), bool) if took is None
            else jnp.asarray(took).astype(bool))
    if live is None:
        live = (jnp.argsort(~took, stable=True).astype(jnp.int32),
                jnp.sum(took, dtype=jnp.int32))
    order, count = live
    group = min(group, lanes)
    size = states.shape[2:]

    def trip(i, carry):
        states, o = carry
        # past the end the slice is clamped: a lane an earlier trip took is
        # met again there, and must not be advanced twice
        first = jnp.minimum(i * group, lanes - group)
        at = jax.lax.dynamic_slice(order, (first,), (group,))
        # a lane's state by a dynamic slice of its own, and put back the same
        # way: in place on the carry. (ONE gather of the group's lanes,
        # ``states[layer, at]``, the v5e compiler serves by copying the whole
        # array: 0.88 ms a trip a layer, my chip run, PR 46.)
        old = jnp.concatenate([
            jax.lax.dynamic_slice(states, (layer, at[j], 0, 0), (1, 1) + size)[0]
            for j in range(group)])
        o_at, new = _advance(old, q[at], k[at], v[at], alpha[at], beta[at])
        real = took[at] & (first + jnp.arange(group) >= i * group)
        new = jnp.where(real[:, None, None], new, old)
        for j in range(group):
            states = jax.lax.dynamic_update_slice(
                states, new[j][None, None], (layer, at[j], 0, 0))
        return states, o.at[at].set(jnp.where(real[:, None], o_at, o[at]))

    states, o = jax.lax.fori_loop(
        0, (count + group - 1) // group, trip,
        (states, jnp.zeros((lanes, states.shape[-1]), jnp.float32)))
    return o.reshape(v.shape), states


@jax.named_scope("chunk")
def delta_chunked(state, q, k, v, alpha, beta, real_len=None, chunk: int = CHUNK,
                  block: int = BLOCK):
    """``T`` tokens a row: ``state (B, d_k, H x d_v)`` float32 in, ``q`` / ``k
    (B, T, H, d_k)``, ``v (B, T, H, d_v)``, ``alpha`` / ``beta (B, T, H)`` ->
    (``o (B, T, H, d_v)`` float32, the state after ``real_len (B,)`` of the
    tokens; None = all ``T``). ``o`` past ``real_len`` is junk nobody reads.
    ``T`` need not be a multiple of ``chunk``: the tail is padded with
    identity tokens. A prompt of more than ``block`` tokens is taken ``block``
    tokens at a time, the state carried from one to the next, so that the
    float32 operands of the triangular systems exist for one block only."""
    f32 = jnp.float32
    b, t_len, h, _ = k.shape
    if real_len is not None:
        real = (jnp.arange(t_len)[None, :]
                < real_len.astype(jnp.int32)[:, None])[..., None]   # (B, T, 1)
        alpha = jnp.where(real, alpha.astype(f32), 1.0)
        beta = jnp.where(real, beta.astype(f32), 0.0)
    pad = -t_len % (chunk if t_len <= block else block)
    if pad:
        widths = ((0, 0), (0, pad))
        q, k, v = (jnp.pad(a, widths + ((0, 0), (0, 0))) for a in (q, k, v))
        alpha = jnp.pad(alpha, widths + ((0, 0),), constant_values=1.0)
        beta = jnp.pad(beta, widths + ((0, 0),))
    padded = t_len + pad
    s = _heads(state.astype(f32), h)
    if padded <= block:
        o, s = _chunked_block(s, q, k, v, alpha, beta, chunk)
    else:
        def blocks(a):
            """``(B, T, ...)`` -> ``(T / block, B, block, ...)``."""
            return jnp.moveaxis(
                a.reshape(b, padded // block, block, *a.shape[2:]), 1, 0)

        def one(s, rows):
            o, s = _chunked_block(s, *rows, chunk)
            return s, o

        s, o = jax.lax.scan(one, s, tuple(map(blocks, (q, k, v, alpha, beta))))
        o = jnp.moveaxis(o, 0, 1).reshape(b, padded, *o.shape[3:])
    return o[:, :t_len], _flat(s)


# Rows of the diagonal blocks ``_unit_lower_inverse`` inverts by forward
# substitution before it doubles them up with matrix products.
INVERSE_BASE = 16


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a (..., C, C)``, ``C`` a
    power of two times ``INVERSE_BASE`` (or less than it), float32. The
    diagonal blocks of ``INVERSE_BASE`` rows by forward substitution (row
    ``i`` of the inverse is ``e_i - a[i, :i] X[:i]``: 15 small steps over
    every block at once), then neighbours joined, ``[[P, 0], [R, Q]]^-1 =
    [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``, until one block is left: matrix
    products, where XLA's ``TriangularSolve`` on the TPU walks the 64 rows of
    7680 systems a layer (my chip run, PR 46: it was most of a prefill's
    delta rule). Products at HIGHEST precision: they are thousandths of a
    layer's arithmetic, and the pseudo-values inherit their error."""
    hi = jax.lax.Precision.HIGHEST
    *lead, c, _ = a.shape
    base = min(INVERSE_BASE, c)
    n = c // base
    blocks = a.reshape(*lead, n, base, n, base)
    diag = jnp.stack([blocks[..., j, :, j, :] for j in range(n)], axis=-3)
    eye = jnp.eye(base, dtype=a.dtype)
    x = jnp.broadcast_to(eye, diag.shape)
    for i in range(1, base):
        # rows >= i of x are still the identity's, and a[i, j >= i] is 0
        row = eye[i] - jnp.einsum("...j,...jk->...k", diag[..., i, :], x,
                                  precision=hi)
        x = x.at[..., i, :].set(row)
    inv = [x[..., j, :, :] for j in range(n)]           # the diagonal's inverses
    size = base
    while len(inv) > 1:
        joined = []
        for pair in range(len(inv) // 2):
            lo = 2 * pair * size
            r = a[..., lo + size:lo + 2 * size, lo:lo + size]
            p_inv, q_inv = inv[2 * pair], inv[2 * pair + 1]
            corner = -jnp.einsum("...ij,...jk,...kl->...il", q_inv, r, p_inv,
                                 precision=hi)
            top = jnp.concatenate([p_inv, jnp.zeros_like(p_inv)], axis=-1)
            joined.append(jnp.concatenate(
                [top, jnp.concatenate([corner, q_inv], axis=-1)], axis=-2))
        inv, size = joined, 2 * size
    return inv[0]


def _chunked_block(s, q, k, v, alpha, beta, chunk: int):
    """The chunked form over ``T`` tokens, ``T`` a multiple of ``chunk``: the
    state ``s (B, H, d_k, d_v)`` float32 in and out, operands as
    ``delta_chunked``'s -> (``o (B, T, H, d_v)`` float32, the state after)."""
    f32 = jnp.float32
    b, t_len, h, d_k = k.shape
    d_v = v.shape[-1]
    q, k, v, alpha, beta = (t.astype(f32) for t in (q, k, v, alpha, beta))
    n = t_len // chunk

    def chunks(a):
        """``(B, T, H, ...)`` -> ``(n, B, H, chunk, ...)``."""
        a = a.reshape(b, n, chunk, h, *a.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v = chunks(q), chunks(k), chunks(v)                   # (n, B, H, C, d)
    log_g = jnp.cumsum(jnp.log(chunks(alpha)), axis=-1)         # (n, B, H, C)
    beta = chunks(beta)[..., None]                              # (n, B, H, C, 1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # D[t, i] = g_t / g_i where i <= t, else 0: the exponent is never positive
    decay = jnp.exp(jnp.where(
        lower, log_g[..., :, None] - log_g[..., None, :], -jnp.inf))
    g = jnp.exp(log_g)[..., None]                               # (n, B, H, C, 1)
    kk = jnp.einsum("...td,...id->...ti", k, k, preferred_element_type=f32)
    a_mat = beta * jnp.where(lower & ~jnp.eye(chunk, dtype=bool), decay * kk, 0.0)
    solved = jnp.einsum(
        "...ti,...id->...td", _unit_lower_inverse(a_mat),
        jnp.concatenate([beta * g * k, beta * v], axis=-1),
        precision=jax.lax.Precision.HIGHEST)
    w, u0 = solved[..., :d_k], solved[..., d_k:]                # (n, B, H, C, d)
    qk = decay * jnp.einsum("...td,...id->...ti", q, k, preferred_element_type=f32)
    k_out = (decay[..., -1, :, None]) * k                       # diag(g_C / g) K
    g_last = g[..., -1, :][..., None]                           # (n, B, H, 1, 1)

    def carry(s, rows):
        w_c, u0_c, q_c, qk_c, k_c, g_c, g_end = rows
        u = u0_c - jnp.einsum("bhtk,bhkv->bhtv", w_c, s, preferred_element_type=f32)
        o = (g_c * jnp.einsum("bhtk,bhkv->bhtv", q_c, s, preferred_element_type=f32)
             + jnp.einsum("bhti,bhiv->bhtv", qk_c, u, preferred_element_type=f32))
        s = g_end * s + jnp.einsum("bhtk,bhtv->bhkv", k_c, u,
                                   preferred_element_type=f32)
        return s, o

    s, o = jax.lax.scan(carry, s, (w, u0, q, qk, k_out, g, g_last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)               # (B, n, C, H, d_v)
    return o.reshape(b, t_len, h, d_v), s
