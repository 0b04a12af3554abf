"""The gated delta rule of a linear-attention layer (Gated DeltaNet,
arXiv:2412.06464).

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
layout ``registry.LaneState`` stores, and every form below takes it in and
hands it out, which is what lets a request's state live beside the paged arena
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

  which is solved ONCE a chunk for both right-hand sides (``W = (I + A)^-1
  diag(beta g) K``, ``U_0 = (I + A)^-1 diag(beta) V``); what is left is three
  matrix products a chunk with the state carried from chunk to chunk::

      U = U_0 - W S_0
      O = diag(g) Q S_0 + ((Q K^T) * D) U,   D[t, i] = g_t / g_i  for i <= t
      S_C = g_C S_0 + (diag(g_C / g) K)^T U

  The state returned is the one after ``real_len`` tokens: past it ``alpha =
  1`` and ``beta = 0`` make a token the identity. ``g_t / g_i`` is formed as
  ``exp(log g_t - log g_i)`` with ``i <= t`` only, so it never exceeds 1.

A decay a CHANNEL (Kimi Delta Attention, arXiv:2510.26692): ``alpha_t`` is a
vector over ``d_k``, ``S <- diag(alpha_t) S`` scales the state's ROWS, and
every form takes ``alpha`` with that one more axis (``(..., H, d_k)`` where a
decay a head is ``(..., H)``). In a chunk the decays no longer factor out of
``K K^T`` and ``Q K^T``: with ``G_t (d_k)`` the running sum of ``log alpha``::

    A[t, i] = beta_t sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])      for i < t

and ``exp(-G)`` alone overflows, so ``_chunked_block_channel`` forms the
products in sub-blocks of ``SUB`` tokens with no exponent above 0: a pair ``(t,
i)`` in ONE sub-block takes ``exp(G_t - G_i)`` directly, summed over the
channel; a pair in two takes the later sub-block's first row ``r`` as its
reference, ``(k_t exp(G_t - G_r)) . (k_i exp(G_r - G_i))``, a matrix product
of two factors <= 1. ``W = (I + A)^-1 diag(beta) (K exp(G))``, ``O = (Q
exp(G)) S_0 + P U`` with ``P`` the same products of ``Q`` and ``K`` for ``i <=
t``, ``S_C = diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U``.

Which chunked form runs where (``_kernel_refusal`` decides from the backend
and the shapes when the program is traced; ``ops.attention.dispatch_tally()``
records it under ``delta_chunked``):

* on one TPU chip, for bfloat16 operands with heads in pairs, ``d_k`` a
  multiple of 16, ``d_v`` a multiple of 64 and a lane's state within the
  kernel's VMEM: ``delta_chunk_kernel``, ONE Pallas kernel a call. The state
  goes in and comes out in the stored layout and stays in VMEM from a lane's
  first chunk to its last; a grid step is one chunk of every head, read from
  the operands as the projection left them (``(T, H x d)``), two heads at a
  time stacked along the 128 rows of a matrix-unit pass; ``(I + A)^-1`` is
  formed inside it (``_unit_lower_inverses``); chunks wholly past ``real_len``
  are skipped, not multiplied as identities. Same mathematics and the same
  roundings as the block form on that chip: the inverse and the solve at
  float32's exactness, the products with the state on bfloat16 operands (what
  the matrix unit makes of a float32 ``dot``'s) into a float32 state.
* the same chip and operands with a decay a CHANNEL:
  ``delta_channel_chunk_kernel``, the other's twin (the same grid, the same
  residence of the state, the same skipping) and not a mode of it: what it
  adds is what a decay a head needs none of. It takes each token's OWN
  log-decay ``(T, H x d_k)`` float32 beside the keys and forms the running sum
  inside a chunk itself (a product with a triangle of ones, the decay's three
  bfloat16 parts each exact); the diagonal sub-blocks' ``exp(G_t - G_i)`` a
  column at a time on the vector unit, once for ``K K^T`` and ``Q K^T``, the
  sub-blocks below them through the later one's first row as matrix products
  (``K K^T``'s and ``W``'s at float32's exactness, ``_exact_dot_f32``); every
  exponent clamped at 0. The state goes a HEAD at a time through its products
  (the decays are its rows' own). The gate counts a chunk's wider operands
  into the VMEM it allows.
* everywhere else (the CPU, another width, float32 operands on the chip):
  ``_chunked_block`` (a decay a head) / ``_chunked_block_channel`` (a decay a
  channel) in plain ``jax.numpy``, the references the tests hold the kernels
  to: the inverse by ``_unit_lower_inverse`` for all of a block's chunks at
  once, a ``lax.scan`` over the chunks, a prompt longer than ``BLOCK`` tokens
  a block at a time.

Which one-token step runs where (``_step_kernel_refusal`` decides, from the
backend, the state's dtype and the widths when the program is traced;
``dispatch_tally()`` records it under ``delta_step_live``):

* on one TPU chip, for a float32 state whose heads go into blocks of whole
  128-lane rows: ``delta_step_kernel``, ONE Pallas kernel a layer, in place on
  the whole state array (aliased). Its grid is (block of whole heads, live
  lane) with the live COUNT as the second bound, so a live lane's state is
  read once and written once, a block at a time, and a lane that took nothing
  costs no grid step. A decay a head and a decay a channel are one kernel:
  the decay's rank is read from its shape.
* everywhere else: ``_step_loop``, plain ``jax.numpy``, ``STEP_GROUP`` lanes a
  trip, four passes over their states: the reference of the kernel's tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tfservingcache_tpu.ops.attention import _record_dispatch

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


# Lanes a trip of ``_step_loop``, ``delta_step_live``'s form off the TPU: a
# trip takes their states out of the array (2.2 MB each at 30 heads of 96 x
# 192), advances them and puts them back; the engine runs 2 to 6 live lanes
# of 16: one or two trips.
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
    (``alpha (B, H, d_k)``: a decay a channel, the state's rows each their
    own) -> (``o (B, H x d_v)`` float32, the state after), all in the state's
    own layout: nothing of the state's size is reshaped."""
    f32 = jnp.float32
    d_v = v.shape[-1]
    k_col = _over_columns(k.transpose(0, 2, 1), d_v)            # (B, d_k, H d_v)
    q_col = _over_columns(q.transpose(0, 2, 1), d_v)
    if alpha.ndim == k.ndim:            # a channel: (B, d_k, H) over columns
        state = _over_columns(alpha.astype(f32).transpose(0, 2, 1), d_v) * state
    else:
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
def delta_step_live(  # static-bounded: layer -- a model's linear-layer index (a few a model)
        states, layer: int, q, k, v, alpha, beta, took=None, live=None):
    """``delta_step`` on layer ``layer``'s slice of the WHOLE state array
    ``states (layers, S, d_k, H x d_v)`` float32, in place on it, touching
    the lanes that took a token and no other -> (``o (S, H, d_v)`` float32,
    zeros for a lane that took none; the array after). The array is a decode
    chunk's carry (donated) and is never copied nor sliced a layer whole.
    ``live`` is ``(order, count)``, the lanes with those that took a token
    first and how many they are (None: worked out from ``took``; ``took``
    None = every lane). Where ``_step_kernel_refusal`` has no objection (one
    TPU chip, a float32 state, heads in blocks of whole 128-lane rows) the
    live lanes go through ``delta_step_kernel``, a grid step a block of
    whole heads of ONE live lane: each live lane's state is read once and
    written once and no other lane's is touched. Elsewhere (the CPU, another
    width) through ``_step_loop``, plain ``jax.numpy``, the reference the
    tests hold the kernel to: a trip takes ``STEP_GROUP`` lanes' states out
    of the array, live or not, and makes four passes over them.
    ``ops.attention.dispatch_tally()`` records which under
    ``delta_step_live``."""
    lanes = states.shape[1]
    q, k, v, alpha, beta = map(jnp.asarray, (q, k, v, alpha, beta))
    took = (jnp.ones((lanes,), bool) if took is None
            else jnp.asarray(took).astype(bool))
    if live is None:
        live = (jnp.argsort(~took, stable=True).astype(jnp.int32),
                jnp.sum(took, dtype=jnp.int32))
    why = _step_kernel_refusal(states.dtype, *k.shape[1:], v.shape[-1])
    _record_dispatch(
        "delta_step_live", "reference" if why else "kernel",
        why or ("interpret" if DELTA_KERNEL_INTERPRET else "pallas"),
        states.shape, k.shape, v.shape)
    if why is not None:
        return _step_loop(states, layer, q, k, v, alpha, beta, took, live)
    o, states = delta_step_kernel(states, layer, q, k, v, alpha, beta, *live,
                                  interpret=bool(DELTA_KERNEL_INTERPRET))
    return jnp.where(took[:, None, None], o.reshape(v.shape), 0.0), states


@jax.named_scope("chunk")
def delta_chunked(state, q, k, v, alpha, beta, real_len=None, chunk: int = CHUNK,
                  block: int = BLOCK):
    """``T`` tokens a row: ``state (B, d_k, H x d_v)`` float32 in, ``q`` / ``k
    (B, T, H, d_k)``, ``v (B, T, H, d_v)``, ``alpha`` / ``beta (B, T, H)``
    (``alpha (B, T, H, d_k)``: a decay a channel) ->
    (``o (B, T, H, d_v)`` float32, the state after ``real_len (B,)`` of the
    tokens; None = all ``T``). ``o`` past ``real_len`` is junk nobody reads.
    ``T`` need not be a multiple of ``chunk``: the tail is padded with
    identity tokens. Where ``_kernel_refusal`` has no objection (one TPU chip,
    the widths the kernel takes) the chunks go through ``delta_chunk_kernel``
    (``delta_channel_chunk_kernel`` for a decay a channel); elsewhere through
    ``_chunked_block`` (``_chunked_block_channel``), a prompt of more than ``block``
    tokens ``block`` tokens at a time, the state carried from one to the next,
    so that the float32 operands of the triangular systems exist for one
    block only."""
    f32 = jnp.float32
    b, t_len, h, _ = k.shape
    channel = alpha.ndim == k.ndim          # a decay a channel
    if real_len is not None:
        real = (jnp.arange(t_len)[None, :]
                < real_len.astype(jnp.int32)[:, None])[..., None]   # (B, T, 1)
        alpha = jnp.where(real[..., None] if channel else real,
                          alpha.astype(f32), 1.0)
        beta = jnp.where(real, beta.astype(f32), 0.0)
    why = _kernel_refusal(state, k, v, chunk, channel)
    _record_dispatch(
        "delta_chunked", "reference" if why else "kernel",
        why or ("interpret" if DELTA_KERNEL_INTERPRET else "pallas"),
        state.shape, k.shape, v.shape)
    pad = -t_len % (chunk if why is None or t_len <= block else block)
    if pad:
        widths = ((0, 0), (0, pad))
        q, k, v = (jnp.pad(a, widths + ((0, 0), (0, 0))) for a in (q, k, v))
        alpha = jnp.pad(alpha, widths + ((0, 0),) * (alpha.ndim - 2),
                        constant_values=1.0)
        beta = jnp.pad(beta, widths + ((0, 0),))
    padded = t_len + pad
    if why is None:
        if channel:     # each token's own log-decay: the kernel sums a chunk's
            kernel = delta_channel_chunk_kernel
            log_g = jnp.maximum(jnp.log(alpha.astype(f32)),
                                LOG_DECAY_FLOOR).reshape(b, padded, -1)
        else:
            kernel = delta_chunk_kernel
            log_g = jnp.cumsum(jnp.log(alpha.astype(f32)).reshape(
                b, padded // chunk, chunk, h), axis=2).reshape(b, padded, h)
        if real_len is None:
            real_len = jnp.full((b,), t_len, jnp.int32)
        o, s = kernel(
            state, *(a.reshape(b, padded, -1) for a in (q, k, v)), log_g,
            beta.astype(f32), real_len, chunk, interpret=bool(DELTA_KERNEL_INTERPRET))
        return o.reshape(b, padded, h, -1)[:, :t_len], s
    s = _heads(state.astype(f32), h)
    chunked_block = _chunked_block_channel if channel else _chunked_block
    if padded <= block:
        o, s = chunked_block(s, q, k, v, alpha, beta, chunk)
    else:
        def blocks(a):
            """``(B, T, ...)`` -> ``(T / block, B, block, ...)``."""
            return jnp.moveaxis(
                a.reshape(b, padded // block, block, *a.shape[2:]), 1, 0)

        def one(s, rows):
            o, s = chunked_block(s, *rows, chunk)
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


# Tokens a sub-block of a chunk where the decay is a channel's: inside one the
# decays between two tokens are taken directly, between two through the later
# one's first row (``_chunked_block_channel``).
SUB = 16
# The least ``log alpha`` the chunked form reckons with: a decay that
# underflowed to 0 would give ``G = -inf`` and ``exp(G_t - G_i)`` a NaN; at
# e^-80 a row of the state is gone all the same (float32's least normal value
# is e^-87).
LOG_DECAY_FLOOR = -80.0


def _decayed_products(x, k, log_g, sub: int, precision=None):
    """``P[t, i] = sum_c x_t[c] k_i[c] exp(G_t[c] - G_i[c])`` for ``i <= t``
    inside each chunk, 0 above the diagonal: ``x`` / ``k`` / ``log_g (..., C,
    d_k)`` float32 (``log_g`` the running log-decay a channel) -> ``(..., C,
    C)``. No exponent is above 0: the diagonal sub-blocks of ``sub`` tokens
    take ``exp(G_t - G_i)`` itself, multiply-and-sum over the channel; the
    sub-blocks below them a matrix product of ``x_t exp(G_t - G_r)`` and ``k_i
    exp(G_r - G_i)`` with ``r`` the later sub-block's first row."""
    f32 = jnp.float32
    *lead, c, d_k = k.shape
    n = c // sub
    cut = lambda a: a.reshape(*lead, n, sub, d_k)                # noqa: E731
    xs, ks, gs = cut(x), cut(k), cut(log_g)
    within = jnp.tril(jnp.ones((sub, sub), bool))[..., None]     # i <= t
    direct = jnp.sum(
        xs[..., :, None, :] * ks[..., None, :, :] * jnp.exp(jnp.where(
            within, gs[..., :, None, :] - gs[..., None, :, :], -jnp.inf)),
        axis=-1)                                                 # (..., n, sub, sub)
    rows = []
    for a in range(n):
        ref = gs[..., a, :1, :]                                  # G_r (..., 1, d_k)
        parts = []
        if a:
            earlier = slice(0, a * sub)
            parts.append(jnp.einsum(
                "...td,...id->...ti", xs[..., a, :, :] * jnp.exp(gs[..., a, :, :] - ref),
                k[..., earlier, :] * jnp.exp(ref - log_g[..., earlier, :]),
                preferred_element_type=f32, precision=precision))
        parts.append(direct[..., a, :, :])
        if a + 1 < n:
            parts.append(jnp.zeros((*lead, sub, c - (a + 1) * sub), f32))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _chunked_block_channel(s, q, k, v, alpha, beta, chunk: int):
    """``_chunked_block`` for a decay a channel, ``alpha (B, T, H, d_k)``: the
    same state ``s (B, H, d_k, d_v)`` float32 in and out and the same
    operands -> (``o (B, T, H, d_v)`` float32, the state after). The decays
    lie INSIDE the intra-chunk products (``_decayed_products``), every other
    factor is ``exp`` of a running log-decay (<= 0) or of the chunk's end
    against a token's (<= 0)."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    b, t_len, h, d_k = k.shape
    d_v = v.shape[-1]
    q, k, v, alpha, beta = (t.astype(f32) for t in (q, k, v, alpha, beta))
    n = t_len // chunk
    sub = SUB if chunk % SUB == 0 else chunk

    def chunks(a):
        """``(B, T, H, ...)`` -> ``(n, B, H, chunk, ...)``."""
        a = a.reshape(b, n, chunk, h, *a.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v = chunks(q), chunks(k), chunks(v)                   # (n, B, H, C, d)
    log_g = jnp.cumsum(jnp.maximum(jnp.log(chunks(alpha)), LOG_DECAY_FLOOR),
                       axis=-2)                                 # (n, B, H, C, d_k)
    beta = chunks(beta)[..., None]                              # (n, B, H, C, 1)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    # the products the inverse is made of at HIGHEST: the pseudo-values
    # inherit their error
    a_mat = beta * jnp.where(strict, _decayed_products(k, k, log_g, sub, hi), 0.0)
    g = jnp.exp(log_g)                                          # <= 1
    solved = jnp.einsum(
        "...ti,...id->...td", _unit_lower_inverse(a_mat),
        jnp.concatenate([beta * g * k, beta * v], axis=-1), precision=hi)
    w, u0 = solved[..., :d_k], solved[..., d_k:]                # (n, B, H, C, d)
    qk = _decayed_products(q, k, log_g, sub)
    g_end = log_g[..., -1:, :]                                  # G_C (n, B, H, 1, d_k)
    k_out = jnp.exp(g_end - log_g) * k                          # K exp(G_C - G)
    g_last = jnp.exp(jnp.swapaxes(g_end, -1, -2))               # (n, B, H, d_k, 1)

    def carry(s, rows):
        w_c, u0_c, qg_c, qk_c, k_c, g_end = rows
        u = u0_c - jnp.einsum("bhtk,bhkv->bhtv", w_c, s, preferred_element_type=f32)
        o = (jnp.einsum("bhtk,bhkv->bhtv", qg_c, s, preferred_element_type=f32)
             + jnp.einsum("bhti,bhiv->bhtv", qk_c, u, preferred_element_type=f32))
        s = g_end * s + jnp.einsum("bhtk,bhtv->bhkv", k_c, u,
                                   preferred_element_type=f32)
        return s, o

    s, o = jax.lax.scan(carry, s, (w, u0, g * q, qk, k_out, g_last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)               # (B, n, C, H, d_v)
    return o.reshape(b, t_len, h, d_v), s


# -- the chunked form as one Pallas kernel --------------------------------------

# Tests flip this to run the kernel through its interpreter on the CPU
# (trace-time only, like ``ops.moe.MOE_KERNEL_INTERPRET``).
DELTA_KERNEL_INTERPRET = False

# What the kernel may hold in VMEM: the lane's state in and out, each twice
# (the pipeline's two buffers), and once more as the kernel keeps it between
# a lane's first chunk and its last, beside a chunk's operands and its float32
# temporaries. 2.2 MB of state at 30 heads of 96 x 192 are 11 MB of it.
VMEM_LIMIT = 64 << 20


def _kernel_refusal(state, k, v, chunk: int, channel: bool = False) -> str | None:
    """Why the chunked rule's kernel (``delta_chunk_kernel`` for a decay a
    head; ``channel``: ``delta_channel_chunk_kernel`` for a decay a channel)
    cannot take these operands on this backend (None = it can). The same
    visible conditions for both: the TPU (one chip: the family binds no mesh),
    bfloat16 operands, heads in pairs whose value columns are whole 128-lane
    rows (so a pair's slice of the state is cut where the rows are), key
    widths of whole sublane tiles, a chunk of whole bfloat16 tiles that is a
    power of two, and the kernel's VMEM: the lane's state five times (in and
    out, two pipeline buffers each, and the resident copy) within half of it,
    and for a decay a channel, whose chunk also holds a float32 log-decay as
    wide as the keys, the state and a chunk's operands (each twice in the
    pipeline and once a pair first) within three quarters of it."""
    h, d_k, d_v = k.shape[2], k.shape[3], v.shape[3]
    if not DELTA_KERNEL_INTERPRET:
        if jax.default_backend() != "tpu":
            return f"backend={jax.default_backend()}"
        if k.dtype != jnp.bfloat16 or v.dtype != jnp.bfloat16:
            return f"operands {k.dtype} / {v.dtype}, not bfloat16"
    if h % 2:
        return f"heads={h} not in pairs"
    if d_k % 16 or (2 * d_v) % 128:
        return f"d_k={d_k} no multiple of 16 or d_v={d_v} no multiple of 64"
    if chunk < 16 or chunk & (chunk - 1):
        return f"chunk={chunk} not a power of two of at least 16"
    held = 5 * state.shape[1] * state.shape[2] * 4
    if held > VMEM_LIMIT // 2:
        return f"a lane's state ({d_k} x {h * d_v} float32) past the VMEM budget"
    if channel:
        # q, k, log-decay (d_k wide) and v, o (d_v wide) of one chunk
        operands = chunk * h * (d_k * (2 + 2 + 4) + d_v * (2 + 4))
        if held + 3 * operands > VMEM_LIMIT * 3 // 4:
            return (f"a lane's state and a chunk's operands ({h} heads of {d_k} / "
                    f"{d_v}, a decay a channel) past the VMEM budget")
    return None


def _split3(m):
    """Float32 ``m`` as the sum of three parts that are bfloat16 values (8 +
    8 + 8 of its 24 bits), each still float32: ``m == m1 + m2 + m3`` with
    nothing rounded."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    m1 = m.astype(bf16).astype(f32)
    r1 = m - m1
    m2 = r1.astype(bf16).astype(f32)
    return m1, m2, r1 - m2


def _exact_dot(m, rhs):
    """``m @ rhs`` for float32 ``m`` and bfloat16 ``rhs`` with nothing
    rounded but the float32 sums: ``m``'s three parts stacked along the rows
    (the matrix unit holds ``rhs`` once), each part's product exact. What
    HIGHEST gives two float32 operands in six passes, in three, because the
    second operand IS bfloat16."""
    rows = m.shape[0]
    parts = jnp.dot(
        jnp.concatenate([p.astype(jnp.bfloat16) for p in _split3(m)], axis=0),
        rhs, preferred_element_type=jnp.float32)
    return (parts[2 * rows:] + parts[rows:2 * rows]) + parts[:rows]


def _unit_lower_inverses(a: list, chunk: int) -> list:
    """``(I + a)^-1`` for each of the kernel's pair matrices ``a (2 chunk, 2
    chunk)`` float32, block-diagonal (a head a block) and strictly lower
    triangular: the 2 x 2 diagonal blocks outright, then neighbours joined as
    ``_unit_lower_inverse`` joins them, ``[[P, 0], [R, Q]]^-1 = [[P^-1, 0],
    [-Q^-1 R P^-1, Q^-1]] = X - X N X`` with ``X`` the blocks' inverses and
    ``N`` the ``R``'s, until a head's block is whole. Both products are
    HIGHEST's: the six products of the operands' bfloat16 parts that are not
    below float32's last bit, each exact in the float32 accumulator. They
    take three passes of the matrix unit, not six: ``N``'s columns are the
    FIRST halves of the joined blocks and ``N X``'s rows the SECOND halves, so
    half of either product's inner dimension is empty, and a second part's
    product rides there, moved over by the blocks' size. Every pair's product
    is written before the next stage's, so that one pair's runs while
    another's is awaited (the ten products of a pair are one chain)."""
    from jax.experimental.pallas import tpu as pltpu

    f32, bf16 = jnp.float32, jnp.bfloat16
    two = 2 * chunk
    row = jax.lax.broadcasted_iota(jnp.int32, (two, two), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (two, two), 1)
    roll = pltpu.roll

    def three_passes(l12, l13, r1, r2, r3):
        """``l1 r1 + l2 r1 + l1 r2 + l2 r2 + l1 r3 + l3 r1`` from operands
        that hold two parts each, the second moved into the empty half."""
        dot = functools.partial(jnp.dot, preferred_element_type=f32)
        l12, l13, r1, r2, r3 = (m.astype(bf16) for m in (l12, l13, r1, r2, r3))
        return (dot(l13, r3) + dot(l12, r2)) + dot(l12, r1)

    def joined(parts, xs, size: int):
        top = (row // size) % 2 == 0            # rows of first halves
        right = (col // size) % 2 == 1          # columns of second halves
        below = ((row // (2 * size)) == (col // (2 * size))) & ~top & ~right
        out = []
        x3 = [_split3(x) for x in xs]
        # N X: N's parts as they are and moved right by ``size``; X's
        # first-half rows as they are and copied down by ``size``
        nx = []
        for n, (x1, x2, x3_) in zip(parts, x3):
            n1, n2, n3 = (jnp.where(below, m, 0.0) for m in n)
            down = roll(x1, size, 0)
            nx.append(three_passes(
                n1 + roll(n2, size, 1), n1 + roll(n3, size, 1),
                jnp.where(top, x1, down), jnp.where(top, x2, roll(x2, size, 0)),
                jnp.where(top, x3_, down)))
        # X (N X): X's second-half columns as they are and moved left by
        # ``size``; N X's rows (all in second halves) as they are and copied up
        for x, (x1, x2, x3_), t in zip(xs, x3, nx):
            t1, t2, t3 = _split3(t)
            up = roll(t1, two - size, 0)
            out.append(x - three_passes(
                jnp.where(right, x1, roll(x2, two - size, 1)),
                jnp.where(right, x1, roll(x3_, two - size, 1)),
                t1 + up, t2 + roll(t2, two - size, 0), t3 + up))
        return out

    eye = (row == col).astype(f32)
    x = [eye - jnp.where((row // 2) == (col // 2), m, 0.0) for m in a]
    parts = [_split3(m) for m in a]
    size = 2
    while size < chunk:
        x = joined(parts, x, size)
        size *= 2
    return x


@jax.jit
def _advance_pairs(k2, q2, v1, lg_r, be_r, lg_c, be_c, s):
    """One chunk of a few pairs of heads, from values to values; every
    argument a tuple with an entry a pair: ``k2`` / ``q2 (2 chunk, d_k)`` (the
    pair's second head's rows under the first's), ``v1 (chunk, 2 d_v)``,
    ``lg_r`` / ``be_r (1, 2 chunk)`` and ``lg_c`` / ``be_c (2 chunk, 1)`` the
    running log-decay and the write strength as rows and as columns, ``s (d_k,
    2 d_v)`` the pair's state -> (``o (chunk, 2 d_v)`` a pair, the states
    after). A pair's matrices are block-diagonal, a head a block. Every stage
    is written for all the pairs before the next, so that one pair's products
    run while another's are awaited. A ``jit`` of its own, so that the
    kernel's every trace (a prompt bucket, a layer) finds THIS traced once a
    process."""
    f32 = jnp.float32
    # what a product with the state takes is rounded to the operands' own
    # dtype: bfloat16 in a serving program, which is what the matrix unit
    # makes of a float32 ``dot``'s operands anyway; float32 operands (the
    # CPU's tests) keep every product float32
    rounded = k2[0].dtype
    chunk, d_v = v1[0].shape[0], v1[0].shape[1] // 2
    two = 2 * chunk
    js = range(len(k2))
    row = jax.lax.broadcasted_iota(jnp.int32, (two, two), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (two, two), 1)
    same = (row < chunk) == (col < chunk)           # the two diagonal blocks
    lower, strict = same & (col <= row), same & (col < row)
    # a pair's first head: the stacked rows' upper half, the value columns'
    # left half
    first = jax.lax.broadcasted_iota(jnp.int32, (two, 1), 0) < chunk
    left = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * d_v), 1) < d_v
    mine = first == left                # a head's rows over its own columns
    nt = (((1,), (1,)), ((), ()))
    dot = functools.partial(jnp.dot, preferred_element_type=f32)
    # D[t, i] = g_t / g_i where i <= t in one head, else 0
    decay = [jnp.exp(jnp.where(lower, lg_c[j] - lg_r[j], -jnp.inf)) for j in js]
    both = [jax.lax.dot_general(jnp.concatenate([k2[j], q2[j]], axis=0), k2[j],
                                nt, preferred_element_type=f32)
            for j in js]                                    # K K^T over Q K^T
    a = [be_c[j] * jnp.where(strict, decay[j] * both[j][:two], 0.0) for j in js]
    qk = [(decay[j] * both[j][two:]).astype(rounded) for j in js]
    x = _unit_lower_inverses(a, chunk)
    # W = (I + a)^-1 diag(beta g) K and U_0 = (I + a)^-1 diag(beta) V
    w = [_exact_dot(x[j] * (be_r[j] * jnp.exp(lg_r[j])), k2[j]).astype(rounded)
         for j in js]
    u0 = [_exact_dot(x[j] * be_r[j], jnp.concatenate([v1[j], v1[j]], axis=0))
          for j in js]                                      # (2C, 2 d_v)
    s_b = [s[j].astype(rounded) for j in js]
    ws_qs = [dot(jnp.concatenate([w[j], q2[j]], axis=0), s_b[j]) for j in js]
    u = [jnp.where(mine, u0[j] - ws_qs[j][:two], 0.0).astype(rounded) for j in js]
    o2 = [jnp.exp(lg_c[j]) * ws_qs[j][two:] + dot(qk[j], u[j]) for j in js]
    # diag(g_C / g) K and g_C, each head's own last token
    ends = [(lg_r[j][:, chunk - 1:chunk], lg_r[j][:, two - 1:two]) for j in js]
    k_out = [(jnp.exp(jnp.where(first, *ends[j]) - lg_c[j])
              * k2[j].astype(f32)).astype(rounded) for j in js]
    grown = [jax.lax.dot_general(k_out[j], u[j], (((0,), (0,)), ((), ())),
                                 preferred_element_type=f32) for j in js]
    return (tuple(jnp.where(left, o2[j][:chunk], o2[j][chunk:]) for j in js),
            tuple(jnp.exp(jnp.where(left, *ends[j])) * s[j] + grown[j] for j in js))


# Pairs of heads a trip of the kernel's loop: the more there are, the more of
# a pair's chain of dependent products (some twenty deep) is hidden behind the
# others' (on the v5e 3 / 5 / 15 pairs a trip: 4.63 / 4.41 / 4.47 ms a layer
# at a bucket of 8192; my chip run, PR 47); the fewer, the shorter the kernel's
# body, which Python traces and lowers for every program that holds it,
# cached or not: set-up pays it on every start.
PAIRS_A_TRIP = 5


def _pairs_a_trip(n_pairs: int) -> int:
    """The largest divisor of ``n_pairs`` that is at most ``PAIRS_A_TRIP``."""
    return max(g for g in range(1, PAIRS_A_TRIP + 1) if n_pairs % g == 0)


def _delta_chunk_body(real_ref, s_in, q_ref, k_ref, v_ref, rows_ref, cols_ref,
                      o_ref, s_ref, s_at, k_at, q_at, v_at, c_at, o_at, *,
                      n_pairs: int, chunk: int, d_k: int, d_v: int):
    """A grid step: every head of one lane over one chunk. ``s_in`` / ``s_ref
    (d_k, H x d_v)`` are the lane's state in and out, ``q_ref`` / ``k_ref
    (chunk, H x d_k)``, ``v_ref`` / ``o_ref (chunk, H x d_v)``; ``rows_ref (H,
    2 chunk)`` holds, a pair of heads, the running log-decay (row ``p``) and
    the write strength (row ``H / 2 + p``) as rows (head 2p's tokens, then
    head 2p + 1's), ``cols_ref (2 chunk, H)`` the same as columns.

    The heads go two at a time, stacked along the rows: a pair's 2 x ``chunk``
    tokens fill the 128 rows of a matrix-unit pass (``_advance_pairs``). The
    scratch arrays hold everything A PAIR FIRST, so that a loop can take its
    pairs by a leading index where the operands' own layout would need a slice
    of the lanes at a traced offset: ``s_at (pairs, d_k, 2 d_v)`` is THE STATE
    between a lane's first chunk (when it is cut out of ``s_in``) and its last
    (when it is put together in ``s_ref``), and lives in VMEM all the while;
    ``k_at`` / ``q_at (pairs, 2 chunk, d_k)``, ``v_at`` / ``o_at (pairs, chunk,
    2 d_v)`` and ``c_at (H, 2 chunk, 1)`` are the chunk's. A chunk wholly past
    ``real_len`` does nothing: the state stays bit for bit and ``o`` is not
    written."""
    from jax.experimental import pallas as pl

    lane, c = pl.program_id(0), pl.program_id(1)
    pairs = range(n_pairs)
    a_trip = _pairs_a_trip(n_pairs)
    columns = [slice(2 * p * d_v, 2 * (p + 1) * d_v) for p in pairs]

    @pl.when(c == 0)
    def _():
        for p in pairs:
            s_at[p] = s_in[:, columns[p]]

    @pl.when(c * chunk < real_ref[lane])
    def _():
        for p in pairs:
            # a pair's key columns, the second head's rows under the first's
            for ref, at in ((k_ref, k_at), (q_ref, q_at)):
                win = ref[:, 2 * p * d_k:2 * (p + 1) * d_k]
                at[p] = jnp.concatenate([win[:, :d_k], win[:, d_k:]], axis=0)
            v_at[p] = v_ref[:, columns[p]]
        for head in range(2 * n_pairs):
            c_at[head] = cols_ref[:, head:head + 1]

        def trip(t, _):
            here = [t * a_trip + j for j in range(a_trip)]
            o, s = _advance_pairs(
                *(tuple(at[p] for p in here) for at in (k_at, q_at, v_at)),
                tuple(rows_ref[pl.ds(p, 1), :] for p in here),
                tuple(rows_ref[pl.ds(n_pairs + p, 1), :] for p in here),
                tuple(c_at[p] for p in here),
                tuple(c_at[n_pairs + p] for p in here),
                tuple(s_at[p] for p in here))
            for j, p in enumerate(here):
                o_at[p], s_at[p] = o[j], s[j]
            return 0

        jax.lax.fori_loop(0, n_pairs // a_trip, trip, 0)
        for p in pairs:
            o_ref[:, columns[p]] = o_at[p]

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        for p in pairs:
            s_ref[:, columns[p]] = s_at[p]


def _pair_rows(x, chunk: int):
    """``(B, T, H)`` float32, a value a token a head -> ``(B, T / chunk, H /
    2, 2 chunk)``: a row a chunk a pair of heads, head ``2p``'s tokens then
    head ``2p + 1``'s."""
    b, t_len, h = x.shape
    x = x.reshape(b, t_len // chunk, chunk, h // 2, 2)
    return x.transpose(0, 1, 3, 4, 2).reshape(b, t_len // chunk, h // 2, 2 * chunk)


def _chunk_block_specs(chunk: int, d_k: int, width: int):
    """The block specs of a chunked-rule kernel's grid (lane, chunk) with
    ``real_len`` prefetched -> (``tokens(columns)``: a chunk of a ``(B, T,
    columns)`` operand; ``vectors(*shape)``: a chunk's block of a ``(B, T /
    chunk, *shape)`` one; ``whole``: the lane's state). The token blocks' index
    maps stay on the lane's last real chunk, so the chunks wholly past
    ``real_len`` fetch nothing."""
    from jax.experimental import pallas as pl

    def at_chunk(c, lane, real):
        last = jnp.maximum((real[lane] + chunk - 1) // chunk - 1, 0)
        return jnp.minimum(c, last)

    tokens = lambda columns: pl.BlockSpec(                           # noqa: E731
        (None, chunk, columns), lambda i, c, real: (i, at_chunk(c, i, real), 0))
    vectors = lambda *shape: pl.BlockSpec(                           # noqa: E731
        (None, None) + shape, lambda i, c, real: (i, at_chunk(c, i, real), 0, 0))
    whole = pl.BlockSpec((None, d_k, width), lambda i, c, real: (i, 0, 0))
    return tokens, vectors, whole


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def delta_chunk_kernel(  # static-bounded: chunk, interpret -- chunk is the module's CHUNK (a power of two the gate checks); interpret is boolean (the tests' flag)
        state, q, k, v, log_g, beta, real_len, chunk: int = CHUNK,
        interpret: bool = False):
    """The chunked rule as ONE kernel a call: ``state (B, d_k, H x d_v)``
    float32 in the layout it is stored in, ``q`` / ``k (B, T, H x d_k)`` and
    ``v (B, T, H x d_v)`` as the projection left them, ``log_g`` / ``beta (B,
    T, H)`` float32 (the running log-decay INSIDE each chunk; tokens past
    ``real_len`` already identities), ``real_len (B,)`` int32, ``T`` a
    multiple of ``chunk`` -> (``o (B, T, H x d_v)`` float32, the state after).
    The grid is (lane, chunk): a lane's state is read from HBM when its first
    chunk starts and written when its last ends, and lives in VMEM between.
    Chunks wholly past ``real_len`` are not computed and their blocks not
    fetched (the index maps stay on the last real chunk). A ``jit`` of its
    own: a program's linear layers share ONE trace and one lowering of the
    kernel's body (Python's work, which no compile cache saves a start)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    b, d_k, width = state.shape
    t_len, h = q.shape[1], beta.shape[-1]
    n = t_len // chunk
    rows = jnp.concatenate([_pair_rows(log_g, chunk), _pair_rows(beta, chunk)],
                           axis=2)                       # (B, n, H, 2C)
    cols = jnp.swapaxes(rows, 2, 3)                      # (B, n, 2C, H)

    tokens, vectors, whole = _chunk_block_specs(chunk, d_k, width)
    n_pairs, d_v = h // 2, width // h
    body = functools.partial(_delta_chunk_body, n_pairs=n_pairs, chunk=chunk,
                             d_k=d_k, d_v=d_v)
    # Behind a barrier: where the state's consumer is an in-place update (the
    # prefill's stack of its layers' states) the v5e compiler may wrap the
    # call and that update into ONE fusion, whose scoped VMEM is the default
    # 16 MB and not the ``VMEM_LIMIT`` asked here: 17 MB at 30 heads of 96 x
    # 192 refuse to compile (PR 48: a prefill whose gate reads ``o`` a block
    # at a time made it choose so).
    return jax.lax.optimization_barrier(pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, n),
            in_specs=[whole, tokens(h * d_k), tokens(h * d_k), tokens(width),
                      vectors(h, 2 * chunk), vectors(2 * chunk, h)],
            out_specs=[tokens(width), whole],
            scratch_shapes=[
                pltpu.VMEM((n_pairs, d_k, 2 * d_v), f32),
                pltpu.VMEM((n_pairs, 2 * chunk, d_k), k.dtype),
                pltpu.VMEM((n_pairs, 2 * chunk, d_k), q.dtype),
                pltpu.VMEM((n_pairs, chunk, 2 * d_v), v.dtype),
                pltpu.VMEM((h, 2 * chunk, 1), f32),
                pltpu.VMEM((n_pairs, chunk, 2 * d_v), f32)]),
        out_shape=[jax.ShapeDtypeStruct((b, t_len, width), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="delta_chunk_kernel",
    )(real_len.astype(jnp.int32), state.astype(f32), q, k, v, rows, cols))


# -- the same, for a decay a channel -----------------------------------------------

def _exact_dot_f32(m, rhs, dims=(((1,), (0,)), ((), ()))):
    """``m @ rhs`` (``dims``: the contraction, a ``dot_general``'s) for float32
    ``m`` AND float32 ``rhs`` at HIGHEST's exactness: the six products of
    their bfloat16 parts that are not below float32's last bit, each exact in
    the float32 accumulator, in six passes of ``m``'s rows (its parts stacked
    along them, so the matrix unit holds each part of ``rhs`` once)."""
    bf16 = jnp.bfloat16
    rows = m.shape[0]
    m1, m2, m3 = (p.astype(bf16) for p in _split3(m))
    r1, r2, r3 = (p.astype(bf16) for p in _split3(rhs))
    dot = functools.partial(jax.lax.dot_general, dimension_numbers=dims,
                            preferred_element_type=jnp.float32)
    by1 = dot(jnp.concatenate([m1, m2, m3], axis=0), r1)
    by2 = dot(jnp.concatenate([m1, m2], axis=0), r2)
    small = (dot(m1, r3) + by1[2 * rows:]) + by2[rows:]
    return (small + (by1[rows:2 * rows] + by2[:rows])) + by1[:rows]


@jax.jit
def _advance_pairs_channel(k2, q2, lg2, v2, be_r, be_c, s):
    """``_advance_pairs`` where the decay is a channel's, every argument again
    a tuple with an entry a pair: ``k2`` / ``q2 (2 chunk, d_k)``, ``lg2 (2
    chunk, d_k)`` float32 the tokens' OWN log-decay (<= 0, floored) and ``v2
    (2 chunk, d_v)``, each the pair's second head's rows under the first's;
    ``be_r (1, 2 chunk)`` / ``be_c (2 chunk, 1)`` the write strength; ``s`` a
    PAIR of states ``(d_k, d_v)``, a head each -> (``o (2 chunk, d_v)``
    stacked as ``v2``, the pairs of states after). The mathematics of
    ``_chunked_block_channel``: the running sum ``G`` inside the chunk is
    formed here (a product with a triangle of ones, exact); ``K K^T`` and ``Q
    K^T`` by sub-blocks of ``SUB`` tokens with no exponent above 0, the
    diagonal sub-blocks' ``exp(G_t - G_i)`` formed once for both; ``K K^T``,
    the inverse and the solve at float32's exactness; the products with the
    state on operands rounded to the operands' dtype, a head's own columns
    only (the decays are the state's ROWS' here, so nothing is gained by a
    pair's states side by side)."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    rounded = k2[0].dtype
    chunk = k2[0].shape[0] // 2
    two = 2 * chunk
    sub = SUB if chunk % SUB == 0 else chunk
    half = sub // 2 if sub % 16 == 0 else sub       # whole float32 tiles
    n_sub, n_blocks = chunk // sub, two // sub
    js = range(len(k2))
    row = jax.lax.broadcasted_iota(jnp.int32, (two, two), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (two, two), 1)
    same = (row < chunk) == (col < chunk)           # the two diagonal blocks
    strict = same & (col < row)
    below = same & ((col // sub) < (row // sub))    # under the diagonal sub-blocks
    tok = jax.lax.broadcasted_iota(jnp.int32, (two, 1), 0)
    first = tok < chunk                              # a pair's first head's rows
    nt = (((1,), (1,)), ((), ()))
    tn = (((0,), (0,)), ((), ()))
    dot = functools.partial(jnp.dot, preferred_element_type=f32)
    heads = (slice(0, chunk), slice(chunk, two))

    def of_head(x, at: int):
        """Each head's row ``at`` of ``x (2 chunk, d)`` over that head's rows."""
        return jnp.where(first, x[at:at + 1], x[chunk + at:chunk + at + 1])

    def never_up(e):
        """Exponents as they go into ``exp``: none above 0 (two running sums
        that should be equal may differ in float32's last bit)."""
        return jnp.minimum(e, 0.0)

    def three(m):
        """Float32 ``m``'s three bfloat16 parts side by side."""
        return jnp.concatenate([p.astype(bf16) for p in _split3(m)], axis=1)

    def summed(x, axis: int):
        """The three parts' products, ``d_k`` wide each along ``axis``, added
        smallest first."""
        p1, p2, p3 = jnp.split(x, 3, axis=axis)
        return (p3 + p2) + p1

    kf = [k2[j].astype(f32) for j in js]
    qf = [q2[j].astype(f32) for j in js]
    # G: the running log-decay inside the chunk, a head's rows its own, and
    # its last row G_C as COLUMNS (a head's over its own ``chunk`` lanes):
    # both by products with 0 / 1, the log-decay's three parts each exact
    before = jnp.where(same & (col <= row), 1.0, 0.0).astype(bf16)
    of_one_head = jnp.where(same, 1.0, 0.0).astype(bf16)
    parts = [three(lg2[j]) for j in js]
    g = [summed(dot(before, parts[j]), 1) for j in js]
    g_end_col = [summed(jax.lax.dot_general(parts[j], of_one_head, tn,
                                            preferred_element_type=f32), 0)
                 for j in js]                                   # (d_k, 2 chunk)
    # the diagonal sub-blocks: exp(G_t - G_i) itself, i <= t, a column (every
    # sub-block's i-th token) at a time, once for K K^T and Q K^T; a
    # sub-block's upper half of rows has nothing under its later columns
    cut = lambda x: x.reshape(n_blocks, sub, x.shape[1])         # noqa: E731
    g3, k3, q3 = ([cut(x[j]) for j in js] for x in (g, kf, qf))
    shape = (n_blocks, half, two)
    at_row = jax.lax.broadcasted_iota(jnp.int32, (n_blocks, half, 1), 1)
    in_block = (jax.lax.broadcasted_iota(jnp.int32, shape, 2) // sub
                == jax.lax.broadcasted_iota(jnp.int32, shape, 0))
    at_col = jax.lax.broadcasted_iota(jnp.int32, shape, 2) % sub
    parts_of = range(0, sub, half)                  # the halves' first rows
    kk3 = [[jnp.zeros(shape, f32) for _ in parts_of] for _ in js]
    qk3 = [[jnp.zeros(shape, f32) for _ in parts_of] for _ in js]
    from_first = [[None for _ in parts_of] for _ in js]
    for i in range(sub):
        here = in_block & (at_col == i)
        for j in js:
            g_i, k_i = g3[j][:, i:i + 1], k3[j][:, i:i + 1]
            for n, lo in enumerate(parts_of):
                if lo + half <= i:
                    continue                        # rows all before token i
                rows = slice(lo, lo + half)
                e = g3[j][:, rows] - g_i
                if lo < i:
                    e = jnp.where(at_row >= i - lo, e, -jnp.inf)
                e = jnp.exp(never_up(e))
                if i == 0:
                    from_first[j][n] = e
                m = e * k_i
                kk3[j][n] = jnp.where(
                    here, jnp.sum(k3[j][:, rows] * m, axis=2, keepdims=True), kk3[j][n])
                qk3[j][n] = jnp.where(
                    here, jnp.sum(q3[j][:, rows] * m, axis=2, keepdims=True), qk3[j][n])
    whole = lambda x: jnp.concatenate(x, axis=1).reshape(two, -1)   # noqa: E731
    kk = [whole(kk3[j]) for j in js]
    qk = [whole(qk3[j]) for j in js]
    # the sub-blocks below them, through the later sub-block's first row r:
    # (x_t exp(G_t - G_r)) . (k_i exp(G_r - G_i)), both factors <= 1
    if n_sub > 1:
        zeros = jnp.zeros((sub, two), f32)
        for j in js:
            e = whole(from_first[j])
            k_l, q_l = kf[j] * e, qf[j] * e
            under_k, under_q = [[zeros], [zeros]], [[zeros], [zeros]]
            for a in range(1, n_sub):
                at = [slice(h.start + a * sub, h.start + (a + 1) * sub) for h in heads]
                right = kf[j] * jnp.exp(never_up(jnp.where(
                    (tok % chunk) < a * sub, of_head(g[j], a * sub) - g[j], -jnp.inf)))
                under = _exact_dot_f32(
                    jnp.concatenate([k_l[at[0]], k_l[at[1]]], axis=0), right, nt)
                under_x = jax.lax.dot_general(
                    jnp.concatenate([q_l[at[0]], q_l[at[1]]], axis=0).astype(rounded),
                    right.astype(rounded), nt, preferred_element_type=f32)
                for head in range(2):
                    under_k[head].append(under[head * sub:(head + 1) * sub])
                    under_q[head].append(under_x[head * sub:(head + 1) * sub])
            kk[j] = jnp.where(below, jnp.concatenate(under_k[0] + under_k[1], axis=0),
                              kk[j])
            qk[j] = jnp.where(below, jnp.concatenate(under_q[0] + under_q[1], axis=0),
                              qk[j])
    a_mat = [be_c[j] * jnp.where(strict, kk[j], 0.0) for j in js]
    qk = [qk[j].astype(rounded) for j in js]
    x = _unit_lower_inverses(a_mat, chunk)
    # W = (I + a)^-1 diag(beta) (K exp(G)) and U_0 = (I + a)^-1 diag(beta) V
    decayed = [jnp.exp(g[j]) for j in js]                       # exp(G) <= 1
    solve = [x[j] * be_r[j] for j in js]
    w = [_exact_dot_f32(solve[j], kf[j] * decayed[j]).astype(rounded) for j in js]
    u0 = [_exact_dot(solve[j], v2[j]) for j in js]              # (2C, d_v)
    q_g = [(qf[j] * decayed[j]).astype(rounded) for j in js]
    # W S over Q exp(G) S, a head at a time
    ws_qs = [[dot(jnp.concatenate([w[j][h], q_g[j][h]], axis=0),
                  s[j][n].astype(rounded)) for n, h in enumerate(heads)] for j in js]
    stacked = lambda a, rows: jnp.concatenate(                       # noqa: E731
        [a[0][rows], a[1][rows]], axis=0)
    u = [(u0[j] - stacked(ws_qs[j], heads[0])).astype(rounded) for j in js]
    o = [stacked(ws_qs[j], heads[1]) + dot(qk[j], u[j]) for j in js]
    # K exp(G_C - G); exp(G_C) scales the state's rows
    k_out = [(kf[j] * jnp.exp(never_up(of_head(g[j], chunk - 1) - g[j]))
              ).astype(rounded) for j in js]
    after = [tuple(
        jnp.exp(g_end_col[j][:, h.start:h.start + 1]) * s[j][n]
        + jax.lax.dot_general(k_out[j][h], u[j][h], tn, preferred_element_type=f32)
        for n, h in enumerate(heads)) for j in js]
    return tuple(o), tuple(after)


def _delta_channel_chunk_body(real_ref, s_in, q_ref, k_ref, v_ref, lg_ref, rows_ref,
                              cols_ref, o_ref, s_ref, s_at, k_at, q_at, lg_at, v_at,
                              c_at, o_at, *, n_pairs: int, chunk: int, d_k: int,
                              d_v: int):
    """``_delta_chunk_body`` for a decay a channel: ``lg_ref (chunk, H x
    d_k)`` float32 is the tokens' log-decay beside ``k_ref``, and ``rows_ref
    (H / 2, 2 chunk)`` / ``cols_ref (2 chunk, H / 2)`` hold the write strength
    alone (``c_at (H / 2, 2 chunk, 1)``). The keys, the queries, the
    log-decay AND the values are cut a pair at a time with the second head's
    rows under the first's (``k_at`` / ``q_at`` / ``lg_at (pairs, 2 chunk,
    d_k)``, ``v_at`` / ``o_at (pairs, 2 chunk, d_v)``), the state a HEAD at a
    time (``s_at (H, d_k, d_v)``): ``_advance_pairs_channel`` multiplies a
    head's state alone. Everything else as there: the state lives in ``s_at``
    from the lane's first chunk to its last, and a chunk wholly past
    ``real_len`` does nothing."""
    from jax.experimental import pallas as pl

    lane, c = pl.program_id(0), pl.program_id(1)
    pairs = range(n_pairs)
    a_trip = _pairs_a_trip(n_pairs)
    value_columns = [slice(head * d_v, (head + 1) * d_v) for head in range(2 * n_pairs)]

    def stacked(ref, p, d):
        """A pair's columns of ``ref``, the second head's rows under the first's."""
        win = ref[:, 2 * p * d:2 * (p + 1) * d]
        return jnp.concatenate([win[:, :d], win[:, d:]], axis=0)

    @pl.when(c == 0)
    def _():
        for head, columns in enumerate(value_columns):
            s_at[head] = s_in[:, columns]

    @pl.when(c * chunk < real_ref[lane])
    def _():
        for p in pairs:
            for ref, at in ((k_ref, k_at), (q_ref, q_at), (lg_ref, lg_at)):
                at[p] = stacked(ref, p, d_k)
            v_at[p] = stacked(v_ref, p, d_v)
            c_at[p] = cols_ref[:, p:p + 1]

        def trip(t, _):
            here = [t * a_trip + j for j in range(a_trip)]
            o, s = _advance_pairs_channel(
                *(tuple(at[p] for p in here) for at in (k_at, q_at, lg_at, v_at)),
                tuple(rows_ref[pl.ds(p, 1), :] for p in here),
                tuple(c_at[p] for p in here),
                tuple((s_at[2 * p], s_at[2 * p + 1]) for p in here))
            for j, p in enumerate(here):
                o_at[p] = o[j]
                s_at[2 * p], s_at[2 * p + 1] = s[j]
            return 0

        jax.lax.fori_loop(0, n_pairs // a_trip, trip, 0)
        for p in pairs:
            o_ref[:, value_columns[2 * p]] = o_at[p, :chunk]
            o_ref[:, value_columns[2 * p + 1]] = o_at[p, chunk:]

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        for head, columns in enumerate(value_columns):
            s_ref[:, columns] = s_at[head]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def delta_channel_chunk_kernel(  # static-bounded: chunk, interpret -- chunk is the module's CHUNK (a power of two the gate checks); interpret is boolean (the tests' flag)
        state, q, k, v, log_decay, beta, real_len, chunk: int = CHUNK,
        interpret: bool = False):
    """``delta_chunk_kernel`` for a decay a channel: the same operands, but
    ``log_decay (B, T, H x d_k)`` float32 is each token's OWN ``log alpha``
    (floored at ``LOG_DECAY_FLOOR``; 0 past ``real_len``, where ``beta`` is 0
    too), beside ``k``'s columns: the running sum inside a chunk is formed in
    the kernel, so no ``(T, H x d_k)`` float32 array is written for it. The
    same grid, the same residence of the lane's state, the same skipping of
    the chunks past ``real_len``; the other kernel's twin, not a mode of it: a
    decay a head factors out of ``K K^T`` and needs none of this one's
    exponentials."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    b, d_k, width = state.shape
    t_len, h = q.shape[1], beta.shape[-1]
    n = t_len // chunk
    rows = _pair_rows(beta, chunk)                       # (B, n, H / 2, 2C)
    cols = jnp.swapaxes(rows, 2, 3)                      # (B, n, 2C, H / 2)

    tokens, vectors, whole = _chunk_block_specs(chunk, d_k, width)
    n_pairs, d_v = h // 2, width // h
    body = functools.partial(_delta_channel_chunk_body, n_pairs=n_pairs,
                             chunk=chunk, d_k=d_k, d_v=d_v)
    # behind a barrier, as ``delta_chunk_kernel`` and for its reason
    return jax.lax.optimization_barrier(pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, n),
            in_specs=[whole, tokens(h * d_k), tokens(h * d_k), tokens(width),
                      tokens(h * d_k), vectors(n_pairs, 2 * chunk),
                      vectors(2 * chunk, n_pairs)],
            out_specs=[tokens(width), whole],
            scratch_shapes=[
                pltpu.VMEM((h, d_k, d_v), f32),
                pltpu.VMEM((n_pairs, 2 * chunk, d_k), k.dtype),
                pltpu.VMEM((n_pairs, 2 * chunk, d_k), q.dtype),
                pltpu.VMEM((n_pairs, 2 * chunk, d_k), f32),
                pltpu.VMEM((n_pairs, 2 * chunk, d_v), v.dtype),
                pltpu.VMEM((n_pairs, 2 * chunk, 1), f32),
                pltpu.VMEM((n_pairs, 2 * chunk, d_v), f32)]),
        out_shape=[jax.ShapeDtypeStruct((b, t_len, width), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="delta_channel_chunk_kernel",
    )(real_len.astype(jnp.int32), state.astype(f32), q, k, v,
      log_decay.astype(f32), rows, cols))


# -- the one-token step on a layer's slice of the whole state array ------------------

def _step_loop(states, layer: int, q, k, v, alpha, beta, took, live):
    """``delta_step_live`` in plain ``jax.numpy``, the form every backend
    takes and the reference of ``delta_step_kernel``: a trip of the loop
    takes ``STEP_GROUP`` lanes' states out of the carry (a dynamic slice a
    lane, no slice of a layer), advances them (``_advance``: four passes over
    them) and puts them back (a dynamic update a lane), so a step moves whole
    trips of lanes' states and the array is never copied. The last trip's
    lanes past ``count`` took nothing and keep their states bit for bit (a
    select)."""
    lanes = states.shape[1]
    order, count = live
    group = min(STEP_GROUP, lanes)
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


# -- the one-token step as a Pallas kernel, for the live lanes ---------------------

# The most of a lane's state a grid step of ``delta_step_kernel`` holds: a
# block is whole heads, in and out each twice in VMEM (the pipeline's buffers
# fetch the next block and write the last one back while this one is
# advanced), within the 16 MB a kernel has without asking. Olmo-Hybrid's lane
# (96 x 5760 float32) is one block, Solar-Open2's (128 x 8192) two.
STEP_BLOCK_BYTES = 9 << 18


def _step_blocking(heads: int, d_k: int, d_v: int) -> tuple[int, int] | None:
    """(heads a UNIT, heads a block) of the step kernel: a unit is the fewest
    heads whose value columns are whole 128-lane rows (one head of 128, two of
    192); a block is the most units within ``STEP_BLOCK_BYTES`` of state that
    divide the heads and are a multiple of 16 heads, or all of them (so that a
    block of ``k (S, H, d_k)`` is whole tiles as the projection left it, with
    no copy made for the kernel); None where there is no such block."""
    unit = next(n for n in range(1, 129) if n * d_v % 128 == 0)
    blocks = [n for n in range(unit, heads + 1, unit)
              if heads % n == 0 and (n % 16 == 0 or n == heads)
              and 4 * d_k * n * d_v <= STEP_BLOCK_BYTES]
    return (unit, max(blocks)) if blocks else None


def _step_kernel_refusal(state_dtype, heads: int, d_k: int, d_v: int) -> str | None:
    """Why ``delta_step_live`` cannot advance its lanes through
    ``delta_step_kernel`` on this backend (None = it can): the TPU (one chip:
    the families bind no mesh), a float32 state, key rows of whole sublane
    tiles and heads that go into blocks of whole 128-lane rows
    (``_step_blocking``). Numbers, not arrays: the host asks too
    (``step_lanes_touched``)."""
    if not DELTA_KERNEL_INTERPRET and jax.default_backend() != "tpu":
        return f"backend={jax.default_backend()}"
    if jnp.dtype(state_dtype) != jnp.float32:
        return f"state {jnp.dtype(state_dtype)}, not float32"
    if d_k % 8:
        return f"d_k={d_k} no multiple of 8"
    if _step_blocking(heads, d_k, d_v) is None:
        return (f"{heads} heads of {d_k} x {d_v} in no blocks of whole 128-lane "
                f"rows within {STEP_BLOCK_BYTES} bytes")
    return None


def _delta_step_body(order_ref, beta_ref, *refs, unit: int, d_v: int,
                     channel: bool):
    """A grid step: one block of whole heads of one LIVE lane's state, read
    once and written once. ``s_in`` / ``s_out (d_k, block x d_v)`` are that
    block in and out (the same bytes of the aliased array), ``k_ref`` / ``q_ref
    (block, d_k)`` its heads' keys and queries as the projection left them
    (``a_ref``, the same shape in float32: a decay a channel); ``v_ref (rows,
    block x d_v)`` holds the lane's value row among its neighbours' and
    ``o_ref (lanes, block x d_v)`` every lane's output row of these columns
    (it stays in VMEM while the grid walks the live lanes and takes the
    lane's row); ``beta_ref (lanes, heads)`` in SMEM holds the write strength
    a scalar a head (``alpha_ref`` beside it: a decay a head). A head's key,
    query and channel decay are transposed once a block (a column a head) and
    spread over the head's ``d_v`` columns by a broadcast along the lanes;
    every product and sum is float32 on the vector unit, in the order
    ``_advance`` has them. A unit of heads is whole 128-lane rows: where it
    holds two heads, a select on the column puts each over its own."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    if channel:
        s_in, k_ref, q_ref, v_ref, a_ref, s_out, o_ref = refs
    else:
        alpha_ref, s_in, k_ref, q_ref, v_ref, s_out, o_ref = refs
    block, d_k = k_ref.shape
    first, lane = pl.program_id(0) * block, order_ref[pl.program_id(1)]
    width = unit * d_v
    col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    # the lane's row of ``v`` and of ``o``: inside a sublane tile of lanes'
    # rows, which is what a load or a store at a traced row can address
    rows = v_ref.shape[0]
    mine = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0) == lane % rows
    tile = pl.ds(pl.multiple_of(lane // rows * rows, rows), rows)
    # a column a head: (d_k, block)
    k_t, q_t = (ref[...].astype(f32).T for ref in (k_ref, q_ref))
    a_t = a_ref[...].T if channel else None

    def over(values):
        """The unit's heads' ``values`` (a column or a scalar each), each
        over its own head's columns."""
        out = values[0]
        for n in range(1, unit):
            out = jnp.where(col >= n * d_v, values[n], out)
        return out

    def spread(columns, head: int):
        return over([jnp.broadcast_to(columns[:, head + n:head + n + 1],
                                      (d_k, width)) for n in range(unit)])

    def scalars(ref, head: int):
        return over([jnp.full((1, width), ref[lane, first + head + n], f32)
                     for n in range(unit)])

    for head in range(0, block, unit):
        at = slice(head * d_v, (head + unit) * d_v)
        decay = spread(a_t, head) if channel else scalars(alpha_ref, head)
        s = decay * s_in[:, at]
        k_col = spread(k_t, head)
        v_row = jnp.sum(jnp.where(mine, v_ref[:, at], 0.0), axis=0, keepdims=True)
        u = scalars(beta_ref, head) * (
            v_row - jnp.sum(k_col * s, axis=0, keepdims=True))
        s = s + k_col * u
        s_out[:, at] = s
        o_row = jnp.sum(spread(q_t, head) * s, axis=0, keepdims=True)
        o_ref[tile, at] = jnp.where(mine, o_row, o_ref[tile, at])


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def delta_step_kernel(  # static-bounded: layer, interpret -- layer is a model's linear-layer index (a few a model); interpret is boolean (the tests' flag)
        states, layer: int, q, k, v, alpha, beta, order, count,
        interpret: bool = False):
    """The one-token step as ONE kernel a layer, for the live lanes: ``states
    (layers, S, d_k, H x d_v)`` float32 in and out IN PLACE
    (``input_output_aliases``: the array is never copied nor sliced), ``q`` /
    ``k (S, H, d_k)``, ``v (S, H, d_v)``, ``alpha (S, H)`` (``(S, H, d_k)``: a
    decay a channel; the rank is read from the shape) and ``beta (S, H)``,
    ``order (S,)`` int32 the lanes with the live ones first and ``count`` how
    many those are -> (``o (S, H x d_v)`` float32, written for the live lanes
    ONLY: the other rows are whatever the buffer held; the array after). The
    grid is (block of whole heads, live lane) and its second bound is
    ``count`` itself, an operand: a lane that took nothing costs no grid step,
    no fetch and no write, and its state keeps its bytes. A block's index map
    reads ``(layer, order[i])`` from the prefetched scalars, so a live lane's
    state crosses HBM once each way, a block at a time through the pipeline's
    buffers. ``v`` and ``o`` go in and out as ``(S, H x d_v)`` float32, lanes
    along the sublanes (a lane's row is picked inside the kernel): a row a
    lane as ``(S, 1, H x d_v)`` made the v5e compiler lay the convolution's
    arrays beside them out a tap a sublane, and a decode step paid 0.13 ms
    for it (my chip run, PR 52)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    _, lanes, d_k, width = states.shape
    h = k.shape[1]
    d_v = width // h
    channel = alpha.ndim == k.ndim
    unit, block = _step_blocking(h, d_k, d_v)
    rows = 8 if lanes % 8 == 0 else lanes       # a sublane tile of value rows
    # a decay a head rides with the write strengths, a scalar a head in SMEM
    scalars = [order.astype(jnp.int32), beta.astype(f32)]
    operands = [states, k, q, v.astype(f32).reshape(lanes, width)]
    (operands if channel else scalars).append(alpha.astype(f32))

    state_block = pl.BlockSpec((None, None, d_k, block * d_v),
                               lambda j, i, order, *_: (layer, order[i], 0, j))
    head_block = pl.BlockSpec((None, block, d_k),
                              lambda j, i, order, *_: (order[i], j, 0))
    value_block = pl.BlockSpec((rows, block * d_v),
                               lambda j, i, order, *_: (order[i] // rows, j))
    out_block = pl.BlockSpec((lanes, block * d_v), lambda j, i, *_: (0, j))
    body = functools.partial(_delta_step_body, unit=unit, d_v=d_v, channel=channel)
    states, o = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(h // block, count.astype(jnp.int32)),
            in_specs=[state_block, head_block, head_block, value_block]
            + [head_block] * channel,
            out_specs=[state_block, out_block]),
        out_shape=[jax.ShapeDtypeStruct(states.shape, f32),
                   jax.ShapeDtypeStruct((lanes, width), f32)],
        input_output_aliases={len(scalars): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="delta_step_kernel",
    )(*scalars, *operands)
    return o, states


def step_lanes_touched(live: int, lanes: int, state_dtype, heads: int, d_k: int,
                       d_v: int) -> int:
    """The lanes whose states one ``delta_step_live`` call reads and writes
    when ``live`` of ``lanes`` took a token, on this backend at these widths:
    the live lanes where the gate lets the kernel run, whole trips of
    ``_step_loop`` where it does not (every lane of an array no larger than a
    trip). Host arithmetic, for the engine's ring
    (``generation.state_write_lanes``)."""
    if _step_kernel_refusal(state_dtype, heads, d_k, d_v) is None:
        return live
    if lanes <= STEP_GROUP:
        return lanes
    return min(lanes, -(-live // STEP_GROUP) * STEP_GROUP)
