"""Attention ops: a Pallas TPU flash-attention kernel + jnp reference.

No reference-counterpart exists (the reference proxies opaque tensors and
never computes; SURVEY.md §5) — this is the TPU-native compute core for the
transformer families. Design per /opt/skills/guides/pallas_guide.md:

  - online-softmax over K/V blocks so the (S x S) score matrix never
    materializes in HBM (memory O(block_q x block_k) in VMEM);
  - block sizes aligned to the MXU/VPU tiling (multiples of 128 lanes);
  - fp32 accumulation regardless of input dtype (bf16 in, f32 softmax);
  - causal masking skips fully-masked K blocks via the loop bound itself.

The public entry ``attention`` dispatches: Pallas kernel on the TPU backend,
jnp reference elsewhere (tests compare the two in interpret mode). Every
dispatch gate records the branch it traced and why (``dispatch_tally``), so
a run can prove which code served it.
"""

from __future__ import annotations

import functools
import math
import threading

import jax
import jax.numpy as jnp

from tfservingcache_tpu.utils.logging import get_logger

log = get_logger("attention")

NEG_INF = -1e30

# -- dispatch record ---------------------------------------------------------
# The gates below choose between a Pallas kernel and its jnp reference while
# a caller's jit is being TRACED, so the choice is invisible at run time: the
# compiled program simply contains one or the other. Each gate therefore
# tallies (gate, branch, reason) per trace and logs each new
# (gate, branch, reason, shapes) once — chip_smoke.py and the tests read the
# tally to assert that the kernels, not the references, served a run.
_DISPATCH_LOCK = threading.Lock()
_DISPATCH_TALLY: dict[tuple[str, str, str], int] = {}
_DISPATCH_LOGGED: set[tuple] = set()


def _record_dispatch(gate: str, branch: str, reason: str, *shapes: tuple) -> None:
    key = (gate, branch, reason)
    with _DISPATCH_LOCK:
        _DISPATCH_TALLY[key] = _DISPATCH_TALLY.get(key, 0) + 1
        first = key + shapes not in _DISPATCH_LOGGED
        _DISPATCH_LOGGED.add(key + shapes)
    if first:
        log.info("attention dispatch: %s traced %s (%s) shapes=%s",
                 gate, branch, reason, list(shapes))


def dispatch_tally() -> dict[tuple[str, str, str], int]:
    """Snapshot of ``{(gate, branch, reason): traces}`` since process start.
    Gates: ``attention``, ``attention_window``, ``paged_attention``,
    ``paged_window_attention``, ``paged_attention_verify``,
    ``paged_latent_attention``, ``ring_attention``, ``moe_experts``,
    ``delta_chunked``, ``delta_step_live``; branch ``"kernel"`` / ``"reference"``."""
    with _DISPATCH_LOCK:
        return dict(_DISPATCH_TALLY)


def _kernel_refusal(head_dim: int, hq: int, hkv: int,
                    head_multiple: int = 64) -> str | None:
    """Why the TPU kernels cannot take these shapes on this backend (None =
    they can). Shared by every gate: the backend must be the TPU, head_dim a
    multiple of ``head_multiple`` (64 where Mosaic's own pipeline fetches the
    blocks and pads the 128-lane dim) and the query heads a multiple of the
    kv heads."""
    backend = jax.default_backend()
    if backend != "tpu":
        return f"backend={backend}"
    if head_dim % head_multiple:
        return f"head_dim={head_dim} not a multiple of {head_multiple}"
    if hq % hkv:
        return f"q heads {hq} not a multiple of kv heads {hkv}"
    return None


# ---------------------------------------------------------------------------
# jnp reference implementation
# ---------------------------------------------------------------------------

def attention_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
    window: int = 0, sm_scale: float | None = None,
) -> jax.Array:
    """(B, Hq, S, D) x (B, Hkv, S, D) attention, fp32 softmax, out in q.dtype.
    ``window`` > 0 (causal only): query ``i`` reads keys ``(i - window, i]``.
    ``sm_scale`` (None = ``D ** -0.5``) is the scores' scale where the head
    computed with is not the row handed in (``diff_queries``).

    GQA-native: Hkv may divide Hq; query heads are grouped over their shared
    K/V head via a reshape, so repeated K/V are never materialized (the whole
    point of GQA's HBM saving — VERDICT.md round-1 weak #7)."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    g = hq // hkv
    # dots in the INPUT dtype (bf16 = full MXU rate, half the HBM reads),
    # f32 accumulation via preferred_element_type — for f32 inputs this is
    # bit-for-bit the old upcast math, for bf16 it is the fast path the
    # flash kernel must honestly beat
    qg = q.reshape(b, hkv, g, sq, d)
    s = jnp.einsum(
        "bkgqd,bkKd->bkgqK", qg, k, preferred_element_type=jnp.float32
    )
    s = s / math.sqrt(d) if sm_scale is None else s * sm_scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window:
            mask &= ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # p @ v stays f32: a bf16-rounded p makes the sharded (TP/EP) einsum
    # diverge from the replicated one beyond parity tolerances — this is
    # the correctness yardstick, the Pallas kernel is the fast path
    o = jnp.einsum("bkgqK,bkKd->bkgqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash kernel
# ---------------------------------------------------------------------------

def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, *, sm_scale: float, causal: bool, block_q: int,
    block_k: int, valid_len: int, window: int = 0,
):
    """``window`` > 0 (causal): query ``i`` reads keys ``(i - window, i]``;
    the K blocks wholly before a Q block's first row's window are skipped by
    the loop's start as those after its last row are by its end."""
    from jax.experimental import pallas as pl

    # Keep operands in their input dtype (bf16) for the MXU dots: a bf16
    # matmul runs at full MXU rate and halves VMEM traffic vs the round-1
    # design that upcast q/k/v to f32 first (the 0.86x regression,
    # VERDICT r2 weak #2). Accumulation stays f32 via preferred_element_type;
    # sm_scale is applied to the f32 scores, not the bf16 operands.
    q = q_ref[0]                                            # (bq, d)
    qi = pl.program_id(1)
    seq_len = k_ref.shape[1]
    q_offset = qi * block_q

    if causal:
        # only K blocks at or before this Q block's last row participate
        num_k_blocks = jnp.minimum(
            (q_offset + block_q + block_k - 1) // block_k, seq_len // block_k
        )
    else:
        num_k_blocks = seq_len // block_k
    # the first K block that holds a key of the first row's window
    first_k_block = (jnp.maximum(q_offset - window + 1, 0) // block_k
                     if window else 0)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]                        # (bk, d)
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                                        # (bq, bk) f32
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = k_pos < valid_len  # padded K rows never participate
        if causal:
            q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            valid = valid & (q_pos >= k_pos)
            if window:
                valid = valid & (q_pos - k_pos < window)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))          # (bq, 1)
        if window:
            # a row's window may begin after this block: all of it masked and
            # m still NEG_INF, where exp(s - m) would be 1
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        else:
            p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc * alpha + pv, m_new, l_new

    acc = jnp.zeros((q.shape[0], q_ref.shape[2]), jnp.float32)
    m = jnp.full((q.shape[0], 1), NEG_INF, jnp.float32)
    l = jnp.zeros((q.shape[0], 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(
        first_k_block, num_k_blocks, body, (acc, m, l))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


# Resident-K/V limit: the 2D-grid kernel pulls each program's WHOLE padded
# K/V row into VMEM (O(S*D) — fine and hardware-proven fast at serving
# shapes, fatal at long-context lengths on a ~16 MiB/core VMEM). Above this
# K+V byte size the streamed 3D-grid kernel runs instead, whose VMEM is
# O(block_q*d + block_k*d) regardless of S (VERDICT r3 weak #3 / next #5).
KV_RESIDENT_LIMIT_BYTES = 4 << 20


def flash_variant(s_padded: int, d: int, itemsize: int) -> str:
    """Which kernel a (padded) shape dispatches to: "resident" | "streamed"."""
    kv_bytes = 2 * s_padded * d * itemsize
    return "resident" if kv_bytes <= KV_RESIDENT_LIMIT_BYTES else "streamed"


def _flash_streamed_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, sm_scale: float,
    causal: bool, block_q: int, block_k: int, valid_len: int, num_k: int,
    window: int = 0,
):
    """One (q-block, k-block) grid step: online-softmax update of the VMEM
    scratch accumulators. K/V arrive one block per step (double-buffered by
    the Pallas pipeline), so VMEM use is independent of sequence length.
    ``window`` > 0: a block wholly before the Q block's first row's window is
    skipped like one wholly after its last row."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    q_offset = qi * block_q
    k_offset = kj * block_k

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0]                                            # (bq, d)
        k = k_ref[0]                                            # (bk, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                            # (bq, bk) f32
        k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = k_pos < valid_len
        if causal:
            q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            valid = valid & (q_pos >= k_pos)
            if window:
                valid = valid & (q_pos - k_pos < window)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:, :1]                                   # (bq, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        if window:
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)   # see _flash_kernel
        else:
            p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        # m/l scratch is (bq, 128) — the VMEM lane tile — holding the value
        # broadcast across lanes; only lane 0 is read back
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # a causal block whose first key strictly follows this q block's last
        # row is fully masked: skip its MXU work (its DMA is already in
        # flight — the bandwidth cost of a static grid — but no compute)
        live = k_offset <= q_offset + block_q - 1
        if window:
            live = live & (k_offset + block_k - 1 >= q_offset - window + 1)
        pl.when(live)(_body)
    else:
        _body()

    @pl.when(kj == num_k - 1)
    def _final():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        ).astype(o_ref.dtype)


def _flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
    window: int = 0,
    sm_scale: float | None = None,
) -> jax.Array:
    """Flash attention over (B, Hq, S, D) x (B, Hkv, S, D). S is padded to a
    block multiple internally. GQA-native: the kernel instance for query head
    h reads K/V head h // (Hq/Hkv) via its BlockSpec index map — grouped K/V
    are streamed, never repeated in HBM.

    Two kernels behind one entry, chosen statically by padded K/V bytes
    (``flash_variant``): the resident 2D-grid kernel (whole K/V row in VMEM;
    hardware-proven fastest at serving lengths) up to
    ``KV_RESIDENT_LIMIT_BYTES``, and a streamed 3D-grid kernel (K/V one
    block per grid step, online-softmax state in VMEM scratch) beyond it —
    so ring-servable long-context lengths (S >= 16k) can never hand
    ``pallas_call`` K/V rows that exceed VMEM.

    Default blocks auto-select: S is first padded to a 128-lane tile multiple,
    then block_q/block_k take the largest of (256)/(512, 256) that divides the
    padded length, falling back to 128 — the v5e-tuned sizes without the
    pathological lcm-padding an asymmetric fixed default would hit on
    non-power-of-two sequence lengths (e.g. generate's exact-size fallback).

    ``window`` > 0 (``flash_window_attention``; causal): query ``i`` reads keys
    ``(i - window, i]``, the K blocks wholly outside a Q block's windows are
    skipped (the resident kernel never visits them, the streamed one does no
    MXU work for them), and the call carries its own name in the device
    trace, ``flash_window_kernel``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if window and not causal:
        raise ValueError("a window is a causal mask's")
    # a windowed call is told apart in the device trace by its name
    name = "flash_window_kernel" if window else None
    b, h, s, d = q.shape
    hkv = k.shape[1]
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    g = h // hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    sp_tile = s + ((-s) % 128)
    if block_q is None:
        block_q = 256 if sp_tile % 256 == 0 else 128
    else:
        block_q = min(block_q, max(s, 16))
    if block_k is None:
        block_k = next(bk for bk in (512, 256, 128) if sp_tile % bk == 0)
    else:
        block_k = min(block_k, max(s, 16))
    pad = (-s) % math.lcm(block_q, block_k)  # both block counts must divide sp
    if pad:
        zeros = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        q, k, v = zeros(q), zeros(k), zeros(v)
    sp = q.shape[2]
    qf = q.reshape(b * h, sp, d)
    kf = k.reshape(b * hkv, sp, d)
    vf = v.reshape(b * hkv, sp, d)

    if flash_variant(sp, d, q.dtype.itemsize) == "resident":
        kernel = functools.partial(
            _flash_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, valid_len=s, window=window,
        )
        grid = (b * h, sp // block_q)
        # program i covers flat (batch, q-head) index i; its K/V row is the
        # owning group's head: batch * hkv + (head // g)
        kv_index = lambda i, j: (i // h * hkv + (i % h) // g, 0, 0)
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((1, sp, d), kv_index),
                pl.BlockSpec((1, sp, d), kv_index),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            out_shape=jax.ShapeDtypeStruct((b * h, sp, d), q.dtype),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            ),
            name=name,
        )(qf, kf, vf)
    else:
        num_k = sp // block_k
        kernel = functools.partial(
            _flash_streamed_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, valid_len=s, num_k=num_k,
            window=window,
        )
        grid = (b * h, sp // block_q, num_k)
        kv_index = lambda i, j, kj: (i // h * hkv + (i % h) // g, kj, 0)
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda i, j, kj: (i, j, 0)),
                pl.BlockSpec((1, block_k, d), kv_index),
                pl.BlockSpec((1, block_k, d), kv_index),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, kj: (i, j, 0)),
            out_shape=jax.ShapeDtypeStruct((b * h, sp, d), q.dtype),
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),      # acc
                pltpu.VMEM((block_q, 128), jnp.float32),    # m (lane-bcast)
                pltpu.VMEM((block_q, 128), jnp.float32),    # l (lane-bcast)
            ],
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
            name=name,
        )(qf, kf, vf)
    out = out.reshape(b, h, sp, d)
    if pad:
        out = out[:, :, :s, :]
    return out


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret",
                              "sm_scale")
)
def flash_attention(  # static-bounded: sm_scale -- one value per model config (None, or the head's own scale of a differential model)
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
    sm_scale: float | None = None,
) -> jax.Array:
    """``_flash_attention`` with no window: every key at or before the query."""
    return _flash_attention(q, k, v, causal, block_q, block_k, interpret,
                            sm_scale=sm_scale)


@functools.partial(
    jax.jit, static_argnames=("window", "block_q", "block_k", "interpret",
                              "sm_scale")
)
def flash_window_attention(  # static-bounded: window, sm_scale -- one value each per model config (sliding_window; None, or the head's own scale of a differential model)
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    window: int,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
    sm_scale: float | None = None,
) -> jax.Array:
    """Causal flash attention in which query ``i`` reads keys ``(i - window,
    i]`` (``_flash_attention``): a window layer's fresh prefill."""
    return _flash_attention(q, k, v, True, block_q, block_k, interpret,
                            window=int(window), sm_scale=sm_scale)


def _flash_carry_kernel(
    rel_ref, q_ref, k_ref, v_ref, acc_in_ref, m_in_ref, l_in_ref,
    acc_out_ref, m_out_ref, l_out_ref, acc_s, m_s, l_s, *,
    sm_scale: float, causal: bool, block_q: int, block_k: int, num_k: int,
):
    """Streamed flash step that THREADS the online-softmax carry: scratch is
    seeded from (acc_in, m_in, l_in) at kj==0 and written back at the last
    kj, so a caller can chain calls over K/V blocks that arrive one at a
    time — ring attention's ppermute hops (parallel/ring_attention.py).
    ``rel_ref`` (SMEM) holds k_off - q_off: global positions are runtime
    values under shard_map (axis_index), never compile-time constants."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    rel = rel_ref[0]

    @pl.when(kj == 0)
    def _init():
        acc_s[...] = acc_in_ref[0]
        m_s[...] = jnp.broadcast_to(m_in_ref[0], m_s.shape)
        l_s[...] = jnp.broadcast_to(l_in_ref[0], l_s.shape)

    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if causal:
            iq = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + qi * block_q
            ik = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + kj * block_k
            s = jnp.where(iq - ik >= rel, s, NEG_INF)
        m_prev = m_s[:, :1]
        l_prev = l_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # guard the all-masked case: with m_new still NEG_INF,
        # exp(NEG_INF - NEG_INF) would be 1 and corrupt l/acc — a fully
        # masked future block must be a strict no-op on the carry
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_s[...] = acc_s[...] * alpha + pv
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    if causal:
        # skip blocks wholly above the causal frontier (rel is traced, so
        # the bound is a runtime predicate, not a shorter grid)
        pl.when(qi * block_q + block_q - 1 - kj * block_k >= rel)(_body)
    else:
        _body()

    @pl.when(kj == num_k - 1)
    def _final():
        acc_out_ref[0] = acc_s[...]
        m_out_ref[0] = m_s[:, :1]
        l_out_ref[0] = l_s[:, :1]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def flash_attention_carry(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    acc: jax.Array,
    m: jax.Array,
    l: jax.Array,
    rel: jax.Array,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One flash pass of local Q against ONE K/V block with carried
    online-softmax state — the ring-attention inner step, score matrix never
    materialized. Shapes: q/k/v (B, H, Sq|Sk, D) (Hkv may divide H);
    acc (B, H, Sq, D) f32; m/l (B, H, Sq, 1) f32; ``rel`` scalar int32 =
    k_off - q_off in global positions. Sq/Sk must be multiples of 128 (ring
    shards are; no padding path here). Returns updated (acc, m, l);
    normalize ``acc / max(l, eps)`` after the last block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if sq % 128 or sk % 128:
        raise ValueError(f"carry kernel needs 128-multiple seq, got {sq}/{sk}")
    g = h // hkv
    if block_q is None:
        block_q = 256 if sq % 256 == 0 else 128
    if block_k is None:
        block_k = next(bk for bk in (512, 256, 128) if sk % bk == 0)
    sm_scale = 1.0 / math.sqrt(d)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)
    accf = acc.reshape(b * h, sq, d)
    mf = m.reshape(b * h, sq, 1)
    lf = l.reshape(b * h, sq, 1)
    rel_arr = jnp.asarray(rel, jnp.int32).reshape((1,))

    num_k = sk // block_k
    kernel = functools.partial(
        _flash_carry_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k=num_k,
    )
    grid = (b * h, sq // block_q, num_k)
    kv_index = lambda i, j, kj: (i // h * hkv + (i % h) // g, kj, 0)
    q_index = lambda i, j, kj: (i, j, 0)
    stat_spec = pl.BlockSpec((1, block_q, 1), q_index)
    acc_o, m_o, l_o = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # rel
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_q, d), q_index),      # acc in
            stat_spec,                                   # m in
            stat_spec,                                   # l in
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            stat_spec,
            stat_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(rel_arr, qf, kf, vf, accf, mf, lf)
    return (
        acc_o.reshape(b, h, sq, d),
        m_o.reshape(b, h, sq, 1),
        l_o.reshape(b, h, sq, 1),
    )


# ---------------------------------------------------------------------------
# Differential attention over rows that hold a PAIR of KV heads
# ---------------------------------------------------------------------------
#
# Differential attention (arXiv:2410.05258) takes query heads in pairs
# ``(q1, q2) = (head 2i, head 2i + 1)`` and KV heads in pairs ``j = i // 2``:
# ``k1 = k(2j)``, ``k2 = k(2j + 1)``, ``V = [v(2j) | v(2j + 1)]``, and
# ``o_i = softmax(q1 k1^T) V - lam softmax(q2 k2^T) V``. A 128-lane row
# ``[k(2j) | k(2j + 1)]`` is what a packed head-64 arena stores (``_as_arena``),
# and with it the two softmaxes are ORDINARY grouped-query attention at a row
# of 2 D: a query padded with zeros, ``[q1 | 0]`` or ``[0 | q2]``, scores its
# own key half exactly (a zero lane adds an exact zero) at ``sm_scale = D **
# -0.5``, and the value product gives the whole ``2 D``-wide ``p . V``. So
# every attention function here serves it unchanged: the model keeps its K and
# V as rows of a pair (``sambay_lm.pair_row``: a free reshape of the
# projection), ``diff_queries`` pads the queries, the caller passes
# ``sm_scale``, and ``diff_outputs`` hands back the two terms for the caller's
# ``- lam``.

def diff_queries(q: jax.Array) -> jax.Array:
    """``q (B, Hq, T, D)`` in the model's head order -> ``(B, Hq, T, 2 D)``
    padded with zeros, in the ORDER grouped-query attention over ``Hq / 4``
    row pairs wants. The four query heads over KV pair ``j`` are ``q1, q2, q1,
    q2`` (heads ``4j .. 4j + 3``): the first and third read ``k(2j)`` and go
    first, as ``[q | 0]``; the second and fourth read ``k(2j + 1)`` and follow,
    as ``[0 | q]`` (NOT the GQA map ``head // 2``, which would hand heads
    ``4j, 4j + 1`` the first key)."""
    b, hq, t, d = q.shape
    # (B, j, a, c, T, D): head 4j + 2a + c, c = which key of the pair it reads
    qd = q.reshape(b, hq // 4, 2, 2, t, d).swapaxes(2, 3)   # (B, j, c, a, T, D)
    own = jnp.eye(2, dtype=q.dtype)[:, None, None, :, None]  # (c, 1, 1, c', 1)
    return (qd[..., None, :] * own).reshape(b, hq, t, 2 * d)


def diff_outputs(out: jax.Array) -> tuple[jax.Array, jax.Array]:
    """An attention call's output over ``diff_queries``' heads, ``(B, Hq, T,
    2 D)`` -> the two terms ``(softmax(q1 k1^T) V, softmax(q2 k2^T) V)``, each
    ``(B, Hq / 2, T, 2 D)`` with pair ``i = 2j + a`` in the model's order."""
    b, hq, t, w = out.shape
    out = out.reshape(b, hq // 4, 2, 2, t, w)               # (B, j, c, a, T, W)
    return (out[:, :, 0].reshape(b, hq // 2, t, w),
            out[:, :, 1].reshape(b, hq // 2, t, w))


# ---------------------------------------------------------------------------
# Paged-KV attention (continuous decode engine)
# ---------------------------------------------------------------------------

def _as_arena(pages: jax.Array, ndim: int = 5) -> jax.Array:
    """The paged functions take the ARENA ``(layers, n_pages, Hkv,
    page_tokens, D)`` (scales: without the last dimension) and a static
    ``layer``, and index it where it lies: a caller never slices a layer
    out, which XLA would materialise (268 MB a layer in the chat cells).
    One layer's pages alone, one dimension fewer than ``ndim`` (4 for the
    scales), are a one-layer arena.

    A K/V arena of head 64 with an even number of KV heads is stored PACKED,
    two KV heads a 128-lane row: ``(layers, n_pages, Hkv // 2, page_tokens,
    128)``, row ``[l, p, j, t] = [row(head 2j, t) | row(head 2j + 1, t)]``
    (``generation.init_paged_cache`` decides, once, from the row's shape).
    Every program here learns it from the array it is handed: the stored row
    is twice the head it computes with (``_packed``)."""
    return pages if pages.ndim == ndim else pages[None]


def _packed(pages: jax.Array, head_dim: int) -> bool:
    """Does this arena store two KV heads of ``head_dim`` a row?"""
    return pages.shape[-1] == 2 * head_dim


def _arena_kv_heads(pages: jax.Array, head_dim: int) -> int:
    """The model's KV heads, whatever the arena stores a row."""
    return pages.shape[-3] * (pages.shape[-1] // head_dim)


def pack_rows(rows: jax.Array, arena: jax.Array) -> jax.Array:
    """New rows ``(..., Hkv, D)`` as ``arena`` stores them: ``(..., Hkv // 2,
    2 D)`` for a packed arena (a free reshape: heads ``2j`` and ``2j + 1``
    are neighbours), the rows themselves for every other."""
    if not _packed(arena, rows.shape[-1]):
        return rows
    *lead, hkv, d = rows.shape
    return rows.reshape(*lead, hkv // 2, 2 * d)


def unpack_pages(pages: jax.Array, head_dim: int) -> jax.Array:
    """Pages taken out of an arena, ``(..., H, page_tokens, W)``, with a tile
    a KV head: ``(..., Hkv, page_tokens, D)``. The inverse of ``pack_rows``
    bit for bit; the identity where ``W`` is the head already."""
    if not _packed(pages, head_dim):
        return pages
    *lead, h, pt, _ = pages.shape
    return jnp.moveaxis(
        pages.reshape(*lead, h, pt, 2, head_dim), -2, -3
    ).reshape(*lead, 2 * h, pt, head_dim)


@jax.named_scope("kv_read")
def paged_gather_kv(
    pages: jax.Array, tables: jax.Array, page_tokens: int, layer: int = 0,
    scale: jax.Array | None = None, head_dim: int | None = None,
) -> jax.Array:
    """Assemble each lane's logical K or V row from the shared page arena.

    ``pages`` is the arena ``(layers, n_pages, Hkv, page_tokens, D)``
    (``_as_arena``); ``tables`` is the per-lane block table
    ``(S, pages_per_slot)`` of page indices. Logical position ``p`` of lane
    ``s`` lives at
    ``pages[layer, tables[s, p // page_tokens], :, p % page_tokens]`` — ONE
    gather of the lanes' own pages out of the whole arena, laid out in
    block-table order, so the result
    ``(S, Hkv, pages_per_slot * page_tokens, D)`` is positionally identical
    to a dense per-lane cache row and the dense causal mask applies as-is.
    A lane only ever gathers its OWN pages plus the shared trash page, so
    no cross-lane bytes are touched even before masking. An int8 arena
    (``scale``: its per-(page, head, token) f32 scales, the arena's shape
    without D) is dequantized to f32 rows AFTER the gather, the lanes' pages
    only: the REFERENCE dequant — the Pallas paged kernels apply the same
    scales in VMEM to the block they just fetched. A packed arena (two KV
    heads a stored row, ``_as_arena``) is unpacked after the gather too, the
    lanes' pages only, to the head ``head_dim`` the caller computes with
    (default: the stored row is the head).

    SILENT-JUNK HAZARD (documented + checked, ISSUE 14): a table entry of
    0 is the trash page — last-writer junk from every parked lane. Junk is
    harmless only while it sits strictly ABOVE ``pos`` (the mask hides it);
    a live lane whose table maps page 0 at a slot BELOW ``pos // page_tokens``
    would attend over garbage with no error anywhere. The admission
    protocol guarantees this cannot happen (reserve_pages covers the full
    prompt + max_new budget up front); ``TPUSC_PAGECHECK=1`` turns the
    guarantee into an assertion at every chunk dispatch
    (model_runtime._check_trash_unreachable)."""
    s_lanes, pps = tables.shape
    pages = _as_arena(pages)
    gathered = pages[layer, tables]                # (S, PPS, Hkv, pt, D)
    if scale is not None:
        gathered = dequantize_pages(
            gathered, _as_arena(scale, 4)[layer, tables])
    if head_dim is not None:
        gathered = unpack_pages(gathered, head_dim)
    _, _, hkv, pt, d = gathered.shape
    return gathered.transpose(0, 2, 1, 3, 4).reshape(
        s_lanes, hkv, pps * pt, d
    )


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    tables: jax.Array,
    pos: jax.Array,
    page_tokens: int,
    layer: int = 0,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    first: jax.Array | None = None,
    sm_scale: float | None = None,
) -> jax.Array:
    """Single-position attention over a paged KV arena — the decode-step
    counterpart of the dense slot read in ``_forward_cached_dyn``.
    ``first`` (``(S,)``, a window layer's call) is each lane's first valid
    token: the mask is then ``first <= k_pos <= pos``. ``sm_scale`` (None =
    ``D ** -0.5``) as in ``attention_reference``.

    Shapes: q ``(S, Hq, 1, D)`` (one query per lane, post-RoPE),
    k_pages/v_pages the arena ``(layers, n_pages, Hkv, page_tokens, D)``
    read at ``layer`` (``_as_arena``; int8 with ``k_scale``/``v_scale``),
    tables ``(S, pages_per_slot)`` int32, pos ``(S,)`` int32 query
    positions. Returns f32 ``(S, Hq, 1, D)``.

    The math mirrors the dense path operation-for-operation (GQA grouped
    K/V, dots in the stored dtype with f32 accumulation via
    ``preferred_element_type``, mask ``k_pos <= pos`` at NEG_INF, probs
    cast to the cache dtype for the value dot) so that with
    ``page_tokens`` dividing ``max_seq`` the reductions run over the same
    length in the same order and greedy decode is token-for-token
    identical to the dense engine. Junk rows — trash-page bytes behind
    unreserved table entries and a lane's own not-yet-written positions —
    sit strictly above ``pos`` and are masked before the softmax."""
    s_lanes, hq, _, d = q.shape
    hkv = _arena_kv_heads(k_pages, d)
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    g = hq // hkv
    kc = paged_gather_kv(k_pages, tables, page_tokens, layer, k_scale, d)
    vc = paged_gather_kv(v_pages, tables, page_tokens, layer, v_scale, d)
    qg = q.reshape(s_lanes, hkv, g, 1, d)                # kc: (S, Hkv, L, D)
    s = jnp.einsum(
        "bkgqd,bkld->bkgql", qg, kc, preferred_element_type=jnp.float32
    )
    s = s / math.sqrt(d) if sm_scale is None else s * sm_scale
    k_pos = jnp.arange(kc.shape[2])
    mask = k_pos[None, None, :] <= pos[:, None, None]    # (S, 1, L)
    if first is not None:
        mask &= k_pos[None, None, :] >= first[:, None, None]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkgql,bkld->bkgqd", p.astype(vc.dtype), vc,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(s_lanes, hq, 1, d)


def paged_verify_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    tables: jax.Array,
    pos: jax.Array,
    page_tokens: int,
    layer: int = 0,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Multi-token-query attention over a paged KV arena — the verify pass
    of in-engine speculative decoding (ISSUE 16).

    Shapes: q ``(S, Hq, T, D)`` (T = spec_tokens + 1 query positions per
    lane, post-RoPE), k_pages/v_pages the arena read at ``layer`` as in
    ``paged_decode_attention``, tables ``(S, pages_per_slot)`` int32, pos
    ``(S,)`` int32 positions of each lane's FIRST query token. Returns f32
    ``(S, Hq, T, D)``.

    Query index ``t`` of lane ``s`` sits at position ``pos[s] + t`` and
    attends with the causal mask ``k_pos <= pos[s] + t`` — with T == 1 this
    degenerates exactly to ``paged_decode_attention``'s mask, and the math
    below mirrors it operation-for-operation (GQA grouped K/V, f32
    accumulation, probs cast to the cache dtype) so the two paths are
    parity-exact over the shared positions. The caller has already
    scattered the T draft K/V rows into the lane's PRIVATE pages at
    ``pos..pos+T-1``; rows above the eventually-accepted prefix are junk a
    later round overwrites — same discipline as the solo verify chunk."""
    s_lanes, hq, t, d = q.shape
    hkv = _arena_kv_heads(k_pages, d)
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    g = hq // hkv
    kc = paged_gather_kv(k_pages, tables, page_tokens, layer, k_scale, d)
    vc = paged_gather_kv(v_pages, tables, page_tokens, layer, v_scale, d)
    qg = q.reshape(s_lanes, hkv, g, t, d)                # kc: (S, Hkv, L, D)
    s = jnp.einsum(
        "bkgqd,bkld->bkgql", qg, kc, preferred_element_type=jnp.float32
    ) / math.sqrt(d)
    k_pos = jnp.arange(kc.shape[2])
    q_pos = pos[:, None] + jnp.arange(t)[None, :]        # (S, T)
    mask = k_pos[None, None, :] <= q_pos[:, :, None]     # (S, T, L)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkgql,bkld->bkgqd", p.astype(vc.dtype), vc,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(s_lanes, hq, t, d)


def dequantize_pages(pages: jax.Array, scales: jax.Array) -> jax.Array:
    """Expand int8 pages ``(..., page_tokens, D)`` against their
    per-(page, head, token) f32 scales ``(..., page_tokens)`` back to f32
    rows. This is the REFERENCE dequant, applied by ``paged_gather_kv`` to
    the pages it gathered — the Pallas paged kernels perform the same
    multiply in VMEM on the block they just streamed, so no f32 copy of an
    arena ever materializes in HBM."""
    return pages.astype(jnp.float32) * scales[..., None]


def _widen_int8(k, v, q):
    """int8 page payloads and the query as f32 MXU operands. The per-row
    scales are NOT applied here: ``(k * ks[:, None]) @ q == (k @ q) * ks``,
    so the kernels scale the (g, pt) scores and probabilities instead of the
    (pt, d) pages — the scale row then broadcasts along sublanes in the
    layout it was loaded in, where scaling the pages would need each lane of
    that row moved to a sublane first."""
    return k.astype(jnp.float32), v.astype(jnp.float32), q.astype(jnp.float32)


def _head_scale_row(scale_ref, head):
    """(1, pt) scale row of kv head ``head`` (this grid step's
    ``program_id(1)``, read outside any ``pl.when`` body — the interpreter
    has no rule for it inside one) out of the ``(1, hkv, pt)`` block."""
    from jax.experimental import pallas as pl

    return scale_ref[0, pl.ds(head, 1), :]


# Tokens one compute block of the paged decode kernel covers: that many
# tokens' pages are fetched by one round of DMAs and multiplied as one
# (tokens, d) operand per kv head. 128 fills the MXU's contraction side of the
# value product; a constant of the kernel, not a serving option.
PAGED_BLOCK_TOKENS = 128


def _paged_decode_kernel(
    tables_ref, pos_ref, active_ref, *rest,
    sm_scale: float, page_tokens: int, block_pages: int, quantized: bool,
    layer: int, windowed: bool = False,
):
    """One LANE of paged decode attention: a grid step per lane, and inside
    it a loop over the lane's LIVE pages only, ``block_pages`` at a time.

    K and V stay in HBM, the WHOLE arena, read at the static ``layer``: no
    operand is a slice XLA would have to materialise. The arena is
    page-major ``(layers, n_pages, hkv, pt, d)``, so one page with all its
    kv heads is one contiguous run: each block is ``block_pages`` page
    copies for K and as many for V (``make_async_copy`` into ``buf[slot, :, p]``, a destination
    strided over heads so that every head's block is contiguous for the
    product), double-buffered — block n+1 lands while block n is multiplied.
    All heads of a block are computed in the step that fetched it, with the
    online-softmax carry in VMEM scratch. Work follows the tokens in flight:
    a lane costs ``ceil((pos // pt + 1) / block_pages)`` blocks, and an
    inactive lane (``active_ref`` 0: retired with a stale ``pos``, or in
    chunked prefill) starts no DMA and writes zeros.

    The last block's slots past the lane's live pages re-fetch its last live
    page (index clamped): their scores are masked, but the value product
    still multiplies what the slot holds, so it must be finite — never a
    dead table entry's trash page or a never-written VMEM slot.

    ``quantized``: pages arrive int8; the lane's per-token f32 scale rows
    ``(1, hkv, 1, tokens)`` come as ordinary VMEM blocks (the wrapper
    gathered them: a scale page's 16-wide rows are under the 128 lanes a
    manual copy can slice) and are applied to scores and probabilities
    (``_widen_int8``) — int8 halves the HBM bytes per KV token.

    ``windowed``: a fourth scalar operand, ``first_ref``, gives each lane's
    FIRST valid token (a window layer: the query reads tokens ``first..pos``
    of its table's view): the tokens before it are masked. The view a window
    call hands in (``window_ring_view``) BEGINS at the page that holds it, so
    no page wholly before the window is in the table, let alone copied, and
    the loop needs no second beginning of its own. Not with ``quantized``
    (the wrapper refuses)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if windowed:
        first_ref, *rest = rest
    q_ref, k_hbm, v_hbm, *rest = rest
    if quantized:
        ks_ref, vs_ref, o_ref, k_buf, v_buf, sem, acc_s, m_s, l_s = rest
    else:
        o_ref, k_buf, v_buf, sem, acc_s, m_s, l_s = rest

    lane = pl.program_id(0)
    pos = pos_ref[lane]
    hkv, _, d = q_ref.shape[1:]
    block_tokens = block_pages * page_tokens
    # a table slot is live iff its first token is at or before pos — the
    # same visibility rule as the reference mask, so the two paths reduce
    # over the same token set
    n_live = jnp.minimum(pos // page_tokens + 1, tables_ref.shape[1])
    n_blocks = jnp.where(active_ref[lane] != 0,
                         pl.cdiv(n_live, block_pages), 0)

    def block_copies(blk, slot):
        copies = []
        for p in range(block_pages):
            page = tables_ref[
                lane, jnp.minimum(blk * block_pages + p, n_live - 1)
            ]
            copies += [
                pltpu.make_async_copy(
                    k_hbm.at[layer, page], k_buf.at[slot, :, p],
                    sem.at[0, slot]),
                pltpu.make_async_copy(
                    v_hbm.at[layer, page], v_buf.at[slot, :, p],
                    sem.at[1, slot]),
            ]
        return copies

    acc_s[...] = jnp.zeros_like(acc_s)
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)

    @pl.when(n_blocks > 0)
    def _first():
        for c in block_copies(0, 0):
            c.start()

    def block_step(blk, carry):
        slot = jax.lax.rem(blk, 2)

        @pl.when(blk + 1 < n_blocks)
        def _next():
            for c in block_copies(blk + 1, 1 - slot):
                c.start()

        for c in block_copies(blk, slot):
            c.wait()
        q = q_ref[0]                                        # (hkv, g, d)
        k = k_buf[slot]                                     # (hkv, bp, pt, d)
        v = v_buf[slot]
        if quantized:
            k, v, q = _widen_int8(k, v, q)
        k = k.reshape(hkv, block_tokens, d)
        v = v.reshape(hkv, block_tokens, d)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                        # (hkv, g, T) f32
        if quantized:
            tok0 = pl.multiple_of(blk * block_tokens, block_tokens)
            s = s * ks_ref[0, :, :, pl.ds(tok0, block_tokens)]
        k_pos = blk * block_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2
        )
        mask = k_pos <= pos
        if windowed:
            mask &= k_pos >= first_ref[lane]
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[:, :, :1]
        l_prev = l_s[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a block that is run holds the lane's token blk * T <= pos, so
        # m_new is finite and masked entries underflow to exactly 0
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * vs_ref[0, :, :, pl.ds(tok0, block_tokens)]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                   # (hkv, g, d)
        acc_s[...] = acc_s[...] * alpha + pv
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)
        return carry

    jax.lax.fori_loop(0, n_blocks, block_step, None)
    o_ref[0] = (
        acc_s[...] / jnp.maximum(l_s[:, :, :1], 1e-30)
    ).astype(o_ref.dtype)


@jax.named_scope("kv_read")
def _lane_scale_rows(scale, tables, pos, block_tokens: int, layer: int):
    """Each lane's scale rows in token order, ``(S, hkv, 1, tokens)`` with
    ``tokens`` rounded up to whole compute blocks: ``scale`` is the arena's
    ``(layers, n_pages, hkv, pt)``, a page's rows are 16 lanes wide, so XLA
    gathers the lanes' own (``scale[layer, tables]``: 1/32 of the int8 KV
    bytes at head 128) where the pages themselves are copied by the kernel.
    Rows above ``pos`` are zeroed: the kernel multiplies masked scores and
    zero probabilities by them, and a dead table slot's trash page may hold
    anything."""
    s_lanes, pps = tables.shape
    _, _, hkv, pt = scale.shape
    rows = scale[layer, tables].transpose(0, 2, 1, 3).reshape(
        s_lanes, hkv, pps * pt)
    rows = jnp.where(jnp.arange(pps * pt) <= pos[:, None, None], rows, 0.0)
    pad = -(pps * pt) % block_tokens
    return jnp.pad(rows, ((0, 0), (0, 0), (0, pad)))[:, :, None, :]


def _paged_decode_call(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    tables: jax.Array,
    pos: jax.Array,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    active: jax.Array | None = None,
    first: jax.Array | None = None,
    *,
    page_tokens: int,
    interpret: bool = False,
    layer: int = 0,
    sm_scale: float | None = None,
) -> jax.Array:
    """Fused paged decode attention: same contract as
    ``paged_decode_attention`` (q ``(S, Hq, 1, D)``, the arena
    ``(layers, n_pages, Hkv, page_tokens, D)`` read at the static ``layer``,
    tables ``(S, pages_per_slot)``, pos ``(S,)`` -> f32 ``(S, Hq, 1, D)``),
    but ONE pass over the LIVE KV bytes: the grid is one step a lane, block
    tables, positions and the ``active`` vector ride in as scalar-prefetch
    operands, and the kernel copies each active lane's live pages straight
    out of the arena where it lies, ``PAGED_BLOCK_TOKENS`` tokens a block (``_paged_decode_kernel``). With
    ``k_scale``/``v_scale`` (the arena's shape without D, f32) the arena
    is int8 and dequantized in VMEM per fetched block. ``active`` (``(S,)``
    bool, default all true) marks lanes whose output the caller keeps: an
    inactive lane's row is zeros, whatever its ``pos`` and table say.

    Tables/pos/active are TRACED data (SMEM), same discipline as the
    reference path: page recycling/admission churn never mints a new
    program.

    A PACKED arena (two KV heads of 64 a 128-lane row, ``_as_arena``) runs
    the same kernel on half the heads, twice the group and a 128-wide row:
    pair ``j``'s queries go in padded with zeros, rows ``0..g-1`` =
    ``[q(head 2j) | 0]`` and rows ``g..2g-1`` = ``[0 | q(head 2j + 1)]``, so a
    row's product with the stored ``[k(2j) | k(2j + 1)]`` over 128 lanes IS
    its own head's score (a zero lane adds an exact zero) and every row keeps
    its own softmax; the value product gives ``[out(2j) | out(2j + 1)]`` for
    every row, of which each keeps its own head's half. Twice the MXU work of
    a memory-bound product and not a byte more from HBM: the page copies are
    whole 128-lane tiles, which a 64-wide page is not.

    ``first`` (``(S,)`` int32; ``paged_window_decode_attention_kernel``) is
    each lane's first valid token: the lane attends over tokens
    ``first..pos`` of its table's view and masks the tokens before ``first``
    (a view that begins at ``first``'s page, as ``window_ring_view`` makes it,
    holds no page wholly before the window). The same kernel body under its
    own name in the device trace, ``paged_window_decode_kernel``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    windowed = first is not None
    if windowed and k_scale is not None:
        raise ValueError("a window call over an int8 arena is not supported")
    s_lanes, hq, _, head = q.shape
    k_pages, v_pages = _as_arena(k_pages), _as_arena(v_pages)
    _, _, hkv, pt, d = k_pages.shape                # as STORED
    packed = _packed(k_pages, head)
    kv_heads = _arena_kv_heads(k_pages, head)
    if hq % kv_heads:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {kv_heads}")
    if pt != page_tokens:
        raise ValueError(f"arena page_tokens {pt} != {page_tokens}")
    if d % 128 and not interpret:
        # Mosaic: "Slice shape along dimension 3 must be aligned to tiling
        # (128)" — a manual copy cannot take a narrower page out of HBM
        raise ValueError(f"head_dim {d} not a multiple of 128")
    g = hq // hkv                                   # packed: both heads' rows
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head)
    quantized = k_scale is not None
    block_pages = max(1, PAGED_BLOCK_TOKENS // page_tokens)

    if packed:
        # (S, pair, half, g/2, 1, head) x (half, 1, half', 1): a row's own
        # half holds its query, the other half zeros
        own = jnp.eye(2, dtype=q.dtype)[:, None, :, None]
        qg = (q.reshape(s_lanes, hkv, 2, g // 2, 1, head) * own).reshape(
            s_lanes, hkv, g, d)
    else:
        qg = q.reshape(s_lanes, hkv, g, d)
    tables = tables.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    if active is None:
        active = jnp.ones((s_lanes,), jnp.int32)
    active = active.astype(jnp.int32)

    def lane_index(s, *_scalars):       # tables, pos, active (and first)
        return (s, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [pl.BlockSpec((1, hkv, g, d), lane_index), hbm, hbm]
    operands = [qg, k_pages, v_pages]
    page_buf = pltpu.VMEM((2, hkv, block_pages, pt, d), k_pages.dtype)
    scratch_shapes = [page_buf, page_buf]
    if quantized:
        operands += [
            _lane_scale_rows(_as_arena(sc, 4), tables, pos, block_pages * pt,
                             layer)
            for sc in (k_scale, v_scale)
        ]
        in_specs += [
            pl.BlockSpec((1, hkv, 1, operands[-1].shape[-1]), lane_index)
        ] * 2
    scratch_shapes += [
        pltpu.SemaphoreType.DMA((2, 2)),            # (k | v, slot)
        pltpu.VMEM((hkv, g, d), jnp.float32),       # acc
        pltpu.VMEM((hkv, g, 128), jnp.float32),     # m (lane-bcast)
        pltpu.VMEM((hkv, g, 128), jnp.float32),     # l (lane-bcast)
    ]

    scalars = [tables, pos, active]
    if windowed:
        scalars.append(first.astype(jnp.int32))
    kernel = functools.partial(
        _paged_decode_kernel, sm_scale=sm_scale, page_tokens=page_tokens,
        block_pages=block_pages, quantized=quantized, layer=layer,
        windowed=windowed,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(s_lanes,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, g, d), lane_index),
        scratch_shapes=scratch_shapes,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_lanes, hkv, g, d), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        # a window call is told apart in the device trace by its name
        name="paged_window_decode_kernel" if windowed else None,
    )(*scalars, *operands)
    if packed:
        # row (pair, half, gi) keeps columns [half * head, (half + 1) * head)
        out = out.reshape(s_lanes, hkv, 2, g // 2, 2, head)
        out = jnp.stack([out[:, :, 0, :, 0], out[:, :, 1, :, 1]], axis=2)
    return out.reshape(s_lanes, hq, 1, head)


@functools.partial(
    jax.jit, static_argnames=("page_tokens", "interpret", "layer", "sm_scale"))
def paged_decode_attention_kernel(  # static-bounded: sm_scale -- one value per model config (None, or the head's own scale of a differential model)
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    tables: jax.Array,
    pos: jax.Array,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    active: jax.Array | None = None,
    *,
    page_tokens: int,
    interpret: bool = False,
    layer: int = 0,
    sm_scale: float | None = None,
) -> jax.Array:
    """``_paged_decode_call`` over every token ``0..pos`` of a lane."""
    return _paged_decode_call(
        q, k_pages, v_pages, tables, pos, k_scale, v_scale, active,
        page_tokens=page_tokens, interpret=interpret, layer=layer,
        sm_scale=sm_scale)


@functools.partial(
    jax.jit, static_argnames=("page_tokens", "interpret", "layer", "sm_scale"))
def paged_window_decode_attention_kernel(  # static-bounded: sm_scale -- as paged_decode_attention_kernel
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    tables: jax.Array,
    pos: jax.Array,
    first: jax.Array,
    active: jax.Array | None = None,
    *,
    page_tokens: int,
    interpret: bool = False,
    layer: int = 0,
    sm_scale: float | None = None,
) -> jax.Array:
    """``_paged_decode_call`` over tokens ``first..pos`` of a lane's view: a
    window layer's decode call (``paged_window_attention``)."""
    return _paged_decode_call(
        q, k_pages, v_pages, tables, pos, None, None, active, first,
        page_tokens=page_tokens, interpret=interpret, layer=layer,
        sm_scale=sm_scale)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,  # static-bounded: causal, partitioned, window, sm_scale -- boolean domains (two programs max each); window and sm_scale are one value each per model config (sliding_window; the head's own scale of a differential model)
              partitioned: bool = False, window: int = 0,
              sm_scale: float | None = None) -> jax.Array:
    """Dispatch: Pallas flash kernel on TPU, jnp reference elsewhere (the
    kernel's interpret mode is for tests, too slow for CPU serving).

    Gate: head_dim a multiple of 64 (Mosaic pads the 128-lane dim; d=64 still
    wins from the unmaterialized (S,S) score matrix), seq >= 128 so there's
    at least one full block of work. ``partitioned`` says the caller is
    being traced into a program GSPMD partitions over a chip group: a bare
    Mosaic kernel cannot be partitioned automatically (the TPU lowering
    refuses it), so there the reference runs — like the paged kernel, the
    flash kernel is single-chip until it gets a shard_map wrapper (ROADMAP
    S7). The branch taken is recorded (``dispatch_tally``). ``window`` > 0 is a
    window layer's call (query ``i`` reads keys ``(i - window, i]``): the same
    gate under its own name, ``attention_window``, and the windowed kernel.
    ``sm_scale`` (None = ``D ** -0.5``) reaches whichever branch runs."""
    gate = "attention_window" if window else "attention"
    why = _kernel_refusal(q.shape[-1], q.shape[1], k.shape[1])
    if why is None and partitioned:
        why = "partitioned program (kernel is single-chip)"
    if why is None and q.shape[2] < 128:
        why = f"seq={q.shape[2]} < 128"
    if why is None and k.shape[2] != q.shape[2]:
        why = "kernel assumes self-attention lengths"
    if why is None and window and not causal:
        why = "a window without a causal mask"
    if why is None:
        _record_dispatch(gate, "kernel", "flash", q.shape, k.shape)
        if window:
            return flash_window_attention(q, k, v, window=window,
                                          sm_scale=sm_scale)
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    _record_dispatch(gate, "reference", why, q.shape, k.shape)
    return attention_reference(q, k, v, causal=causal, window=window,
                               sm_scale=sm_scale)


# Tests flip this to force the Pallas paged kernel through its interpreter
# on CPU (tier-1 parity without a chip). Trace-time only: flip it BEFORE the
# first paged dispatch or clear the jit caches of callers. Test-only: nothing
# in the package or its tools sets it, and chip_smoke.py asserts it is False.
PAGED_KERNEL_INTERPRET = False


def _paged_kernel_traced(gate: str, kernel: bool, q: jax.Array,
                         k_pages: jax.Array, head_multiple: int = 64,
                         reads_packed: bool = False) -> bool:
    """The paged gates' shared decision, recorded: True = trace the Pallas
    kernel, False = trace the gather+einsum reference. What a kernel copies
    out of HBM is the STORED row, so that is the width the gate holds to
    ``head_multiple``: the head itself, or 128 for a packed arena
    (``_as_arena``), which only a kernel that ``reads_packed`` takes."""
    head = q.shape[-1]
    if not kernel:
        why = "kernel=False"
    elif _packed(k_pages, head) and not reads_packed:
        why = "two kv heads a stored row: the decode kernel alone reads it"
    elif PAGED_KERNEL_INTERPRET:
        why = None
    else:
        why = _kernel_refusal(k_pages.shape[-1], q.shape[1],
                              _arena_kv_heads(k_pages, head), head_multiple)
    shapes = (q.shape, k_pages.shape, (str(k_pages.dtype),))
    if why is None:
        _record_dispatch(
            gate, "kernel",
            "interpret" if PAGED_KERNEL_INTERPRET else "pallas", *shapes
        )
        return True
    _record_dispatch(gate, "reference", why, *shapes)
    return False


def paged_attention(  # static-bounded: kernel, page_tokens, layer, sm_scale, PAGED_KERNEL_INTERPRET -- kernel and the interpret flag are booleans (two programs max); page_tokens is one value per slot state (ServingConfig kv_page_tokens); layer is the caller's unrolled loop index, below the model's depth; sm_scale is one value per model config
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    tables: jax.Array,
    pos: jax.Array,
    page_tokens: int,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    kernel: bool = True,
    active: jax.Array | None = None,
    layer: int = 0,
    sm_scale: float | None = None,
) -> jax.Array:
    """Paged decode dispatch over the arena ``(layers, n_pages, Hkv,
    page_tokens, D)`` at the static ``layer`` (``_as_arena``), mirroring
    ``attention``'s gate: the fused Pallas kernel on the TPU backend when
    shapes qualify (the STORED row a multiple of 128: the kernel copies whole
    pages out of the arena itself, and Mosaic slices an HBM operand only in
    whole 128-lane tiles. That is a head of 128, or a head of 64 whose arena
    is packed, two KV heads a row, ``_as_arena``; a head of 64 in an arena
    that could not pack (an odd number of KV heads, int8) is refused as
    before. GQA divisibility), the gather+einsum reference everywhere else.
    ``kernel=False`` (serving.kv_paged_kernel, and every mesh runtime)
    forces the reference path unconditionally. On a TPU with ``kernel=True``
    and qualifying shapes there is no quiet way out: the kernel is traced,
    and a kernel that fails to lower or compile raises. An int8 arena
    (``k_scale`` present) is dequantized in-kernel on the fast path; the
    reference dequantizes the lanes' pages after it gathered them (same
    math, minus the bandwidth win). ``active`` (``(S,)`` bool, default all
    true) names the lanes whose rows the caller keeps: the kernel does no
    work for the others and returns zeros there, the reference computes them
    like any lane. The branch taken is recorded (``dispatch_tally``)."""
    if _paged_kernel_traced("paged_attention", kernel, q, k_pages,
                            head_multiple=128, reads_packed=True):
        return paged_decode_attention_kernel(
            q, k_pages, v_pages, tables, pos, k_scale, v_scale, active,
            page_tokens=page_tokens, interpret=PAGED_KERNEL_INTERPRET,
            layer=layer, sm_scale=sm_scale,
        )
    return paged_decode_attention(q, k_pages, v_pages, tables, pos,
                                  page_tokens, layer, k_scale, v_scale,
                                  sm_scale=sm_scale)


def window_ring_pages(window: int, page_tokens: int) -> int:
    """Pages a lane keeps in a window layer: the most that ``window``
    consecutive tokens touch (``window / page_tokens + 1`` where the page
    divides the window)."""
    return (int(window) + int(page_tokens) - 2) // int(page_tokens) + 1


def window_ring_view(pos: jax.Array, window: int, page_tokens: int,
                     ring_pages: int):
    """What a window layer's decode call reads of each lane's ring, worked
    out from ``pos (S,)`` alone -> ``(tables (S, ring_pages), pos in the view,
    first valid token in the view)``. Position ``p`` of lane ``s`` lives in
    page ``s * ring_pages + (p // page_tokens) % ring_pages`` of the window
    arena; the table is the lane's ring ROTATED so that the page holding the
    oldest needed token, ``max(0, pos - window + 1)``, comes first, and the
    two positions are counted from that page's first token."""
    s_lanes = pos.shape[0]
    first = jnp.maximum(pos - (window - 1), 0)
    page0 = first // page_tokens
    ring = (page0[:, None] + jnp.arange(ring_pages)[None, :]) % ring_pages
    tables = jnp.arange(s_lanes)[:, None] * ring_pages + ring
    base = page0 * page_tokens
    return tables.astype(jnp.int32), pos - base, first - base


def paged_window_attention(  # static-bounded: kernel, page_tokens, window, layer, sm_scale, PAGED_KERNEL_INTERPRET -- as paged_attention; window is one value per model config (sliding_window)
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    pos: jax.Array,
    page_tokens: int,
    window: int,
    kernel: bool = True,
    active: jax.Array | None = None,
    layer: int = 0,
    sm_scale: float | None = None,
) -> jax.Array:
    """A WINDOW layer's decode dispatch over its ring arena ``(window layers,
    lanes x ring_pages, Hkv, page_tokens, D)`` at the static ``layer``: each
    lane attends over the last ``window`` tokens up to ``pos`` in the pages
    it owns for life (``window_ring_view``: no table is an operand, the view
    is derived here from ``pos``). ``paged_attention``'s gate under its own
    name, ``paged_window_attention``: the fused kernel with a first valid
    token (the view begins at its page, so no page before the window is
    copied; its device-trace name is ``paged_window_decode_kernel``), else the
    gather + einsum reference with the same bound."""
    s_lanes = q.shape[0]
    ring_pages = _as_arena(k_pages).shape[1] // s_lanes
    tables, pos_v, first_v = window_ring_view(
        pos, window, page_tokens, ring_pages)
    if _paged_kernel_traced("paged_window_attention", kernel, q, k_pages,
                            head_multiple=128, reads_packed=True):
        return paged_window_decode_attention_kernel(
            q, k_pages, v_pages, tables, pos_v, first_v, active,
            page_tokens=page_tokens, interpret=PAGED_KERNEL_INTERPRET,
            layer=layer, sm_scale=sm_scale,
        )
    return paged_decode_attention(q, k_pages, v_pages, tables, pos_v,
                                  page_tokens, layer, first=first_v,
                                  sm_scale=sm_scale)


# ---------------------------------------------------------------------------
# Paged LATENT attention (MLA's absorbed form over one shared row a token)
# ---------------------------------------------------------------------------

# Tokens one compute block of the latent kernel covers. A latent page is one
# (page_tokens, width) tile of one shared row a token, 12 KiB at 16 x 384
# bf16, where a K/V page is a tile a kv head: two blocks of PAGED_BLOCK_TOKENS
# in flight are 0.2 MB, far under what the HBM moves in a copy's latency, so
# the block is wide (tests/test_mla_moe.py's on-chip rows time the choices).
LATENT_BLOCK_TOKENS = 2048
# Page copies issued (unrolled) a round of the kernel's copy loop.
LATENT_COPY_GROUP = 8


def paged_latent_attention_reference(
    q: jax.Array, pages: jax.Array, tables: jax.Array, pos: jax.Array,
    page_tokens: int, value_width: int, sm_scale: float, layer: int = 0,
) -> jax.Array:
    """Absorbed latent attention over the paged arena, gather + einsum: the
    reference of ``paged_latent_decode_attention_kernel`` and the path of
    every T > 1 forward (verify, chunked prefill).

    q ``(S, H, T, W)``: each head's query against the latent row (the
    absorbed ``q_n W_kvb,K`` beside ``rope(q_r)``, zero in the row's pad
    columns); ``pages`` the one-sided arena ``(layers, n_pages, 1,
    page_tokens, W)`` read at ``layer``; query ``t`` of lane ``s`` sits at
    ``pos[s] + t`` and sees rows at or below it. All H heads read the SAME
    row (group H over one kv head); a row's first ``value_width`` columns are
    its value. Returns f32 ``(S, H, T, value_width)``."""
    rows = paged_gather_kv(pages, tables, page_tokens, layer)[:, 0]  # (S, L, W)
    t = q.shape[2]
    s = jnp.einsum("shtw,slw->shtl", q, rows,
                   preferred_element_type=jnp.float32) * sm_scale
    q_pos = pos[:, None] + jnp.arange(t)[None, :]                    # (S, T)
    mask = jnp.arange(rows.shape[1])[None, None, :] <= q_pos[:, :, None]
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("shtl,slc->shtc", p.astype(rows.dtype),
                      rows[..., :value_width],
                      preferred_element_type=jnp.float32)


def _paged_latent_decode_kernel(
    tables_ref, pos_ref, active_ref, q_ref, kv_hbm, o_ref, buf, sem, acc_s,
    m_s, l_s, *, sm_scale: float, page_tokens: int, block_pages: int,
    value_width: int, layer: int,
):
    """One LANE of latent decode attention, after ``_paged_decode_kernel``'s
    plan: a grid step a lane, a double-buffered loop over the lane's LIVE
    pages copied out of the arena in HBM by the kernel itself, nothing for an
    inactive lane. What differs is the row: ONE ``(page_tokens, W)`` tile a
    page, shared by all H heads, so the score product is ``(H, W) x (tokens,
    W)^T`` and the value product takes the same block's first ``value_width``
    columns. A page is 12 KiB where a K/V page with its heads is 32 or 64, so
    a block is many pages (``LATENT_BLOCK_TOKENS``) and its copies are issued
    by a loop over the block's LIVE groups of pages only: the last block's
    dead groups are copied by nobody, keep what an earlier block (or the
    zeroing at the first lane) left there, finite, and are masked out of the
    scores."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lane = pl.program_id(0)
    pos = pos_ref[lane]
    block_tokens = block_pages * page_tokens
    n_live = jnp.minimum(pos // page_tokens + 1, tables_ref.shape[1])
    n_blocks = jnp.where(active_ref[lane] != 0,
                         pl.cdiv(n_live, block_pages), 0)

    @pl.when(lane == 0)
    def _finite():
        buf[...] = jnp.zeros_like(buf)

    def groups_in(blk):
        """Groups of ``LATENT_COPY_GROUP`` pages of block ``blk`` that hold a
        live page."""
        return pl.cdiv(jnp.minimum(n_live - blk * block_pages, block_pages),
                       LATENT_COPY_GROUP)

    def start_block(blk, slot):
        # a loop over the block's live GROUPS, a group's copies unrolled:
        # issuing a copy costs the scalar core about 50 ns, a loop round a
        # page more. The last group's slots past the lane's live pages
        # re-fetch its last live page (at most a group less one of them).
        def group(g, carry):
            for j in range(LATENT_COPY_GROUP):
                p = g * LATENT_COPY_GROUP + j
                page = tables_ref[
                    lane, jnp.minimum(blk * block_pages + p, n_live - 1)]
                pltpu.make_async_copy(
                    kv_hbm.at[layer, page, 0], buf.at[slot, p],
                    sem.at[slot]).start()
            return carry
        jax.lax.fori_loop(0, groups_in(blk), group, None)

    def wait_block(blk, slot):
        # the semaphore counts bytes: one wait a group, for a group's bytes
        def group(g, carry):
            at = pl.ds(g * LATENT_COPY_GROUP, LATENT_COPY_GROUP)
            pltpu.make_async_copy(
                kv_hbm.at[layer, pl.ds(0, LATENT_COPY_GROUP), 0],
                buf.at[slot, at], sem.at[slot]).wait()
            return carry
        jax.lax.fori_loop(0, groups_in(blk), group, None)

    acc_s[...] = jnp.zeros_like(acc_s)
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)

    @pl.when(n_blocks > 0)
    def _first():
        start_block(0, 0)

    def block_step(blk, carry):
        slot = jax.lax.rem(blk, 2)

        @pl.when(blk + 1 < n_blocks)
        def _next():
            start_block(blk + 1, 1 - slot)

        wait_block(blk, slot)
        q = q_ref[0]                                        # (H, W)
        kv = buf[slot].reshape(block_tokens, q.shape[-1])   # (T, W)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                        # (H, T) f32
        k_pos = blk * block_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos, s, NEG_INF)
        m_prev = m_s[:, :1]
        l_prev = l_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(kv.dtype), kv[:, :value_width],
                     preferred_element_type=jnp.float32)    # (H, value_width)
        acc_s[...] = acc_s[...] * alpha + pv
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)
        return carry

    jax.lax.fori_loop(0, n_blocks, block_step, None)
    o_ref[0] = (acc_s[...] / jnp.maximum(l_s[:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("page_tokens", "value_width", "sm_scale",
                              "interpret", "layer"))
def paged_latent_decode_attention_kernel(
    q: jax.Array, pages: jax.Array, tables: jax.Array, pos: jax.Array,
    active: jax.Array | None = None, *, page_tokens: int, value_width: int,
    sm_scale: float, interpret: bool = False, layer: int = 0,
) -> jax.Array:
    """Fused latent decode attention: ``paged_latent_attention_reference``'s
    contract at T = 1 (q ``(S, H, 1, W)``, the whole one-sided arena
    ``(layers, n_pages, 1, page_tokens, W)`` and a static ``layer`` -> f32
    ``(S, H, 1, value_width)``) in ONE pass over the live rows, which no
    reduction over heads ever repeats: the arena is read once a lane for all
    H heads. ``active`` as in ``paged_decode_attention_kernel``: an inactive
    lane copies nothing and answers zeros. Tables, positions and ``active``
    are traced data (SMEM)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_lanes, h, _, w = q.shape
    pages = _as_arena(pages)
    if pages.shape[2:] != (1, page_tokens, w):
        raise ValueError(f"latent arena {pages.shape} is not (layers, pages, "
                         f"1, {page_tokens}, {w})")
    if (w % 128 or value_width % 128) and not interpret:
        raise ValueError(f"row width {w} / value width {value_width} not a "
                         "multiple of 128")
    if pages.shape[1] < LATENT_COPY_GROUP:
        raise ValueError(f"latent arena of {pages.shape[1]} pages: the kernel "
                         f"waits for {LATENT_COPY_GROUP} page copies at a time")
    block_pages = -(-max(1, LATENT_BLOCK_TOKENS // page_tokens)
                    // LATENT_COPY_GROUP) * LATENT_COPY_GROUP
    if active is None:
        active = jnp.ones((s_lanes,), jnp.int32)

    def lane_index(s, tbl, ps, act):
        return (s, 0, 0)

    kernel = functools.partial(
        _paged_latent_decode_kernel, sm_scale=sm_scale, page_tokens=page_tokens,
        block_pages=block_pages, value_width=value_width, layer=layer)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_lanes,),
        in_specs=[pl.BlockSpec((1, h, w), lane_index),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, h, value_width), lane_index),
        scratch_shapes=[
            pltpu.VMEM((2, block_pages, page_tokens, w), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((h, value_width), jnp.float32),      # acc
            pltpu.VMEM((h, 128), jnp.float32),              # m (lane-bcast)
            pltpu.VMEM((h, 128), jnp.float32),              # l (lane-bcast)
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_lanes, h, value_width), jnp.float32),
        interpret=interpret,
        # "arbitrary": the lanes run in order on one core, so the first lane's
        # zeroing of the page buffers holds for all of them
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_latent_decode_kernel",
    )(tables.astype(jnp.int32), pos.astype(jnp.int32),
      active.astype(jnp.int32), q[:, :, 0], pages)
    return out[:, :, None]


def paged_latent_attention(  # static-bounded: kernel, page_tokens, value_width, sm_scale, layer, PAGED_KERNEL_INTERPRET -- kernel and the interpret flag are booleans; page_tokens is one value per slot state; value_width and sm_scale are one value per model config; layer is the caller's unrolled loop index
    q: jax.Array, pages: jax.Array, tables: jax.Array, pos: jax.Array,
    page_tokens: int, value_width: int, sm_scale: float, kernel: bool = True,
    active: jax.Array | None = None, layer: int = 0,
) -> jax.Array:
    """Latent paged attention dispatch, mirroring ``paged_attention``: the
    fused kernel for a decode step (T = 1) on the TPU when the row and value
    widths are whole 128-lane tiles, the gather + einsum reference everywhere
    else (``kernel=False``, another backend, T > 1: the verify and
    chunked-prefill forwards). The branch taken is recorded under
    ``('paged_latent_attention', 'kernel'|'reference', reason)``."""
    if not kernel:
        why = "kernel=False"
    elif q.shape[2] != 1:
        why = f"T={q.shape[2]} > 1 (the kernel is the decode step's)"
    elif PAGED_KERNEL_INTERPRET:
        why = None
    elif jax.default_backend() != "tpu":
        why = f"backend={jax.default_backend()}"
    elif q.shape[-1] % 128 or value_width % 128:
        why = (f"row width {q.shape[-1]} or value width {value_width} not a "
               "multiple of 128")
    else:
        why = None
    shapes = (q.shape, pages.shape, (str(pages.dtype),))
    if why is None:
        _record_dispatch(
            "paged_latent_attention", "kernel",
            "interpret" if PAGED_KERNEL_INTERPRET else "pallas", *shapes)
        return paged_latent_decode_attention_kernel(
            q, pages, tables, pos, active, page_tokens=page_tokens,
            value_width=value_width, sm_scale=sm_scale,
            interpret=PAGED_KERNEL_INTERPRET, layer=layer)
    _record_dispatch("paged_latent_attention", "reference", why, *shapes)
    return paged_latent_attention_reference(
        q, pages, tables, pos, page_tokens, value_width, sm_scale, layer)


def _paged_verify_kernel(
    tables_ref, pos_ref, q_ref, k_ref, v_ref, *rest, sm_scale: float,
    page_tokens: int, num_pages: int, num_queries: int, group: int,
    quantized: bool,
):
    """One (lane, kv-head, table-slot) grid step of paged VERIFY attention.

    The grid's last dimension walks the lane's block-table row; the
    BlockSpec index maps (scalar-prefetched tables/pos) turn each step into
    a DMA of exactly one arena page, read IN PLACE, with the online-softmax
    carry in VMEM scratch like ``_flash_streamed_kernel``. The query
    block carries T query positions folded into the row axis — row ``r``
    of the ``(T*g, d)`` block is query offset ``r // g`` of the lane, at
    position ``pos + r // g``. One extra iota-compare per page gives each
    row its own causal frontier, so the T-position verify pass of a spec
    round streams the arena exactly ONCE instead of T times. Visibility
    extends to the page holding ``pos + T - 1`` (the draft rows the caller
    just scattered); rows whose frontier ends earlier simply mask the
    whole page — at j == 0 every row sees k_pos 0, so the online-softmax
    max is finite from the first step and fully-masked later pages
    contribute exp(NEG_INF - finite) == 0, never NaN."""
    from jax.experimental import pallas as pl

    if quantized:
        ks_ref, vs_ref, o_ref, acc_s, m_s, l_s = rest
    else:
        o_ref, acc_s, m_s, l_s = rest

    s_i = pl.program_id(0)
    h_i = pl.program_id(1)
    j = pl.program_id(2)
    pos = pos_ref[s_i]

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    # a table slot is live iff ANY query row can see it: the deepest
    # frontier is pos + T - 1 (the last draft row, written this round)
    @pl.when(j <= (pos + num_queries - 1) // page_tokens)
    def _body():
        q = q_ref[0, 0]                                     # (T*g, d)
        k = k_ref[0, 0]                                     # (pt, d)
        v = v_ref[0, 0]
        if quantized:
            k, v, q = _widen_int8(k, v, q)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                        # (T*g, pt) f32
        if quantized:
            s = s * _head_scale_row(ks_ref, h_i)
        k_pos = j * page_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        # row r is query offset r // g: per-row causal frontier pos + r//g
        q_off = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        s = jnp.where(k_pos <= pos + q_off, s, NEG_INF)
        m_prev = m_s[:, :1]
        l_prev = l_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pw = p * _head_scale_row(vs_ref, h_i) if quantized else p
        pv = jax.lax.dot_general(
            pw.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_s[...] = acc_s[...] * alpha + pv
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(j == num_pages - 1)
    def _final():
        o_ref[0, 0] = (
            acc_s[...] / jnp.maximum(l_s[:, :1], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("page_tokens", "interpret", "layer"))
def paged_verify_attention_kernel(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    tables: jax.Array,
    pos: jax.Array,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    *,
    page_tokens: int,
    interpret: bool = False,
    layer: int = 0,
) -> jax.Array:
    """Fused paged verify attention: same contract as
    ``paged_verify_attention`` (q ``(S, Hq, T, D)``, the arena read at the
    static ``layer`` through the index maps, a block's layer dimension
    squeezed, tables, pos -> f32 ``(S, Hq, T, D)``) with one pass over the
    KV bytes. The T query positions fold into the GQA group axis — blocks become
    ``(T*g, d)`` with row ``r`` at query offset ``r // g`` — so the grid is
    ``(lanes, kv_heads, pages_per_slot)``, tables and pos ride in as
    scalar-prefetch operands and T never becomes a grid dim.
    T is a shape, not a static arg: one program per (config, spec_tokens)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_lanes, hq, t_q, d = q.shape
    k_pages, v_pages = _as_arena(k_pages), _as_arena(v_pages)
    _, _, hkv, pt, _ = k_pages.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if pt != page_tokens:
        raise ValueError(f"arena page_tokens {pt} != {page_tokens}")
    g = hq // hkv
    pps = tables.shape[1]
    sm_scale = 1.0 / math.sqrt(d)
    quantized = k_scale is not None

    # (S, Hq, T, D) -> (S, hkv, T*g, d) with row r = t*g + gi, so the
    # kernel recovers the query offset as r // g
    qg = (
        q.reshape(s_lanes, hkv, g, t_q, d)
        .transpose(0, 1, 3, 2, 4)
        .reshape(s_lanes, hkv, t_q * g, d)
    )
    tables = tables.astype(jnp.int32)
    pos = pos.astype(jnp.int32)

    def q_index(s, h, j, tbl, ps):
        return (s, h, 0, 0)

    def kv_index(s, h, j, tbl, ps):
        # the last live page holds pos + T - 1 (draft rows written this
        # round); clamp dead trailing slots to it: the block index repeats,
        # so the pipeline skips the re-fetch, and the trash page behind
        # unreserved entries is never touched
        jj = jnp.minimum(j, (ps[s] + t_q - 1) // page_tokens)
        return (layer, tbl[s, jj], h, 0, 0)

    def scale_index(s, h, j, tbl, ps):
        jj = jnp.minimum(j, (ps[s] + t_q - 1) // page_tokens)
        return (layer, tbl[s, jj], 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, t_q * g, d), q_index),
        pl.BlockSpec((None, 1, 1, pt, d), kv_index),
        pl.BlockSpec((None, 1, 1, pt, d), kv_index),
    ]
    operands = [qg, k_pages, v_pages]
    if quantized:
        # a page's scales for EVERY kv head: the TPU lowering wants a
        # block's last two dims to be the array's own (or 8x128 multiples),
        # which a per-head (1, 1, pt) block is not; the kernel picks its
        # head's row (_head_scale_row)
        in_specs += [
            pl.BlockSpec((None, 1, hkv, pt), scale_index),
            pl.BlockSpec((None, 1, hkv, pt), scale_index),
        ]
        operands += [_as_arena(k_scale, 4), _as_arena(v_scale, 4)]

    kernel = functools.partial(
        _paged_verify_kernel, sm_scale=sm_scale, page_tokens=page_tokens,
        num_pages=pps, num_queries=t_q, group=g, quantized=quantized,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_lanes, hkv, pps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, t_q * g, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((t_q * g, d), jnp.float32),      # acc
            pltpu.VMEM((t_q * g, 128), jnp.float32),    # m (lane-bcast)
            pltpu.VMEM((t_q * g, 128), jnp.float32),    # l (lane-bcast)
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (s_lanes, hkv, t_q * g, d), jnp.float32
        ),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(tables, pos, *operands)
    return (
        out.reshape(s_lanes, hkv, t_q, g, d)
        .transpose(0, 1, 3, 2, 4)
        .reshape(s_lanes, hq, t_q, d)
    )


def paged_attention_verify(  # static-bounded: kernel, page_tokens, layer, PAGED_KERNEL_INTERPRET -- kernel and the interpret flag are booleans (two programs max); page_tokens is one value per slot state (ServingConfig kv_page_tokens); layer is the caller's unrolled loop index, below the model's depth
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    tables: jax.Array,
    pos: jax.Array,
    page_tokens: int,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    kernel: bool = True,
    layer: int = 0,
) -> jax.Array:
    """Multi-token-query (verify) dispatch with exactly ``paged_attention``'s
    gate and arena operands: fused Pallas kernel on the TPU backend when
    shapes qualify, the gather+einsum reference elsewhere, ``kernel=False``
    forcing the reference unconditionally. Per-row acceptance downstream is traced
    data; only (config, spec_tokens) mints programs here."""
    if _paged_kernel_traced("paged_attention_verify", kernel, q, k_pages):
        return paged_verify_attention_kernel(
            q, k_pages, v_pages, tables, pos, k_scale, v_scale,
            page_tokens=page_tokens, interpret=PAGED_KERNEL_INTERPRET,
            layer=layer,
        )
    return paged_verify_attention(q, k_pages, v_pages, tables, pos,
                                  page_tokens, layer, k_scale, v_scale)
