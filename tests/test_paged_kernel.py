"""Fused Pallas paged-attention decode kernel (`serving.kv_paged_kernel`):
interpret-mode kernel-vs-reference parity (ragged pos and block edges,
page_tokens in {8,16}, GQA groups in {1,3,4}, int8 arenas, inactive lanes,
a poisoned arena), byte-for-byte reference
dispatch with the knob off, greedy token-for-token parity kernel-on vs
kernel-off through the continuous engine, and the hardware-gated
`paged_decode` entries tools/tpu_kernel_check.py runs on a real chip
(max-abs-err + bandwidth-proxy timing at S in {4,16,32} lanes and at the
benchmark's steady cell: 10 of 32 lanes active, ragged lengths)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tfservingcache_tpu.models.generation as generation
import tfservingcache_tpu.ops.attention as att
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import build, export_artifact
from tfservingcache_tpu.ops.attention import (
    dequantize_pages,
    paged_attention,
    paged_decode_attention,
    paged_decode_attention_kernel,
    paged_verify_attention,
    paged_verify_attention_kernel,
)
from tfservingcache_tpu.runtime.batcher import ContinuousGenerateEngine
from tfservingcache_tpu.runtime.model_runtime import TPUModelRuntime
from tfservingcache_tpu.types import Model, ModelId

# f32 model dtype: the kernel's online softmax and the reference's plain
# softmax are algebraically identical but round differently in bf16 (the
# unnormalized-vs-normalized probs differ in the last bf16 bit) — in f32
# the divergence is ~1e-7 and greedy argmax parity is robust.
TINY = {
    "vocab_size": 97,
    "d_model": 48,
    "n_layers": 2,
    "n_heads": 4,
    "n_kv_heads": 2,
    "d_ff": 96,
    "max_seq": 64,
    "dtype": "float32",
}

PT = 8


def _arena(lanes, hq, hkv, d, pps, pt, seed=0, dtype=np.float32, pos=None):
    """Random scattered arena + ragged pos (random unless given): every
    lane's pages land at shuffled arena slots (page 0 stays trash),
    trailing table slots 0."""
    rng = np.random.default_rng(seed)
    n_pages = lanes * pps + 1
    perm = rng.permutation(np.arange(1, n_pages))
    tables = perm.reshape(lanes, pps).astype(np.int32)
    k_pages = rng.standard_normal((n_pages, hkv, pt, d)).astype(dtype)
    v_pages = rng.standard_normal((n_pages, hkv, pt, d)).astype(dtype)
    q = rng.standard_normal((lanes, hq, 1, d)).astype(dtype)
    if pos is None:
        pos = rng.integers(0, pps * pt, lanes).astype(np.int32)
    # park table slots past each lane's live pages on trash, as the real
    # block tables do — the kernel's clamped index map must never read them
    for s in range(lanes):
        live = -(-(int(pos[s]) + 1) // pt)
        tables[s, live:] = 0
    return q, k_pages, v_pages, tables, pos


def _edge_case(hq, hkv, d, pt, seed):
    """The shapes the block loop can get wrong, in one arena: ``pos`` at 0,
    on a compute block's last token and on the next block's first, a full
    table, a mid-page lane; ``pages_per_slot`` NOT a multiple of the block;
    two INACTIVE lanes with a stale high ``pos`` and a zeroed table (a
    retired lane, a lane in chunked prefill). Returns the clean arena (for
    the reference), ``active``, and a POISONED copy of the pages: NaN in
    every page no live lane's table reaches below its ``pos``, the trash
    page included — a kernel that reads a dead page fails the comparison."""
    block = att.PAGED_BLOCK_TOKENS
    pps = (block + 2 * pt) // pt + 1           # one block, two pages, one odd
    assert pps % (block // pt)
    pos = np.array([0, block - 1, block, pps * pt - 1, 2 * pt + 5,
                    pps * pt - 3, pps * pt], np.int32)
    active = np.array([1, 1, 1, 1, 1, 0, 0], bool)
    q, kp, vp, tables, _ = _arena(len(pos), hq, hkv, d, pps, pt, seed=seed,
                                  pos=pos)
    tables[~active] = 0
    poison = np.ones(kp.shape[0], bool)
    poison[tables[tables > 0]] = False
    return q, kp, vp, tables, pos, active, poison


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("layout", ["ragged", "edges"])
@pytest.mark.parametrize("pt", [8, 16])
@pytest.mark.parametrize("g", [1, 3, 4])  # GQA group size hq/hkv
def test_kernel_matches_reference_interpret(pt, g, layout, quantized):
    """Interpret-mode kernel parity against the gather+einsum reference
    over scattered pages, at MHA (g=1) and GQA (g=3, 4), float and int8
    arenas. ``ragged``: random pos, every lane active (no ``active``
    operand). ``edges``: ``_edge_case`` — block boundaries, inactive lanes
    (rows of zeros), and a poisoned arena under the kernel only."""
    hkv, d = 2, 16
    seed = g * 7 + pt
    if layout == "ragged":
        q, kp, vp, tables, pos = _arena(
            lanes=5, hq=hkv * g, hkv=hkv, d=d, pps=4, pt=pt, seed=seed
        )
        active = poison = None
    else:
        q, kp, vp, tables, pos, active, poison = _edge_case(
            hkv * g, hkv, d, pt, seed
        )
    q, tables, pos = jnp.asarray(q), jnp.asarray(tables), jnp.asarray(pos)
    kern_pages, scales = [jnp.asarray(kp), jnp.asarray(vp)], []
    ref_pages = list(kern_pages)
    if quantized:
        kq, ks = generation._quantize_kv_rows(kern_pages[0])
        vq, vs = generation._quantize_kv_rows(kern_pages[1])
        kern_pages, scales = [kq, vq], [ks, vs]
        ref_pages = [dequantize_pages(kq, ks), dequantize_pages(vq, vs)]
    if poison is not None:
        bad = jnp.asarray(poison)
        kern_pages = [
            jnp.where(bad[:, None, None, None],
                      127 if quantized else np.nan, x).astype(x.dtype)
            for x in kern_pages
        ]
        scales = [jnp.where(bad[:, None, None], np.nan, x) for x in scales]
    want = np.asarray(paged_decode_attention(q, *ref_pages, tables, pos, pt))
    got = np.asarray(paged_decode_attention_kernel(
        q, *kern_pages, tables, pos, *scales,
        active=None if active is None else jnp.asarray(active),
        page_tokens=pt, interpret=True,
    ))
    if active is not None:
        assert (got[~active] == 0).all()
        got, want = got[active], want[active]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_kernel_int8_matches_dequantized_reference():
    """int8 arena: in-kernel dequant must equal the reference run on the
    explicitly dequantized pages (same scales, same math)."""
    q, kp, vp, tables, pos = _arena(
        lanes=4, hq=4, hkv=2, d=16, pps=4, pt=PT, seed=3
    )
    kq, ks = generation._quantize_kv_rows(jnp.asarray(kp))
    vq, vs = generation._quantize_kv_rows(jnp.asarray(vp))
    want = np.asarray(paged_decode_attention(
        jnp.asarray(q), dequantize_pages(kq, ks), dequantize_pages(vq, vs),
        jnp.asarray(tables), jnp.asarray(pos), PT,
    ))
    got = np.asarray(paged_decode_attention_kernel(
        jnp.asarray(q), kq, vq, jnp.asarray(tables), jnp.asarray(pos),
        ks, vs, page_tokens=PT, interpret=True,
    ))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # and the quantization itself stays within the int8 rounding envelope
    np.testing.assert_allclose(
        np.asarray(dequantize_pages(kq, ks)), kp, atol=2e-2, rtol=2e-2
    )


def test_kernel_rejects_bad_shapes():
    q, kp, vp, tables, pos = _arena(
        lanes=2, hq=3, hkv=2, d=16, pps=2, pt=PT
    )
    with pytest.raises(ValueError, match="multiple"):
        paged_decode_attention_kernel(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(pos),
            page_tokens=PT, interpret=True,
        )


def test_dispatch_kernel_off_is_reference_path():
    """`kernel=False` (serving.kv_paged_kernel=false) must route through
    paged_decode_attention itself — bitwise identical, not merely close."""
    q, kp, vp, tables, pos = _arena(
        lanes=3, hq=4, hkv=2, d=16, pps=4, pt=PT, seed=5
    )
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(pos))
    off = np.asarray(paged_attention(*args, PT, kernel=False))
    ref = np.asarray(paged_decode_attention(*args, PT))
    assert (off == ref).all()
    # on CPU the TPU-shape gate also falls back to the reference
    on_cpu = np.asarray(paged_attention(*args, PT, kernel=True))
    assert (on_cpu == ref).all()


# -- the arena where it lies (PR 26) -------------------------------------------

@pytest.mark.parametrize("layer", [0, 2], ids=["first", "last"])
@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("kernel", ["decode", "verify"])
def test_kernel_reads_its_layer_of_the_whole_arena(kernel, quantized, layer):
    """The kernels take the 5-D arena ``(layers, n_pages, hkv, pt, d)`` and a
    static ``layer`` and index it themselves (a manual copy of
    ``arena[layer, page]``, a BlockSpec with the layer squeezed), so that no
    caller slices a layer out. Three layers of different pages: the kernel at
    the first and the last equals the gather+einsum reference on THAT layer's
    pages alone, and so does the reference on the whole arena."""
    t_q = 1 if kernel == "decode" else 3
    lanes, hkv, g, d, pps, layers = 4, 2, 2, 16, 4, 3
    per_layer = [_arena(lanes, hkv * g, hkv, d, pps, PT, seed=40 + li)
                 for li in range(layers)]
    # verify writes draft rows up to pos + T - 1: keep them inside the table
    pos = np.minimum(per_layer[0][4], pps * PT - t_q).astype(np.int32)
    tables = np.arange(1, lanes * pps + 1, dtype=np.int32).reshape(lanes, pps)
    q = np.random.default_rng(7).standard_normal(
        (lanes, hkv * g, t_q, d)).astype(np.float32)
    q, tables, pos = jnp.asarray(q), jnp.asarray(tables), jnp.asarray(pos)
    arena = [jnp.stack([jnp.asarray(c[w]) for c in per_layer]) for w in (1, 2)]
    scales = []
    if quantized:
        (kq, ks), (vq, vs) = (generation._quantize_kv_rows(a) for a in arena)
        arena, scales = [kq, vq], [ks, vs]
        layer_pages = [dequantize_pages(a[layer], sc[layer])
                       for a, sc in zip(arena, scales)]
    else:
        layer_pages = [a[layer] for a in arena]
    if kernel == "decode":
        ref_fn, kern_fn, gate = (paged_decode_attention,
                                 paged_decode_attention_kernel, paged_attention)
    else:
        ref_fn, kern_fn, gate = (paged_verify_attention,
                                 paged_verify_attention_kernel,
                                 att.paged_attention_verify)
    want = np.asarray(ref_fn(q, *layer_pages, tables, pos, PT))
    got = np.asarray(kern_fn(q, *arena, tables, pos, *scales,
                             page_tokens=PT, interpret=True, layer=layer))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    other = np.asarray(kern_fn(q, *arena, tables, pos, *scales,
                               page_tokens=PT, interpret=True, layer=1))
    assert np.abs(other - want).max() > 1e-2          # the layer is not ignored
    # the reference on the whole arena gathers the lanes' pages of that layer
    # and dequantizes after the gather: the same numbers
    whole = np.asarray(gate(q, *arena, tables, pos, PT, *scales,
                            kernel=False, layer=layer))
    np.testing.assert_allclose(whole, want, atol=1e-6, rtol=1e-6)


# -- a head of 64, two KV heads a stored row (PR 34) ---------------------------

HEAD64 = 64


def _pack(pages):
    """``(..., hkv, pt, 64)`` pages as a packed arena stores them,
    ``(..., hkv // 2, pt, 128)``: row ``t`` of pair ``j`` is ``[row(head 2j, t) |
    row(head 2j + 1, t)]``, written out by hand (not through ``pack_rows``)."""
    return np.concatenate([pages[..., 0::2, :, :], pages[..., 1::2, :, :]], -1)


def _head64_cfg(hkv, g=1, layers=2, dtype="float32"):
    return {"n_layers": layers, "n_kv_heads": hkv, "n_heads": hkv * g,
            "d_model": hkv * g * HEAD64, "dtype": dtype}


@pytest.mark.parametrize("layout", ["ragged", "edges"])
@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("hkv", [2, 8])
def test_packed_kernel_matches_reference_interpret(hkv, g, layout):
    """The decode kernel over a PACKED arena (head 64, two KV heads a 128-lane
    row) through the interpreter, against the gather+einsum reference on the
    same pages unpacked: ragged positions; ``_edge_case``'s block boundaries,
    a lane whose last block is partly dead, inactive lanes (rows of zeros) and
    an arena poisoned wherever no live lane reads. The reference over the
    packed arena (it unpacks what it gathered) equals the reference over the
    unpacked one bit for bit."""
    pt = 16
    seed = 11 * hkv + g
    if layout == "ragged":
        q, kp, vp, tables, pos = _arena(
            lanes=5, hq=hkv * g, hkv=hkv, d=HEAD64, pps=12, pt=pt, seed=seed)
        active = poison = None
    else:
        q, kp, vp, tables, pos, active, poison = _edge_case(
            hkv * g, hkv, HEAD64, pt, seed)
    q, tables, pos = jnp.asarray(q), jnp.asarray(tables), jnp.asarray(pos)
    want = np.asarray(paged_decode_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), tables, pos, pt))
    packed = [jnp.asarray(_pack(kp)), jnp.asarray(_pack(vp))]
    assert packed[0].shape == (kp.shape[0], hkv // 2, pt, 2 * HEAD64)
    np.testing.assert_array_equal(
        np.asarray(paged_decode_attention(q, *packed, tables, pos, pt)), want)
    if poison is not None:
        bad = jnp.asarray(poison)[:, None, None, None]
        packed = [jnp.where(bad, np.nan, x) for x in packed]
    got = np.asarray(paged_decode_attention_kernel(
        q, *packed, tables, pos,
        active=None if active is None else jnp.asarray(active),
        page_tokens=pt, interpret=True))
    assert got.shape == want.shape == (q.shape[0], hkv * g, 1, HEAD64)
    if active is not None:
        assert (got[~active] == 0).all()
        got, want = got[active], want[active]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("writer", ["write_rows", "write_rows_live", "insert"])
@pytest.mark.parametrize("hkv", [2, 8])
def test_rows_written_into_a_packed_arena_come_back_as_the_unpacked_arenas(
        hkv, writer):
    """Write -> read round trip: the rows ``_paged_write_rows`` (every lane's,
    and a decode chunk's live lanes') and ``_paged_insert_jit`` put into the
    packed arena ``init_paged_cache`` builds come back from ``paged_gather_kv``
    and ``_paged_gather_prefix_jit`` bit for bit as those of an arena with a
    tile a KV head (made by hand: a program learns the form from the array it
    is handed)."""
    layers, n_pages, pt, lanes, pps = 2, 40, 8, 6, 4
    cfg = _head64_cfg(hkv, layers=layers)
    packed = generation.init_paged_cache(cfg, n_pages, pt)
    assert packed["k"].shape == (layers, n_pages, hkv // 2, pt, 2 * HEAD64)
    plain = {side: jnp.zeros((layers, n_pages, hkv, pt, HEAD64), jnp.float32)
             for side in ("k", "v")}
    assert sum(a.nbytes for a in packed.values()) == sum(
        a.nbytes for a in plain.values())
    rng = np.random.default_rng(hkv)
    tables = 1 + rng.permutation(lanes * pps).reshape(lanes, pps).astype(np.int32)
    if writer == "insert":
        p_pad, lane = 16, 2
        pk, pv = (jnp.asarray(rng.standard_normal(
            (layers, 1, hkv, p_pad, HEAD64)), jnp.float32) for _ in range(2))
        out = {}
        for name, cache in (("packed", packed), ("plain", plain)):
            k, v, _ = generation._paged_insert_jit(
                cache["k"], cache["v"], None, pk, pv, tables[lane], np.int32(3),
                page_tokens=pt)
            out[name] = {"k": k, "v": v}
    else:
        t_q = 3
        pos = rng.integers(0, pps * pt - t_q, lanes)
        positions = pos[:, None] + np.arange(t_q)[None]
        pages = jnp.asarray(np.take_along_axis(tables, positions // pt, axis=1))
        off = jnp.asarray(positions % pt)
        rows = [jnp.asarray(rng.standard_normal((lanes, t_q, hkv, HEAD64)),
                            jnp.float32) for _ in range(2)]
        active = jnp.asarray([1, 0, 1, 1, 0, 1], bool)
        live = generation._live_lanes(active) if writer == "write_rows_live" else None
        out = {name: generation._paged_write_rows(cache, 1, pages, off, *rows, live)
               for name, cache in (("packed", packed), ("plain", plain))}
    assert out["packed"]["k"].shape == packed["k"].shape
    tables = jnp.asarray(tables)
    for side in ("k", "v"):
        assert np.asarray(out["plain"][side]).any()
        for layer in range(layers):
            np.testing.assert_array_equal(
                np.asarray(att.paged_gather_kv(out["packed"][side], tables, pt,
                                               layer, head_dim=HEAD64)),
                np.asarray(att.paged_gather_kv(out["plain"][side], tables, pt,
                                               layer, head_dim=HEAD64)))
    some = jnp.asarray(np.asarray(tables)[2, :3])
    for a, b in zip(
            generation._paged_gather_prefix_jit(
                out["packed"]["k"], out["packed"]["v"], None, some, width=HEAD64),
            generation._paged_gather_prefix_jit(
                out["plain"]["k"], out["plain"]["v"], None, some, width=HEAD64)):
        assert a.shape == (layers, 1, hkv, 3 * pt, HEAD64)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _rows_written_one_by_one(arena, rows, table, base, pt):
    """The row scatter's contract in NumPy: row ``r >= base`` of ``rows
    (layers, heads as stored, P_pad, ...)`` goes to page ``table[r // pt]``
    (the trash page past the table), offset ``r % pt``; nothing else moves."""
    out = np.array(arena)
    for r in range(int(base), rows.shape[2]):
        page = table[r // pt] if r // pt < len(table) else 0
        out[:, page, :, r % pt] = rows[:, :, r]
    return out


@pytest.mark.parametrize("arena, p_pad, base, reserved", [
    ("head128", 32, 0, 4),
    ("head64_packed", 32, 0, 4),
    ("int8", 32, 0, 4),
    ("one_sided", 32, 0, 4),
    ("head128", 32, 16, 4),            # base on a page's edge: pages 0, 1 stay
    ("head128", 32, 21, 4),            # base cuts page 2: its rows 16..20 stay
    ("head64_packed", 32, 21, 4),
    ("int8", 32, 21, 4),
    ("one_sided", 32, 21, 4),
    ("head128", 32, 32, 4),            # nothing to write
    ("head128", 64, 0, 3),             # the bucket overshoots the reservation
    ("head128", 64, 0, 10),            # ... and the table (6 pages a lane)
    ("head64_packed", 64, 13, 3),
    ("head128", 4, 0, 1),              # a bucket under one page
    ("int8", 4, 3, 1),
    ("head128", 20, 0, 3),             # 16 cached rows + a suffix bucket of 4
    ("one_sided", 20, 18, 3),
    ("window", 32, 0, 4),
    ("window", 64, 0, 3),
])
def test_the_page_insert_puts_every_row_where_the_row_scatter_put_it(
        arena, p_pad, base, reserved):
    """``_paged_insert_jit`` (and ``_window_paged_insert_jit``'s global layers)
    write whole pages; the arena must come out bit for bit as a row-by-row
    write leaves it, on every page but the trash page: the lane's pages hold
    the prompt's rows from ``base`` up, the rows under ``base`` of the page it
    cuts and the rows past the bucket keep their old bytes, pages wholly under
    ``base`` and every other lane's pages are untouched, and what overshoots
    the reservation (table entries 0) or the table lands on page 0. The arena
    starts random, so a row that moved shows."""
    layers, pt, pps, lanes, lane = 3, 8, 6, 3, 1
    head = 64 if arena == "head64_packed" else 128
    hkv = 1 if arena == "one_sided" else 4
    n_pages = lanes * pps + 1
    rng = np.random.default_rng(p_pad + base)
    stored = (layers, n_pages, hkv // 2, pt, 128) if head == 64 else (
        layers, n_pages, hkv, pt, head)
    sides = ("k",) if arena == "one_sided" else ("k", "v")
    if arena == "int8":
        start = {s: rng.integers(-127, 128, stored).astype(np.int8) for s in sides}
        scales = {s: rng.random(stored[:-1]).astype(np.float32) for s in sides}
    else:
        start = {s: rng.standard_normal(stored).astype(np.float32) for s in sides}
        scales = None
    table = np.zeros(pps, np.int32)
    table[:min(reserved, pps)] = 1 + rng.permutation(n_pages - 1)[:min(reserved, pps)]
    n_rows = layers + 2 if arena == "window" else layers   # two window layers
    new = {s: rng.standard_normal((n_rows, 1, hkv, p_pad, head)).astype(np.float32)
           for s in sides}
    operands = [jnp.asarray(start["k"]), jnp.asarray(start["v"]) if "v" in start else None]
    rows_in = [jnp.asarray(new["k"]), jnp.asarray(new["v"]) if "v" in new else None]
    if arena == "window":
        ring_pages, window_layers = 2, (1, 3)
        rings = [jnp.asarray(rng.standard_normal(
            (2, lanes * ring_pages, hkv, pt, head)).astype(np.float32))
            for _ in sides]
        ring_start = [np.asarray(r) for r in rings]
        k, v, wk, wv = generation._window_paged_insert_jit(
            *operands, *rings, *rows_in, table, np.int32(lane), np.int32(p_pad - 1),
            page_tokens=pt, window_layers=window_layers, ring_pages=ring_pages)
        got, got_scales = {"k": k, "v": v}, None
        for ring, before, side in zip((wk, wv), ring_start, sides):
            # the rings as the parent's program left them: another lane's ring
            # bit for bit, this lane's slot of position p holds row p
            ring = np.asarray(ring)
            other = np.ones(lanes * ring_pages, bool)
            other[lane * ring_pages:(lane + 1) * ring_pages] = False
            np.testing.assert_array_equal(ring[:, other], before[:, other])
            for p in range(p_pad - 1 - ring_pages * pt, p_pad - 1):
                np.testing.assert_array_equal(
                    ring[:, lane * ring_pages + (p // pt) % ring_pages, :, p % pt],
                    new[side][list(window_layers), 0, :, p])
        new = {s: np.delete(new[s], window_layers, axis=0) for s in sides}
    else:
        k, v, got_scales = generation._paged_insert_jit(
            *operands, None if scales is None else {
                s: jnp.asarray(scales[s]) for s in sides},
            *rows_in, table, np.int32(base), page_tokens=pt)
        got = {"k": k, "v": v}
    assert (got["v"] is None) == (arena == "one_sided")
    for side in sides:
        rows = new[side][:, 0]                              # (layers, hkv, P, head)
        if head == 64:                                      # two heads a row
            rows = rows.reshape(layers, hkv // 2, 2, p_pad, head).transpose(
                0, 1, 3, 2, 4).reshape(layers, hkv // 2, p_pad, 128)
        if arena == "int8":
            # under jit, as every program that writes the arena runs it
            rows, row_scales = map(
                np.asarray, jax.jit(generation._quantize_kv_rows)(rows))
            want = _rows_written_one_by_one(scales[side], row_scales, table, base, pt)
            np.testing.assert_array_equal(np.asarray(got_scales[side])[:, 1:], want[:, 1:])
        want = _rows_written_one_by_one(start[side], rows, table, base, pt)
        assert got[side].dtype == start[side].dtype
        np.testing.assert_array_equal(np.asarray(got[side])[:, 1:], want[:, 1:])
        written = (want != start[side]).any(axis=(0, 2, 3, 4))
        assert set(np.flatnonzero(written)) <= {0, *table}, "the case moves another lane"
        if base < min(p_pad, reserved * pt):
            assert written[1:].any()


@pytest.mark.parametrize("arena, shape", [
    ("head64_even", (2, 9, 4, 8, 128)),          # packed
    ("head64_odd", (2, 9, 5, 8, 64)),            # SmolLM2's 5 KV heads
    ("head64_int8", (2, 9, 8, 8, 64)),
    ("head64_mesh", (2, 9, 8, 8, 64)),
    ("head128", (2, 9, 8, 8, 128)),
    ("head32", (2, 9, 8, 8, 32)),
    ("latent", (2, 9, 1, 8, 384)),
])
def test_only_a_two_sided_head_64_row_with_even_heads_is_stored_packed(
        arena, shape, monkeypatch):
    """``init_paged_cache`` decides, from the row's shape alone; every other
    arena has the shape it had. The gate then records the packed arena's decode
    as ``kernel`` (the interpreter here; ``pallas`` on a chip), its verify pass
    as ``reference`` by name, and the others as they were recorded."""
    from tfservingcache_tpu.models.registry import CacheRow

    kw = {}
    if arena == "latent":
        cfg, kw["row"] = {"n_layers": 2, "dtype": "float32"}, CacheRow(1, 1, 384, 256)
    else:
        hkv = 5 if arena == "head64_odd" else 8
        head = {"head128": 128, "head32": 32}.get(arena, HEAD64)
        cfg = {"n_layers": 2, "n_kv_heads": hkv, "n_heads": hkv,
               "d_model": hkv * head, "dtype": "float32"}
    if arena == "head64_int8":
        kw["arena_dtype"] = "int8"
    if arena == "head64_mesh":
        from jax.sharding import Mesh

        kw["mesh"] = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    cache = generation.init_paged_cache(cfg, 9, 8, **kw)
    assert cache["k"].shape == shape
    assert ("v" in cache) == (arena != "latent")
    if arena == "head64_int8":
        assert cache["k_scale"].shape == shape[:-1]
    if arena == "latent":
        return
    monkeypatch.setattr(att, "PAGED_KERNEL_INTERPRET", True)
    hq, head = cfg["n_heads"], cfg["d_model"] // cfg["n_heads"]
    tables = jnp.asarray(np.arange(1, 9, dtype=np.int32).reshape(2, 4))
    pos = jnp.asarray([5, 17], jnp.int32)
    scales = [cache[s] for s in ("k_scale", "v_scale") if s in cache]
    packed = arena == "head64_even"

    def traced(gate, fn, t_q):
        q = jnp.ones((2, hq, t_q, head), jnp.float32)
        before = att.dispatch_tally()
        out = fn(q, cache["k"], cache["v"], tables, pos, 8, *scales)
        assert out.shape == q.shape
        after = att.dispatch_tally()
        return [key[1:] for key in after
                if key[0] == gate and after[key] != before.get(key, 0)]

    assert traced("paged_attention", paged_attention, 1) == [
        ("kernel", "interpret")]
    assert traced("paged_attention_verify", att.paged_attention_verify, 3) == [
        ("reference", "two kv heads a stored row: the decode kernel alone "
                      "reads it") if packed else ("kernel", "interpret")]
    # on this backend, with the interpreter off, every shape is refused alike
    monkeypatch.setattr(att, "PAGED_KERNEL_INTERPRET", False)
    assert traced("paged_attention", paged_attention, 1) == [
        ("reference", "backend=cpu")]


def test_the_gate_takes_a_packed_arena_and_refuses_a_64_wide_row_by_name(
        monkeypatch):
    """What the gate answers on a TPU, asked here by patching the backend's
    name (nothing is run): a packed arena's decode is the Pallas kernel; a
    head of 64 stored a tile a KV head (an odd number of heads, int8) is
    refused with the text it always had; a head of 128 is taken as before."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q64 = jnp.ones((2, 8, 1, 64), jnp.bfloat16)
    abstract = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    gate = lambda q, pages, **kw: att._paged_kernel_traced(  # noqa: E731
        "paged_attention", True, q, pages, head_multiple=128, **kw)
    before = att.dispatch_tally()
    assert gate(q64, abstract(3, 9, 4, 16, 128), reads_packed=True)
    assert not gate(q64, abstract(3, 9, 4, 16, 128))
    assert not gate(q64, abstract(3, 9, 8, 16, 64), reads_packed=True)
    assert not gate(jnp.ones((2, 5, 1, 64)), abstract(3, 9, 5, 16, 64),
                    reads_packed=True)
    assert gate(jnp.ones((2, 8, 1, 128)), abstract(3, 9, 8, 16, 128),
                reads_packed=True)
    after = att.dispatch_tally()
    moved = {key[1:]: after[key] - before.get(key, 0) for key in after
             if key[0] == "paged_attention" and after[key] != before.get(key, 0)}
    assert moved == {
        ("kernel", "pallas"): 2,
        ("reference",
         "two kv heads a stored row: the decode kernel alone reads it"): 1,
        ("reference", "head_dim=64 not a multiple of 128"): 2}


# -- engine-level greedy parity ----------------------------------------------

@pytest.fixture
def interpret_kernel(monkeypatch):
    """Force the dispatcher's kernel arm on CPU via interpret mode. The
    decode-chunk jit reads the flag at trace time, so traces from other
    tests (or the flag-off arm) must be dropped around the toggle."""
    generation._paged_decode_chunk_jit.clear_cache()
    monkeypatch.setattr(att, "PAGED_KERNEL_INTERPRET", True)
    yield
    generation._paged_decode_chunk_jit.clear_cache()


def _load(tmp_path, name="lm"):
    export_artifact("transformer_lm", str(tmp_path), name=name, version=1,
                    config=TINY)
    rt = TPUModelRuntime(ServingConfig(platform="cpu"))
    mid = ModelId(name, 1)
    rt.ensure_loaded(Model(identifier=mid, path=str(tmp_path / name / "1")))
    return rt, mid


def _ragged_prompts(rows=6, width=11, seed=0):
    rng = np.random.default_rng(seed)
    lens = list(int(x) for x in rng.integers(2, width + 1, rows))
    ids = np.zeros((rows, width), np.int32)
    for b, length in enumerate(lens):
        ids[b, :length] = rng.integers(1, TINY["vocab_size"], length)
    return ids, lens


def test_greedy_parity_kernel_on_vs_off(tmp_path, interpret_kernel):
    """Token-for-token greedy parity through the continuous engine:
    kernel-on (interpret) vs kernel-off must be indistinguishable on
    ragged prompts, and the arena must drain clean in both arms."""
    ids, lens = _ragged_prompts()
    outs = {}
    for arm, kern in (("off", False), ("on", True)):
        rt, mid = _load(tmp_path / arm)
        eng = ContinuousGenerateEngine(rt, slots=4, chunk_tokens=4,
                                       page_tokens=PT, arena_pages=32,
                                       paged_kernel=kern)
        try:
            outs[arm] = eng.generate(mid, ids, prompt_lengths=lens,
                                     max_new_tokens=8)
            st = rt._slot_states[mid]
            assert st.kernel is kern
            st.check_page_conservation()
        finally:
            eng.close()
            rt.close()
    assert (outs["on"] == outs["off"]).all()


# -- hardware-gated proofs (tools/tpu_kernel_check.py `paged_decode`) ---------

@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)",
)
@pytest.mark.parametrize("lanes", [4, 16, 32, "cell"])
def test_paged_decode_kernel_on_tpu(lanes):
    """Hardware proof for the paged decode kernel: Mosaic-compiles and
    matches the gather+einsum reference. The timing ratio against the
    reference is PRINTED, not asserted: it is a measurement for PERF.md, and
    a bring-up check must stay about compile + parity. Rows 4 / 16 / 32:
    every lane live at 1024 tokens, so both sides stream the same KV bytes
    (the reference twice: gather out + einsum in). Row ``cell``: the
    benchmark's steady cell (``mistral7b-chat-steady``): 32 query heads over
    8 kv heads of 128, 128 table slots, 32 lanes of which 10 are active
    with ragged lengths 100-1900 and 22 are retired (stale pos, zeroed
    table) — the kernel's work follows the 10, the reference's all 32."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    pt = 16
    if lanes == "cell":
        lanes, hq, hkv, d, pps = 32, 32, 8, 128, 128
        rng = np.random.default_rng(24)
        pos = rng.integers(100, 1900, lanes).astype(np.int32)
        active = np.arange(lanes) < 10
    else:
        hq, hkv, d, pps = 8, 8, 128, 64  # 1024-token logical rows
        # long-lived lanes: bandwidth-bound shape, not mask-bound
        pos = np.full((lanes,), pps * pt - 1, np.int32)
        active = np.ones(lanes, bool)
    q, kp, vp, tables, pos = _arena(
        lanes, hq, hkv, d, pps, pt, seed=lanes, pos=pos
    )
    tables[~active] = 0
    live_tokens = int((pos + 1)[active].sum())
    q, kp, vp = (jnp.asarray(q, jnp.bfloat16), jnp.asarray(kp, jnp.bfloat16),
                 jnp.asarray(vp, jnp.bfloat16))
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)
    act = jnp.asarray(active)

    out = paged_decode_attention_kernel(
        q, kp, vp, tables, pos, active=act, page_tokens=pt
    )
    ref = paged_decode_attention(q, kp, vp, tables, pos, pt)
    assert not np.asarray(out)[~active].any()
    err = float(jnp.max(jnp.abs(out - ref)[act]))
    assert err < 3e-2, f"paged kernel diverges: max abs err {err}"

    # the arena rides as an argument: closed over, it would be compiled into
    # the timing loop as a constant of hundreds of MB
    args = (q, kp, vp, tables, pos, act)
    t_kern = chained_device_time(
        lambda q, kp, vp, tables, pos, act: paged_decode_attention_kernel(
            q, kp, vp, tables, pos, active=act, page_tokens=pt
        ), args
    )
    t_ref = chained_device_time(
        lambda q, kp, vp, tables, pos, act: paged_decode_attention(
            q, kp, vp, tables, pos, pt
        ), args
    )
    kv_bytes = 2 * live_tokens * hkv * d * kp.dtype.itemsize
    print(
        f"\n[paged_decode] S={lanes} active={int(active.sum())} "
        f"live_tokens={live_tokens} hq={hq} hkv={hkv} d={d} pt={pt}: "
        f"kernel {t_kern*1e3:.3f} ms ({kv_bytes/t_kern/1e9:.0f} GB/s proxy), "
        f"gather+einsum {t_ref*1e3:.3f} ms, speedup {t_ref/t_kern:.2f}x, "
        f"max_abs_err {err:.4f}",
        flush=True,
    )


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)",
)
def test_paged_decode_int8_on_tpu():
    """Hardware proof for the int8 arena: in-kernel dequant Mosaic-compiles
    and tracks the bf16 kernel within the int8 rounding envelope, at half
    the streamed KV bytes."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    lanes, hq, hkv, d, pt, pps = 16, 8, 8, 128, 16, 64
    q, kp, vp, tables, pos = _arena(lanes, hq, hkv, d, pps, pt, seed=2)
    pos = np.full((lanes,), pps * pt - 1, np.int32)
    tables[:, :] = np.arange(1, lanes * pps + 1).reshape(lanes, pps)
    q16 = jnp.asarray(q, jnp.bfloat16)
    kq, ks = generation._quantize_kv_rows(jnp.asarray(kp))
    vq, vs = generation._quantize_kv_rows(jnp.asarray(vp))
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)

    out8 = paged_decode_attention_kernel(
        q16, kq, vq, tables, pos, ks, vs, page_tokens=pt
    )
    out16 = paged_decode_attention_kernel(
        q16, jnp.asarray(kp, jnp.bfloat16), jnp.asarray(vp, jnp.bfloat16),
        tables, pos, page_tokens=pt
    )
    err = float(jnp.max(jnp.abs(out8 - out16)))
    assert err < 5e-2, f"int8 kernel diverges from bf16: max abs err {err}"
    t8 = chained_device_time(
        lambda q, kq, vq, tables, pos, ks, vs: paged_decode_attention_kernel(
            q, kq, vq, tables, pos, ks, vs, page_tokens=pt
        ), (q16, kq, vq, tables, pos, ks, vs)
    )
    print(
        f"\n[paged_decode int8] S={lanes}: kernel {t8*1e3:.3f} ms, "
        f"max_abs_err_vs_bf16 {err:.4f}",
        flush=True,
    )


def _tpu_case(g, d, quantized, t_q=1, seed=0):
    """Ragged scattered arena at a bring-up shape: ``hkv`` 4 kv heads x
    group ``g``, 16-token pages (the serving default), lanes whose pos ends
    mid-page and on a page edge."""
    lanes, hkv, pt, pps = 8, 4, 16, 16
    q, kp, vp, tables, pos = _arena(lanes, hkv * g, hkv, d, pps, pt,
                                    seed=seed)
    rng = np.random.default_rng(seed + 1)
    q = rng.standard_normal((lanes, hkv * g, t_q, d)).astype(np.float32)
    # verify writes draft rows up to pos + T - 1: keep them inside the table
    pos = np.minimum(pos, pps * pt - t_q).astype(np.int32)
    tables[:, :] = np.arange(1, lanes * pps + 1).reshape(lanes, pps)
    q = jnp.asarray(q, jnp.bfloat16)
    if quantized:
        kq, ks = generation._quantize_kv_rows(jnp.asarray(kp))
        vq, vs = generation._quantize_kv_rows(jnp.asarray(vp))
        kern_args = (q, kq, vq, jnp.asarray(tables), jnp.asarray(pos), ks, vs)
        ref_pages = (dequantize_pages(kq, ks), dequantize_pages(vq, vs))
    else:
        kb, vb = jnp.asarray(kp, jnp.bfloat16), jnp.asarray(vp, jnp.bfloat16)
        kern_args = (q, kb, vb, jnp.asarray(tables), jnp.asarray(pos))
        ref_pages = (kb, vb)
    return kern_args, ref_pages, pt


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)",
)
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("g", [1, 2, 3, 4])
def test_paged_decode_shapes_on_tpu(g, d, quantized):
    """Bring-up matrix for the decode kernel: the (hkv, g, d) query block
    with g < 8, the (hkv, g, 128) scratch rows, the int8 page copies and
    the gathered scale rows all meet Mosaic here; compile + parity against
    the jnp reference (dequantized pages for int8), no timing. Head 64 is
    refused BY NAME: the kernel copies pages out of HBM itself and Mosaic
    slices an HBM operand in whole 128-lane tiles only, so the kernel
    raises and the ``paged_attention`` gate traces the reference there."""
    kern_args, (kr, vr), pt = _tpu_case(g, d, quantized, seed=g * 10 + d)
    q, _, _, tables, pos = kern_args[:5]
    ref = paged_decode_attention(q, kr, vr, tables, pos, pt)
    if d % 128:
        with pytest.raises(ValueError, match="multiple of 128"):
            paged_decode_attention_kernel(*kern_args, page_tokens=pt)
        why = ("paged_attention", "reference",
               f"head_dim={d} not a multiple of 128")
        before = att.dispatch_tally().get(why, 0)
        out = paged_attention(*kern_args[:5], pt, *kern_args[5:])
        assert att.dispatch_tally().get(why, 0) == before + 1
    else:
        out = paged_decode_attention_kernel(*kern_args, page_tokens=pt)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"\n[paged_decode shapes] g={g} d={d} "
          f"{'int8' if quantized else 'bf16'}: max_abs_err {err:.4f}",
          flush=True)
    assert np.isfinite(err) and err < 3e-2, (
        f"decode kernel diverges at g={g} d={d} quantized={quantized}: {err}"
    )


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)",
)
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("g", [1, 2, 4])
def test_paged_verify_shapes_on_tpu(g, d, quantized):
    """Bring-up matrix for the verify kernel (T = 5 query positions folded
    into the group axis, per-row frontier by ``iota // group``): compile +
    parity against the jnp reference, no timing."""
    kern_args, (kr, vr), pt = _tpu_case(g, d, quantized, t_q=5,
                                        seed=g * 10 + d + 1)
    out = paged_verify_attention_kernel(*kern_args, page_tokens=pt)
    q, _, _, tables, pos = kern_args[:5]
    ref = paged_verify_attention(q, kr, vr, tables, pos, pt)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"\n[paged_verify shapes] g={g} d={d} "
          f"{'int8' if quantized else 'bf16'}: max_abs_err {err:.4f}",
          flush=True)
    assert np.isfinite(err) and err < 3e-2, (
        f"verify kernel diverges at g={g} d={d} quantized={quantized}: {err}"
    )


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)",
)
@pytest.mark.parametrize("live", [1, 6, 16, 32])
def test_paged_decode_packed_on_tpu(live):
    """PR 34 on the chip: the decode kernel over a PACKED arena at the LFM2
    cell's shape (``lfm2-longgen-steady``: 32 query heads over 8 KV heads of
    64, stored as 4 pairs a 128-lane row, 16-token pages, 256 table slots a
    lane, an arena of 8193 pages x 3 layers, read at its last layer) with
    ``live`` of 32 lanes active at ragged lengths 300-1500, the rest retired.
    Mosaic-compiles, matches the gather + einsum reference on the same arena
    (which gathers every table slot of every lane and unpacks), and both
    times are PRINTED for PERF.md, not asserted."""
    from tfservingcache_tpu.utils.benchtime import chained_device_time

    lanes, hq, hkv, d, pt, pps, layers, n_pages = 32, 32, 8, 64, 16, 256, 3, 8193
    rng = np.random.default_rng(live)
    pos = rng.integers(300, 1500, lanes).astype(np.int32)
    active = np.arange(lanes) < live
    tables = np.zeros((lanes, pps), np.int32)
    nxt = 1
    for lane in np.flatnonzero(active):
        n = int(pos[lane]) // pt + 1
        tables[lane, :n] = np.arange(nxt, nxt + n)
        nxt += n
    cfg = _head64_cfg(hkv, hq // hkv, layers=layers, dtype="bfloat16")
    keys = jax.random.split(jax.random.PRNGKey(live), 3)
    arena = generation.init_paged_cache(cfg, n_pages, pt)
    assert arena["k"].shape == (layers, n_pages, hkv // 2, pt, 2 * d)
    kp, vp = (jax.random.normal(key, arena["k"].shape, jnp.bfloat16)
              for key in keys[:2])
    q = jax.random.normal(keys[2], (lanes, hq, 1, d), jnp.bfloat16)
    tables, pos, act = jnp.asarray(tables), jnp.asarray(pos), jnp.asarray(active)
    layer = layers - 1

    out = paged_decode_attention_kernel(q, kp, vp, tables, pos, active=act,
                                        page_tokens=pt, layer=layer)
    ref = paged_decode_attention(q, kp, vp, tables, pos, pt, layer)
    assert out.shape == ref.shape == (lanes, hq, 1, d)
    assert not np.asarray(out)[~active].any()
    err = float(jnp.max(jnp.abs(out - ref)[act]))
    assert err < 3e-2, f"packed paged kernel diverges: max abs err {err}"
    args = (q, kp, vp, tables, pos, act)
    t_kern = chained_device_time(
        lambda q, kp, vp, tables, pos, act: paged_decode_attention_kernel(
            q, kp, vp, tables, pos, active=act, page_tokens=pt, layer=layer),
        args)
    t_ref = chained_device_time(
        lambda q, kp, vp, tables, pos, act: paged_decode_attention(
            q, kp, vp, tables, pos, pt, layer), args)
    live_tokens = int((np.asarray(pos) + 1)[active].sum())
    kv_bytes = 2 * live_tokens * hkv * d * 2
    print(f"\n[paged_decode packed] head 64, 8 KV heads as 4 pairs, {live} of "
          f"{lanes} lanes live ({live_tokens} tokens): kernel {t_kern*1e3:.4f} ms "
          f"({kv_bytes/t_kern/1e9:.0f} GB/s of live KV), gather+einsum "
          f"{t_ref*1e3:.3f} ms, max_abs_err {err:.4f}", flush=True)


def _random_bf16_params(family, cfg):
    """A family's leaves at ``cfg``'s widths, made on the device: normal x 0.02
    for matrices, ones for gains, all bfloat16 (the on-chip rows' weights)."""
    shapes = jax.eval_shape(build(family, cfg).init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    return jax.tree_util.tree_unflatten(treedef, [
        (0.02 * jax.random.normal(jax.random.PRNGKey(i), a.shape, jnp.bfloat16)
         if a.ndim > 1 else jnp.ones(a.shape, jnp.bfloat16))
        for i, a in enumerate(leaves)])


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)",
)
def test_decode_chunk_time_does_not_follow_the_arena_on_tpu():
    """The invariant of PR 26 on the chip: a decode step writes its new rows
    and reads its live pages, so its time does not depend on how large the
    arena is. One decode chunk (8 steps) of the benchmark's Mistral cell
    (``mistral7b-chat-steady``: 8 layers of 4096, 32 / 8 heads of 128, FFN
    14336, vocab 32768, 32 lanes, 128 table slots, kernel on), the same four
    live lanes of 600-1500 tokens, at ``kv_arena_pages`` 512 and 4096: the
    step times agree within 10 % (before: 8 passes over a layer's slice a
    step, so the larger arena cost about eight times more)."""
    cfg = build("transformer_lm", {
        "vocab_size": 32768, "d_model": 4096, "n_layers": 8, "n_heads": 32,
        "n_kv_heads": 8, "d_ff": 14336, "max_seq": 2048, "rope_theta": 1e6,
        "dtype": "bfloat16"}).config
    params = _random_bf16_params("transformer_lm", cfg)
    lanes, pps, pt, chunk = 32, 128, 16, 8
    live = np.asarray([600, 900, 1200, 1500], np.int32)
    tables = np.zeros((lanes, pps), np.int32)
    nxt = 1
    for lane, p in enumerate(live):
        n = -(-(int(p) + chunk + 1) // pt)
        tables[lane, :n] = np.arange(nxt, nxt + n)
        nxt += n
    assert nxt <= 512
    pos = np.zeros((lanes,), np.int32)
    pos[:len(live)] = live
    active = np.arange(lanes) < len(live)
    counter = np.uint32(1)
    ms = {}
    for n_pages in (512, 4096):
        cache = generation.init_paged_cache(cfg, n_pages + 1, pt)
        k, v = cache["k"], cache["v"]
        tok = jnp.ones((lanes,), jnp.int32)
        best = float("inf")
        for _ in range(6):                   # the first call compiles
            t0 = time.perf_counter()
            k, v, _, _, _, toks, _, _, _ = generation._paged_decode_chunk_jit(
                params, k, v, None, tables, tok, pos, active, counter,
                np.zeros((lanes,), np.float32), np.zeros((lanes,), np.int32),
                cfg_key=tuple(sorted(cfg.items())), chunk=chunk,
                page_tokens=pt, kernel=True)
            jax.block_until_ready(toks)
            best = min(best, time.perf_counter() - t0)
        ms[n_pages] = best / chunk * 1e3
        del k, v, cache
    print(f"\n[decode chunk vs arena] Mistral cell shape, 4 live lanes "
          f"({int(live.sum())} tokens): {ms[512]:.3f} ms a step at 512 pages, "
          f"{ms[4096]:.3f} ms at 4096 pages", flush=True)
    assert abs(ms[4096] - ms[512]) / ms[512] < 0.10, ms


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs real TPU (conftest forces CPU; run via tools/tpu_kernel_check.py)",
)
def test_decode_chunk_write_follows_the_live_lanes_on_tpu(monkeypatch):
    """PR 32 on the chip: the KV write of a decode chunk takes the rows of its
    live lanes, not of the 32 the engine was built with. One decode chunk (8
    steps) of the benchmark's OLMoE cell (``olmoe-chat-steady``: 8 layers of
    2048, 16 / 16 heads of 128, 64 experts of 1024 of which a token takes 8,
    vocab 50304, 32 lanes, a 2048-page arena, kernel on) at 2 live lanes of 32
    and at 32 of 32, as the tree writes and with every lane's rows written
    (the parent's write: no order handed down), the arena's content the same
    for both. At 2 lanes the step must come out at least 0.3 ms shorter (512
    rows a side a layer less, 95 ns each: 0.7 ms; measured 3.920 -> 3.321); at
    32 lanes it may cost the loop's eight trips a layer (measured 10.741 ->
    11.009; asserted within 6 %)."""
    cfg = build("moe_lm", {
        "vocab_size": 50304, "d_model": 2048, "n_layers": 8, "n_heads": 16,
        "n_kv_heads": 16, "d_ff": 1024, "n_experts": 64, "top_k": 8,
        "norm_topk_prob": False, "qk_norm": True, "tie_embeddings": False,
        "max_seq": 4096, "rope_theta": 1e4, "dtype": "bfloat16"}).config
    params = _random_bf16_params("moe_lm", cfg)
    lanes, pt, chunk, n_pages = 32, 16, 8, 2048
    pps = cfg["max_seq"] // pt
    counter = np.uint32(1)
    live_lanes = generation._live_lanes
    ms = {}
    for write in ("live", "every"):
        monkeypatch.setattr(generation, "_live_lanes",
                            live_lanes if write == "live" else lambda active: None)
        generation._paged_decode_chunk_jit.clear_cache()
        for live in (2, 32):
            idx = np.sort(np.random.default_rng(live).permutation(lanes)[:live])
            tables = np.zeros((lanes, pps), np.int32)
            pos = np.zeros((lanes,), np.int32)
            nxt = 1
            for lane in idx:
                pos[lane] = 400 + 11 * int(lane)
                n = -(-(int(pos[lane]) + chunk + 1) // pt)
                tables[lane, :n] = np.arange(nxt, nxt + n)
                nxt += n
            assert nxt <= n_pages
            active = np.zeros((lanes,), bool)
            active[idx] = True
            # a fresh arena a row: what the experts hit follows its content
            cache = generation.init_paged_cache(cfg, n_pages + 1, pt)
            k, v = cache["k"], cache["v"]
            tok = jnp.ones((lanes,), jnp.int32)
            best = float("inf")
            for _ in range(6):                   # the first call compiles
                t0 = time.perf_counter()
                k, v, _, _, _, toks, _, _, _ = generation._paged_decode_chunk_jit(
                    params, k, v, None, tables, tok, pos, active, counter,
                    np.zeros((lanes,), np.float32), np.zeros((lanes,), np.int32),
                    cfg_key=tuple(sorted(cfg.items())), family="moe_lm",
                    chunk=chunk, page_tokens=pt, kernel=True)
                jax.block_until_ready(toks)
                best = min(best, time.perf_counter() - t0)
            ms[write, live] = best / chunk * 1e3
            del k, v, cache
    generation._paged_decode_chunk_jit.clear_cache()
    print(f"\n[decode chunk vs live lanes] OLMoE cell shape, ms a step: "
          f"2 live of 32: {ms['live', 2]:.3f} (every lane's rows written: "
          f"{ms['every', 2]:.3f}); 32 of 32: {ms['live', 32]:.3f} "
          f"({ms['every', 32]:.3f})", flush=True)
    assert ms["every", 2] - ms["live", 2] > 0.3, ms
    assert ms["live", 32] < 1.06 * ms["every", 32], ms
