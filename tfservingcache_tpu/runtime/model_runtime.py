"""The TPU model runtime: artifact -> JAX fn -> XLA executable pinned in HBM.

This is the component that dissolves the reference's L1 process boundary
(SURVEY.md §7 design stance): where the reference POSTs a desired-state
ReloadConfigRequest to tensorflow_model_server and polls GetModelStatus every
500 ms until AVAILABLE (cachemanager.go:167-195), this runtime loads the
artifact, ``jit``-compiles the family's apply fn, runs a warmup call to
materialize the executable + params in HBM, and flips the state machine to
AVAILABLE — all in-process, nothing to poll.

HBM is the scarce resource (the reference only budgets disk bytes —
SURVEY.md §7 hard part (b)); resident models live in a byte-budgeted LRU
whose eviction drops executable + param references so XLA frees device
memory.

Variable request batch sizes are padded up to power-of-two buckets so each
model compiles O(log max_batch) executables instead of one per batch size —
dynamic shapes would otherwise force an XLA recompile per novel batch.
"""

from __future__ import annotations

import collections
import functools
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Mapping, NamedTuple

import numpy as np

from tfservingcache_tpu.cache.lru import LRUEntry
from tfservingcache_tpu.native import make_lru_cache
from tfservingcache_tpu.config import ServingConfig
from tfservingcache_tpu.models.registry import (
    ModelDef,
    SharedRows,
    TensorSpec,
    lane_layers,
    window_layers,
    load_artifact,
    static_config,
)
from tfservingcache_tpu.runtime.base import BaseRuntime, ModelNotLoadedError, RuntimeError_
from tfservingcache_tpu.types import Model, ModelId, ModelState
from tfservingcache_tpu.utils.accounting import LEDGER
from tfservingcache_tpu.utils.flight_recorder import RECORDER
from tfservingcache_tpu.utils.lockcheck import lockchecked
from tfservingcache_tpu.utils.logging import get_logger
from tfservingcache_tpu.utils.metrics import Metrics
from tfservingcache_tpu.utils import bring_up
from tfservingcache_tpu.utils.bring_up import BUILT
from tfservingcache_tpu.utils.tracing import TRACER, current_span, host_span

log = get_logger("runtime")


def next_bucket(n: int) -> int:
    """Smallest power of two >= n (batch padding bucket)."""
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def check_page_tokens(n: Any) -> int:
    """``serving.kv_page_tokens`` as an int, refused below 1: the paged arena
    is the only KV layout, so there is nothing a 0 could fall back to."""
    n = int(n)
    if n < 1:
        raise ValueError(
            "serving.kv_page_tokens must be >= 1 (the KV arena's page size "
            f"in tokens), got {n}"
        )
    return n


# Speculative-decoding worst case (VERDICT r5 #6): at acceptance ~0 every
# verify round still pays spec_tokens draft forwards + one chunked target
# forward to emit ONE token — strictly more target work per token than plain
# decode. Below this tokens-per-round the draft is pure overhead for any
# spec_tokens >= 2, so a sustained run of such generates auto-disables the
# (target, draft) pair; disabled pairs re-audition periodically in case the
# workload (or draft version) changed.
SPEC_MIN_TOKENS_PER_ROUND = 1.5
SPEC_DISABLE_AFTER = 8      # consecutive low-acceptance generates
SPEC_REPROBE_EVERY = 64     # every Nth gated request runs the draft again


def tree_nbytes(tree: Any) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree) if hasattr(x, "nbytes"))


@functools.lru_cache(maxsize=256)
def _split_fn(dtype_str: str, shapes: tuple[tuple[int, ...], ...]):
    """Jitted on-device re-slice of one packed parameter buffer. Cached per
    (dtype, shape list) — one compile per model family, shared by every
    tenant's load."""
    import jax

    def split(buf):
        parts = []
        off = 0
        for shape in shapes:
            n = 1
            for d in shape:
                n *= d
            parts.append(buf[off:off + n].reshape(shape))
            off += n
        return parts

    return jax.jit(split)


_PACK_CHUNK_BYTES = 256 << 20


def _pack_plan(arrs: list[np.ndarray]) -> list[list[int]]:
    """Deterministic transfer plan: flat indices grouped per dtype, each
    group sliced into <=~256 MB chunks. Shared by the serialized and the
    pipelined packed transfer so both issue the IDENTICAL device-op
    sequence — only who assembles the host buffers differs."""
    groups: dict[str, list[int]] = {}
    for i, a in enumerate(arrs):
        groups.setdefault(a.dtype.str, []).append(i)
    chunks: list[list[int]] = []
    for idxs in groups.values():
        chunk: list[int] = []
        chunk_bytes = 0
        for i in idxs:
            chunk.append(i)
            chunk_bytes += arrs[i].nbytes
            if chunk_bytes >= _PACK_CHUNK_BYTES:
                chunks.append(chunk)
                chunk, chunk_bytes = [], 0
        if chunk:
            chunks.append(chunk)
    return chunks


def packed_device_put(host_params: Any, device: Any) -> Any:
    """Single-stream host->device transfer of a parameter pytree.

    The cold-miss path is bandwidth-bound on the host<->HBM link (round-2
    profile: ~80% of the LM 3.14 s cold p50 was device_put of 38 separate
    leaves). Leaves are concatenated per dtype into contiguous host buffers,
    shipped in one transfer each, and re-sliced on device by a cached jitted
    split — per-leaf transfer round trips collapse to one per ~256 MB chunk.
    Chunking bounds the transient device overshoot (packed buffer + its
    re-sliced copies coexist until the split returns) to params + one chunk,
    so a model near the HBM budget still loads.
    """
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(host_params)
    arrs = [np.asarray(x) for x in leaves]
    if len(arrs) <= 2:
        return jax.device_put(host_params, device)
    out: list[Any] = [None] * len(arrs)
    for chunk in _pack_plan(arrs):
        flat = (
            np.concatenate([arrs[i].ravel() for i in chunk])
            if len(chunk) > 1
            else arrs[chunk[0]].ravel()
        )
        buf = jax.device_put(flat, device)
        parts = _split_fn(flat.dtype.str, tuple(arrs[i].shape for i in chunk))(buf)
        del buf  # the split's output is the only live device copy
        for i, p in zip(chunk, parts):
            out[i] = p
    return jax.tree_util.tree_unflatten(treedef, out)


def _flatten_for_pack(host_params: Any):
    """-> (outer leaves, outer treedef, flat np arrays, owner map). The one
    flatten bookkeeping shared by the pipelined transfer and the host-tier
    entry builder: QuantLeaf stays a single OUTER leaf contributing its q
    and scale as two FLAT arrays, so both consumers agree on what a flat
    index means."""
    import jax

    from tfservingcache_tpu.models.registry import QuantLeaf

    is_quant = lambda x: isinstance(x, QuantLeaf)  # noqa: E731
    outer, treedef = jax.tree_util.tree_flatten(host_params, is_leaf=is_quant)
    arrs: list[np.ndarray] = []
    owner: list[tuple[int, str]] = []  # flat idx -> (outer idx, plain|q|scale)
    for oi, leaf in enumerate(outer):
        if is_quant(leaf):
            arrs.append(np.asarray(leaf.q))
            owner.append((oi, "q"))
            arrs.append(np.asarray(leaf.scale))
            owner.append((oi, "scale"))
        else:
            arrs.append(np.asarray(leaf))
            owner.append((oi, "plain"))
    return outer, treedef, arrs, owner


def _shard_chunk_plan(
    arrs: list[np.ndarray], shard_list: list[Any]
) -> list[tuple[Any, list[tuple[int, tuple]]]]:
    """Chunk→shard segment map for a sharded packed transfer (ISSUE 20):
    for every addressable device, the host-index slices of each flat leaf
    that land on it (``NamedSharding.addressable_devices_indices_map``),
    grouped per dtype into <=~256 MB chunks like ``_pack_plan``. Devices
    iterate in id order and leaves in flat order, so the device-op stream
    stays a pure function of (params, shardings) — the same determinism
    contract as the unsharded plan. A replicated leaf contributes its full
    slice to EVERY device (that is what replication costs on any path);
    a partitioned leaf ships each device only its own shard — the
    per-host/per-device shard filter."""
    seg_by_dev: dict[Any, list[tuple[int, tuple]]] = {}
    for i, (arr, sharding) in enumerate(zip(arrs, shard_list)):
        for dev, idx in sharding.addressable_devices_indices_map(
            arr.shape
        ).items():
            seg_by_dev.setdefault(dev, []).append((i, idx))
    plan: list[tuple[Any, list[tuple[int, tuple]]]] = []
    for dev in sorted(seg_by_dev, key=lambda d: d.id):
        by_dtype: dict[str, list[tuple[int, tuple]]] = {}
        for i, idx in seg_by_dev[dev]:
            by_dtype.setdefault(arrs[i].dtype.str, []).append((i, idx))
        for group in by_dtype.values():
            chunk: list[tuple[int, tuple]] = []
            chunk_bytes = 0
            for i, idx in group:
                chunk.append((i, idx))
                chunk_bytes += arrs[i][idx].nbytes  # view: shape math only
                if chunk_bytes >= _PACK_CHUNK_BYTES:
                    plan.append((dev, chunk))
                    chunk, chunk_bytes = [], 0
            if chunk:
                plan.append((dev, chunk))
    return plan


def packed_device_put_sharded(
    host_params: Any,
    shardings: Any,
    buffer_depth: int = 2,
) -> Any:
    """Pipelined packed transfer of a pytree onto a (single-process) mesh:
    ``shardings`` is a pytree of ``NamedSharding`` matching ``host_params``
    (parallel/sharding.param_shardings). Each device receives only its own
    shard bytes, packed per dtype into ~256 MB chunks assembled on a side
    thread while the previous chunk's ``device_put`` streams — the same
    double-buffering as the unsharded pipelined path, minus the on-device
    dequant interleave (the mesh branch dequantizes on host first, because
    partition rules name float leaves). The global arrays are assembled
    from the landed per-device shards via
    ``jax.make_array_from_single_device_arrays`` — committed shardings,
    identical to what ``shard_params`` would have produced."""
    import queue as queue_mod

    import jax

    outer, treedef, arrs, owner = _flatten_for_pack(host_params)
    if any(role != "plain" for _, role in owner):
        raise ValueError(
            "sharded packed transfer requires host-dequantized leaves"
        )
    shard_list = jax.tree_util.tree_leaves(shardings)
    if len(shard_list) != len(arrs):
        raise ValueError("shardings tree does not match params tree")
    if len(arrs) <= 2:
        return jax.device_put(host_params, shardings)

    plan = _shard_chunk_plan(arrs, shard_list)
    done = object()
    q: Any = queue_mod.Queue(maxsize=max(1, buffer_depth))
    stop = threading.Event()

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue_mod.Full:
                continue
        return False

    def assemble() -> None:
        try:
            for dev, chunk in plan:
                parts = [
                    np.ascontiguousarray(arrs[i][idx]).ravel()
                    for i, idx in chunk
                ]
                flat = parts[0] if len(parts) == 1 else np.concatenate(parts)
                if not put((dev, chunk, flat)):
                    return
                del parts, flat
            put(done)
        except BaseException as e:  # noqa: BLE001 - re-raised by the consumer
            put(e)

    # flat idx -> {device: landed single-device shard}
    shard_parts: dict[int, dict[Any, Any]] = {i: {} for i in range(len(arrs))}
    worker = threading.Thread(
        target=assemble, name="tpusc-shard-assembler", daemon=True
    )
    worker.start()
    try:
        while True:
            item = q.get()
            if item is done:
                break
            if isinstance(item, BaseException):
                raise item
            dev, chunk, flat = item
            buf = jax.device_put(flat, dev)
            parts = _split_fn(
                flat.dtype.str, tuple(arrs[i][idx].shape for i, idx in chunk)
            )(buf)
            del buf, flat
            for (i, _idx), p in zip(chunk, parts):
                shard_parts[i][dev] = p
    finally:
        stop.set()
        worker.join(timeout=5.0)

    out: list[Any] = [None] * len(arrs)
    for i, (arr, sharding) in enumerate(zip(arrs, shard_list)):
        devs = sharding.addressable_devices_indices_map(arr.shape)
        out[i] = jax.make_array_from_single_device_arrays(
            arr.shape, sharding, [shard_parts[i][d] for d in devs]
        )
        shard_parts[i] = {}
    return jax.tree_util.tree_unflatten(treedef, out)


def packed_device_put_pipelined(
    host_params: Any,
    device: Any,
    buffer_depth: int = 2,
    capture: list | None = None,
    shardings: Any | None = None,
) -> tuple[Any, float]:
    """Double-buffered packed transfer with interleaved on-device dequant.

    -> (device params with every QuantLeaf already expanded, seconds spent
    dispatching dequants). Two overlaps over ``packed_device_put``:

      * chunk N+1's host-side ``concatenate`` runs on an assembler thread
        (feeding a queue bounded at ``buffer_depth`` chunks) while chunk N's
        async ``device_put`` streams — today that concat blocks the link;
      * a quantized leaf whose q and scale have both landed dequantizes
        immediately, overlapping the remaining chunks' transfer, instead of
        waiting for the whole tree (via ``_dequantize_on_device`` per leaf,
        so the q/scale references drop with the same per-leaf discipline).

    Every DEVICE op (device_put, split, dequant) still issues from the
    calling thread, in the same ``_pack_plan`` order as the serialized path
    — the device-op stream is a pure function of the artifact, never of
    host thread timing.

    ``capture``, when given, collects ``(chunk, flat)`` pairs as each chunk
    ships — the host-tier retention hook. Captured buffers are always OWNED
    (a single-element chunk's ``ravel`` is a view into the artifact's blob;
    retaining it would pin the whole file mapping, so views are copied).

    ``shardings`` (ISSUE 20) is the shard filter: a pytree of
    ``NamedSharding`` matching ``host_params`` routes the transfer through
    ``packed_device_put_sharded`` — per-device shard chunks instead of
    whole-leaf chunks, ``device`` ignored, dequant seconds 0.0 (the mesh
    branch dequantizes on host before calling).
    """
    import queue as queue_mod

    import jax

    from tfservingcache_tpu.models.registry import QuantLeaf

    if shardings is not None:
        return (
            packed_device_put_sharded(
                host_params, shardings, buffer_depth=buffer_depth
            ),
            0.0,
        )
    outer, treedef, arrs, owner = _flatten_for_pack(host_params)
    if len(arrs) <= 2:
        params = jax.device_put(host_params, device)
        t0 = time.monotonic()
        return _dequantize_on_device(params), time.monotonic() - t0

    chunks = _pack_plan(arrs)
    done = object()
    q: Any = queue_mod.Queue(maxsize=max(1, buffer_depth))
    stop = threading.Event()

    def put(item) -> bool:
        # bounded put that can always be abandoned: a consumer-side failure
        # sets ``stop`` and the assembler must not block on a full queue
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue_mod.Full:
                continue
        return False

    def assemble() -> None:
        try:
            for chunk in chunks:
                flat = (
                    np.concatenate([arrs[i].ravel() for i in chunk])
                    if len(chunk) > 1
                    else arrs[chunk[0]].ravel()
                )
                if not put((chunk, flat)):
                    return
                del flat
            put(done)
        except BaseException as e:  # noqa: BLE001 - re-raised by the consumer
            put(e)

    out_outer: list[Any] = [None] * len(outer)
    landed: dict[int, dict[str, Any]] = {}  # quant leaves awaiting both halves
    dequant_s = 0.0
    worker = threading.Thread(
        target=assemble, name="tpusc-chunk-assembler", daemon=True
    )
    worker.start()
    try:
        while True:
            item = q.get()
            if item is done:
                break
            if isinstance(item, BaseException):
                raise item
            chunk, flat = item
            buf = jax.device_put(flat, device)
            parts = _split_fn(
                flat.dtype.str, tuple(arrs[i].shape for i in chunk)
            )(buf)
            if capture is not None:
                capture.append(
                    (chunk, flat if flat.base is None else flat.copy())
                )
            del buf, flat  # the split's output is the only live device copy
            for i, p in zip(chunk, parts):
                oi, role = owner[i]
                if role == "plain":
                    out_outer[oi] = p
                    continue
                got = landed.setdefault(oi, {})
                got[role] = p
                if len(got) == 2:
                    ql = QuantLeaf(got["q"], got["scale"], outer[oi].orig_dtype)
                    del landed[oi]
                    t0 = time.monotonic()
                    out_outer[oi] = _dequantize_on_device(ql)
                    dequant_s += time.monotonic() - t0
    finally:
        stop.set()
        worker.join(timeout=5.0)
    return jax.tree_util.tree_unflatten(treedef, out_outer), dequant_s


def _abstract_post_dequant(host_params: Any) -> Any:
    """``jax.ShapeDtypeStruct`` pytree of ``host_params`` AFTER device
    dequant — the signature the family executable is traced against."""
    import jax

    from tfservingcache_tpu.models.registry import QuantLeaf

    def leaf(x):
        if isinstance(x, QuantLeaf):
            return jax.ShapeDtypeStruct(
                np.asarray(x.q).shape, np.dtype(x.orig_dtype)
            )
        a = np.asarray(x)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    return jax.tree_util.tree_map(
        leaf, host_params, is_leaf=lambda x: isinstance(x, QuantLeaf)
    )


@functools.lru_cache(maxsize=None)
def _dequant_fn(orig_dtype: str):
    """Cached jitted per-leaf dequant (q int8, scale f32) -> orig_dtype.
    The jit cache further keys on shapes, so every tenant of a family
    reuses one executable per leaf shape."""
    import jax
    import jax.numpy as jnp

    return jax.jit(
        lambda q, s: (q.astype(jnp.float32) * s).astype(jnp.dtype(orig_dtype))
    )


def _dequantize_on_device(params: Any) -> Any:
    """Expand QuantLeaf nodes (int8 q + f32 scale, already device-resident)
    into their original float dtype on device — the compute side of the
    int8 artifact transport. The q/scale references are dropped leaf by
    leaf as outputs materialize, so the transient HBM overshoot stays
    ~one leaf, not int8-tree + float-tree at once (the same bounded-
    overshoot discipline as packed_device_put's chunking)."""
    import jax

    from tfservingcache_tpu.models.registry import QuantLeaf

    def leaf(x):
        if isinstance(x, QuantLeaf):
            out = _dequant_fn(x.orig_dtype)(x.q, x.scale)
            x.q = x.scale = None  # free the int8 buffer once XLA is done
            return out
        return x

    return jax.tree_util.tree_map(
        leaf, params, is_leaf=lambda x: isinstance(x, QuantLeaf)
    )


def _dequantize_on_host(params: Any) -> Any:
    """Host-side expansion for the sharded branch (partition rules name
    float leaves)."""
    import jax

    from tfservingcache_tpu.models.registry import QuantLeaf

    return jax.tree_util.tree_map(
        lambda x: x.dequant_host() if isinstance(x, QuantLeaf) else x,
        params, is_leaf=lambda x: isinstance(x, QuantLeaf),
    )


def build_packed_entry(
    model_def: ModelDef,
    host_params: Any,
    jitted: Any,
    hbm_bytes: int,
    captured: list | None = None,
) -> Any:
    """Build a host-tier ``PackedModelEntry`` from a model's host pytree.

    ``captured`` — the chunk buffers the pipelined transfer just shipped —
    is reused verbatim when present (the load already paid the
    concatenates); otherwise the chunks are re-assembled here from the
    same ``_pack_plan``, which is the demotion path (params pulled back
    from the device) and the non-pipelined load paths (small trees,
    serialized fallback). Either way every retained buffer is OWNED:
    views into an artifact's decoded blob are copied rather than pinned.
    """
    from tfservingcache_tpu.cache.host_tier import PackedModelEntry
    from tfservingcache_tpu.models.registry import QuantLeaf, _leaf_path_str

    outer, treedef, arrs, owner = _flatten_for_pack(host_params)
    quant_dtypes = {
        oi: leaf.orig_dtype
        for oi, leaf in enumerate(outer)
        if isinstance(leaf, QuantLeaf)
    }
    # outer idx -> artifact leaf path, so a peer can synthesize a complete
    # v2 manifest from this entry alone (protocol/peer_transfer.py). Same
    # path convention as save_artifact; flatten order matches outer (both
    # flatten the same tree with the same is_leaf).
    import jax

    paths_with_leaves = jax.tree_util.tree_flatten_with_path(
        host_params, is_leaf=lambda x: isinstance(x, QuantLeaf)
    )[0]
    paths = [_leaf_path_str(kp) for kp, _ in paths_with_leaves]
    if captured:
        chunks = [(list(chunk), flat) for chunk, flat in captured]
    else:
        chunks = []
        for chunk in _pack_plan(arrs):
            flat = (
                np.concatenate([arrs[i].ravel() for i in chunk])
                if len(chunk) > 1
                else np.array(arrs[chunk[0]].ravel())
            )
            chunks.append((chunk, flat))
    return PackedModelEntry(
        model_def=model_def,
        chunks=chunks,
        owner=owner,
        shapes=[a.shape for a in arrs],
        quant_dtypes=quant_dtypes,
        treedef=treedef,
        jitted=jitted,
        hbm_bytes=int(hbm_bytes),
        nbytes=sum(f.nbytes for _, f in chunks),
        paths=paths,
    )


def promote_packed_entry(entry: Any, device: Any) -> tuple[Any, float]:
    """Replay a ``PackedModelEntry``'s chunks into HBM -> (device params,
    dequant dispatch seconds). This is ``packed_device_put_pipelined``'s
    consumer loop minus everything promotion gets to skip: no provider
    fetch, no artifact decode, no host-side concatenate (the buffers are
    retained pre-packed) — the identical device-op sequence the original
    load issued, fed straight from host RAM."""
    import jax

    from tfservingcache_tpu.models.registry import QuantLeaf

    out_outer: list[Any] = [None] * entry.treedef.num_leaves
    landed: dict[int, dict[str, Any]] = {}
    dequant_s = 0.0
    for chunk, flat in entry.chunks:
        buf = jax.device_put(flat, device)
        parts = _split_fn(
            flat.dtype.str, tuple(entry.shapes[i] for i in chunk)
        )(buf)
        del buf
        for i, p in zip(chunk, parts):
            oi, role = entry.owner[i]
            if role == "plain":
                out_outer[oi] = p
                continue
            got = landed.setdefault(oi, {})
            got[role] = p
            if len(got) == 2:
                ql = QuantLeaf(got["q"], got["scale"], entry.quant_dtypes[oi])
                del landed[oi]
                t0 = time.monotonic()
                out_outer[oi] = _dequantize_on_device(ql)
                dequant_s += time.monotonic() - t0
    return jax.tree_util.tree_unflatten(entry.treedef, out_outer), dequant_s


def unpack_entry_host(entry: Any) -> Any:
    """Rebuild the HOST pytree from a ``PackedModelEntry``'s retained
    chunks, expanding quant leaves on host (``dequant_host``). The sharded
    promotion path (ISSUE 20) consumes this: its transfer re-slices
    per-device segments out of whole leaves, so the whole-leaf chunk replay
    that ``promote_packed_entry`` runs doesn't apply — and partition rules
    name float leaves, so quant pairs must expand before sharding."""
    import jax

    from tfservingcache_tpu.models.registry import QuantLeaf

    flat: list[Any] = [None] * len(entry.shapes)
    for chunk, buf in entry.chunks:
        off = 0
        for i in chunk:
            shape = entry.shapes[i]
            n = int(np.prod(shape, dtype=np.int64)) if shape else 1
            flat[i] = buf[off:off + n].reshape(shape)
            off += n
    outer: list[Any] = [None] * entry.treedef.num_leaves
    pending: dict[int, dict[str, Any]] = {}
    for i, (oi, role) in enumerate(entry.owner):
        if role == "plain":
            outer[oi] = flat[i]
        else:
            pending.setdefault(oi, {})[role] = flat[i]
    for oi, got in pending.items():
        ql = QuantLeaf(got["q"], got["scale"], entry.quant_dtypes[oi])
        outer[oi] = ql.dequant_host()
    return jax.tree_util.tree_unflatten(entry.treedef, outer)


@dataclass
class LoadedModel:
    model_def: ModelDef
    params: Any                      # device-resident pytree
    jitted: Any                      # jax.jit-wrapped apply
    hbm_bytes: int
    load_lock: threading.Lock = field(default_factory=threading.Lock)


class PrefillRows(NamedTuple):
    """The ``pk`` a prefill of a model with lane-state or window layers hands
    to ``slot_admit`` (opaque to the engine between the two): its K rows for
    the arena, the lane state at ``prompt_len`` for the admitted lane (None
    for a model with no such layer) and the prompt's true length (a window
    layer's ring takes the prompt's LAST rows, never the bucket's pad)."""

    k: Any
    lane: Any
    prompt_len: int = 0


def _state_device_bytes(state: "SlotDecodeState") -> tuple[int, int, int]:
    """(global arena, window ring arena, lane state) bytes a slot state holds
    on this host's devices."""
    import jax

    def actual(arr: Any) -> int:
        # Sharded arena (ISSUE 20): the bytes actually ALLOCATED on this
        # host's devices, the per-shard sum, not the logical array size (2x
        # wrong on a 2-way KV-head split). From the sharding, never from the
        # buffers: a monitoring thread reads this while the engine's thread
        # donates the arrays to its next program, and a donated array's
        # buffers are gone (its shape, dtype and sharding are not)
        sharding = getattr(arr, "sharding", None)
        local = getattr(sharding, "addressable_devices", ())
        if len(local) <= 1:
            return int(arr.nbytes)
        shard = sharding.shard_shape(arr.shape)
        return int(np.prod(shard)) * arr.dtype.itemsize * len(local)

    nbytes = actual(state.k) + (actual(state.v) if state.v is not None else 0)
    if state.scales is not None:
        nbytes += sum(actual(a) for a in state.scales.values())
    ring = sum(actual(a) for a in state.window or ())
    # every part of a state of several parts (a tuple of arrays)
    lane = sum(actual(part)
               for part in jax.tree_util.tree_leaves(state.lane_state))
    return nbytes, ring, lane


class ChunkInFlight(NamedTuple):
    """A launched decode chunk's output futures that the host reads
    (``slot_decode_chunk_launch`` -> ``slot_decode_chunk_fetch``): the lanes'
    last token and next position after it, the (S, chunk) emitted tokens and
    an expert model's routing stats (None for a dense model)."""

    tok: Any
    pos: Any
    toks: Any
    stats: Any


@dataclass
class SlotDecodeState:
    """Device + host state of one model's continuous-decode lanes
    (runtime/batcher.py ContinuousGenerateEngine). ``k``/``v`` hold the
    shared page arena (layers, arena_pages + 1, n_kv, page_tokens, hd) —
    page 0 is the trash page — and each lane reads/writes through its
    ``block_tables`` row; the free-list hands pages out at admission and
    recycles them at retirement. The host mirrors (tok/pos/active/temps/
    topks, block tables, free-list) are owned by the engine's scheduler
    thread, which writes them in many places; the runtime reads them at a
    chunk's launch and sends the device only those that differ from what it
    last sent (``resident``: a comparison of values, not flags a writer
    could forget).
    ``lane_state`` holds what the model's layers with a fixed state keep
    (``registry.LaneState``), one slice a lane beside the arena; an admission
    overwrites its lane's slice, so retirement needs no device work.
    ``window`` holds the WINDOW layers' ring arena ``(wk, wv)``, ``(window
    layers, slots x ring_pages, n_kv, page_tokens, hd)`` each (a ``CacheRow``
    with a ``window``): ``k`` / ``v``, the tables and the free list are then
    the GLOBAL layers' alone. A lane owns its ``ring_pages`` pages of every
    window layer for life, so the ring has no free list, no reservation and
    no host table, and retirement needs no work there either."""

    model_id: ModelId
    cfg_key: tuple
    family: str
    slots: int
    max_seq: int
    k: Any                           # device page arena
    v: Any                           # None for a one-sided (latent) arena
    tok: np.ndarray                  # (S,) i32 — last sampled token per lane
    pos: np.ndarray                  # (S,) i32 — next write position
    active: np.ndarray               # (S,) bool
    temps: np.ndarray                # (S,) f32 per-lane temperature
    topks: np.ndarray                # (S,) i32 per-lane top_k
    # the chunks' key stream: chunk number n draws from
    # split(PRNGKey(n), chunk), derived inside the chunk's program
    chunk_counter: int = 0
    # the decode chunk's small operands as the device last had them, by name
    # (CHUNK_OPERANDS and "counter"): (device array, the host values it
    # holds). For tok / pos / counter the array is the last chunk's own
    # output; tok / pos hold None for values until that chunk is fetched
    resident: dict = field(default_factory=dict)
    # how many of them the last chunk's launch had to upload (ring ``uploads``)
    uploads: int = 0
    # time.monotonic() at which the last decode chunk's program call returned
    # its futures: the end of the chunk's launch path (ring ``launch_ms``)
    launched_t: float = 0.0
    # the last decode chunk's routing stats of a model with expert layers:
    # (experts_hit, expert_rows_max, expert_rows_local: generation.MOE_STATS),
    # chunk means; None for a dense model
    moe_stats: tuple | None = None
    # device array (lane layers, slots, rows, width) in the model's dtype;
    # None for a model whose layers all keep rows in the arena
    lane_state: Any = None
    # (wk, wv) device ring arena of the model's window layers; None for a
    # model with none. ``window_tokens`` is their window, ``window_rows`` the
    # window layers' indices among the model's row layers (where a prefill's
    # K/V holds their rows); ``ring_pages`` the pages a lane owns in each
    window: Any = None
    window_tokens: int = 0
    window_rows: tuple = ()
    # layers whose decode call reads a global arena layer that several layers
    # read (``generation.shared_readers``; the ring's ``shared_pages``); 0 for
    # a model in which every layer reads its own rows
    shared_readers: int = 0
    # -- arena bookkeeping (scheduler-thread-owned) --
    page_tokens: int = 0             # tokens a page; >= 1 in a built state
    arena_pages: int = 0             # usable pages (excludes trash page 0)
    pages_per_slot: int = 0          # ceil(max_seq / page_tokens)
    # int8 arena (serving.kv_arena_dtype): per-row f32 scale buffers riding
    # with the page payload ({"k","v"} device arrays, None for model dtype).
    # All page bookkeeping above is PAGE-COUNT based, so quantization never
    # touches reserve/release/CoW/census semantics — scales just travel with
    # every page write/copy.
    scales: Any = None
    arena_dtype: str = ""            # "" = model dtype; "int8" = quantized
    # serving.kv_paged_kernel: fused Pallas paged-attention decode kernel
    # (ops/attention.paged_attention gate) vs the gather+einsum reference
    kernel: bool = True
    block_tables: np.ndarray | None = None   # (S, pages_per_slot) i32
    free_pages: list = field(default_factory=list)
    lane_pages: dict = field(default_factory=dict)  # lane -> [page ids]
    # -- cross-request shared-prefix KV (ISSUE 9): page_refs[pg] counts every
    # owner of a page — referencing lanes plus the prefix index's own holds.
    # A page is writable by a lane iff its refcount is exactly 1 (the lane is
    # the sole owner); a first write into a refs>1 page goes through CoW
    # (cow_page + generation._page_copy_jit). Invariant, checked by
    # check_page_conservation: every arena page is exactly one of free,
    # trash (page 0), or refs > 0.
    page_refs: np.ndarray | None = None      # (arena_pages + 1,) i32
    prefix_index: Any = None                 # PagePrefixIndex | None
    # -- in-engine speculative decoding (ISSUE 16): the draft model's own
    # SlotDecodeState rides on the target's — same slot count and
    # page_tokens, its own arena/tables/free-list/census, no prefix index —
    # so every scheduler reserve/release call mirrors 1:1 onto the draft
    # arena and both censuses stay exact. The draft state's tok/pos/active
    # host mirrors alias the target's (identical by construction: both
    # caches advance through the same accepted positions). None = spec off.
    spec_draft_id: Any = None        # ModelId of the attached draft
    spec_draft: Any = None           # the draft's SlotDecodeState
    spec_tokens: int = 0             # draft proposals per verify round

    @property
    def ring_pages(self) -> int:
        return 0 if self.window is None else self.window[0].shape[1] // self.slots

    def pages_needed(self, tokens: int) -> int:
        return -(-int(tokens) // self.page_tokens)

    def lane_capacity(self, lane: int) -> int:
        """Token capacity currently reserved for ``lane`` (page-granular)."""
        return len(self.lane_pages.get(lane, ())) * self.page_tokens

    def reserve_pages(self, lane: int, tokens: int,
                      shared_pages: list | tuple = (),
                      cow_headroom: int = 0) -> bool:
        """Reserve enough pages for ``tokens`` (the row's full prompt +
        max_new budget, so a mid-decode row can never starve) and point the
        lane's block table at them. ``shared_pages`` are already-resident
        prefix pages mapped READ-ONLY at the front of the row (refcount
        bump, no allocation — this is what multiplies admitted slots); only
        the private remainder is popped from the free-list, plus
        ``cow_headroom`` pages that must EXIST free but are left unpopped
        for an immediately-following slot_cow. False when the free-list
        can't cover it — the caller blocks admission and retries after
        retirements."""
        need = self.pages_needed(tokens)
        n_map = len(shared_pages)
        priv = max(0, need - n_map)
        if priv + cow_headroom > len(self.free_pages):
            return False
        pages = [int(pg) for pg in shared_pages]
        pages += [self.free_pages.pop() for _ in range(priv)]
        if self.page_refs is not None:
            for pg in pages:
                self.page_refs[pg] += 1
        self.lane_pages[lane] = pages
        self.block_tables[lane, :] = 0
        self.block_tables[lane, :len(pages)] = pages
        return True

    def release_pages(self, lane: int) -> None:
        """Drop a retired/failed lane's page references — a page returns to
        the free-list only when its LAST owner lets go (shared prefix pages
        survive for their other referencing lanes / the prefix index) — and
        park the lane on the trash page (zeroed table row) so its frozen
        in-chunk rewrites can never touch a recycled page's next occupant."""
        pages = self.lane_pages.pop(lane, None)
        if pages:
            if self.page_refs is None:
                self.free_pages.extend(pages)
            else:
                for pg in pages:
                    n = int(self.page_refs[pg]) - 1
                    self.page_refs[pg] = max(n, 0)
                    if n <= 0:
                        self.free_pages.append(pg)
        if self.block_tables is not None:
            self.block_tables[lane, :] = 0

    def cow_page(self, lane: int, slot: int) -> tuple[int, int] | None:
        """Host half of copy-on-write: swap ``lane``'s block-table entry at
        ``slot`` to a fresh free page and move the lane's reference onto it.
        Returns (src, dst) for the device page copy, or None when the
        free-list is empty (callers reserve cow_headroom so the admission
        protocol can't hit that)."""
        if not self.free_pages:
            return None
        src = int(self.block_tables[lane, slot])
        dst = self.free_pages.pop()
        self.page_refs[dst] = 1
        self.block_tables[lane, slot] = dst
        self.lane_pages[lane][slot] = dst
        n = int(self.page_refs[src]) - 1
        self.page_refs[src] = max(n, 0)
        if n <= 0:
            # the "shared" page was sole-owned after all (caller raced its
            # own check) — recycle rather than leak it
            self.free_pages.append(src)
        return src, dst

    def page_stats(self) -> dict:
        """Distinct-page split of the arena (trash page 0 excluded):
        ``free`` on the free-list, ``cached`` held only by the prefix index
        (reclaimable under admission pressure), ``shared`` referenced by a
        lane AND at least one other owner, ``private`` sole-owned by one
        lane. Used by the flight recorder / gauges; a shared page counts
        ONCE no matter how many lanes read it, so pages_used reflects true
        admission headroom."""
        lane_refs: dict[int, int] = {}
        for pages in self.lane_pages.values():
            for pg in pages:
                lane_refs[pg] = lane_refs.get(pg, 0) + 1
        held = (self.prefix_index.held_pages()
                if self.prefix_index is not None else {})
        shared = sum(1 for pg, n in lane_refs.items()
                     if n > 1 or pg in held)
        return {
            "free": len(self.free_pages),
            "cached": sum(1 for pg in held if pg not in lane_refs),
            "shared": shared,
            "private": len(lane_refs) - shared,
        }

    def check_page_conservation(self) -> None:
        """Assert the refcount invariant over the whole arena: every usable
        page is exactly one of free, or referenced, with ``page_refs``
        agreeing with the actual lane + index reference census — i.e. no
        page is leaked and none is double-booked. Test/bench hook (cheap:
        O(arena), host-only)."""
        census = np.zeros(self.arena_pages + 1, np.int64)
        for pages in self.lane_pages.values():
            for pg in pages:
                census[pg] += 1
        if self.prefix_index is not None:
            for pg, n in self.prefix_index.held_pages().items():
                census[pg] += n
        free = set(self.free_pages)
        assert len(free) == len(self.free_pages), "duplicate free-list pages"
        assert 0 not in free, "trash page on the free list"
        assert census[0] == 0, "trash page is referenced"
        for pg in range(1, self.arena_pages + 1):
            refs = int(census[pg])
            if pg in free:
                assert refs == 0, f"page {pg} free but referenced {refs}x"
            else:
                assert refs > 0, f"page {pg} leaked (not free, unreferenced)"
            if self.page_refs is not None:
                got = int(self.page_refs[pg])
                assert got == refs, (
                    f"page {pg}: page_refs says {got}, census says {refs}"
                )


# TPUSC_PAGECHECK=1 (same opt-in idiom as utils/lockcheck.py's
# TPUSC_LOCKCHECK): assert before every paged decode chunk that no LIVE
# lane's block table maps the trash page below its visible position.
# `paged_gather_kv` / the Pallas kernel read whatever the table points at —
# a trash-page entry behind `pos` would silently attend over junk KV (no
# crash, just wrong tokens), which is exactly the failure mode this guard
# exists to catch in tests and soaks.
_PAGECHECK = os.environ.get("TPUSC_PAGECHECK", "") == "1"


def _check_trash_unreachable(state: SlotDecodeState) -> None:
    """Raise if any active lane's block-table row maps page 0 (trash) in a
    slot the lane's attention window can reach (pages covering tokens
    0..pos inclusive). Host-only, O(slots x pages_per_slot)."""
    for lane in range(state.slots):
        if not bool(state.active[lane]):
            continue
        # pos is the NEXT write position; the chunk's first step writes at
        # pos and attends over 0..pos inclusive
        live = state.pages_needed(int(state.pos[lane]) + 1)
        row = state.block_tables[lane, :live]
        if (row == 0).any():
            bad = int(np.argmax(row == 0))
            raise AssertionError(
                f"TPUSC_PAGECHECK: lane {lane} maps trash page 0 at "
                f"block-table slot {bad} below pos={int(state.pos[lane])} "
                f"(live pages={live}) — attention would read junk KV"
            )


# the mirrors a decode chunk takes between the arena and its counter, in the
# program's argument order
CHUNK_OPERANDS = ("block_tables", "tok", "pos", "active", "temps", "topks")


def _counter_word(n: int) -> np.uint32:
    """Chunk number ``n`` as the program takes it: its low 32 bits, which is
    all ``jax.random.PRNGKey`` ever read of a Python int."""
    return np.uint32(n & 0xFFFFFFFF)


def _chunk_operands(state: SlotDecodeState) -> list:
    """The decode chunk's small operands for this launch, ``CHUNK_OPERANDS``
    then the counter. Where the arena lives on ONE device, each is the device
    array ``state.resident`` kept if the mirror still holds the values that
    array was made from, else a fresh upload (recorded with a copy of the
    values: the engine writes its mirrors in place); ``state.uploads`` counts
    the uploads. So a decode-only boundary sends nothing, a retirement
    ``active`` and the tables (whose row ``release_pages`` zeroes), an
    admission what it wrote, and a write made anywhere else (park, resume,
    preemption, a speculation round, a test) is seen like any other. ``tok`` /
    ``pos`` kept from a chunk still IN FLIGHT hold no host values (None): the
    mirrors trail them until that chunk's fetch, and the engine writes no
    mirror meanwhile, so they are current by construction and go as they
    are. On a mesh the mirrors go as they are, as before: an operand kept from the
    program's outputs would carry the placement the compiler chose for that
    output, another program's signature."""
    import jax

    mirrors = [(name, getattr(state, name)) for name in CHUNK_OPERANDS]
    mirrors.append(("counter", _counter_word(state.chunk_counter)))
    devices = state.k.devices()
    if len(devices) != 1:
        state.resident.clear()
        state.uploads = len(mirrors)
        return [host for _name, host in mirrors]
    stale = [(name, np.array(host)) for name, host in mirrors
             if (kept := state.resident.get(name)) is None
             or (kept[1] is not None and not np.array_equal(kept[1], host))]
    if stale:
        # one call for all of them: a device_put costs the host 0.3 ms
        sent = jax.device_put([host for _name, host in stale], *devices)
        state.resident.update(
            (name, (array, host)) for (name, host), array in zip(stale, sent))
    state.uploads = len(stale)
    return [state.resident[name][0] for name, _host in mirrors]


def _mesh_serialized(fn):
    """Serialize device-program launches on mesh runtimes (ISSUE 20). A
    partitioned program's launch enqueues a collective participant on every
    mesh device; two threads interleaving launches can enqueue them in
    DIFFERENT per-device orders — the CPU backend deadlocks its rendezvous
    outright, and real device queues would cross-schedule the collectives.
    Every dispatch entry point that an arbitrary thread may call (solo
    generate/predict, the engine scheduler's slot_* ops) holds the
    runtime-wide RLock for the duration of the call, so launches hit all
    devices in one consistent order. Single-device runtimes skip the lock:
    concurrent dispatch overlap there is free and safe."""

    @functools.wraps(fn)
    def wrapped(self, *args, **kwargs):
        if self.mesh is None:
            return fn(self, *args, **kwargs)
        with self._mesh_dispatch_lock:
            return fn(self, *args, **kwargs)

    return wrapped


@lockchecked
class TPUModelRuntime(BaseRuntime):
    # Guarded-field registry (tools/tpusc_check TPUSC001 + TPUSC_LOCKCHECK=1).
    _tpusc_guarded = {
        "_load_locks": "_load_locks_guard",
        "_adopted": "_adopted_lock",
        "_spec_health": "_spec_lock",
        "_jitted_by_key": "_jit_lock",
        "_aot_cache": "_aot_lock",
        "_aot_futures": "_aot_lock",
        "_slot_states": "_slot_lock",
        "_slot_init_guards": "_slot_lock",
    }

    def __init__(
        self,
        cfg: ServingConfig | None = None,
        metrics: Metrics | None = None,
        mesh: Any | None = None,
        group: int = 0,
        host_tier_bytes: int = 0,
    ) -> None:
        super().__init__()
        import jax

        self.cfg = cfg or ServingConfig()
        self.metrics = metrics
        self.mesh = mesh  # jax.sharding.Mesh for multi-chip models (parallel/)
        self.group = group  # chip-group index on this host (metrics label)
        # LOCAL devices: in a multi-controller (cross-host) deployment
        # jax.devices() includes peers' non-addressable chips — the
        # single-device path and health probe must stay on this process's own
        # the process's first device discovery initialises the backend (about
        # 12 s to reach a chip; nothing where the caller already has)
        with bring_up.stage("backend_init", metrics):
            self._devices = jax.local_devices(backend=self.cfg.platform or None)
        if mesh is not None:
            from tfservingcache_tpu.parallel.sharding import is_single_process

            # does this runtime's chip-group mesh span processes?
            self._mp_mesh = not is_single_process(mesh)
        else:
            self._mp_mesh = False
        self._replicate_out = None  # lazily-built cached reshard-identity jit
        self._resident = make_lru_cache(
            self.cfg.hbm_capacity_bytes,
            on_evict=self._on_evict,
            max_items=self.cfg.max_concurrent_models,
        )
        self._load_locks: dict[ModelId, threading.Lock] = {}
        self._load_locks_guard = threading.Lock()
        # one-shot transfer-ready entries handed over by a peer fetch
        # (CacheManager adopt, cache/providers/peer.py): the next _load of
        # that model promotes straight from these chunks — no artifact
        # read-back of bytes that just crossed the wire. Independent of the
        # host tier on purpose: the fast first load must not depend on the
        # warm-tier budget being enabled.
        self._adopted: dict[ModelId, Any] = {}
        self._adopted_lock = threading.Lock()
        # Host-RAM warm tier (cache/host_tier.py): packed transfer chunks +
        # executable handles of evicted models, so re-admission skips fetch
        # and decode and pays only the H2D stream. Single-process only
        # (ISSUE 20 lifted the single-process-mesh gate, mesh_fast_path
        # restores it): a CROSS-HOST group's device-op stream must not
        # depend on which models happen to sit in one process's host tier,
        # but a mesh owned entirely by this process has no peer to diverge
        # from. Demotions that must re-pack from the device copy run on the
        # worker thread below — never in the evicting thread, which
        # typically holds load or slot-map locks (see _on_evict).
        self._host_tier = None
        self._demote_queue: queue.Queue | None = None
        if host_tier_bytes > 0 and (
            mesh is None or (not self._mp_mesh and self.cfg.mesh_fast_path)
        ):
            from tfservingcache_tpu.cache.host_tier import HostRamTier

            self._host_tier = HostRamTier(host_tier_bytes, metrics)
            self._demote_queue = queue.Queue()
            self._demote_thread = threading.Thread(
                target=self._demote_loop, name="tpusc-demote", daemon=True
            )
            self._demote_thread.start()
        # prefix KV cache (OFF unless budgeted). Mesh/group runtimes get it
        # too (VERDICT r5 #7): on a cross-host group every process's cache
        # evolves identically under the lockstep op stream, the LEADER's hit
        # decision rides the work envelope (prefix_rows below) so followers
        # provably run the same program, and a reform (multihost.py) resets
        # every cache to empty together
        self._prefix_cache = None
        if self.cfg.prefix_cache_bytes > 0:
            from tfservingcache_tpu.runtime.prefix_cache import PrefixCache

            self._prefix_cache = PrefixCache(self.cfg.prefix_cache_bytes)
        # speculative acceptance gate (_spec_admit/_spec_observe): per
        # (target, draft) low-acceptance streaks and disabled flags.
        # Active on single-process runtimes; a multi-process FOLLOWER keeps
        # it off (it obeys the envelope), and the group LEADER re-activates
        # it (multihost.py) to decide for the whole group.
        self._spec_health: dict[tuple[ModelId, ModelId], dict] = {}
        self._spec_lock = threading.Lock()
        self._spec_gate_active = not self._mp_mesh
        # One jitted apply per (family, config) build key: all tenants of a
        # family share one XLA executable — tenant N's cold load is
        # params-transfer only. Entries are refcounted by resident models and
        # dropped when the last tenant is evicted, so executables don't pin
        # device memory after every user of them is gone.
        self._jitted_by_key: dict[str, tuple[Any, int]] = {}
        # RLock: _resident.put below runs eviction callbacks (_on_evict takes
        # this lock to decrement) in the inserting thread
        self._jit_lock = threading.RLock()
        # Pipelined cold load: AOT executables compiled on a side executor
        # concurrently with the params transfer, keyed by
        # (family cache_key, input signature). jax.jit's dispatch cache never
        # sees AOT-compiled programs, so warmup and predict must route
        # matching calls through these directly; entries share the lifetime
        # of the family's refcounted jit entry (_on_evict / close).
        self._aot_cache: dict[tuple[str, tuple], tuple[Any, float, float]] = {}
        self._aot_futures: dict[tuple[str, tuple], Any] = {}
        self._aot_lock = threading.Lock()
        self._compile_pool: Any = None  # lazy 1-thread executor
        # continuous-decode slot arrays (ContinuousGenerateEngine), one per
        # model with in-flight continuous generates. Their K/V HBM is
        # engine-owned working memory (like the prefix cache's budget, it is
        # NOT charged to the resident-model LRU) and dies with the model:
        # _on_evict / reset_group_state / close all drop it.
        self._slot_states: dict[ModelId, SlotDecodeState] = {}
        # the engine programs' first runs (bring_up.first_run): the jitted
        # function, its call's abstract arguments and statics, what the call
        # site said of it; the newest 64, for ``program_memory``
        self._first_runs: collections.deque = collections.deque(maxlen=64)
        self._slot_lock = threading.Lock()
        # _mesh_serialized: one consistent per-device launch order for
        # partitioned programs (held only when self.mesh is not None)
        self._mesh_dispatch_lock = threading.RLock()
        # per-model once-guards for slot-state allocation (the array is big;
        # see slot_decode_state) — entries are popped once the state lands
        self._slot_init_guards: dict[ModelId, threading.Lock] = {}

    # -- load ---------------------------------------------------------------
    def ensure_loaded(self, model: Model) -> str:
        """-> which residency tier actually served this call: ``"hbm"``
        (already resident), ``"host"`` (warm-tier promotion), ``"disk"``
        (full load from the artifact). Feeds the ``tpusc_reload_source``
        counter in CacheManager."""
        mid = model.identifier
        if self.is_loaded(mid):
            return "hbm"
        with self._load_locks_guard:
            lock = self._load_locks.setdefault(mid, threading.Lock())
        try:
            with lock:
                if self.is_loaded(mid):  # singleflight: someone else finished it
                    return "hbm"
                return self._load(model)
        finally:
            # Failure-path pruning (mirror of _on_evict): a model whose load
            # keeps failing never becomes resident, so the evict-side prune
            # never runs for it and a storm of failing tenants would grow
            # this dict without bound. Drop the idle lock when nothing landed.
            if not self.is_loaded(mid):
                with self._load_locks_guard:
                    held = self._load_locks.get(mid)
                    if held is lock and not held.locked():
                        del self._load_locks[mid]

    def adopt_packed_entry(self, model_id: ModelId, entry: Any) -> None:
        """Hand over a transfer-ready ``PackedModelEntry`` that did NOT come
        from this runtime's own demotion — a peer fetch rebuilt it off the
        wire (protocol/peer_transfer.py). The next ``_load`` of ``model_id``
        consumes it via the promotion path: same pipelined device_put the
        warm tier replays, skipping the artifact read-back. One-shot and
        advisory: a MULTI-PROCESS mesh runtime drops it (cross-host group op
        streams must not depend on per-process residency; a single-process
        mesh promotes it through the sharded replay — ISSUE 20), and any
        promotion failure falls through to the full disk load."""
        if self.mesh_lockstep:
            return
        with self._adopted_lock:
            self._adopted[model_id] = entry

    def _fill_family_jit(self, entry: Any) -> None:
        """A demoted entry carries the family's live jit handle; a
        wire-adopted one can't. If the family executable is still resident
        this is a no-op (_promote shares it); otherwise build the same jit
        the disk path would so promotion installs a usable handle. Adoption
        is gated to single-process runtimes (mesh_lockstep), so the plain
        jit suffices — sharding comes from the committed params, and a
        mesh-bound family gets its apply rebound here just like the disk
        path would."""
        import jax

        model_def = entry.model_def
        apply_fn = (
            model_def.bind_mesh(self.mesh)
            if (self.mesh is not None and model_def.bind_mesh is not None)
            else model_def.apply
        )
        with self._jit_lock:
            if model_def.cache_key in self._jitted_by_key:
                return
            entry.jitted = jax.jit(apply_fn)

    def _load(self, model: Model) -> str:
        mid = model.identifier
        with self._adopted_lock:
            adopted = self._adopted.pop(mid, None)
        if adopted is not None:
            try:
                if adopted.jitted is None:
                    self._fill_family_jit(adopted)
                self._promote(model, adopted)
                return "host"
            except Exception as e:  # noqa: BLE001 - full path still works
                log.warning(
                    "promotion of adopted entry for %s failed (%s); "
                    "falling back to the full load path", mid, e,
                )
        if self._host_tier is not None:
            entry = self._host_tier.get(mid)
            if entry is not None:
                try:
                    self._promote(model, entry)
                    return "host"
                except Exception as e:  # noqa: BLE001 - full path still works
                    log.warning(
                        "host-tier promotion of %s failed (%s); "
                        "falling back to the full load path", mid, e,
                    )
                    self._host_tier.remove(mid)
        self._set_state(mid, ModelState.START)
        t0 = time.monotonic()
        built0 = BUILT.build_s
        with TRACER.span("load", model=str(mid), tier="disk") as load_span:
            self._load_traced(model, mid, t0, load_span)
        self._note_load(load_span, built0)
        # Σ(stage)/wall: ~1.0 = strictly serialized stages, >1 = the
        # pipeline overlapped them (AOT compile / per-leaf dequant running
        # during the transfer). Annotated on the span AND observed as a
        # metric so bench artifacts surface the win without re-deriving it.
        if load_span.children and load_span.duration_s > 0:
            stages_s = sum(c.duration_s for c in load_span.children)
            ratio = stages_s / load_span.duration_s
            load_span.attrs["cold_overlap_ratio"] = round(ratio, 3)
            if self.metrics is not None:
                self.metrics.cold_overlap_ratio.observe(ratio)
                # the same overlap in seconds: what the bring-up account
                # would count twice, under the stages and under the builds (a
                # pipelined load's AOT compile runs beside its transfer)
                self.metrics.cold_stage_seconds.labels("load_overlap").observe(
                    max(0.0, stages_s - load_span.duration_s))
        if self.metrics is not None:
            # per-stage cold histograms: the in-production "where do my cold
            # seconds go" (and the int8 crossover: device_transfer +
            # device_dequant across artifact encodings on THIS link)
            for child in load_span.children:
                self.metrics.cold_stage_seconds.labels(child.name).observe(
                    child.duration_s
                )
        return "disk"

    def _promote(self, model: Model, entry: Any) -> None:
        """Host-tier promotion: stream the retained packed chunks back into
        HBM and rebind the retained executable handles. No provider fetch,
        no artifact read, no host decode, no warmup — the retained jit
        handle still carries the family's compiled dispatch cache (and the
        AOT entries rebound below route warmup-shaped calls), so the only
        wall time is the H2D replay itself."""
        import jax

        mid = model.identifier
        self._set_state(mid, ModelState.START)
        t0 = time.monotonic()
        built0 = BUILT.build_s
        hbm = 0
        try:
            with TRACER.span("load", model=str(mid), tier="host") as load_span:
                self._set_state(mid, ModelState.LOADING)
                rules = entry.model_def.partition_rules
                if self.mesh is not None and rules:
                    # sharded replay (ISSUE 20): rebuild host leaves and
                    # stream per-device shard chunks — the committed
                    # shardings must match what the disk load produced, or
                    # the revived executable would reshard on first call
                    from tfservingcache_tpu.parallel.sharding import (
                        param_shardings,
                    )

                    with TRACER.span(
                        "device_transfer", promoted=True, sharded=True
                    ):
                        host_params = unpack_entry_host(entry)
                        params = packed_device_put_sharded(
                            host_params,
                            param_shardings(host_params, rules, self.mesh),
                            buffer_depth=self.cfg.cold_pipeline_buffer_depth,
                        )
                        del host_params
                    dequant_s = 0.0
                else:
                    with TRACER.span("device_transfer", promoted=True):
                        params, dequant_s = promote_packed_entry(
                            entry, self._devices[0]
                        )
                if dequant_s > 0:
                    TRACER.attach(
                        load_span, "device_dequant", dequant_s, overlapped=True
                    )
                model_def = entry.model_def
                key = model_def.cache_key
                with self._jit_lock:
                    shared = self._jitted_by_key.get(key)
                    created = shared is None
                    if created:
                        # family executable died with its last HBM tenant;
                        # the tier entry's handle revives it (jit's dispatch
                        # cache lives on the function object, so prior
                        # compiles come back with it)
                        jitted = entry.jitted
                        self._jitted_by_key[key] = (jitted, 0)
                    else:
                        jitted = shared[0]
                if entry.aot_entries:
                    with self._aot_lock:
                        for k, v in entry.aot_entries.items():
                            self._aot_cache.setdefault(k, v)
                hbm = entry.hbm_bytes or tree_nbytes(params)
                loaded = LoadedModel(model_def, params, jitted, hbm)
                TRACER.annotate(hbm_bytes=hbm, promoted_from="host")
                try:
                    with TRACER.span("transfer_sync", pinned_by="promotion"):
                        jax.block_until_ready(params)
                    with self._jit_lock:
                        jfn, refs = self._jitted_by_key.get(key, (jitted, 0))
                        self._jitted_by_key[key] = (jfn, refs + 1)
                        try:
                            self._resident.put(mid, hbm, loaded)
                        except Exception:
                            jfn, refs = self._jitted_by_key[key]
                            if refs <= 1:
                                del self._jitted_by_key[key]
                            else:
                                self._jitted_by_key[key] = (jfn, refs - 1)
                            raise
                except Exception:
                    with self._jit_lock:
                        cur = self._jitted_by_key.get(key)
                        if created and cur is not None and cur[1] == 0:
                            del self._jitted_by_key[key]
                            self._drop_aot_family(key)
                    raise
                self._set_state(mid, ModelState.AVAILABLE)
        except Exception as e:
            self._set_state(mid, ModelState.END)
            raise RuntimeError_(f"failed to promote {mid}: {e}") from e
        dt = time.monotonic() - t0
        self._note_load(load_span, built0)
        self._update_gauges()
        log.info(
            "promoted %s from host tier in %.3fs (%d HBM bytes)", mid, dt, hbm
        )

    def _note_load(self, load_span: Any, built0: float) -> None:
        """The bring-up account's ``load`` stage: the span's wall less the
        builds on this thread (a warm-up that compiled here), the device's
        bytes at its end, what the runtime answers for beside them."""
        bring_up.note_stage(
            "load", load_span.duration_s, built0, self.metrics, self._devices,
            span=load_span, owned=sum(self.owned_device_bytes().values()),
            **{k: load_span.attrs[k] for k in ("model", "tier")})

    def _load_traced(
        self, model: Model, mid: ModelId, t0: float, load_span: Any
    ) -> None:
        import jax

        try:
            self._set_state(mid, ModelState.LOADING)
            with TRACER.span("artifact_read"):
                # always read int8 artifacts RAW (q + scales): which branch
                # dequantizes where is only known after the family's
                # partition rules are in hand
                model_def, host_params = load_artifact(
                    model.path, raw_quant=True
                )
            from tfservingcache_tpu.models.registry import QuantLeaf

            has_quant = any(
                isinstance(x, QuantLeaf)
                for x in jax.tree_util.tree_leaves(
                    host_params, is_leaf=lambda n: isinstance(n, QuantLeaf)
                )
            )
            pipelined = self.cold_pipeline_enabled
            captured: list | None = None  # host-tier chunk capture (pipelined)
            if pipelined and self.cfg.warmup:
                # first tenant of a family: get the AOT compile in flight
                # BEFORE the transfer starts so they overlap. (A streaming
                # provider fetch kicks this even earlier, off model.json —
                # but STALE reloads and non-streaming providers arrive here
                # with nothing in flight.)
                with self._jit_lock:
                    first_tenant = model_def.cache_key not in self._jitted_by_key
                if first_tenant:
                    self._precompile_async(
                        model_def, _abstract_post_dequant(host_params)
                    )
            if self.mesh is not None and model_def.partition_rules:
                # multi-chip model: params sharded over the chip group per the
                # family's partition rules; XLA partitions the computation and
                # inserts ICI collectives from the committed shardings.
                # Quant leaves dequantize on HOST first — the rules name
                # float leaves, not q/scale pairs.
                from tfservingcache_tpu.parallel.sharding import (
                    param_shardings,
                    shard_params,
                )

                if has_quant:
                    # its own stage: the int8 crossover comparison must see
                    # where the mesh path's dequant seconds go (host, here)
                    with TRACER.span("host_dequant"):
                        host_params = _dequantize_on_host(host_params)
                if pipelined:
                    # per-device packed-chunk streaming (ISSUE 20): the
                    # shard filter feeds each device only its own bytes,
                    # chunk assembly overlapping the previous chunk's
                    # device_put, and the AOT compile submitted above
                    # overlaps the whole transfer — the same pipeline the
                    # single-chip path runs, sharding-parameterized
                    with TRACER.span(
                        "device_transfer", pipelined=True, sharded=True
                    ):
                        params, _ = packed_device_put_pipelined(
                            host_params,
                            self._devices[0],
                            buffer_depth=self.cfg.cold_pipeline_buffer_depth,
                            shardings=param_shardings(
                                host_params,
                                model_def.partition_rules,
                                self.mesh,
                            ),
                        )
                else:
                    with TRACER.span("device_transfer"):
                        params = shard_params(
                            host_params, model_def.partition_rules, self.mesh
                        )
            elif pipelined:
                # pipelined packed path: host chunk assembly on a side
                # thread, device ops in the identical _pack_plan order on
                # this one, quant leaves dequantized as they land
                if self._host_tier is not None:
                    captured = []
                with TRACER.span("device_transfer", pipelined=True):
                    params, dequant_s = packed_device_put_pipelined(
                        host_params,
                        self._devices[0],
                        buffer_depth=self.cfg.cold_pipeline_buffer_depth,
                        capture=captured,
                    )
                if has_quant:
                    # the dequant dispatches ran INSIDE the transfer span;
                    # attach their accumulated time as the usual
                    # device_dequant stage so the histogram stays comparable
                    # across serialized and pipelined loads (quant-only, as
                    # in the serialized branch)
                    TRACER.attach(
                        load_span, "device_dequant", dequant_s, overlapped=True
                    )
            else:
                # packed path ships the raw int8 bytes — the transfer is the
                # cold-path bottleneck the int8 artifact exists to halve —
                # and dequantizes on device
                with TRACER.span("device_transfer"):
                    params = packed_device_put(host_params, self._devices[0])
                if has_quant:
                    # own span, quantized artifacts only: a no-op dequant
                    # sample per bf16 load would blend the histogram the
                    # cross-encoding comparison reads
                    with TRACER.span("device_dequant"):
                        params = _dequantize_on_device(params)
            key = model_def.cache_key
            # mesh-aware families (ring/context-parallel attention) build
            # their apply against THIS group's mesh; per-runtime jit cache
            # means the binding can't leak across groups
            apply_fn = (
                model_def.bind_mesh(self.mesh)
                if (self.mesh is not None and model_def.bind_mesh is not None)
                else model_def.apply
            )
            with self._jit_lock:
                entry = self._jitted_by_key.get(key)
                created = entry is None
                if created:
                    if self._mp_mesh:
                        # cross-process group: outputs must come back fully
                        # replicated so the leader process can read them (a
                        # sharded output is only partially addressable here)
                        from jax.sharding import NamedSharding, PartitionSpec

                        jitted = jax.jit(
                            apply_fn,
                            out_shardings=NamedSharding(self.mesh, PartitionSpec()),
                        )
                    else:
                        jitted = jax.jit(apply_fn)
                    # refcount 0 until this model is actually resident; the
                    # failure path below removes a 0-ref entry it created
                    self._jitted_by_key[key] = (jitted, 0)
                else:
                    jitted = entry[0]
            try:
                hbm = tree_nbytes(params)
                loaded = LoadedModel(model_def, params, jitted, hbm)
                TRACER.annotate(hbm_bytes=hbm, shared_executable=not created)
                if self.cfg.warmup and created:
                    # first tenant of a family: compile + pin before AVAILABLE.
                    # Siblings share the executable, so their warmup would be
                    # a pure extra device round trip — skip it and only force
                    # the (async) params transfer to completion instead.
                    aot = self._aot_wait(model_def) if pipelined else None
                    if aot is not None:
                        compiled, compile_s, started = aot
                        # the compile ran on the executor, overlapped with
                        # fetch/read/transfer: attach its TRUE duration as
                        # the usual compile_warmup stage (histogram and
                        # first-load classification stay comparable) while
                        # the wall only paid whatever wait remained
                        TRACER.attach(
                            load_span, "compile_warmup", compile_s,
                            start_s=started, family=model_def.family,
                            overlapped=True,
                        )
                        try:
                            with TRACER.span("transfer_sync", pinned_by="aot_warmup"):
                                self._warmup(loaded, compiled=compiled)
                        except Exception as e:  # noqa: BLE001 - jit always works
                            log.warning(
                                "AOT warmup for %s failed (%s); recompiling via jit",
                                model_def.family, e,
                            )
                            self._drop_aot(model_def)
                            with TRACER.span("compile_warmup", family=model_def.family):
                                self._warmup(loaded)
                    else:
                        with TRACER.span("compile_warmup", family=model_def.family):
                            self._warmup(loaded)  # compile here, outside the lock
                else:
                    # transfer is async: this sync is where the host<->HBM
                    # link's sustained rate actually shows up for siblings
                    with TRACER.span("transfer_sync"):
                        jax.block_until_ready(params)
                with self._jit_lock:
                    # increment + insert atomically w.r.t. evictions: an
                    # eviction of a same-family sibling between put and
                    # increment would otherwise free the shared executable
                    jfn, refs = self._jitted_by_key.get(key, (jitted, 0))
                    self._jitted_by_key[key] = (jfn, refs + 1)
                    try:
                        self._resident.put(mid, hbm, loaded)
                    except Exception:
                        jfn, refs = self._jitted_by_key[key]
                        if refs <= 1:
                            del self._jitted_by_key[key]
                        else:
                            self._jitted_by_key[key] = (jfn, refs - 1)
                        raise
            except Exception:
                with self._jit_lock:
                    cur = self._jitted_by_key.get(key)
                    if created and cur is not None and cur[1] == 0:
                        del self._jitted_by_key[key]  # don't pin an executable no one uses
                        self._drop_aot_family(key)
                raise
            # eager inclusive retain: the packed chunks are in hand right
            # now (captured off the pipelined transfer, or rebuilt from
            # host_params) — retaining at load time instead of only at
            # eviction means demotion is usually a pure LRU touch, never a
            # device_get, and a model evicted microseconds after load is
            # still promotable. Advisory: failure just means this model
            # reloads the slow way.
            self._retain_packed(mid, model_def, host_params, jitted, hbm, captured)
            self._set_state(mid, ModelState.AVAILABLE)
        except Exception as e:
            self._set_state(mid, ModelState.END)
            raise RuntimeError_(f"failed to load {mid}: {e}") from e
        dt = time.monotonic() - t0
        self._update_gauges()
        log.info("loaded %s in %.2fs (%d HBM bytes)", mid, dt, hbm)

    def _warmup(self, loaded: LoadedModel, compiled: Any = None) -> None:
        """One tiny call per model at load: compiles the bucket-1 executable
        and pins params before the first real request hits. ``compiled`` (a
        pipelined load's AOT executable) is invoked directly — AOT
        compilation does not seed jax.jit's dispatch cache, so going through
        ``loaded.jitted`` here would pay the full compile a second time."""
        import jax

        inputs = {
            name: np.zeros(self._concrete_shape(spec, 1), spec.np_dtype())
            for name, spec in loaded.model_def.input_spec.items()
        }
        fn = compiled if compiled is not None else loaded.jitted
        out = fn(loaded.params, inputs)
        jax.block_until_ready(out)

    @staticmethod
    def _concrete_shape(spec: TensorSpec, batch: int) -> tuple[int, ...]:
        return tuple(batch if isinstance(d, str) else d for d in spec.norm_shape())

    # -- pipelined cold load (compile-while-transfer) -----------------------
    @property
    def mesh_lockstep(self) -> bool:
        """True when this runtime's device-op stream must stay LOCKSTEP — a
        pure function of the request sequence, never of host thread timing
        or per-process residency — which is what actually forces the
        serialized-load/solo-generate fallbacks. Before ISSUE 20 every
        mesh runtime was lockstep; now only cross-process groups are (each
        follower must replay the leader's exact op stream), plus any mesh
        with ``serving.mesh_fast_path`` off (the A/B lever). Consumers:
        adopt_packed_entry, the batcher's engine dispatch, and the local
        backend's engine construction."""
        return self.mesh is not None and (
            self._mp_mesh or not self.cfg.mesh_fast_path
        )

    @property
    def cold_pipeline_enabled(self) -> bool:
        """Pipelined cold loads run on single-chip AND single-process mesh
        runtimes (ISSUE 20): the sharded branch streams per-device shard
        chunks through ``packed_device_put_sharded``, feeding each device
        only its own bytes. Lockstep (cross-host) groups keep the strictly
        serialized path regardless of the config flag — their device-op
        stream must stay a pure function of the load sequence, never of
        host thread timing."""
        return bool(self.cfg.cold_load_pipeline) and not self.mesh_lockstep

    def precompile_from_meta(self, meta: Mapping[str, Any]) -> None:
        """Start the family AOT compile from artifact metadata alone —
        called by CacheManager's streaming fetch the moment model.json
        lands, while params.bin is still coming off the provider. Advisory:
        any failure just leaves the load on the compile-in-warmup path."""
        if not (self.cold_pipeline_enabled and self.cfg.warmup):
            return
        try:
            from tfservingcache_tpu.models.registry import (
                abstract_params_from_meta,
                build,
            )

            abs_params = abstract_params_from_meta(meta)
            if abs_params is None:
                return  # v1 artifact: no manifest to precompile from
            model_def = build(meta["family"], meta.get("config"))
            with self._jit_lock:
                if model_def.cache_key in self._jitted_by_key:
                    return  # family executable already live: nothing to hide
            self._precompile_async(model_def, abs_params)
        except Exception as e:  # noqa: BLE001 - advisory only
            log.warning("early precompile skipped: %s", e)

    def _warmup_sig(self, model_def: ModelDef) -> tuple:
        return tuple(sorted(
            (name, self._concrete_shape(spec, 1), spec.np_dtype().name)
            for name, spec in model_def.input_spec.items()
        ))

    @staticmethod
    def _inputs_sig(inputs: Mapping[str, np.ndarray]) -> tuple:
        return tuple(sorted(
            (name, tuple(a.shape), a.dtype.name) for name, a in inputs.items()
        ))

    def _precompile_async(self, model_def: ModelDef, abs_params: Any):
        """Submit (idempotently) the family's warmup-signature AOT compile;
        -> the in-flight Future, or None when already compiled."""
        key = (model_def.cache_key, self._warmup_sig(model_def))
        with self._aot_lock:
            if key in self._aot_cache:
                return None
            fut = self._aot_futures.get(key)
            if fut is not None:
                return fut
            if self._compile_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._compile_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="tpusc-precompile"
                )
            fut = self._compile_pool.submit(
                self._aot_compile, model_def, abs_params, key
            )
            self._aot_futures[key] = fut
            return fut

    def _aot_compile(  # jit-surface: AOT warmup, one-shot per key via _aot_futures under _aot_lock
        self, model_def: ModelDef, abs_params: Any, key: tuple
    ) -> tuple[Any, float, float]:
        import jax

        started = time.time()
        t0 = time.monotonic()
        try:
            abs_inputs = {
                name: jax.ShapeDtypeStruct(
                    self._concrete_shape(spec, 1), spec.np_dtype()
                )
                for name, spec in model_def.input_spec.items()
            }
            apply_fn = model_def.apply
            if self.mesh is not None and model_def.partition_rules:
                # mesh AOT (ISSUE 20): lower against SHARDED abstract params
                # — the executable the sharded pipelined load installs must
                # accept the committed layouts the transfer produces, or
                # _apply_fast would silently recompile via jit on first use
                from tfservingcache_tpu.parallel.sharding import (
                    param_shardings,
                )

                shardings = param_shardings(
                    abs_params, model_def.partition_rules, self.mesh
                )
                abs_params = jax.tree_util.tree_map(
                    lambda a, s: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=s
                    ),
                    abs_params,
                    shardings,
                )
                if model_def.bind_mesh is not None:
                    apply_fn = model_def.bind_mesh(self.mesh)
            compiled = (
                jax.jit(apply_fn).lower(abs_params, abs_inputs).compile()
            )
        except BaseException:
            with self._aot_lock:
                self._aot_futures.pop(key, None)
            raise
        entry = (compiled, time.monotonic() - t0, started)
        with self._aot_lock:
            self._aot_cache[key] = entry
            self._aot_futures.pop(key, None)
        return entry

    def _aot_wait(self, model_def: ModelDef) -> tuple[Any, float, float] | None:
        """The family's warmup-signature AOT entry, waiting out an in-flight
        compile — None when never submitted or the compile failed."""
        key = (model_def.cache_key, self._warmup_sig(model_def))
        with self._aot_lock:
            entry = self._aot_cache.get(key)
            fut = self._aot_futures.get(key)
        if entry is not None:
            return entry
        if fut is None:
            return None
        try:
            return fut.result()
        except Exception as e:  # noqa: BLE001 - fall back to jit warmup
            log.warning(
                "AOT precompile of %s failed (%s); falling back to jit warmup",
                model_def.family, e,
            )
            return None

    def _drop_aot(self, model_def: ModelDef) -> None:
        with self._aot_lock:
            self._aot_cache.pop(
                (model_def.cache_key, self._warmup_sig(model_def)), None
            )

    def _drop_aot_family(self, cache_key: str) -> None:
        """Drop a family's AOT executables alongside its freed jit entry
        (last tenant evicted) — they must not outlive the executable they
        shadow."""
        with self._aot_lock:
            for k in [k for k in self._aot_cache if k[0] == cache_key]:
                del self._aot_cache[k]

    def _apply_fast(
        self, loaded: LoadedModel, padded: Mapping[str, np.ndarray]
    ) -> Any:
        """Run the forward through the family's AOT executable when this
        exact padded signature has one (a pipelined load's warmup shapes),
        else through jit dispatch. jax.jit never sees AOT-compiled programs,
        so without this routing the first predict after a pipelined load at
        the warmup shape would silently recompile."""
        # one uncontended acquire per predict (_aot_lock only ever guards
        # dict ops, never a compile); the common no-AOT case skips the
        # signature computation entirely
        key = entry = None
        with self._aot_lock:
            if self._aot_cache:
                key = (loaded.model_def.cache_key, self._inputs_sig(padded))
                entry = self._aot_cache.get(key)
        if entry is not None:
            try:
                return entry[0](loaded.params, dict(padded))
            except Exception as e:  # noqa: BLE001 - jit path always works
                log.warning(
                    "AOT executable rejected inputs (%s); using jit", e
                )
                with self._aot_lock:
                    self._aot_cache.pop(key, None)
        return loaded.jitted(loaded.params, padded)

    # -- predict ------------------------------------------------------------
    @_mesh_serialized
    def predict(
        self,
        model_id: ModelId,
        inputs: Mapping[str, np.ndarray],
        output_filter: list[str] | None = None,
    ) -> dict[str, np.ndarray]:
        import jax

        loaded = self._resident.get(model_id)
        if loaded is None:
            raise ModelNotLoadedError(f"model {model_id} is not loaded")
        spec = loaded.model_def.input_spec
        missing = set(spec) - set(inputs)
        if missing:
            raise RuntimeError_(f"missing inputs {sorted(missing)} for {model_id}")
        unknown = set(inputs) - set(spec)
        if unknown:
            raise RuntimeError_(f"unknown inputs {sorted(unknown)} for {model_id}")

        dyn_sizes, padded = self._pad_to_bucket(spec, inputs, loaded.model_def.axis_caps)
        out_spec = loaded.model_def.output_spec
        derived = loaded.model_def.derived_outputs
        if output_filter:
            names = list(output_filter)
        elif loaded.model_def.default_outputs:
            # family-declared serving default (LMs: last_token_logits) —
            # full outputs stay reachable via an explicit output_filter
            names = list(loaded.model_def.default_outputs)
        else:
            names = list(out_spec)
        unknown_out = [n for n in names if n not in out_spec and n not in derived]
        if unknown_out:
            raise RuntimeError_(
                f"output_filter names unknown outputs {unknown_out} for {model_id} "
                f"(available: {sorted(out_spec) + sorted(derived)})"
            )
        with TRACER.span("infer", model=str(model_id)):
            dev_out = self._apply_fast(loaded, padded)
            # select + un-pad ON DEVICE so device_get ships only the bytes
            # the caller asked for — for an LM, last_token_logits transfers
            # (B, V) instead of the padded (B', S', V) logits tensor
            selected: dict[str, Any] = {}
            for name in names:
                if name in derived:
                    fn, _dspec = derived[name]
                    selected[name] = fn(dev_out, dyn_sizes)
                    continue
                arr = dev_out[name]
                ospec = out_spec[name]
                if dyn_sizes:
                    for axis, axis_name in ospec.dynamic_axes():
                        true = dyn_sizes.get(axis_name)
                        if (
                            true is not None
                            and getattr(arr, "ndim", 0) > axis
                            and arr.shape[axis] > true
                        ):
                            arr = jax.lax.slice_in_dim(arr, 0, true, axis=axis)
                selected[name] = arr
            out = jax.device_get(selected)
        return {name: np.asarray(arr) for name, arr in out.items()}

    def _pad_to_bucket(
        self,
        spec: Mapping[str, TensorSpec],
        inputs: Mapping[str, np.ndarray],
        axis_caps: Mapping[str, int] | None = None,
    ) -> tuple[dict[str, int], dict[str, np.ndarray]]:
        """-> (true size per named dynamic axis, padded inputs).

        Every named dynamic axis ("batch", "seq", ...) is padded up to its own
        power-of-two bucket; the same name must agree across inputs. A capped
        axis (ModelDef.axis_caps, e.g. BERT's pos-table max_seq) clamps the
        bucket to the cap and rejects true sizes beyond it.
        """
        dyn_sizes: dict[str, int] = {}
        for name, s in spec.items():
            arr = np.asarray(inputs[name])
            for axis, axis_name in s.dynamic_axes():
                if arr.ndim <= axis:
                    raise RuntimeError_(
                        f"input {name!r} needs at least {axis + 1} dims, got shape {arr.shape}"
                    )
                size = arr.shape[axis]
                if axis_name in dyn_sizes and dyn_sizes[axis_name] != size:
                    raise RuntimeError_(
                        f"inconsistent {axis_name!r} dim: {dyn_sizes[axis_name]} vs "
                        f"{size} ({name!r})"
                    )
                dyn_sizes[axis_name] = size
        if not dyn_sizes:
            return {}, {k: np.asarray(v) for k, v in inputs.items()}
        caps = axis_caps or {}
        for axis_name, size in dyn_sizes.items():
            cap = caps.get(axis_name)
            if cap is not None and size > cap:
                raise RuntimeError_(
                    f"{axis_name!r} dim {size} exceeds this model's maximum {cap}"
                )
        buckets = {
            n: min(next_bucket(v), caps[n]) if n in caps else next_bucket(v)
            for n, v in dyn_sizes.items()
        }
        padded: dict[str, np.ndarray] = {}
        for name, s in spec.items():
            arr = np.asarray(inputs[name], dtype=s.np_dtype())
            pad = [(0, 0)] * arr.ndim
            changed = False
            for axis, axis_name in s.dynamic_axes():
                if buckets[axis_name] != arr.shape[axis]:
                    pad[axis] = (0, buckets[axis_name] - arr.shape[axis])
                    changed = True
            padded[name] = np.pad(arr, pad) if changed else arr
        return dyn_sizes, padded

    @_mesh_serialized
    def generate(
        self,
        model_id: ModelId,
        input_ids: np.ndarray,
        prompt_lengths: list[int] | None = None,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_k: int = 0,
        seed: int = 0,
        draft_model_id: ModelId | None = None,
        spec_tokens: int = 4,
        prefix_rows: int | None = None,
        spec_admitted: bool | None = None,
    ) -> np.ndarray:
        """KV-cached autoregressive decoding (models/generation.py).

        ``prefix_rows`` forces the prefix-cache decision (None = decide
        locally): a cross-host group's leader decides once and ships the
        decision in the work envelope so every process provably runs the
        same program (0 = full prefill, N = reuse exactly N cached rows; a
        follower that cannot honor N raises before any device op).

        Prompt seq, max_new_tokens AND the batch axis are padded to
        power-of-two buckets so one compiled generate program serves the
        whole bucket; output is truncated back to the requested rows/tokens.
        temperature/top_k are traced into the program (not static), so novel
        sampling configs never trigger a recompile. (B, max_new_tokens) int32.

        ``draft_model_id`` switches to greedy speculative decoding
        (models/speculative.py): the draft proposes ``spec_tokens`` tokens
        per round, this model verifies them in one chunked forward; output
        is bit-identical to its own greedy decode. Requires temperature 0
        and a loaded draft sharing the vocabulary.

        ``spec_admitted=True`` marks the draft-acceptance gate as already
        decided upstream (the group leader admits once in its envelope
        builder; re-admitting here would double-count the reprobe cadence).
        """
        import math as _math

        import jax

        loaded = self._resident.get(model_id)
        if loaded is None:
            raise ModelNotLoadedError(f"model {model_id} is not loaded")
        if not loaded.model_def.engine_ready:
            raise RuntimeError_(
                "generate is supported for the decoder-LM families "
                "(transformer_lm, moe_lm: ModelDef.engine_ready), not "
                f"{loaded.model_def.family!r}"
            )
        self._refuse_experts_on_mesh(loaded)
        self._refuse_latent(
            loaded, "a draft_model" if draft_model_id is not None else None)
        self._refuse_lane_state(
            loaded, "a draft_model" if draft_model_id is not None else None)
        draft = None
        if draft_model_id is not None:
            if temperature > 0.0:
                raise RuntimeError_(
                    "speculative decoding (draft_model) requires temperature 0 "
                    "— sampled acceptance is not implemented"
                )
            # spec_tokens is a jit STATIC arg fed from the request body: the
            # same compile-DoS vector _sample's docstring hardens temperature/
            # top_k against. Clamp to [1, 8] and round up to a power of two
            # so the whole space mints at most 4 programs (1, 2, 4, 8).
            if spec_tokens < 1:
                raise RuntimeError_(
                    f"spec_tokens must be >= 1, got {spec_tokens}"
                )
            spec_tokens = min(next_bucket(min(spec_tokens, 8)), 8)
            draft = self._resident.get(draft_model_id)
            if draft is None:
                raise ModelNotLoadedError(
                    f"draft model {draft_model_id} is not loaded"
                )
        from tfservingcache_tpu.models.generation import generate as gen

        ids = np.asarray(input_ids, np.int32)
        if ids.ndim != 2 or not ids.size:
            raise RuntimeError_(f"input_ids must be (batch, seq), got {ids.shape}")
        b, s = ids.shape
        if prompt_lengths is None:
            lengths = np.full((b,), s, np.int32)
        else:
            lengths = np.asarray(prompt_lengths, np.int32)
            if lengths.shape != (b,) or (lengths < 1).any() or (lengths > s).any():
                raise RuntimeError_(f"bad prompt_lengths {lengths!r} for shape {ids.shape}")
        if max_new_tokens < 1:
            raise RuntimeError_("max_new_tokens must be >= 1")
        if not _math.isfinite(temperature) or temperature < 0.0:
            raise RuntimeError_(f"temperature must be a finite value >= 0, got {temperature}")
        if top_k < 0:
            raise RuntimeError_(f"top_k must be >= 0, got {top_k}")
        max_seq = loaded.model_def.config["max_seq"]
        s_bucket = next_bucket(s)
        new_bucket = next_bucket(max_new_tokens)
        if s_bucket + new_bucket > max_seq:
            # bucket overshoot may exceed max_seq even when the true request
            # fits; fall back to exact sizes before rejecting
            s_bucket, new_bucket = s, max_new_tokens
            if s + max_new_tokens > max_seq:
                raise RuntimeError_(
                    f"prompt {s} + max_new_tokens {max_new_tokens} exceeds "
                    f"max_seq {max_seq}"
                )
        if s_bucket != s:
            ids = np.pad(ids, ((0, 0), (0, s_bucket - s)))
        # batch axis buckets too: a client-chosen batch size must not mint a
        # fresh compile per novel B (padding rows decode junk that's sliced
        # off below; prompt_length 1 keeps their mask valid)
        b_bucket = next_bucket(b)
        if b_bucket != b:
            ids = np.pad(ids, ((0, b_bucket - b), (0, 0)))
            lengths = np.pad(lengths, (0, b_bucket - b), constant_values=1)
        with TRACER.span(
            "generate", model=str(model_id), tokens=new_bucket, batch=b,
            draft=str(draft_model_id) if draft_model_id else "",
        ):
            if (
                draft is not None
                and spec_admitted is None
                and not self._spec_admit(model_id, draft_model_id)
            ):
                # sustained low acceptance: the draft is pure overhead, fall
                # back to plain greedy decode (identical output) until the
                # pair re-auditions
                TRACER.annotate(spec_gated=True)
                draft = None
            # a cached prefix is K/V rows: a model with lane-state layers
            # would continue from it without its state, so it skips the cache
            # (and so does one with window layers: its fresh prefill is the
            # path that builds no score block over the cache's length)
            prefix_capable = (
                self._prefix_cache is not None and ids.shape[0] == 1
                and not lane_layers(loaded.model_def.layer_state)
                and not window_layers(loaded.model_def.layer_state)
            )
            if prefix_rows is not None:
                if prefix_rows < 0:
                    # the leader runs the cache-LESS plain path (no
                    # return_cache, no insert): this process must run
                    # the identical program even if it has a cache
                    prefix_capable = False
                elif not prefix_capable:
                    # a forced prefix-machinery decision (miss included:
                    # its gen runs with return_cache, a different
                    # program than plain) this process cannot attempt
                    # must fail LOUDLY before any device op
                    raise RuntimeError_(
                        f"prefix-cache divergence for {model_id}: leader "
                        f"decided {prefix_rows} cached rows but this "
                        "process cannot run the prefix path "
                        "(prefix_cache_bytes mismatch across the group?)"
                    )
            if draft is not None:
                toks, rounds = self._speculative(
                    loaded, draft, model_id, ids, lengths, new_bucket,
                    max_new_tokens, spec_tokens,
                    forced_rows=prefix_rows if prefix_capable else None,
                    prefix_capable=prefix_capable,
                )
                self._spec_observe(
                    model_id, draft_model_id, new_bucket, rounds
                )
            else:
                toks = None
                if prefix_capable:
                    toks = self._prefix_generate(
                        loaded, model_id, ids, int(lengths[0]), new_bucket,
                        max_new_tokens, temperature, top_k, seed,
                        forced_rows=prefix_rows,
                    )
                if toks is None:
                    toks = gen(
                        loaded.model_def,
                        loaded.params,
                        ids,
                        prompt_lengths=lengths,
                        max_new_tokens=new_bucket,
                        temperature=temperature,
                        top_k=top_k,
                        rng=jax.random.PRNGKey(seed),
                    )
            if self._mp_mesh and not isinstance(toks, np.ndarray):
                # force the token array fully replicated so this process can
                # read it (inferred output sharding may split it across
                # hosts); all group processes execute this identity in
                # lockstep. The prefix path already returns host tokens.
                toks = self._replicated(toks)
            toks = np.asarray(jax.device_get(toks))
        return toks[:b, :max_new_tokens]

    # -- continuous-decode slot surface (ContinuousGenerateEngine) ----------
    def eos_id_of(self, model_id: ModelId) -> int | None:
        """The model's EOS token id when its config declares one (an
        optional ``eos_id`` key — toy artifacts and tests set it; absent
        means no early stopping). None when unset or the model is not
        resident."""
        loaded = self._resident.get(model_id, touch=False)
        if loaded is None:
            return None
        eos = loaded.model_def.config.get("eos_id")
        return None if eos is None else int(eos)

    def max_seq_of(self, model_id: ModelId) -> int | None:
        """The model's max sequence length when its config declares one.
        None when unset (non-LM families) or the model is not resident —
        callers treat None as "cannot pre-validate", not as unlimited."""
        loaded = self._resident.get(model_id, touch=False)
        if loaded is None:
            return None
        ms = loaded.model_def.config.get("max_seq")
        return None if ms is None else int(ms)

    @_mesh_serialized
    def slot_decode_state(
        self,
        model_id: ModelId,
        slots: int,
        page_tokens: int | None = None,
        arena_pages: int | None = None,
        share_prefix_bytes: int | None = None,
        arena_dtype: str | None = None,
        paged_kernel: bool | None = None,
    ) -> SlotDecodeState:
        """Create-or-get the model's slot state. One compiled decode-chunk
        program serves all ``slots`` lanes. ``page_tokens`` / ``arena_pages``
        default to the runtime's ServingConfig knobs: the arena holds pages
        of ``page_tokens`` tokens (``arena_pages == 0`` auto-sizes to slots
        x ceil(max_seq/page_tokens), every lane can hold the longest
        request; with ``arena_dtype == "int8"`` the page count grows to
        fill the SAME byte budget, which is where the capacity win comes
        from). An existing state always wins; later callers' knobs
        are ignored, same as ``slots``.

        Allocation runs under a per-model once-guard, NOT under
        ``_slot_lock``: the array can be hundreds of MB (seconds of HBM
        traffic) and the map lock is taken by eviction/reset paths. The
        guard closes the first-admission race where two concurrent first
        requests each allocated a full arena and one was thrown away.
        """
        loaded = self._resident.get(model_id)
        if loaded is None:
            raise ModelNotLoadedError(f"model {model_id} is not loaded")
        if not loaded.model_def.engine_ready:
            raise RuntimeError_(
                "continuous decode supports the decoder-LM families "
                "(transformer_lm, moe_lm: ModelDef.engine_ready), not "
                f"{loaded.model_def.family!r}"
            )
        self._refuse_experts_on_mesh(loaded)
        self._refuse_latent(loaded)
        with self._slot_lock:
            st = self._slot_states.get(model_id)
            if st is not None:
                return st
            guard = self._slot_init_guards.setdefault(
                model_id, threading.Lock()
            )
        with guard:
            with self._slot_lock:
                st = self._slot_states.get(model_id)
            if st is not None:
                return st  # the racer that held the guard built it
            import jax

            with bring_up.stage("engine_build", self.metrics, self._devices,
                                model=str(model_id)) as late:
                st = self._build_slot_state(
                    loaded, model_id, slots, page_tokens, arena_pages,
                    share_prefix_bytes, arena_dtype, paged_kernel,
                )
                # once, on the cold path: the stage ends when the arenas, the
                # lane state and the window rings ARE on the device
                jax.block_until_ready(
                    (st.k, st.v, st.scales, st.lane_state, st.window))
                late["owned"] = sum(_state_device_bytes(st)) + sum(
                    self.owned_device_bytes().values())
            with self._slot_lock:
                st = self._slot_states.setdefault(model_id, st)
                self._slot_init_guards.pop(model_id, None)
            return st

    def _build_slot_state(
        self,
        loaded: LoadedModel,
        model_id: ModelId,
        slots: int,
        page_tokens: int | None,
        arena_pages: int | None,
        share_prefix_bytes: int | None = None,
        arena_dtype: str | None = None,
        paged_kernel: bool | None = None,
    ) -> SlotDecodeState:
        from tfservingcache_tpu.models.generation import (
            _window_of,
            init_lane_state,
            init_paged_cache,
            shared_readers,
            window_rows,
        )

        if page_tokens is None:
            page_tokens = getattr(self.cfg, "kv_page_tokens", 16)
        page_tokens = check_page_tokens(page_tokens)
        if arena_pages is None:
            arena_pages = int(getattr(self.cfg, "kv_arena_pages", 0))
        if share_prefix_bytes is None:
            share_prefix_bytes = int(
                getattr(self.cfg, "kv_share_prefix_bytes", 0)
            )
        if arena_dtype is None:
            arena_dtype = str(getattr(self.cfg, "kv_arena_dtype", "") or "")
        if paged_kernel is None:
            paged_kernel = bool(getattr(self.cfg, "kv_paged_kernel", True))
        if arena_dtype == "int8":
            self._refuse_latent(loaded, "the int8 arena (kv_arena_dtype)")
        # a mesh, and the serving options whose machinery moves K/V pages and
        # would leave a lane state (or a window layer's ring) behind: refused
        # HERE, once, by the name of the first that is set, so such a model's
        # first :generate says so
        self._refuse_lane_state(loaded, next((what for option, what in (
            (arena_dtype == "int8", "the int8 arena (kv_arena_dtype)"),
            (share_prefix_bytes, "shared-prefix KV (kv_share_prefix_bytes)"),
            (getattr(self.cfg, "conversation_kv_bytes", 0),
             "conversation park/resume (conversation_kv_bytes)"),
            (getattr(self.cfg, "spec_draft_model", ""),
             "in-engine speculation (spec_draft_model)"),
            (getattr(self.cfg, "prefill_chunk_tokens", 0),
             "chunked prefill (prefill_chunk_tokens)"),
        ) if option), None))
        # The fused Pallas decode kernel is single-chip-only (it indexes the
        # whole KV-head axis locally); on a mesh the gather+einsum reference
        # serves the sharded arena, pinned bitwise by tests/test_mesh_parity
        if self.mesh is not None:
            paged_kernel = False
        # Sharded arena (ISSUE 20): pages partition over the KV-head axis on
        # a fast-path mesh; a lockstep runtime never builds slot state (its
        # requests go to runtime.generate), so its arena would be unsharded
        arena_mesh = None if self.mesh_lockstep else self.mesh
        # the programs' config: the family's own plus what the ModelDef
        # declares its layers keep (the arena has a layer a layer with rows)
        cfg = dict(static_config(loaded.model_def))
        max_seq = int(cfg["max_seq"])
        pps = -(-max_seq // page_tokens)
        usable = int(arena_pages) if arena_pages else slots * pps
        if not arena_pages and arena_dtype == "int8":
            # Byte-matched auto-size: int8 pages are smaller (1-byte
            # payload + 4-byte f32 scale per row vs the model dtype's
            # itemsize), so the SAME byte budget holds more pages — that
            # growth IS the int8 capacity win. Explicit kv_arena_pages is
            # honored verbatim.
            import jax.numpy as jnp

            hd = int(cfg["d_model"]) // int(cfg["n_heads"])
            model_item = jnp.dtype(
                cfg.get("dtype", "bfloat16")
            ).itemsize
            usable = max(
                usable, (usable * hd * model_item) // (hd + 4)
            )
        # +1: page 0 is the trash page, permanently reserved
        cache = init_paged_cache(
            cfg, usable + 1, page_tokens, arena_dtype, mesh=arena_mesh,
            row=loaded.model_def.cache_row, lanes=slots,
        )
        window = (cache.pop("wk"), cache.pop("wv")) if "wk" in cache else None
        scales = None
        if "k_scale" in cache:
            scales = {"k": cache["k_scale"], "v": cache["v_scale"]}
        prefix_index = None
        if share_prefix_bytes and share_prefix_bytes > 0:
            from tfservingcache_tpu.runtime.prefix_cache import (
                PagePrefixIndex,
            )

            page_nbytes = sum(
                int(a.nbytes)
                for a in cache.values()
            ) // (usable + 1)
            prefix_index = PagePrefixIndex(
                page_tokens, page_nbytes, int(share_prefix_bytes)
            )
        st = SlotDecodeState(
            model_id=model_id,
            cfg_key=static_config(loaded.model_def),
            family=loaded.model_def.family,
            slots=slots,
            max_seq=max_seq,
            tok=np.zeros((slots,), np.int32),
            pos=np.zeros((slots,), np.int32),
            active=np.zeros((slots,), bool),
            temps=np.zeros((slots,), np.float32),
            topks=np.zeros((slots,), np.int32),
            k=cache["k"],
            v=cache.get("v"),
            lane_state=init_lane_state(cfg, slots),
            window=window,
            window_tokens=_window_of(cfg),
            window_rows=window_rows(cfg),
            shared_readers=shared_readers(cfg),
            scales=scales,
            arena_dtype=arena_dtype,
            kernel=bool(paged_kernel),
            page_tokens=page_tokens,
            arena_pages=usable,
            pages_per_slot=pps,
            block_tables=np.zeros((slots, pps), np.int32),
            free_pages=list(range(1, usable + 1)),
            page_refs=np.zeros((usable + 1,), np.int32),
            prefix_index=prefix_index,
        )
        self._note_arena_bytes(st)
        return st

    def _note_arena_bytes(self, state: SlotDecodeState) -> None:
        """Publish ``tpusc_gen_kv_arena_bytes{dtype}`` for a freshly built
        arena. Gauge semantics are "bytes currently allocated with
        this dtype label"; drop paths zero the label rather than tracking a
        cross-model sum (one continuous-decode model per runtime in
        practice — the engine keys slot state by model_id)."""
        if self.metrics is None:
            return
        nbytes, ring, lane = _state_device_bytes(state)
        label = state.arena_dtype or str(state.k.dtype)
        # both arenas under the dtype; each under its kind
        self.metrics.gen_kv_arena_bytes.labels(dtype=label).set(nbytes + ring)
        model = self.metrics.model_label(state.model_id.name,
                                         state.model_id.version)
        self.metrics.kv_arena_bytes.labels(model, "global").set(nbytes)
        self.metrics.kv_arena_bytes.labels(model, "window").set(ring)
        self.metrics.lane_state_bytes.labels(model).set(lane)

    def owned_device_bytes(self) -> dict[str, int]:
        """The device bytes somebody answers for (``bring_up.owned`` in
        ``/monitoring/engine``): the resident models' parameters, the engines'
        arenas (global and window) and their lane states. What the allocator
        counts beyond them (``tpusc_device_bytes``) nobody owns."""
        with self._slot_lock:
            states = list(self._slot_states.values())
        states += [st.spec_draft for st in states if st.spec_draft is not None]
        parts = [_state_device_bytes(st) for st in states]
        return {"weights": int(self._resident.total_bytes),
                "arenas": sum(p[0] + p[1] for p in parts),
                "lane_state": sum(p[2] for p in parts)}

    def _first_run(self, fn: Any, args: tuple, statics: dict, outputs: Any,
                   **attrs: Any) -> None:
        """The slow half of a call site's ``if BUILT.flag:`` (a build was
        booked on this thread): where it was ``fn``'s, this call was a new
        program's first execution; book it (``bring_up.first_run`` waits for
        ``outputs`` once) and keep the call's ABSTRACT arguments, so that
        ``program_memory`` can ask the executable for its temporaries later."""
        import jax

        rec = bring_up.first_run(
            (fn.__name__,), outputs, self.metrics, self._devices,
            owned=sum(self.owned_device_bytes().values()), **attrs)
        if rec is None:
            return

        def abstract(x: Any) -> Any:
            shape, dtype = getattr(x, "shape", None), getattr(x, "dtype", None)
            if shape is None or dtype is None:
                return x
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=getattr(x, "sharding", None))

        self._first_runs.append(
            (fn, jax.tree_util.tree_map(abstract, args), statics, attrs))

    def program_memory(self) -> list[dict[str, Any]]:  # jit-surface: on demand only (/monitoring/engine?programs=1), one lowering a kept first run
        """What each engine program that ran here keeps on the device while it
        runs, from its executable (``memory_analysis()`` of the kept abstract
        arguments, lowered and compiled again: a compilation-cache load where
        the cache is on, and booked as a build like any other). On a v5e the
        allocator's ``bytes_in_use`` never shows a program's temporaries, only
        ``bytes_reserved`` the largest of them (PERF.md); this names them.
        Never called during a set-up or by the engine."""
        out = []
        for fn, args, statics, attrs in list(self._first_runs):
            row: dict[str, Any] = {"program": fn.__name__, **attrs}
            try:
                mem = fn.lower(*args, **statics).compile().memory_analysis()
                row.update(
                    temp_bytes=int(mem.temp_size_in_bytes),
                    argument_bytes=int(mem.argument_size_in_bytes),
                    output_bytes=int(mem.output_size_in_bytes),
                    alias_bytes=int(mem.alias_size_in_bytes),
                    code_bytes=int(mem.generated_code_size_in_bytes))
            except Exception as e:  # noqa: BLE001 - a diagnostic: say so, go on
                row["error"] = f"{type(e).__name__}: {e}"[:300]
            out.append(row)
        return out

    def mesh_topology(self) -> dict | None:
        """Structural stamp for /monitoring/engine: a number without its
        topology is unreadable later. None off-mesh."""
        if self.mesh is None:
            return None
        return {
            "mesh_devices": int(self.mesh.devices.size),
            "mesh_axes": {k: int(v) for k, v in self.mesh.shape.items()},
            "mesh_fast_path": not self.mesh_lockstep,
        }

    def drop_slot_state(self, model_id: ModelId) -> None:
        with self._slot_lock:
            st = self._slot_states.pop(model_id, None)
        if st is not None and self.metrics is not None:
            label = st.arena_dtype or str(st.k.dtype)
            self.metrics.gen_kv_arena_bytes.labels(dtype=label).set(0)
            model = self.metrics.model_label(
                st.model_id.name, st.model_id.version)
            self.metrics.lane_state_bytes.labels(model).set(0)
            for kind in ("global", "window"):
                self.metrics.kv_arena_bytes.labels(model, kind).set(0)

    def _count_prefill_rows(self, real: int, bucket: int) -> None:
        """``tpusc_prefill_rows_total`` for one admission prefill of ``real``
        tokens padded to ``bucket`` rows."""
        if self.metrics is None:
            return
        from tfservingcache_tpu.models.real_rows import rows_computed

        for kind, rows in (("real", real), ("bucket", bucket),
                           ("computed", rows_computed(real, bucket))):
            self.metrics.prefill_rows.labels(kind).inc(rows)

    @_mesh_serialized
    def slot_prefill(
        self,
        model_id: ModelId,
        prompt: np.ndarray,          # (P,) true prompt tokens, no padding
        temperature: float,
        top_k: int,
        seed: int,
    ) -> tuple[int, Any, Any, bool]:
        """Admission prefill for one request: run the prompt through a
        (1, P_bucket)-row prefill (reusing a prefix-cache hit's rows when
        one exists — reuse ONLY; the continuous engine never inserts back,
        its completions live in the slot array, not in cache entries) and
        sample the request's first token. -> (first_token, k, v, prefix_hit)
        with k/v ready for ``slot_admit`` (for a model with lane-state
        layers ``k`` is a ``PrefillRows``: the rows and the lane state)."""
        tok, pk, pv, hit, _last = self._slot_prefill_impl(
            model_id, prompt, temperature, top_k, seed
        )
        return tok, pk, pv, hit

    def _slot_prefill_impl(  # static-bounded: cfg_key -- one value per resident model (model_def.config)
        self,
        model_id: ModelId,
        prompt: np.ndarray,
        temperature: float,
        top_k: int,
        seed: int,
    ) -> tuple[int, Any, Any, bool, Any]:
        """slot_prefill body, also returning the last-position logits (the
        5th element, a (1, V) f32 device array) — the shared-prefix
        publisher caches them so an exact re-admission can sample its first
        token without re-running the prefill."""
        import jax

        from tfservingcache_tpu.models.generation import (
            _slot_prefill_from_cache_jit,
            _slot_prefill_jit,
        )

        loaded = self._resident.get(model_id)
        if loaded is None:
            raise ModelNotLoadedError(f"model {model_id} is not loaded")
        cfg = loaded.model_def.config
        cfg_key = static_config(loaded.model_def)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        p = prompt.shape[0]
        max_seq = int(cfg["max_seq"])
        rng = jax.random.PRNGKey(seed)
        temp = np.float32(temperature)
        tk = np.int32(top_k)

        hit = None
        windowed = bool(window_layers(loaded.model_def.layer_state))
        if self._prefix_cache is not None and not windowed and not lane_layers(
                loaded.model_def.layer_state):
            hit = self._prefix_cache.lookup(model_id, prompt)
            if hit is not None:
                s_pad = next_bucket(p - hit.valid_len)
                if hit.k.shape[3] + s_pad > max_seq:
                    hit = None  # padded hit would overflow the slot lane
            if self.metrics is not None:
                (self.metrics.prefix_cache_hits if hit is not None
                 else self.metrics.prefix_cache_misses).inc()
        if hit is not None:
            ids = prompt[None, :]
            suffix, suffix_len = self._prefix_suffix(ids, p, hit)
            self._count_prefill_rows(suffix_len, suffix.shape[1])
            tok, pk, pv, last = _slot_prefill_from_cache_jit(
                loaded.params, suffix,
                np.asarray([suffix_len], np.int32),
                hit.k, hit.v, np.asarray([hit.valid_len], np.int32),
                rng, temp, tk, cfg_key=cfg_key,
                family=loaded.model_def.family,
            )
        else:
            s_pad = next_bucket(p)
            if s_pad > max_seq:
                s_pad = p  # bucket overshoot: exact size (same rule as generate)
            ids = np.zeros((1, s_pad), np.int32)
            ids[0, :p] = prompt
            self._count_prefill_rows(p, s_pad)
            args = (loaded.params, ids, np.asarray([p], np.int32), rng, temp, tk)
            statics = {"cfg_key": cfg_key, "family": loaded.model_def.family}
            tok, pk, pv, last, lane = outs = _slot_prefill_jit(*args, **statics)
            if BUILT.flag:
                self._first_run(_slot_prefill_jit, args, statics, outs,
                                bucket=s_pad)
            if lane is not None or windowed:
                pk = PrefillRows(pk, lane, p)
        return int(np.asarray(tok)[0]), pk, pv, hit is not None, last

    # -- chunked prefill over the paged arena (ISSUE 19) ---------------------
    @_mesh_serialized
    def slot_prefill_chunk(  # static-bounded: cfg_key, chunk_size -- cfg_key is one value per resident model (model_def.config); chunk_size is one pow2 value per engine (serving.prefill_chunk_tokens)
        self,
        model_id: ModelId,
        state: SlotDecodeState,
        lane: int,
        tokens: np.ndarray,   # (t,) this chunk's prompt tokens, t <= chunk_size
        start: int,           # absolute position of tokens[0] in the prompt
        chunk_size: int,      # STATIC padded chunk width (engine-clamped pow2)
    ) -> np.ndarray:
        """Write one prefill chunk into ``lane``'s reserved pages and return
        the chunk's last REAL token logits as a (1, V) f32 host array. The
        engine calls this once per scheduler boundary while the lane sits in
        its PREFILLING state; on the final chunk it feeds the returned
        logits to ``sample_first_token`` with the request's own seed. The
        chunk is zero-padded up to ``chunk_size`` so one compiled program
        serves every chunk (pad rows land past the prompt end inside the
        reservation — or in the trash page past it — and are overwritten
        write-before-read by decode)."""
        import jax

        from tfservingcache_tpu.models.generation import (
            _paged_prefill_chunk_jit,
        )

        loaded = self._resident.get(model_id)
        if loaded is None:
            raise ModelNotLoadedError(f"model {model_id} is not loaded")
        self._refuse_lane_state(
            loaded, "chunked prefill (prefill_chunk_tokens)")
        cfg = loaded.model_def.config
        cfg_key = static_config(loaded.model_def)
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        t = tokens.shape[0]
        if not 0 < t <= chunk_size:
            raise ValueError(
                f"prefill chunk of {t} tokens outside (0, {chunk_size}]"
            )
        toks = np.zeros((1, chunk_size), np.int32)
        toks[0, :t] = tokens
        table_row = np.asarray(state.block_tables[lane:lane + 1], np.int32)
        state.k, state.v, scales, last = _paged_prefill_chunk_jit(
            loaded.params, state.k, state.v, state.scales, table_row,
            toks, np.asarray([start], np.int32), np.asarray([t], np.int32),
            cfg_key=cfg_key, family=loaded.model_def.family,
            page_tokens=state.page_tokens, kernel=state.kernel,
        )
        if scales is not None:
            state.scales = scales
        return np.asarray(jax.device_get(last), np.float32)

    def sample_first_token(
        self,
        last: np.ndarray,     # (1, V) f32 last-position logits
        temperature: float,
        top_k: int,
        seed: int,
    ) -> int:
        """Sample a request's first token from prefill-final logits under
        its own seed — the same split-then-sample the prefill jits do, so a
        chunked prefill's first token matches a monolithic prefill of the
        same prompt under the same seed."""
        import jax

        from tfservingcache_tpu.models.generation import _sample_logits_jit

        tok = _sample_logits_jit(
            np.asarray(last, np.float32), jax.random.PRNGKey(seed),
            np.float32(temperature), np.int32(top_k),
        )
        return int(np.asarray(tok)[0])

    # -- shared-prefix KV over the paged arena (ISSUE 9) ---------------------
    def shared_prefix_plan(
        self,
        state: SlotDecodeState,
        prompt: np.ndarray,
    ) -> Any:
        """Longest viable page-aligned shared prefix for ``prompt`` from the
        state's radix index (None when sharing is off or nothing matches).
        Viability trim: the suffix prefill pads to a pow2 bucket, and
        cached_len + bucket must fit the lane — when it doesn't, shed
        mapped pages (each shed moves ``page_tokens`` tokens back into the
        suffix) until it does, mirroring the dense hit's overflow rule."""
        idx = getattr(state, "prefix_index", None)
        if idx is None:
            return None
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        p = prompt.shape[0]
        plan = idx.lookup(prompt)
        if plan is None:
            return None
        if plan.kind == "exact":
            return plan
        while plan.n_full > 0 and \
                plan.covered + next_bucket(p - plan.covered) > state.max_seq:
            plan.pages.pop()
            plan.n_full -= 1
        if plan.n_full == 0:
            return None
        return plan

    @_mesh_serialized
    def slot_prefill_shared(  # static-bounded: cfg_key -- one value per resident model (model_def.config)
        self,
        model_id: ModelId,
        state: SlotDecodeState,
        prompt: np.ndarray,
        temperature: float,
        top_k: int,
        seed: int,
        plan: Any,
    ) -> tuple[int, Any, Any, str, Any]:
        """Admission prefill with shared-prefix reuse ->
        (first_token, pk, pv, kind, last_logits).

        ``plan.kind == "exact"``: zero prefill compute — the first token is
        sampled from the publisher's cached last-position logits under THIS
        request's seed (the same split-then-sample the prefill jits do, so
        it is byte-identical to a cold prefill of the same prompt);
        pk/pv are None and the caller skips slot_admit. ``"shared"``: gather
        the mapped full pages to dense rows and prefill only the suffix
        (kind stays "shared"). ``plan is None``: full/dense-cache path via
        _slot_prefill_impl; kind is "dense" on a legacy dense-cache hit,
        "miss" otherwise."""
        import jax

        from tfservingcache_tpu.models.generation import (
            _cache_row,
            _paged_gather_prefix_jit,
            _sample_logits_jit,
            _slot_prefill_from_cache_jit,
        )

        if plan is None:
            tok, pk, pv, hit, last = self._slot_prefill_impl(
                model_id, prompt, temperature, top_k, seed
            )
            return tok, pk, pv, ("dense" if hit else "miss"), last
        loaded = self._resident.get(model_id)
        if loaded is None:
            raise ModelNotLoadedError(f"model {model_id} is not loaded")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        p = prompt.shape[0]
        rng = jax.random.PRNGKey(seed)
        temp = np.float32(temperature)
        tk = np.int32(top_k)
        if plan.kind == "exact":
            tok = _sample_logits_jit(
                np.asarray(plan.logits, np.float32), rng, temp, tk
            )
            return int(np.asarray(tok)[0]), None, None, "exact", plan.logits
        cfg = loaded.model_def.config
        cfg_key = static_config(loaded.model_def)
        covered = plan.covered
        ck, cv = _paged_gather_prefix_jit(
            state.k, state.v, state.scales, np.asarray(plan.pages, np.int32),
            width=_cache_row(dict(cfg_key)).width,
        )
        suffix_len = p - covered
        s_pad = next_bucket(suffix_len)
        suffix = np.zeros((1, s_pad), np.int32)
        suffix[0, :suffix_len] = prompt[covered:]
        self._count_prefill_rows(suffix_len, s_pad)
        tok, pk, pv, last = _slot_prefill_from_cache_jit(
            loaded.params, suffix,
            np.asarray([suffix_len], np.int32),
            ck, cv, np.asarray([covered], np.int32),
            rng, temp, tk, cfg_key=cfg_key,
            family=loaded.model_def.family,
        )
        return int(np.asarray(tok)[0]), pk, pv, "shared", last

    @_mesh_serialized
    def slot_cow(self, state: SlotDecodeState, lane: int, slot: int) -> None:
        """Copy-on-write: give ``lane`` a private copy of the page behind
        its block-table ``slot`` before its first write lands there. The
        page copy + host table swap are data (one compiled program total),
        never a new decode-chunk signature. Raises when no free page exists
        — the admission protocol reserves cow_headroom precisely so this
        cannot happen."""
        from tfservingcache_tpu.models.generation import _page_copy_jit

        swap = state.cow_page(lane, slot)
        if swap is None:
            raise RuntimeError_(
                f"CoW for lane {lane} slot {slot}: free-list empty "
                "(cow_headroom was not reserved?)"
            )
        src, dst = swap
        state.k, state.v, state.scales = _page_copy_jit(
            state.k, state.v, state.scales, np.int32(src), np.int32(dst)
        )

    def shared_prefix_publish(
        self,
        state: SlotDecodeState,
        lane: int,
        prompt: np.ndarray,
        last_logits: Any,
    ) -> None:
        """After admitting ``lane``, publish its prompt's pages into the
        radix index so later same-prefix admissions can share them. Full
        page chunks are indexed IN PLACE (the index just increfs the lane's
        own pages — the lane only ever writes past the prompt). A partially
        filled boundary page is EAGER-COPIED into a fresh free page for the
        index (the lane keeps decoding into its original), so the indexed
        copy stays pristine — tail tokens + zeros — and neither side ever
        needs CoW against the other. Skipped silently when nothing
        page-aligned is shareable or no free page exists for the copy."""
        idx = getattr(state, "prefix_index", None)
        if idx is None:
            return
        from tfservingcache_tpu.models.generation import _page_copy_jit

        prompt = np.asarray(prompt, np.int32).reshape(-1)
        p = prompt.shape[0]
        pt = state.page_tokens
        n_full = p // pt
        tail_len = p - n_full * pt
        lane_pg = state.lane_pages.get(lane)
        if lane_pg is None or len(lane_pg) < state.pages_needed(p):
            return
        if last_logits is not None:
            last_logits = np.asarray(last_logits, np.float32)
        boundary = None
        if tail_len and last_logits is not None and state.free_pages:
            src = lane_pg[n_full]
            boundary = state.free_pages.pop()
            state.k, state.v, state.scales = _page_copy_jit(
                state.k, state.v, state.scales,
                np.int32(src), np.int32(boundary)
            )
        added, released = idx.insert(
            prompt, lane_pg[:n_full], boundary, last_logits, state.page_refs
        )
        for pg in added:
            state.page_refs[pg] += 1
        for pg in released:
            n = int(state.page_refs[pg]) - 1
            state.page_refs[pg] = max(n, 0)
            if n <= 0:
                state.free_pages.append(pg)
        if boundary is not None and boundary not in added:
            state.free_pages.append(boundary)  # index declined the tail

    def reclaim_prefix_pages(
        self,
        state: SlotDecodeState,
        want_pages: int,
        protect: list | tuple = (),
    ) -> int:
        """Admission pressure valve: evict cold index-only prefix pages
        (zero lane refs, skipping ``protect`` — the blocked request's own
        plan pages) back onto the free-list so a live admission never loses
        a page fight to cold cache. Returns how many pages were freed."""
        idx = getattr(state, "prefix_index", None)
        if idx is None:
            return 0
        released = idx.reclaim(
            state.page_refs, want_pages, frozenset(int(p) for p in protect)
        )
        freed = 0
        for pg in released:
            n = int(state.page_refs[pg]) - 1
            state.page_refs[pg] = max(n, 0)
            if n <= 0:
                state.free_pages.append(pg)
                freed += 1
        return freed

    # -- conversation KV lifecycle (ISSUE 18) --------------------------------
    @_mesh_serialized
    def park_lane(self, state: SlotDecodeState, lane: int,
                  history: np.ndarray) -> Any:
        """Export a retiring lane's live pages for conversation parking
        (cache/conversation_kv.py): host copies of the pages covering
        ``history`` (the token prefix whose K/V rows are valid in the
        lane), raw arena dtype + int8 scales — NOT dequantized, so the
        parked bytes re-import bit-identical at half the dense footprint.
        Read-only on the arena: the caller still release_pages() the lane
        normally, so the conservation census never sees a parked page as a
        new reference source. None when the lane has nothing parkable
        (empty history, or a lane whose reservation no longer covers it —
        a crash-recovery race, not an error)."""
        import jax

        from tfservingcache_tpu.cache.conversation_kv import ParkedConversation
        from tfservingcache_tpu.models.generation import _pages_export_jit

        loaded = self._resident.get(state.model_id, touch=False)
        if loaded is not None:      # parked pages would return without the state
            self._refuse_lane_state(
                loaded, "conversation park/resume (conversation_kv_bytes)")
        history = np.asarray(history, np.int32).reshape(-1)
        if history.shape[0] <= 0:
            return None
        n = state.pages_needed(history.shape[0])
        pages = state.lane_pages.get(lane)
        if pages is None or len(pages) < n or n == 0:
            return None
        pg = np.asarray(pages[:n], np.int32)
        k, v, scales = _pages_export_jit(state.k, state.v, state.scales, pg)
        ks = vs = None
        if scales is not None:
            ks = np.asarray(jax.device_get(scales["k"]))
            vs = np.asarray(jax.device_get(scales["v"]))
        return ParkedConversation(
            model_id=str(state.model_id),
            history=history.copy(),
            pages_k=np.asarray(jax.device_get(k)),
            pages_v=None if v is None else np.asarray(jax.device_get(v)),
            k_scale=ks,
            v_scale=vs,
            page_tokens=state.page_tokens,
        )

    def plan_conversation_resume(
        self, state: SlotDecodeState, prompt: np.ndarray, parked: Any,
    ) -> tuple[int, int] | None:
        """Viability check for resuming ``prompt`` from a parked
        conversation: -> (covered, n_pages) — the longest common
        token prefix of the parked history and the new prompt (clamped so
        at least one suffix token remains to prefill), and the parked
        pages that cover it. ``covered`` need NOT be page-aligned:
        the suffix insert's write-before-read discipline overwrites the
        boundary page's stale tail exactly like a dense-cache hit. Sheds
        whole pages when covered + the suffix's pow2 bucket would overflow
        the lane (mirroring shared_prefix_plan's trim). None when nothing
        is resumable — wrong page size / arena layout / dtype, divergent
        first token, or the trim shed everything."""
        if parked is None:
            return None
        if int(parked.page_tokens) != state.page_tokens:
            return None
        shape = tuple(parked.pages_k.shape)
        arena = tuple(state.k.shape)
        if len(shape) != 5 or shape[0] != arena[0] or shape[2:] != arena[2:]:
            return None
        if str(np.dtype(parked.pages_k.dtype)) != str(state.k.dtype):
            return None
        if (state.scales is None) != (parked.k_scale is None):
            return None
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        p = prompt.shape[0]
        hist = np.asarray(parked.history, np.int32).reshape(-1)
        m = min(p - 1, hist.shape[0])
        if m <= 0:
            return None
        eq = hist[:m] == prompt[:m]
        covered = m if eq.all() else int(np.argmax(~eq))
        # never resume past the pages actually parked
        covered = min(covered, int(shape[1]) * state.page_tokens)
        while covered > 0 and \
                covered + next_bucket(p - covered) > state.max_seq:
            covered = (state.pages_needed(covered) - 1) * state.page_tokens
        if covered <= 0:
            return None
        return covered, state.pages_needed(covered)

    @_mesh_serialized
    def slot_resume_prefill(  # static-bounded: cfg_key -- one value per resident model (model_def.config)
        self,
        model_id: ModelId,
        state: SlotDecodeState,
        lane: int,
        prompt: np.ndarray,
        parked: Any,
        covered: int,
        n_pages: int,
        temperature: float,
        top_k: int,
        seed: int,
    ) -> tuple[int, Any, Any, Any]:
        """Resume admission prefill: re-import the parked pages into the
        first ``n_pages`` of ``lane``'s freshly reserved PRIVATE pages
        (one batched donated scatter), gather the covered prefix dense,
        and prefill only the suffix -> (first_token, pk, pv, last_logits),
        with pk/pv ready for ``slot_admit(..., base_tokens=covered)``.
        Sampling parity is the exact-hit discipline (PR 9): the same
        split-then-sample as a full prefill under the same seed, over
        byte-identical K/V rows — so greedy AND seeded-sampling streams
        match a full re-prefill of the whole history."""
        import jax

        from tfservingcache_tpu.models.generation import (
            _cache_row,
            _paged_gather_prefix_jit,
            _pages_import_jit,
            _slot_prefill_from_cache_jit,
        )

        loaded = self._resident.get(model_id)
        if loaded is None:
            raise ModelNotLoadedError(f"model {model_id} is not loaded")
        cfg = loaded.model_def.config
        cfg_key = static_config(loaded.model_def)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        p = prompt.shape[0]
        pages = np.asarray(state.lane_pages[lane][:n_pages], np.int32)
        pk_pg = np.ascontiguousarray(parked.pages_k[:, :n_pages])
        pv_pg = (None if parked.pages_v is None else
                 np.ascontiguousarray(parked.pages_v[:, :n_pages]))
        pscales = None
        if state.scales is not None:
            pscales = {
                "k": np.ascontiguousarray(parked.k_scale[:, :n_pages]),
                "v": np.ascontiguousarray(parked.v_scale[:, :n_pages]),
            }
        state.k, state.v, state.scales = _pages_import_jit(
            state.k, state.v, state.scales, pages, pk_pg, pv_pg, pscales
        )
        ck, cv = _paged_gather_prefix_jit(
            state.k, state.v, state.scales, pages,
            width=_cache_row(dict(cfg_key)).width,
        )
        suffix_len = p - covered
        s_pad = next_bucket(suffix_len)
        suffix = np.zeros((1, s_pad), np.int32)
        suffix[0, :suffix_len] = prompt[covered:]
        self._count_prefill_rows(suffix_len, s_pad)
        rng = jax.random.PRNGKey(seed)
        tok, pk, pv, last = _slot_prefill_from_cache_jit(
            loaded.params, suffix,
            np.asarray([suffix_len], np.int32),
            ck, cv, np.asarray([covered], np.int32),
            rng, np.float32(temperature), np.int32(top_k),
            cfg_key=cfg_key, family=loaded.model_def.family,
        )
        return int(np.asarray(tok)[0]), pk, pv, last

    @_mesh_serialized
    def slot_admit(self, state: SlotDecodeState, idx: int, pk: Any, pv: Any,
                   base_tokens: int = 0) -> None:
        """Copy an admitted request's prefill K/V into lane ``idx``'s pages
        (in-place via donation). The caller (scheduler thread) owns the host
        mirrors and sets tok/pos/active/temps/topks itself, and must have
        reserved the lane's pages (reserve_pages) first — the insert
        scatters through the lane's block-table row.
        ``base_tokens`` is the shared-prefix boundary: prefill rows below it
        belong to read-only shared pages and are redirected to the trash
        page (the suffix prefill only produced junk there anyway).
        A ``PrefillRows`` also carries the request's lane state, which goes
        into slice ``idx`` of ``state.lane_state`` in a dispatch beside the
        page insert (span ``state_insert``): a reused lane starts from its own
        request's state, whatever its predecessor left."""
        from tfservingcache_tpu.models.generation import (
            _lane_insert_jit,
            _paged_insert_jit,
            _window_paged_insert_jit,
        )

        prompt_len = 0
        if isinstance(pk, PrefillRows):
            if pk.lane is not None:
                # a child span where a trace is open; on the engine's thread
                # (no trace open: a span there would be a root of its own an
                # admission) the profiler's ``tpusc.state_insert`` annotation
                # alone
                span = (functools.partial(TRACER.span, lane=int(idx))
                        if current_span() is not None else host_span)
                args = (state.lane_state, pk.lane, np.int32(idx))
                with span("state_insert"):
                    state.lane_state = _lane_insert_jit(*args)
                if BUILT.flag:
                    self._first_run(_lane_insert_jit, args, {},
                                    state.lane_state)
            pk, prompt_len = pk.k, pk.prompt_len
        elif state.lane_state is not None or state.window is not None:
            raise RuntimeError_(
                f"{state.family}: an admission without its lane state or its "
                "prompt's length (a prefill that continued from cached rows?)")
        if state.window is not None:
            # both arenas in one dispatch: every row of a global layer into
            # the lane's pages, a window layer's last rows into its ring
            args = (state.k, state.v, *state.window, pk, pv,
                    np.asarray(state.block_tables[idx], np.int32),
                    np.int32(idx), np.int32(prompt_len))
            statics = {"page_tokens": state.page_tokens,
                       "window_layers": state.window_rows,
                       "ring_pages": state.ring_pages}
            state.k, state.v, *state.window = outs = _window_paged_insert_jit(
                *args, **statics)
            state.window = tuple(state.window)
            if BUILT.flag:
                self._first_run(_window_paged_insert_jit, args, statics, outs)
            dropped = max(0, prompt_len - state.ring_pages * state.page_tokens)
            if self.metrics is not None and dropped:
                self.metrics.gen_window_rows_dropped.labels(
                    self.metrics.model_label(
                        state.model_id.name, state.model_id.version)
                ).inc(dropped * len(state.window_rows))
            return
        args = (state.k, state.v, state.scales, pk, pv,
                np.asarray(state.block_tables[idx], np.int32),
                np.int32(base_tokens))
        statics = {"page_tokens": state.page_tokens}
        state.k, state.v, state.scales = outs = _paged_insert_jit(
            *args, **statics)
        if BUILT.flag:
            self._first_run(_paged_insert_jit, args, statics, outs)

    @_mesh_serialized
    def slot_decode_chunk_launch(self, state: SlotDecodeState, chunk: int) -> ChunkInFlight:  # static-bounded: chunk -- engine clamps to a pow2 cover (batcher: min(chunk_tokens, _next_bucket(...)))
        """The launch half of ``slot_decode_chunk``: everything the host does
        before the device has the chunk: the residency lookup, a comparison a
        mirror, an upload of each mirror a boundary changed (none on a
        decode-only one), ONE program call (the chunk derives its own keys).
        The state's arenas and its resident ``tok`` / ``pos`` / counter are
        rebound to the program's output futures, so the NEXT chunk can be
        launched before this one is fetched: its operands are this chunk's
        outputs where they are (``tok`` / ``pos`` are not donated, so this
        chunk's stay fetchable) and residents the host did not touch. Raises
        ModelNotLoadedError when the model was evicted mid-decode.
        ``tpusc.chunk_launch`` covers the call; ``state.launched_t`` is the
        clock at its end."""
        from tfservingcache_tpu.models.generation import (
            _paged_decode_chunk_jit,
        )

        with host_span("chunk_launch"):
            loaded = self._resident.get(state.model_id)
            if loaded is None:
                raise ModelNotLoadedError(
                    f"model {state.model_id} is not loaded")
            state.chunk_counter += 1
            if _PAGECHECK:
                _check_trash_unreachable(state)
            tables, tok, pos, active, temps, topks, counter = _chunk_operands(
                state)
            # a model with window layers hands in their ring arena and gets
            # it back last (one output more); every other call is as it was
            ring = () if state.window is None else (state.window,)
            args = (loaded.params, state.k, state.v, state.scales,
                    tables, tok, pos, active, counter, temps, topks,
                    state.lane_state, *ring)
            statics = {"cfg_key": state.cfg_key, "family": state.family,
                       "chunk": chunk, "page_tokens": state.page_tokens,
                       "kernel": state.kernel}
            (state.k, state.v, state.scales, tok, pos,
             toks, stats, state.lane_state, counter, *ring
             ) = outs = _paged_decode_chunk_jit(*args, **statics)
            if ring:
                state.window = ring[0]
            if BUILT.flag:      # the one test a launch pays for the account
                self._first_run(_paged_decode_chunk_jit, args, statics, outs,
                                chunk=chunk)
            # the donated operands' last references go HERE, inside the launch
            # (``launch_ms``), where the call's own argument list let them go
            # before the account kept them for a first run
            del args
            if state.resident:
                # the next chunk's tok / pos / counter are this chunk's
                # outputs, where they are. Until this chunk is fetched the
                # mirrors TRAIL them: no host values to compare with (None),
                # the arrays are current by construction
                state.resident.update(
                    tok=(tok, None), pos=(pos, None),
                    counter=(counter, _counter_word(state.chunk_counter + 1)))
        state.launched_t = time.monotonic()
        return ChunkInFlight(tok, pos, toks, stats)

    def slot_decode_chunk_fetch(self, state: SlotDecodeState,
                                flight: ChunkInFlight) -> np.ndarray:
        """The fetch half: ONE ``device_get`` of a launched chunk's outputs
        (an expert model's routing numbers ride with the tokens), under
        ``tpusc.chunk_fetch``; the ``tok`` / ``pos`` mirrors take what the
        chunk left and the (S, chunk) emitted tokens are returned. Chunks are
        fetched in the order they were launched."""
        import jax

        with host_span("chunk_fetch"):
            got = jax.device_get(flight)
        for name in ("tok", "pos"):
            kept = state.resident.get(name)
            if kept is not None and kept[0] is getattr(flight, name):
                # no later chunk took them: the mirrors caught up
                state.resident[name] = (kept[0], getattr(got, name))
        # np.array (not asarray): device_get hands back READ-ONLY views and
        # the scheduler writes these mirrors at the next admission
        state.tok = np.array(got.tok, dtype=np.int32)
        state.pos = np.array(got.pos, dtype=np.int32)
        state.moe_stats = None if got.stats is None else tuple(
            float(x) for x in got.stats)
        return np.asarray(got.toks)

    @_mesh_serialized
    def slot_decode_chunk(self, state: SlotDecodeState, chunk: int) -> np.ndarray:  # static-bounded: chunk -- engine clamps to a pow2 cover (batcher: min(chunk_tokens, _next_bucket(...)))
        """Advance every active lane by ``chunk`` decode steps in one
        dispatch; updates the state's device K/V and host tok/pos mirrors
        and returns the (S, chunk) emitted tokens: launch, then fetch (the
        engine calls the halves itself to keep one chunk in flight). Two
        profiler annotations split the call (inside the engine's
        ``tpusc.decode_chunk``): ``tpusc.chunk_launch`` until the program
        call has returned its futures, ``tpusc.chunk_fetch`` around the one
        fetch; ``state.launched_t`` is the clock between them."""
        return self.slot_decode_chunk_fetch(
            state, self.slot_decode_chunk_launch(state, chunk))

    @_mesh_serialized
    def slot_attach_draft(self, state: SlotDecodeState, draft_id: ModelId,
                          spec_tokens: int = 4) -> SlotDecodeState:
        """Attach ``draft_id``'s decode state to ``state`` for in-engine
        speculative rounds (runtime/batcher.py under serving.spec_draft_model):
        builds the draft's own paged arena with the target's slot count and
        page size — auto-sized, quantized and kernel-gated exactly like the
        target's — and pins it on ``state.spec_draft`` so its lifecycle is
        the target state's (dropped together; NOT registered in
        ``_slot_states``). Idempotent for the same draft. The draft must be
        resident, share the target's vocabulary, and be engine_ready (the
        arena's private-page discipline is what makes ragged rollback
        free). ``spec_tokens`` is clamped to the same
        {1,2,4,8} jit-signature buckets as the solo path."""
        if state.spec_draft is not None and state.spec_draft_id == draft_id:
            return state.spec_draft
        loaded = self._resident.get(state.model_id)
        draft = self._resident.get(draft_id)
        if loaded is None or draft is None:
            missing = state.model_id if loaded is None else draft_id
            raise ModelNotLoadedError(f"model {missing} is not loaded")
        if not draft.model_def.engine_ready:
            raise RuntimeError_(
                "continuous speculation supports decoder-LM drafts "
                "(transformer_lm, moe_lm: ModelDef.engine_ready) only, not "
                f"{draft.model_def.family!r}"
            )
        for half in (loaded, draft):
            self._refuse_latent(half, "a draft_model")
            self._refuse_lane_state(
                half, "in-engine speculation (spec_draft_model)")
        if (draft.model_def.config["vocab_size"]
                != loaded.model_def.config["vocab_size"]):
            raise RuntimeError_(
                "draft and target must share a vocabulary: "
                f"{draft.model_def.config['vocab_size']} vs "
                f"{loaded.model_def.config['vocab_size']}"
            )
        if spec_tokens < 1:
            raise RuntimeError_(
                f"spec_tokens must be >= 1, got {spec_tokens}"
            )
        d_st = self._build_slot_state(
            draft, draft_id, state.slots, state.page_tokens, 0, 0,
            state.arena_dtype, state.kernel,
        )
        # the build re-pointed the arena-bytes gauge at the draft; restore
        # the target's value — the gauge documents the SERVING arena (the
        # draft arena is spec overhead, visible via spec_* metrics instead)
        self._note_arena_bytes(state)
        # host mirrors alias the target's: both caches always sit at the
        # same accepted positions, so one array serves both censuses
        d_st.tok = state.tok
        d_st.pos = state.pos
        d_st.active = state.active
        state.spec_draft_id = draft_id
        state.spec_draft = d_st
        state.spec_tokens = min(next_bucket(min(int(spec_tokens), 8)), 8)
        return d_st

    @_mesh_serialized
    def slot_decode_spec_round(
        self, state: SlotDecodeState
    ) -> tuple[np.ndarray, np.ndarray]:
        """One speculative draft/verify round for every active lane —
        the spec counterpart of ``slot_decode_chunk``. Requires an attached
        draft (``slot_attach_draft``). Returns (toks (S, spec+1), accept
        (S,)): lane ``s`` emitted ``toks[s, :accept[s]]`` this round
        (accept == 0 for frozen lanes). Raises ModelNotLoadedError naming
        whichever half of the pair was evicted mid-decode — the engine
        detaches the draft and falls back to plain chunks on the draft,
        fails its rows on the target, exactly like ``slot_decode_chunk``."""
        import jax

        from tfservingcache_tpu.models.speculative import (
            _paged_spec_round_jit,
        )

        d_st = state.spec_draft
        if d_st is None:
            raise RuntimeError_("no draft attached (slot_attach_draft)")
        loaded = self._resident.get(state.model_id)
        if loaded is None:
            raise ModelNotLoadedError(f"model {state.model_id} is not loaded")
        d_loaded = self._resident.get(d_st.model_id)
        if d_loaded is None:
            raise ModelNotLoadedError(
                f"draft model {d_st.model_id} is not loaded"
            )
        # the draft mirrors may have been rebound by admission writes on
        # the target's arrays; re-alias before the census checks
        d_st.tok, d_st.pos, d_st.active = state.tok, state.pos, state.active
        state.chunk_counter += 1
        rng = jax.random.PRNGKey(state.chunk_counter)
        if _PAGECHECK:
            _check_trash_unreachable(state)
            _check_trash_unreachable(d_st)
        (state.k, state.v, state.scales,
         d_st.k, d_st.v, d_st.scales,
         tok, pos, toks, accept) = _paged_spec_round_jit(
            loaded.params, d_loaded.params,
            state.k, state.v, state.scales,
            d_st.k, d_st.v, d_st.scales,
            np.asarray(state.block_tables, np.int32),
            np.asarray(d_st.block_tables, np.int32),
            state.tok, state.pos, state.active,
            rng, state.temps, state.topks,
            cfg_t_key=state.cfg_key, cfg_d_key=d_st.cfg_key,
            family_t=state.family, family_d=d_st.family,
            spec=state.spec_tokens, page_tokens=state.page_tokens,
            kernel=state.kernel,
        )
        # np.array (not asarray): device_get hands back READ-ONLY views and
        # the scheduler writes these mirrors at the next admission
        state.tok = np.array(jax.device_get(tok), dtype=np.int32)
        state.pos = np.array(jax.device_get(pos), dtype=np.int32)
        d_st.tok, d_st.pos = state.tok, state.pos
        return (np.asarray(jax.device_get(toks)),
                np.array(jax.device_get(accept), dtype=np.int32))

    # -- unload / introspection --------------------------------------------
    def _on_evict(self, model_id: ModelId, entry: LRUEntry[LoadedModel]) -> None:
        """``_resident``'s eviction callback. Where ``_resident.put`` makes
        room inside a ``load`` the eviction is that request's time: an
        ``evict`` span under the load (attrs: victim, bytes, ``demoted``).
        Outside any request (unload, close) there is no trace to join."""
        if current_span() is None:
            self._evict(model_id, entry)
            return
        with TRACER.span(
            "evict", victim=str(model_id), bytes=entry.size_bytes
        ) as sp:
            sp.attrs["demoted"] = self._evict(model_id, entry)

    def _evict(self, model_id: ModelId, entry: LRUEntry[LoadedModel]) -> str:
        """-> how the victim reached the host tier: ``retained`` (its packed
        entry was already there: an LRU touch), ``queued`` (handed to the
        demote worker) or ``none`` (no host tier)."""
        self._set_state(model_id, ModelState.UNLOADING)
        if self._prefix_cache is not None:
            # an unloaded model's prefix KV must not outlive it in HBM
            self._prefix_cache.drop_model(model_id)
        # likewise the continuous engine's slot K/V (the engine's next
        # dispatch sees ModelNotLoadedError and fails its in-flight rows)
        self.drop_slot_state(model_id)
        with self._spec_lock:
            # acceptance history dies with either half of the pair (a
            # re-loaded model or new draft version starts fresh)
            for pair in [p for p in self._spec_health if model_id in p]:
                del self._spec_health[pair]
        # Demotion (HBM -> host tier). The eager retain at load time makes
        # the common case a pure O(1) LRU touch; only a model whose packed
        # entry was skipped (capacity) or tier-evicted while resident needs
        # re-creating from the device copy, and THAT work — device_get +
        # chunk repack, potentially seconds for a big model — is handed to
        # the demote worker. The evicting thread (often a loader that
        # triggered this eviction while holding its own load lock, or a
        # caller inside the slot-map critical section) never pays it, so a
        # slow demotion cannot block concurrent hits on other models. The
        # queue item holds the LoadedModel, keeping the device arrays alive
        # until the worker has copied them out.
        demoted = "none"
        if self._host_tier is not None:
            demoted = "retained"
            if not self._host_tier.touch(model_id):
                self._demote_queue.put(("demote", model_id, entry.payload))
                demoted = "queued"
        # Only the LRU's reference is dropped; in-flight predicts holding the
        # LoadedModel keep the device arrays alive until they finish, then XLA
        # frees the HBM when the last reference goes. (Nulling the fields here
        # would crash those in-flight calls.)
        key = entry.payload.model_def.cache_key
        with self._jit_lock:
            shared = self._jitted_by_key.get(key)
            if shared is not None:
                jitted, refs = shared
                if refs <= 1:
                    del self._jitted_by_key[key]  # last tenant gone: free the executable
                    self._drop_aot_family(key)
                else:
                    self._jitted_by_key[key] = (jitted, refs - 1)
        self._set_state(model_id, ModelState.END)
        # prune the per-model load lock so a 1000-tenant churn doesn't grow
        # the dict forever; a racer holding the popped lock only risks one
        # redundant (idempotent) load, never corruption
        with self._load_locks_guard:
            lock = self._load_locks.get(model_id)
            if lock is not None and not lock.locked():
                del self._load_locks[model_id]
        if self.metrics is not None:
            self.metrics.evictions.labels("hbm").inc()
        self._update_gauges()
        log.info("unloaded %s (freed %d HBM bytes)", model_id, entry.size_bytes)
        return demoted

    def unload(self, model_id: ModelId) -> None:
        self._resident.remove(model_id, run_callback=True)
        # _on_evict prunes _spec_health only when the model was RESIDENT;
        # an unload of a non-resident id (already evicted, or gate state
        # resurrected by a generate that finished after eviction) must
        # still drop the pair entries, or tenant churn grows the dict
        # forever (ISSUE 16 satellite — both roles of the pair)
        with self._spec_lock:
            for pair in [p for p in self._spec_health if model_id in p]:
                del self._spec_health[pair]

    def is_loaded(self, model_id: ModelId) -> bool:
        return self._resident.get(model_id, touch=False) is not None

    # -- host-RAM warm tier -------------------------------------------------
    @property
    def host_tier_enabled(self) -> bool:
        return self._host_tier is not None

    def host_tier_contains(self, model_id: ModelId) -> bool:
        """Advisory residency probe (router warmth / manager accounting)."""
        return self._host_tier is not None and model_id in self._host_tier

    def unload_and_discard(self, model_id: ModelId) -> None:
        """Disk-evict hook (CacheManager): drop HBM residency AND the
        host-tier entry. Tiers are inclusive downward — a host entry must
        imply its artifact is still on disk, or a promoted model could
        serve weights the store has already dropped and a later STALE check
        would have nothing to reconcile against. The trailing queue item
        runs AFTER any demotion the unload itself enqueued (single FIFO
        worker), so the discard wins regardless of interleaving."""
        self.unload(model_id)
        if self._host_tier is not None:
            self._host_tier.remove(model_id)
            self._demote_queue.put(("discard", model_id, None))

    def drain_demotions(self) -> None:
        """Block until every queued demotion/discard has run (tests/bench:
        makes tier contents deterministic before asserting on them)."""
        if self._demote_queue is not None:
            self._demote_queue.join()

    def _retain_packed(
        self,
        mid: ModelId,
        model_def: ModelDef,
        host_params: Any,
        jitted: Any,
        hbm_bytes: int,
        captured: list | None = None,
    ) -> None:
        """Insert/update ``mid``'s packed entry in the host tier. Advisory:
        never fails the surrounding load/demotion — worst case the model
        just reloads through the full path next time."""
        if self._host_tier is None:
            return
        try:
            entry = build_packed_entry(
                model_def, host_params, jitted, hbm_bytes, captured=captured
            )
            # snapshot the family's AOT executables: if the family dies in
            # HBM before this model promotes, rebinding these recovers the
            # warmup-shaped fast path without a recompile
            with self._aot_lock:
                entry.aot_entries = {
                    k: v
                    for k, v in self._aot_cache.items()
                    if k[0] == model_def.cache_key
                }
            self._host_tier.put(mid, entry)
        except Exception as e:  # noqa: BLE001 - advisory by design
            log.warning("host-tier retain of %s skipped: %s", mid, e)

    def _demote_loop(self) -> None:
        """Demote worker: the only thread that pays device_get + repack for
        models evicted without a retained entry, and the serialization
        point that orders discards after demotions."""
        while True:
            item = self._demote_queue.get()
            try:
                if item is None:
                    return
                kind, mid, payload = item
                if kind == "demote":
                    self._demote_impl(mid, payload)
                elif not self.is_loaded(mid):  # "discard"
                    self._host_tier.remove(mid)
            except Exception:  # noqa: BLE001 - worker must survive any job
                log.exception("host-tier demotion failed")
            finally:
                self._demote_queue.task_done()

    def _demote_impl(self, mid: ModelId, loaded: LoadedModel) -> None:
        import jax

        if self._host_tier is None or mid in self._host_tier:
            return
        if self.is_loaded(mid):
            # re-admitted while queued: its (re)load re-retained, and the
            # queued LoadedModel may be a stale generation — skip
            return
        host_params = jax.device_get(loaded.params)
        self._retain_packed(
            mid, loaded.model_def, host_params, loaded.jitted, loaded.hbm_bytes
        )

    def _replicated(self, t):  # jit-surface: one-time lazy replicate-out identity, memoized on self
        """Jitted identity with fully-replicated out_sharding (cached — a
        fresh lambda per call would retrace and recompile per request); all
        group processes execute it in lockstep."""
        import jax

        if self._replicate_out is None:
            from jax.sharding import NamedSharding, PartitionSpec

            self._replicate_out = jax.jit(
                lambda x: x,
                out_shardings=NamedSharding(self.mesh, PartitionSpec()),
            )
        return self._replicate_out(t)

    def _spec_admit(self, target: ModelId, draft: ModelId) -> bool:
        """Should this request run its draft? False once sustained low
        acceptance disabled the pair; every SPEC_REPROBE_EVERY-th gated
        request re-auditions the draft so a workload shift can re-enable it.
        On a cross-host group only the LEADER holds an active gate — its
        decision rides the work envelope (draft dropped when gated), so
        every process still executes the same program."""
        if not self._spec_gate_active:
            return True
        with self._spec_lock:
            st = self._spec_health.get((target, draft))
            if st is None or not st["disabled"]:
                return True
            st["skipped"] += 1
            return st["skipped"] % SPEC_REPROBE_EVERY == 0

    def _spec_observe(self, target: ModelId, draft: ModelId, emitted: int,
                      rounds: int, engine: str = "solo") -> None:
        """Record one speculative generate's acceptance; flip the pair's
        disabled flag on a sustained low streak (VERDICT r5 #6 — the health
        signal existed since round 4 but nothing acted on it). ``engine``
        labels the cumulative counters (solo generate vs continuous spec
        rounds — the acceptance-rate trend per serving path)."""
        tpr = emitted / max(1, rounds)
        if self.metrics is not None:
            label = self.metrics.model_label(target.name, target.version)
            self.metrics.spec_tokens_per_round.labels(model=label).set(
                round(tpr, 3)
            )
            self.metrics.spec_accepted_tokens.labels(engine=engine).inc(
                int(emitted)
            )
            self.metrics.spec_rounds.labels(engine=engine).inc(int(rounds))
        if not self._spec_gate_active:
            return
        if not (self.is_loaded(target) and self.is_loaded(draft)):
            # either half unloaded mid-generate: recording would resurrect
            # the pair entry unload() just pruned (the setdefault below),
            # re-leaking gate state for a dead pair
            return
        with self._spec_lock:
            st = self._spec_health.setdefault(
                (target, draft),
                {"low_streak": 0, "disabled": False, "skipped": 0},
            )
            if tpr >= SPEC_MIN_TOKENS_PER_ROUND:
                if st["disabled"]:
                    log.info(
                        "draft %s re-enabled for %s (%.2f tokens/round)",
                        draft, target, tpr,
                    )
                st.update(low_streak=0, disabled=False, skipped=0)
                return
            st["low_streak"] += 1
            if not st["disabled"] and st["low_streak"] >= SPEC_DISABLE_AFTER:
                st["disabled"] = True
                st["skipped"] = 0
                log.warning(
                    "draft %s auto-disabled for %s: %d consecutive generates "
                    "below %.1f tokens/round (last %.2f) — speculative rounds "
                    "were doing more target work per token than plain decode; "
                    "falling back (re-audition every %d requests)",
                    draft, target, SPEC_DISABLE_AFTER,
                    SPEC_MIN_TOKENS_PER_ROUND, tpr, SPEC_REPROBE_EVERY,
                )
                if self.metrics is not None:
                    self.metrics.spec_draft_autodisabled.inc()

    def _prefix_generate(self, loaded, model_id, ids, prompt_len: int,
                         new_bucket: int, max_new: int, temperature: float,
                         top_k: int, seed: int,
                         forced_rows: int | None = None):
        """B=1 generate through the prefix KV cache: reuse the longest
        cached token-prefix's K/V rows, prefill only the suffix, and store
        the (prompt + completion) rows for the next turn. Output matches the
        plain path in exact arithmetic — same math at the same positions,
        shared decode-scan rng split structure — but the hit path's
        suffix-only prefill is a different matmul shape, so near-tied
        argmax/sampling under accelerator float reassociation can differ
        between hit and miss (same caveat as models/speculative.py); don't
        rely on seed-reproducibility across cache state.

        ``forced_rows`` (group mode): the leader's decision from the work
        envelope. Every process must run the SAME program, so a forced hit
        this cache cannot honor raises — BEFORE any device op — instead of
        silently prefilling a different shape into the group's collective."""
        import jax

        from tfservingcache_tpu.models.generation import (
            generate as gen,
            generate_from_cache,
        )

        prompt = ids[0, :prompt_len]
        rng = jax.random.PRNGKey(seed)
        hit = self._prefix_resolve(model_id, prompt, forced_rows)
        if hit is None:
            toks_d, k_full, v_full = gen(
                loaded.model_def, loaded.params, ids,
                prompt_lengths=np.array([prompt_len], np.int32),
                max_new_tokens=new_bucket, temperature=temperature,
                top_k=top_k, rng=rng, return_cache=True,
            )
        else:
            suffix, suffix_len = self._prefix_suffix(ids, prompt_len, hit)
            toks_d, k_full, v_full = generate_from_cache(
                loaded.model_def, loaded.params, suffix, suffix_len,
                hit.k, hit.v, hit.valid_len, max_new_tokens=new_bucket,
                temperature=temperature, top_k=top_k, rng=rng,
                return_cache=True,
            )
        return self._prefix_store(
            model_id, prompt, prompt_len, max_new, toks_d, k_full, v_full, hit
        )

    def _prefix_resolve(self, model_id, prompt, forced_rows: int | None):
        """Hit decision for the prefix paths (plain + speculative): local
        lookup, or the group leader's forced decision — which this process
        must honor exactly or fail loudly before any device op."""
        pc = self._prefix_cache
        if forced_rows == 0:
            pc.note_forced_miss()
            return None
        hit = pc.lookup(model_id, prompt)
        if forced_rows is not None and forced_rows > 0:
            if hit is None or hit.valid_len < forced_rows:
                raise RuntimeError_(
                    f"prefix-cache divergence for {model_id}: leader decided "
                    f"{forced_rows} cached rows, this process has "
                    f"{0 if hit is None else hit.valid_len} — group states "
                    "are out of lockstep (re-form required)"
                )
            if hit.valid_len > forced_rows:
                from tfservingcache_tpu.runtime.prefix_cache import PrefixEntry

                hit = PrefixEntry(hit.tokens[:forced_rows], hit.k, hit.v,
                                  forced_rows, hit.nbytes)
        return hit

    @staticmethod
    def _prefix_suffix(ids, prompt_len: int, hit):
        """(padded suffix ids, true suffix length) after ``hit``'s rows."""
        l_use = hit.valid_len
        suffix = ids[:1, l_use:prompt_len]
        suffix_len = prompt_len - l_use
        s_pad = next_bucket(suffix_len)
        if s_pad != suffix.shape[1]:
            suffix = np.pad(suffix, ((0, 0), (0, s_pad - suffix.shape[1])))
        return suffix, suffix_len

    def _prefix_store(self, model_id, prompt, prompt_len: int, max_new: int,
                      toks_d, k_full, v_full, hit):
        """Read back tokens, insert the (prompt + completion) rows for the
        next turn, record stats. Rows are valid through prompt_len +
        new_bucket (plain: the scan forwards the carry before sampling;
        speculative: the final-carry writeback) — but the entry must stop at
        the TRUE max_new: the bucket-padding generations were never returned
        to the client, so the next turn's prompt diverges exactly there and
        an entry containing them would never match again (review repro:
        max_new=5 bucketed to 8 made every conversation a permanent miss)."""
        import jax

        pc = self._prefix_cache
        if self._mp_mesh:
            # sharded result: force replication so THIS process can read the
            # tokens (same jitted identity the plain path uses); K/V stay
            # sharded — each process caches its own shards
            toks_d = self._replicated(toks_d)
        toks = np.asarray(jax.device_get(toks_d))
        valid = prompt_len + max_new
        entry_tokens = np.concatenate([prompt, toks[0, :max_new]])
        # store at the power-of-two FLOOR of the valid rows: only pow2 row
        # blocks may be cached (an odd width would mint a novel jit trace
        # shape on every later hit), the floor always fits the cache array,
        # and the tail it drops is at most half — the next turn still
        # reuses the bulk of the history
        l_store = 1 << (valid.bit_length() - 1)
        if l_store >= 16:
            pc.insert(
                model_id, entry_tokens[:l_store],
                k_full[:, :, :, :l_store, :], v_full[:, :, :, :l_store, :],
                l_store,
            )
        TRACER.annotate(prefix_hit=hit is not None,
                        prefix_rows=0 if hit is None else hit.valid_len)
        if self.metrics is not None:
            (self.metrics.prefix_cache_hits if hit is not None
             else self.metrics.prefix_cache_misses).inc()
            self.metrics.prefix_cache_bytes.set(pc.total_bytes)
        return toks

    def _speculative(self, loaded, draft, model_id, ids, lengths, new_bucket,
                     max_new: int, spec_tokens: int,
                     forced_rows: int | None, prefix_capable: bool):
        """Speculative decoding, prefix-cache aware (VERDICT r5 composition):
        when the cache is on and B=1, the TARGET's prefill starts from the
        cached prompt-prefix rows and the completion is inserted back — a
        draft-assisted conversation pays target prefill only for its new
        tokens from turn 2. Returns (tokens — host array on the prefix
        path, device array otherwise — and the verify-round count)."""
        from tfservingcache_tpu.models.speculative import speculative_generate

        if not prefix_capable:
            # device tokens returned as-is: generate()'s shared tail handles
            # the group replication + device_get exactly once
            toks, rounds = speculative_generate(
                loaded.model_def, loaded.params, draft.model_def,
                draft.params, ids, prompt_lengths=lengths,
                max_new_tokens=new_bucket, spec_tokens=spec_tokens,
                return_rounds=True,
            )
            return toks, int(rounds)

        prompt_len = int(lengths[0])
        prompt = ids[0, :prompt_len]
        hit = self._prefix_resolve(model_id, prompt, forced_rows)
        cached_kv = None
        if hit is not None:
            suffix, suffix_len = self._prefix_suffix(ids, prompt_len, hit)
            cached_kv = (suffix, suffix_len, hit.k, hit.v, hit.valid_len)
        toks_d, rounds, k_full, v_full = speculative_generate(
            loaded.model_def, loaded.params, draft.model_def, draft.params,
            ids, prompt_lengths=np.array([prompt_len], np.int32),
            max_new_tokens=new_bucket, spec_tokens=spec_tokens,
            return_rounds=True, return_cache=True, cached_kv=cached_kv,
        )
        toks = self._prefix_store(
            model_id, prompt, prompt_len, max_new, toks_d, k_full, v_full, hit
        )
        return toks, int(rounds)

    def resident_headroom(self) -> tuple[int | None, int]:
        """(free resident model slots or None if uncapped, free HBM bytes).
        Advisory snapshot for the assignment warmer: warming past this would
        evict actively-serving models (ADVICE r3: a post-remap sweep must
        help live traffic, not churn it)."""
        free_slots = (
            None if self._resident.max_items is None
            else max(0, self._resident.max_items - len(self._resident))
        )
        return free_slots, max(
            0, self.cfg.hbm_capacity_bytes - self._resident.total_bytes
        )

    def family_of(self, model_id: ModelId) -> str | None:
        """Family of a resident model (None when not loaded)."""
        loaded = self._resident.get(model_id, touch=False)
        return None if loaded is None else loaded.model_def.family

    def engine_ready_of(self, model_id: ModelId) -> bool:
        """Whether a resident model's family declares itself engine-ready
        (``ModelDef.engine_ready``: every layer's state one of the kinds its
        ``layer_state`` declares, rows in the paged arena or a fixed state a
        lane, and a row-invariant step) — what the continuous engine asks
        before it co-batches it. False when not loaded."""
        loaded = self._resident.get(model_id, touch=False)
        return loaded is not None and loaded.model_def.engine_ready

    def _refuse_experts_on_mesh(self, loaded: LoadedModel) -> None:
        """``:generate`` of an expert model on a TPU chip group is refused by
        name: the grouped expert kernel is single-chip and the generate
        programs cannot tell that they are partitioned (``:predict`` can, and
        takes ``jax.lax.ragged_dot`` there)."""
        import jax

        if (self.mesh is not None and jax.default_backend() == "tpu"
                and "n_experts" in loaded.model_def.config):
            raise RuntimeError_(
                "generate of an expert model on a chip group is not supported: "
                "the grouped expert kernel (ops/moe.py) is single-chip"
            )

    def _refuse_latent(self, loaded: LoadedModel, what: str | None = None) -> None:
        """What a latent-attention family (a one-sided ``cache_row``) cannot
        do yet is refused by name, never answered wrongly: generation on a
        chip group always (its weights are already one chip's share of a
        layer), and ``what`` where the caller is about to use it: the int8
        arena (no quantized form of the latent row), a ``draft_model`` (the
        speculative programs hold K and V sides)."""
        row = loaded.model_def.cache_row
        if row is None or row.sides != 1:
            return
        if self.mesh is not None:
            what = "generation on a chip-group mesh"
        if what:
            raise RuntimeError_(
                f"{loaded.model_def.family} (latent attention) does not "
                f"support {what}")

    def _refuse_lane_state(self, loaded: LoadedModel,
                           what: str | None = None) -> None:
        """What a model that keeps state BESIDE the global arena cannot do yet
        is refused by name, never answered wrongly: a model with lane-state
        layers (``registry.LaneState``: a fixed state a request beside its
        pages) and a model with window layers (a ``registry.CacheRow`` with a
        ``window``: a ring of pages a lane, with no block table and no row
        older than a window). Generation on a chip-group mesh always (neither
        is partitioned), and ``what`` where the caller is about to use it.
        Everything refused moves or reuses K/V PAGES by a lane's table, or
        runs a forward of several positions over the arena, and would leave
        the lane's state behind or find no ring to turn: shared-prefix hits,
        conversation park/resume, a ``draft_model``, chunked prefill, the int8
        arena.

        One site an entrance: the serving options where the slot state is
        built; a request's ``draft_model`` in ``generate``; and the three
        methods an engine reaches with the runtime's option unset
        (``park_lane`` by priority preemption, ``slot_prefill_chunk`` and
        ``slot_attach_draft`` by the engine's own constructor arguments)."""
        state = loaded.model_def.layer_state
        kind = ", ".join(name for name, has in (
            ("lane-state layers", lane_layers(state)),
            ("window layers", window_layers(state)),
            ("layers that read another layer's rows",
             any(isinstance(s, SharedRows) for s in state)),
        ) if has)
        if not kind:
            return
        if self.mesh is not None:
            what = "generation on a chip-group mesh"
        if what:
            raise RuntimeError_(
                f"{loaded.model_def.family} ({kind}) does not support {what}")

    def signature(self, model_id: ModelId):
        loaded = self._resident.get(model_id, touch=False)
        if loaded is None:
            raise ModelNotLoadedError(f"model {model_id} is not loaded")
        d = loaded.model_def
        # derived outputs advertised alongside concrete ones so clients can
        # discover filterable names via GetModelMetadata
        out_spec = dict(d.output_spec)
        out_spec.update({name: spec for name, (_fn, spec) in d.derived_outputs.items()})
        return d.input_spec, out_spec, d.method_name

    def check(self) -> None:
        """Health probe: the devices must answer a trivial computation
        (replaces the reference's probe-model GetModelStatus trick,
        cachemanager.go:76-89 — NOT_FOUND from a live backend = healthy)."""
        import jax
        import jax.numpy as jnp

        x = jax.device_put(jnp.ones((8,)), self._devices[0])
        if float(jnp.sum(x)) != 8.0:
            raise RuntimeError_("device smoke computation returned wrong result")

    @property
    def hbm_bytes_in_use(self) -> int:
        return self._resident.total_bytes

    def resident_models(self) -> list[ModelId]:
        return self._resident.keys_mru_first()

    def reset_group_state(self) -> None:
        """Drop every resident model plus the prefix KV and draft-acceptance
        histories — the clean slate a re-forming cross-host group resets to
        (parallel/multihost.py): after a follower death the survivors' (or a
        restarted follower's empty) states must match EXACTLY before the
        lockstep op stream resumes; re-deriving parity is impossible, so the
        group re-forms empty and cold-loads on demand like the reference's
        remapped ring keys (SURVEY §3.4)."""
        for mid in self.resident_models():
            self._resident.remove(mid, run_callback=True)
        if self._host_tier is not None:
            # drain first: the removals above may have queued demotions that
            # would otherwise repopulate the tier after the clear
            self.drain_demotions()
            self._host_tier.clear()
        if self._prefix_cache is not None:
            self._prefix_cache.clear()
        with self._slot_lock:
            self._slot_states.clear()
            self._slot_init_guards.clear()
        with self._spec_lock:
            self._spec_health.clear()

    def _update_gauges(self) -> None:
        # cost ledger: re-stamp every resident tenant's HBM level (and zero
        # the just-evicted — gauge_sync's owner-scoped sweep). Loads/evicts
        # are rare, so the O(resident) walk is off every request path.
        LEDGER.gauge_sync(
            "hbm_bytes",
            {
                str(mid): float(e.size_bytes)
                for mid, e in self._resident.items_lru_first()
            },
            owner=f"hbm:{id(self)}",
        )
        peak = RECORDER.observe_watermark(
            f"hbm_bytes:g{self.group}", float(self._resident.total_bytes)
        )
        if self.metrics is None:
            return
        self.metrics.hbm_bytes_in_use.labels(str(self.group)).set(self._resident.total_bytes)
        self.metrics.hbm_bytes_peak.labels(str(self.group)).set(peak)
        self.metrics.models_resident.labels(str(self.group)).set(len(self._resident))

    def close(self) -> None:
        if self._host_tier is not None:
            self._host_tier.close()  # put() no-ops from here on
            self._demote_queue.put(None)  # worker exits after queued jobs
            self._demote_thread.join(timeout=5.0)
        self._resident.clear()
        with self._adopted_lock:
            self._adopted.clear()
        with self._slot_lock:
            self._slot_states.clear()
            self._slot_init_guards.clear()
        with self._jit_lock:
            self._jitted_by_key.clear()
        with self._aot_lock:
            self._aot_cache.clear()
            self._aot_futures.clear()
            pool, self._compile_pool = self._compile_pool, None
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)
