"""Conversation KV tier: park, spill, and resume decode state (ISSUE 18).

The three-tier residency model the *model* artifacts already enjoy
(HBM -> host DRAM -> disk, cache/host_tier.py + cache/disk_cache.py) applied
to *KV pages*: when a request carrying a ``conversation_id`` retires, the
lane's live pages (int8 + per-row scales when the arena is quantized, so
half the bytes) and its token history are exported into this byte-budgeted
tier instead of being discarded. The next turn re-imports the parked pages
into the arena and prefills only the suffix — O(new tokens) instead of
O(conversation), the way SGLang-lineage stacks scale session reuse past HBM
(PAPERS.md).

Tier discipline mirrors ``HostRamTier``: one shared LRU engine per level
(native/lru.py via ``make_lru_cache``), byte budget, MRU touch on get,
evict callbacks outside the internal lock. The host level's evict callback
IS the spill: the coldest conversation serializes to a flat blob
(``pack_parked``) and moves into a second byte-budgeted LRU over disk
files. A disk hit promotes back to host. The same blob format rides PR 8's
integrity-checked peer wire when the ring rebalances
(protocol/peer_transfer.py ``iter_kv_frames``/``KVStreamReceiver``), so a
conversation survives its node changing.

``get`` PEEKS — the entry survives until the next park of the same
conversation replaces it — so a crashed lane (runtime/batcher.py
generate_recovery) can re-resume from its parked ancestor instead of
re-prefilling the whole history.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import struct
import threading
from dataclasses import dataclass
from typing import Any

import numpy as np

from tfservingcache_tpu.cache.lru import CapacityError, LRUEntry
from tfservingcache_tpu.native import make_lru_cache
from tfservingcache_tpu.utils.flight_recorder import RECORDER
from tfservingcache_tpu.utils.lockcheck import lockchecked
from tfservingcache_tpu.utils.logging import get_logger

log = get_logger("conversation_kv")

# blob format tag (disk spill files and the peer KV wire share it)
KV_BLOB_MAGIC = b"TPKV1\n"
_HDR_LEN = struct.Struct("<I")


@dataclass
class ParkedConversation:
    """One parked conversation's resumable decode state.

    ``pages_k``/``pages_v`` are OWNED host copies of the lane's live arena
    pages in block-table order, shape ``(layers, n_pages, n_kv, page_tokens,
    hd)`` in the arena dtype (int8 when the arena is quantized, in which
    case ``k_scale``/``v_scale`` carry the per-row f32 scales). ``history``
    is the exact token prefix those pages cover — resume matches it against
    the new prompt to decide how many tokens skip prefill. Page bytes
    round-trip bit-exact: park copies raw arena rows and resume scatters
    them back verbatim, so a resumed lane's KV is byte-identical to one
    that never retired.
    """

    model_id: str
    history: np.ndarray                 # (tokens,) int32
    pages_k: np.ndarray
    pages_v: np.ndarray | None
    k_scale: np.ndarray | None
    v_scale: np.ndarray | None
    page_tokens: int
    nbytes: int = 0

    def __post_init__(self) -> None:
        if not self.nbytes:
            self.nbytes = sum(
                a.nbytes
                for a in (self.history, self.pages_k, self.pages_v,
                          self.k_scale, self.v_scale)
                if a is not None
            )


def _raw_bytes(a: np.ndarray) -> memoryview:
    # uint8 view, not tobytes(): extension dtypes (bfloat16) lack the
    # buffer protocol and a view avoids copying the page payload
    return memoryview(np.ascontiguousarray(a).reshape(-1).view(np.uint8))


def pack_parked(parked: ParkedConversation) -> bytes:
    """Serialize to a flat self-describing blob (disk spill + peer wire).

    Layout: magic, u32 header length, JSON header (model id, page_tokens,
    history length, per-array dtype/shape), then the raw array bytes
    concatenated in header order. Byte-exact round-trip by construction —
    arrays are stored as their raw memory, no npz/pickle re-encode.
    """
    arrays: list[tuple[str, np.ndarray]] = [
        ("history", parked.history),
        ("pages_k", parked.pages_k),
    ]
    if parked.pages_v is not None:      # None: a one-sided (latent) arena
        arrays.append(("pages_v", parked.pages_v))
    if parked.k_scale is not None:
        arrays.append(("k_scale", parked.k_scale))
    if parked.v_scale is not None:
        arrays.append(("v_scale", parked.v_scale))
    header = {
        "model": str(parked.model_id),
        "page_tokens": int(parked.page_tokens),
        "arrays": [
            {"name": n, "dtype": a.dtype.name, "shape": list(a.shape)}
            for n, a in arrays
        ],
    }
    hb = json.dumps(header).encode()
    parts = [KV_BLOB_MAGIC, _HDR_LEN.pack(len(hb)), hb]
    parts.extend(_raw_bytes(a) for _, a in arrays)
    return b"".join(parts)


def unpack_parked(blob: bytes | memoryview) -> ParkedConversation:
    import ml_dtypes  # registers bfloat16/float8 names with np.dtype

    del ml_dtypes
    mv = memoryview(blob)
    n_magic = len(KV_BLOB_MAGIC)
    if bytes(mv[:n_magic]) != KV_BLOB_MAGIC:
        raise ValueError("bad parked-KV blob: wrong magic")
    (hlen,) = _HDR_LEN.unpack_from(mv, n_magic)
    off = n_magic + _HDR_LEN.size
    header = json.loads(bytes(mv[off:off + hlen]).decode())
    off += hlen
    out: dict[str, np.ndarray] = {}
    for ent in header["arrays"]:
        dt = np.dtype(ent["dtype"])
        shape = tuple(int(s) for s in ent["shape"])
        nb = int(np.prod(shape)) * dt.itemsize if shape else dt.itemsize
        a = np.frombuffer(mv, np.uint8, nb, off).view(dt).reshape(shape)
        out[ent["name"]] = a.copy()  # own the buffer, don't pin the blob
        off += nb
    if off != len(mv):
        raise ValueError(
            f"bad parked-KV blob: {len(mv) - off} trailing bytes"
        )
    return ParkedConversation(
        model_id=header["model"],
        history=out["history"],
        pages_k=out["pages_k"],
        pages_v=out.get("pages_v"),
        k_scale=out.get("k_scale"),
        v_scale=out.get("v_scale"),
        page_tokens=int(header["page_tokens"]),
    )


ConvKey = tuple[str, str]  # (model_id, conversation_id)


@lockchecked
class ConversationKVTier:
    """Two-level byte-budgeted LRU of ``ParkedConversation``.

    Level 1 (host DRAM) holds live ``ParkedConversation`` payloads; its
    evict callback spills the blob to level 2 (disk files under
    ``disk_dir``) when a disk budget is configured, else the conversation
    is simply dropped (counted as an eviction either way). A zero host
    budget disables the tier entirely — every ``put`` is a no-op and every
    ``get`` a miss, byte-identical behavior to a build without the tier.
    """

    # Guarded-field registry (tools/tpusc_check TPUSC001 + TPUSC_LOCKCHECK=1).
    _tpusc_guarded = {
        "_hits": "_stats_lock",
        "_spilled_hits": "_stats_lock",
        "_misses": "_stats_lock",
        "_parked_total": "_stats_lock",
        "_spills": "_stats_lock",
        "_migrations_in": "_stats_lock",
    }

    def __init__(
        self,
        capacity_bytes: int,
        disk_capacity_bytes: int = 0,
        disk_dir: str | None = None,
        metrics: Any = None,
    ) -> None:
        self.metrics = metrics
        self.capacity_bytes = max(0, int(capacity_bytes))
        self.disk_capacity_bytes = max(0, int(disk_capacity_bytes))
        self.disk_dir = disk_dir
        self.enabled = self.capacity_bytes > 0
        # host level: payload = ParkedConversation
        self.host = make_lru_cache(max(1, self.capacity_bytes), self._on_evict_host)
        # disk level: payload = blob path; evict callback deletes the file
        self._spill = (
            self.enabled and self.disk_capacity_bytes > 0 and disk_dir is not None
        )
        self.disk = make_lru_cache(max(1, self.disk_capacity_bytes), self._on_evict_disk)
        self._closed = threading.Event()
        self._stats_lock = threading.Lock()
        self._hits = 0
        self._spilled_hits = 0
        self._misses = 0
        self._parked_total = 0
        self._spills = 0
        self._migrations_in = 0
        if self._spill:
            os.makedirs(disk_dir, exist_ok=True)
        self._update_gauges()

    # -- core ---------------------------------------------------------------
    def put(self, conversation_id: str, parked: ParkedConversation) -> None:
        """Park (or re-park, replacing the previous turn's entry)."""
        if not self.enabled or self._closed.is_set():
            return
        key = (str(parked.model_id), str(conversation_id))
        try:
            self.host.put(key, parked.nbytes, parked)
        except CapacityError:
            log.warning(
                "conversation %s (%d KV bytes) exceeds parked-KV budget %d; dropped",
                conversation_id, parked.nbytes, self.capacity_bytes,
            )
            return
        # a re-park supersedes any spilled copy of the same conversation
        self.disk.remove(key, run_callback=True)
        with self._stats_lock:
            self._parked_total += 1
        self._update_gauges()

    def get(
        self, conversation_id: str, model_id: str, touch: bool = True,
    ) -> tuple[ParkedConversation | None, str]:
        """Look up parked state; returns ``(parked, outcome)`` with outcome
        one of ``hit`` (host), ``spilled`` (read back + re-promoted from
        disk), ``miss``. PEEKS — the entry stays parked so a crashed lane
        can resume again; the next park of the same conversation replaces
        it."""
        if not self.enabled:
            return None, "miss"
        key = (str(model_id), str(conversation_id))
        parked = self.host.get(key, touch=touch)
        if parked is not None:
            self._count("hit")
            return parked, "hit"
        path = self.disk.get(key, touch=touch)
        if path is not None:
            try:
                with open(path, "rb") as f:
                    parked = unpack_parked(f.read())
            except (OSError, ValueError) as e:
                log.warning("parked-KV read-back failed for %s: %s", key, e)
                self.disk.remove(key, run_callback=True)
                self._count("miss")
                self._update_gauges()
                return None, "miss"
            # promote host-ward (may itself spill a colder conversation);
            # drop the disk copy so bytes are never double-counted
            self.disk.remove(key, run_callback=True)
            try:
                self.host.put(key, parked.nbytes, parked)
            except CapacityError:
                pass  # serve it anyway; too big to re-park
            self._count("spilled")
            self._update_gauges()
            return parked, "spilled"
        self._count("miss")
        return None, "miss"

    def adopt(self, conversation_id: str, parked: ParkedConversation) -> None:
        """Land a conversation migrated from a peer (ring rebalance)."""
        self.put(conversation_id, parked)
        with self._stats_lock:
            self._migrations_in += 1

    def drop(self, conversation_id: str, model_id: str) -> None:
        key = (str(model_id), str(conversation_id))
        self.host.remove(key, run_callback=False)
        self.disk.remove(key, run_callback=True)
        self._update_gauges()

    def drop_model(self, model_id: str) -> None:
        """Forget every conversation parked for a model (unload path)."""
        mid = str(model_id)
        for key in [k for k in self.host.keys_mru_first() if k[0] == mid]:
            self.host.remove(key, run_callback=False)
        for key in [k for k in self.disk.keys_mru_first() if k[0] == mid]:
            self.disk.remove(key, run_callback=True)
        self._update_gauges()

    # -- eviction / spill ---------------------------------------------------
    def _on_evict_host(self, key: ConvKey, entry: LRUEntry[ParkedConversation]) -> None:
        if self._spill and not self._closed.is_set():
            blob = pack_parked(entry.payload)
            name = hashlib.sha256(
                f"{key[0]}\x00{key[1]}".encode()
            ).hexdigest()[:24]
            path = os.path.join(self.disk_dir, f"{name}.kv")
            try:
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)
                self.disk.put(key, len(blob), path)
                with self._stats_lock:
                    self._spills += 1
                if self.metrics is not None:
                    self.metrics.evictions.labels("conversation_kv_host").inc()
                self._update_gauges()
                log.info(
                    "parked conversation %s spilled host->disk (%d bytes)",
                    key[1], len(blob),
                )
                return
            except (OSError, CapacityError) as e:
                log.warning("parked-KV spill failed for %s: %s", key, e)
        if self.metrics is not None:
            self.metrics.evictions.labels("conversation_kv_host").inc()
        self._update_gauges()

    def _on_evict_disk(self, key: ConvKey, entry: LRUEntry[str]) -> None:
        try:
            os.unlink(entry.payload)
        except OSError:
            pass
        if self.metrics is not None:
            self.metrics.evictions.labels("conversation_kv_disk").inc()
        self._update_gauges()

    # -- outcome accounting (resume path calls back into metrics) -----------
    def _count(self, outcome: str) -> None:
        with self._stats_lock:
            if outcome == "hit":
                self._hits += 1
            elif outcome == "spilled":
                self._spilled_hits += 1
            else:
                self._misses += 1
        if self.metrics is not None:
            self.metrics.kv_resume.labels(outcome).inc()

    # -- introspection ------------------------------------------------------
    def stats(self) -> dict[str, Any]:
        with self._stats_lock:
            hits, spilled = self._hits, self._spilled_hits
            misses = self._misses
            parked, spills = self._parked_total, self._spills
            migrations = self._migrations_in
        lookups = hits + spilled + misses
        return {
            "enabled": self.enabled,
            "host_conversations": len(self.host),
            "disk_conversations": len(self.disk),
            "host_bytes": self.host.total_bytes,
            "disk_bytes": self.disk.total_bytes,
            "hits": hits,
            "spilled_hits": spilled,
            "misses": misses,
            "hit_rate": round((hits + spilled) / lookups, 4) if lookups else 0.0,
            "parked_total": parked,
            "spills": spills,
            "migrations_in": migrations,
        }

    def parked_page_count(self, model_id: str | None = None) -> int:
        """Pages currently parked (host tier only — disk entries are opaque
        blobs). Feeds the conservation census's parked-page extension."""
        total = 0
        for key, entry in self.host.items_lru_first():
            if model_id is not None and key[0] != str(model_id):
                continue
            total += int(entry.payload.pages_k.shape[1])
        return total

    @property
    def total_bytes(self) -> int:
        return self.host.total_bytes + self.disk.total_bytes

    def __len__(self) -> int:
        return len(self.host) + len(self.disk)

    def _update_gauges(self) -> None:
        host_b = float(self.host.total_bytes)
        disk_b = float(self.disk.total_bytes)
        n = len(self.host) + len(self.disk)
        if self.metrics is not None:
            self.metrics.kv_parked_bytes.labels("host").set(host_b)
            self.metrics.kv_parked_bytes.labels("disk").set(disk_b)
            self.metrics.kv_parked_conversations.set(n)
        RECORDER.note_conversation_kv(self.stats())

    def clear(self) -> None:
        self.host.clear()
        self.disk.clear()
        self._update_gauges()

    def close(self) -> None:
        self._closed.set()
        # plain clear, not spill: the process is going away
        self._spill = False
        self.host.clear()
        self.disk.clear()
        if self.disk_dir is not None:
            shutil.rmtree(self.disk_dir, ignore_errors=True)
        self._update_gauges()
