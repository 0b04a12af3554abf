"""Native (C++) runtime components, loaded via ctypes with Python fallback.

The reference's runtime is native code (Go — pkg/taskhandler/cluster.go,
pkg/cachemanager/lrucache.go); here the equivalent hot-path pieces are C++
(src/tpusc_native.cc) behind a plain-C ABI:

  - BLAKE2b-64 hashing (placement hash, RFC 7693)
  - consistent-hash ring (``NativeHashRing`` — same placement as the Python
    ``HashRing``, verified bit-exact by tests/test_native.py)
  - byte-budgeted LRU index (``NativeLRUCache`` — same semantics as
    ``cache.lru.LRUCache``)

Loading order: ``make`` (re)builds ``libtpusc_native.so`` next to this file
from ``src/tpusc_native.cc`` when a toolchain exists (the library is a build
product, never committed); an existing library is used when the toolchain is
gone; else ``load()`` returns None and callers use the pure-Python
implementations. Which tier serves is logged once. Set ``TPUSC_NO_NATIVE=1``
to force the Python tier.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Any, Callable, Generic, Hashable, Iterator, TypeVar

from tfservingcache_tpu.cache.lru import CapacityError, LRUEntry

from tfservingcache_tpu.utils.lockcheck import lockchecked
from tfservingcache_tpu.utils.logging import get_logger

log = get_logger("native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libtpusc_native.so")

_lib: ctypes.CDLL | None = None
_load_attempted = False
_load_lock = threading.Lock()


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.tpusc_blake2b64.argtypes = [ctypes.c_char_p, ctypes.c_longlong]
    lib.tpusc_blake2b64.restype = ctypes.c_ulonglong
    lib.tpusc_ring_new.argtypes = [ctypes.c_int]
    lib.tpusc_ring_new.restype = ctypes.c_void_p
    lib.tpusc_ring_free.argtypes = [ctypes.c_void_p]
    lib.tpusc_ring_set_members.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.c_int,
    ]
    lib.tpusc_ring_len.argtypes = [ctypes.c_void_p]
    lib.tpusc_ring_len.restype = ctypes.c_int
    lib.tpusc_ring_members.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
    ]
    lib.tpusc_ring_members.restype = ctypes.c_int
    lib.tpusc_ring_get_n.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_int,
    ]
    lib.tpusc_ring_get_n.restype = ctypes.c_int
    lib.tpusc_lru_new.argtypes = [ctypes.c_longlong, ctypes.c_longlong]
    lib.tpusc_lru_new.restype = ctypes.c_void_p
    lib.tpusc_lru_free.argtypes = [ctypes.c_void_p]
    lib.tpusc_lru_total.argtypes = [ctypes.c_void_p]
    lib.tpusc_lru_total.restype = ctypes.c_longlong
    lib.tpusc_lru_len.argtypes = [ctypes.c_void_p]
    lib.tpusc_lru_len.restype = ctypes.c_int
    lib.tpusc_lru_contains.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.tpusc_lru_contains.restype = ctypes.c_int
    lib.tpusc_lru_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    lib.tpusc_lru_get.restype = ctypes.c_longlong
    lib.tpusc_lru_put.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong,
        ctypes.c_char_p, ctypes.c_int,
    ]
    lib.tpusc_lru_put.restype = ctypes.c_int
    lib.tpusc_lru_remove.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.tpusc_lru_remove.restype = ctypes.c_longlong
    lib.tpusc_lru_ensure_free.argtypes = [
        ctypes.c_void_p, ctypes.c_longlong, ctypes.c_char_p, ctypes.c_int,
    ]
    lib.tpusc_lru_ensure_free.restype = ctypes.c_int
    lib.tpusc_lru_keys.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
    ]
    lib.tpusc_lru_keys.restype = ctypes.c_int
    lib.tpusc_lru_clear.argtypes = [ctypes.c_void_p]
    lib.tpusc_json_encode.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.c_char_p, ctypes.c_longlong,
    ]
    lib.tpusc_json_encode.restype = ctypes.c_longlong
    lib.tpusc_json_parse.argtypes = [
        ctypes.c_char_p, ctypes.c_longlong, ctypes.c_char_p,
    ]
    lib.tpusc_json_parse.restype = ctypes.c_void_p
    lib.tpusc_jp_ok.argtypes = [ctypes.c_void_p]
    lib.tpusc_jp_ok.restype = ctypes.c_int
    lib.tpusc_jp_declined.argtypes = [ctypes.c_void_p]
    lib.tpusc_jp_declined.restype = ctypes.c_int
    lib.tpusc_jp_error.argtypes = [ctypes.c_void_p]
    lib.tpusc_jp_error.restype = ctypes.c_char_p
    lib.tpusc_jp_skeleton.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
    ]
    lib.tpusc_jp_skeleton.restype = ctypes.c_void_p
    lib.tpusc_jp_ntensors.argtypes = [ctypes.c_void_p]
    lib.tpusc_jp_ntensors.restype = ctypes.c_int
    lib.tpusc_jp_tensor_info.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_longlong),
    ]
    lib.tpusc_jp_tensor_info.restype = ctypes.c_int
    lib.tpusc_jp_tensor_data.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.tpusc_jp_tensor_data.restype = ctypes.c_void_p
    lib.tpusc_jp_free.argtypes = [ctypes.c_void_p]
    return lib


def load() -> ctypes.CDLL | None:
    """The native library, building it on first use if needed; None if
    unavailable (no toolchain / disabled)."""
    global _lib, _load_attempted
    if _lib is not None:
        return _lib
    with _load_lock:
        if _lib is not None or _load_attempted:
            return _lib
        _load_attempted = True
        if os.environ.get("TPUSC_NO_NATIVE"):
            log.info("native tier off (TPUSC_NO_NATIVE): pure-Python tier")
            return None
        # Always (re)run make when a toolchain exists — it no-ops when the .so
        # is current and rebuilds after source edits, so a stale library can't
        # silently diverge from src/ (placement parity depends on this).  An
        # existing .so is still used if the toolchain is gone.
        try:
            subprocess.run(
                ["make", "-C", _DIR],
                check=True,
                capture_output=True,
                timeout=120,
            )
        except (OSError, subprocess.SubprocessError) as e:
            if not os.path.exists(_LIB_PATH):
                log.warning("native tier did not build (%s): pure-Python "
                            "tier", e)
                return None
        try:
            _lib = _bind(ctypes.CDLL(_LIB_PATH))
        except (OSError, AttributeError) as e:
            # AttributeError: a stale prebuilt .so predating a newer symbol
            # (no toolchain to rebuild) must not take down the whole tier
            log.warning("native tier did not load (%s): pure-Python tier", e)
            return None
        log.info("native tier: %s", _LIB_PATH)
        return _lib


def native_available() -> bool:
    return load() is not None


def blake2b64(data: bytes) -> int:
    lib = load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    return int(lib.tpusc_blake2b64(data, len(data)))


def _call_buffered(fn: Callable[[ctypes.Array, int], int], initial: int = 4096) -> list[str]:
    """Run a needed-size-returning C call, growing the buffer on demand;
    decode the '\\n'-joined result."""
    cap = initial
    while True:
        buf = ctypes.create_string_buffer(cap)
        needed = fn(buf, cap)
        if needed < 0:
            raise CapacityError("native tier reported a capacity violation")
        if needed <= cap:
            raw = buf.value.decode()
            return raw.split("\n") if raw else []
        cap = needed


class NativeHashRing:
    """Drop-in for ``cluster.hashring.HashRing`` backed by the C++ ring.

    Placement-identical to the Python ring (same BLAKE2b-64 points, same
    vnode naming ``member#i``, same tie-break) so mixed native/fallback
    fleets agree on every key's owners.
    """

    def __init__(self, vnodes: int = 160) -> None:
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.vnodes = vnodes
        self._ptr = lib.tpusc_ring_new(vnodes)

    def __del__(self) -> None:
        ptr, self._ptr = getattr(self, "_ptr", None), None
        if ptr and getattr(self, "_lib", None) is not None:
            self._lib.tpusc_ring_free(ptr)

    def set_members(self, members: list[str]) -> None:
        for m in members:
            if not m or "\n" in m or "\x00" in m:
                raise ValueError(f"member {m!r} not representable in the native ring")
        arr = (ctypes.c_char_p * len(members))(
            *[m.encode() for m in members]
        )
        self._lib.tpusc_ring_set_members(self._ptr, arr, len(members))

    @property
    def members(self) -> set[str]:
        return set(
            _call_buffered(lambda b, c: self._lib.tpusc_ring_members(self._ptr, b, c))
        )

    def __len__(self) -> int:
        return int(self._lib.tpusc_ring_len(self._ptr))

    def get_n(self, key: str, n: int) -> list[str]:
        kb = key.encode()
        return _call_buffered(
            lambda b, c: self._lib.tpusc_ring_get_n(self._ptr, kb, n, b, c)
        )

    def get(self, key: str) -> str | None:
        nodes = self.get_n(key, 1)
        return nodes[0] if nodes else None


def make_ring(vnodes: int = 160):
    """Native ring when available, Python fallback otherwise."""
    if native_available():
        return NativeHashRing(vnodes)
    from tfservingcache_tpu.cluster.hashring import HashRing

    return HashRing(vnodes)


K = TypeVar("K", bound=Hashable)
V = TypeVar("V")


def _key_str(key: Any) -> str:
    # ModelId carries its canonical routing key; anything else must have a
    # stable, unique str().  Keys travel across the C ABI as NUL-terminated,
    # '\n'-joined strings, so those bytes (and the empty string) are rejected
    # loudly instead of silently corrupting eviction reporting.
    s = key.key if hasattr(key, "key") else str(key)
    if not s or "\n" in s or "\x00" in s:
        raise ValueError(f"key {key!r} not representable in the native tier")
    return s


@lockchecked
class NativeLRUCache(Generic[K, V]):
    """Drop-in for ``cache.lru.LRUCache``: the (key, size, order, budget)
    index lives in C++; payloads and evict callbacks stay on the Python side.

    Same contract as the Python tier: thread-safe, single eviction pass per
    put, oversized items rejected, callbacks run outside the native lock.
    """

    # Guarded-field registry (tools/tpusc_check TPUSC001 + TPUSC_LOCKCHECK=1).
    _tpusc_guarded = {"_payloads": "_lock"}

    def __init__(
        self,
        capacity_bytes: int,
        on_evict: Callable[[K, LRUEntry[V]], None] | None = None,
        max_items: int | None = None,
    ) -> None:
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.capacity_bytes = int(capacity_bytes)
        self.max_items = max_items
        self._on_evict = on_evict
        self._lock = threading.RLock()  # guards the Python-side payload map
        self._payloads: dict[str, tuple[K, LRUEntry[V]]] = {}
        self._ptr = lib.tpusc_lru_new(
            self.capacity_bytes, -1 if max_items is None else int(max_items)
        )

    def __del__(self) -> None:
        ptr, self._ptr = getattr(self, "_ptr", None), None
        if ptr and getattr(self, "_lib", None) is not None:
            self._lib.tpusc_lru_free(ptr)

    # -- introspection ------------------------------------------------------
    @property
    def total_bytes(self) -> int:
        return int(self._lib.tpusc_lru_total(self._ptr))

    def __len__(self) -> int:
        return int(self._lib.tpusc_lru_len(self._ptr))

    def __contains__(self, key: K) -> bool:
        return bool(self._lib.tpusc_lru_contains(self._ptr, _key_str(key).encode()))

    def _keys(self, mru_first: bool) -> list[str]:
        return _call_buffered(
            lambda b, c: self._lib.tpusc_lru_keys(self._ptr, int(mru_first), b, c)
        )

    def keys_mru_first(self) -> list[K]:
        with self._lock:
            return [self._payloads[s][0] for s in self._keys(True) if s in self._payloads]

    def items_lru_first(self) -> Iterator[tuple[K, LRUEntry[V]]]:
        with self._lock:
            return iter(
                [self._payloads[s] for s in self._keys(False) if s in self._payloads]
            )

    # -- core ---------------------------------------------------------------
    def get(self, key: K, touch: bool = True) -> V | None:
        s = _key_str(key)
        # lock spans the native call so a concurrent put/remove of the same
        # key can't desync the native index from the payload map
        with self._lock:
            size = self._lib.tpusc_lru_get(self._ptr, s.encode(), int(touch))
            if size < 0:
                return None
            held = self._payloads.get(s)
        return held[1].payload if held is not None else None

    def put(self, key: K, size_bytes: int, payload: V) -> list[K]:
        s = _key_str(key)
        size_bytes = int(size_bytes)
        if size_bytes > self.capacity_bytes:
            raise CapacityError(
                f"item {key!r} ({size_bytes}B) exceeds cache capacity {self.capacity_bytes}B"
            )
        sb = s.encode()
        with self._lock:
            old = self._payloads.get(s)
            evicted_keys = _call_buffered(
                lambda b, c: self._lib.tpusc_lru_put(self._ptr, sb, size_bytes, b, c)
            )
            evicted: list[tuple[K, LRUEntry[V]]] = []
            if old is not None:
                evicted.append(old)
            for ek in evicted_keys:
                held = self._payloads.pop(ek, None)
                if held is not None:
                    evicted.append(held)
            self._payloads[s] = (key, LRUEntry(size_bytes, payload))
        self._run_callbacks(evicted)
        return [k for k, _ in evicted if _key_str(k) != s]

    def remove(self, key: K, run_callback: bool = False) -> V | None:
        s = _key_str(key)
        with self._lock:
            if self._lib.tpusc_lru_remove(self._ptr, s.encode()) < 0:
                return None
            held = self._payloads.pop(s, None)
        if held is None:
            return None
        if run_callback and self._on_evict is not None:
            self._on_evict(held[0], held[1])
        return held[1].payload

    def ensure_free_bytes(self, n: int) -> list[K]:
        n = int(n)
        if n > self.capacity_bytes:
            raise CapacityError(
                f"requested {n}B free exceeds cache capacity {self.capacity_bytes}B"
            )
        with self._lock:
            keys = _call_buffered(
                lambda b, c: self._lib.tpusc_lru_ensure_free(self._ptr, n, b, c)
            )
            evicted = [self._payloads.pop(s) for s in keys if s in self._payloads]
        self._run_callbacks(evicted)
        return [k for k, _ in evicted]

    def clear(self) -> None:
        with self._lock:
            evicted = list(self._payloads.values())
            self._payloads.clear()
            self._lib.tpusc_lru_clear(self._ptr)
        self._run_callbacks(evicted)

    def _run_callbacks(self, evicted: list[tuple[K, LRUEntry[V]]]) -> None:
        if self._on_evict is None:
            return
        for key, entry in evicted:
            self._on_evict(key, entry)


def make_lru_cache(
    capacity_bytes: int,
    on_evict: Callable[[Any, LRUEntry[Any]], None] | None = None,
    max_items: int | None = None,
):
    """Native LRU tier when available, Python fallback otherwise."""
    if native_available():
        return NativeLRUCache(capacity_bytes, on_evict, max_items)
    from tfservingcache_tpu.cache.lru import LRUCache

    return LRUCache(capacity_bytes, on_evict, max_items)


# -- JSON tensor encoder ------------------------------------------------------

# numpy dtype name -> tpusc_json_encode kind (src/tpusc_native.cc)
_JSON_KINDS = {
    "float32": 1, "float64": 2, "int32": 3, "int64": 4, "bool": 5, "uint8": 6,
}


def json_encode_array(arr) -> bytes | None:
    """JSON nested-list text for a numeric ndarray, written straight from the
    buffer by the native encoder — ~10x json.dumps(arr.tolist()) on the REST
    response hot path. Returns None when the library or dtype is unavailable
    (caller falls back to the Python path). Float text is the shortest
    round-trip repr for the SOURCE dtype; non-finite values use Python's
    json tokens (NaN/Infinity/-Infinity)."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    a = np.asarray(arr)
    if not a.dtype.isnative:
        return None  # C++ reads host byte order; '>f4' etc. take the Python path
    if not a.flags["C_CONTIGUOUS"]:
        # NOT ascontiguousarray unconditionally: it promotes 0-d to 1-d,
        # which would wrap a scalar response in brackets
        a = np.ascontiguousarray(a)
    kind = _JSON_KINDS.get(a.dtype.name)
    if kind is None:
        return None
    ndim = a.ndim
    shape = (ctypes.c_int64 * max(ndim, 1))(*(a.shape or (0,)))
    # first-try guess; the C side owns the real bound and returns -(needed)
    # when this is short, so the width tables can't drift apart
    cap = int(a.size) * 14 + 64
    for _ in range(2):
        buf = ctypes.create_string_buffer(cap)
        wrote = lib.tpusc_json_encode(
            a.ctypes.data_as(ctypes.c_void_p), kind, shape, ndim, buf, cap
        )
        if wrote >= 0:
            return buf.raw[:wrote]
        if wrote == -1:
            return None
        cap = -wrote
    return None


# -- JSON request parser ------------------------------------------------------

_PARSE_NONCE = None


def json_parse_request(body: bytes):
    """Parse a JSON request body with dense numeric subtrees extracted as
    numpy arrays (int64 when every token is integral, else float64).

    Returns the parsed structure, or None when the native tier is
    unavailable or declines (caller falls back to ``json.loads``). Raises
    ``ValueError`` for bodies the parser proves malformed — message parity
    with json.loads is NOT guaranteed, so callers should re-raise through
    their existing error mapping.

    Extraction marks subtrees with a per-process nonce'd placeholder string,
    so payload strings cannot collide with placeholders across processes;
    a literal placeholder string inside the SAME request could only remap
    that request's own tensors, never another request's."""
    import secrets

    import numpy as np

    global _PARSE_NONCE
    lib = load()
    if lib is None:
        return None
    if _PARSE_NONCE is None:
        _PARSE_NONCE = secrets.token_hex(8)
    nonce = _PARSE_NONCE
    h = lib.tpusc_json_parse(body, len(body), nonce.encode())
    if not h:
        return None
    try:
        if not lib.tpusc_jp_ok(h):
            if lib.tpusc_jp_declined(h):
                return None  # beyond this parser (e.g. depth), not malformed
            raise ValueError(
                (lib.tpusc_jp_error(h) or b"invalid JSON").decode()
            )
        slen = ctypes.c_longlong()
        sptr = lib.tpusc_jp_skeleton(h, ctypes.byref(slen))
        skeleton = ctypes.string_at(sptr, slen.value)
        import json

        tree = json.loads(skeleton)
        nt = lib.tpusc_jp_ntensors(h)
        if nt == 0:
            return tree
        arrays = []
        for k in range(nt):
            is_int = ctypes.c_int()
            nelems = ctypes.c_longlong()
            shape = (ctypes.c_int64 * 32)()
            ndim = lib.tpusc_jp_tensor_info(
                h, k, ctypes.byref(is_int), shape, 32, ctypes.byref(nelems)
            )
            dt = np.int64 if is_int.value else np.float64
            data = lib.tpusc_jp_tensor_data(h, k)
            flat = np.ctypeslib.as_array(
                ctypes.cast(data, ctypes.POINTER(ctypes.c_int64 if is_int.value
                                                 else ctypes.c_double)),
                shape=(max(nelems.value, 0),),
            )
            arrays.append(
                flat.astype(dt, copy=True).reshape(tuple(shape[:ndim]))
            )
        prefix = "\x07" + nonce + ":"

        def swap(v):
            if isinstance(v, dict):
                return {k2: swap(x) for k2, x in v.items()}
            if isinstance(v, list):
                return [swap(x) for x in v]
            if isinstance(v, str) and v.startswith(prefix):
                idx = int(v[len(prefix):])
                return arrays[idx]
            return v

        return swap(tree)
    finally:
        lib.tpusc_jp_free(h)
