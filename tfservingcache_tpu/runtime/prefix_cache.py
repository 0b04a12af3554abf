"""Prefix KV cache: reuse a prompt's (and its generation's) device-resident
K/V rows across ``:generate`` requests.

No reference counterpart (the reference proxies opaque Predicts). The
serving pattern this targets is conversational: turn N's prompt extends
turn N-1's prompt + completion, so the expensive prefill over the shared
history is paid once. Entries store a power-of-two row block — the CALLER
(_prefix_generate) slices to the pow2 floor of the valid rows, so hits
never mint novel jit trace shapes — plus the exact token ids those rows
encode; a lookup matches the longest cached entry whose tokens are a
prefix of the new prompt, token-for-token (no hash-collision risk).

Byte-budgeted LRU, OFF by default (``serving.prefix_cache_bytes = 0``):
entries hold real HBM. Cross-host groups are supported (VERDICT r5 #7):
each process caches its own K/V shards, the LEADER's hit decision rides the
work envelope (``peek`` + ``generate(prefix_rows=...)``) so every process
provably runs the same program, and group re-formation resets all caches to
empty together. Entries are bucketed per model so one tenant's scan never
pays for another's, and ``drop_model`` is O(that model's entries).
"""

from __future__ import annotations

import itertools
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any

import numpy as np

from tfservingcache_tpu.types import ModelId
from tfservingcache_tpu.utils.lockcheck import lockchecked


@dataclass
class PrefixEntry:
    tokens: np.ndarray          # (L,) int32 — what the valid rows encode
    k: Any                      # (layers, 1, n_kv, Lpad, hd) device array
    v: Any
    valid_len: int              # L <= Lpad
    nbytes: int


# lookup() linear-scans one model's entries under the global lock; this cap
# keeps the B=1 :generate hot path O(small) no matter how large the byte
# budget is (ADVICE r4). 32 concurrent conversations per tenant model before
# the model's own LRU starts dropping the coldest thread.
_MAX_ENTRIES_PER_MODEL = 32


@lockchecked
class PrefixCache:
    # Guarded-field registry (tools/tpusc_check TPUSC001 + TPUSC_LOCKCHECK=1).
    _tpusc_guarded = {
        "_by_model": "_lock",
        "_recency": "_lock",
        "_total": "_lock",
    }

    def __init__(self, capacity_bytes: int) -> None:
        self.capacity_bytes = int(capacity_bytes)
        self._lock = threading.Lock()
        # per-model LRU of entries (token bytes -> entry), with a global
        # recency order across models for byte-budget eviction
        self._by_model: dict[ModelId, OrderedDict[bytes, PrefixEntry]] = {}
        self._recency: OrderedDict[tuple[ModelId, bytes], None] = OrderedDict()
        self._total = 0
        self.hits = 0
        self.misses = 0

    def _best_match(self, model_id: ModelId,  # lock-held: _lock
                    prompt: np.ndarray) -> tuple[bytes | None, int]:
        """(backing key, usable rows) of the longest entry whose tokens are
        a STRICT prefix of ``prompt`` (strict: at least one suffix token must
        remain to prefill — the forward needs a non-empty block). Callers
        hold the lock. The ONE matching rule: ``lookup`` (mutating) and
        ``peek`` (the group leader's envelope decision) must never diverge,
        so they share this."""
        best_tok, best = None, 0
        for tok_bytes, ent in self._by_model.get(model_id, {}).items():
            usable = min(ent.valid_len, prompt.shape[0] - 1)
            if usable < 1 or usable <= best:
                continue
            if np.array_equal(ent.tokens[:usable], prompt[:usable]):
                best_tok, best = tok_bytes, usable
        return best_tok, best

    def lookup(self, model_id: ModelId, prompt: np.ndarray) -> PrefixEntry | None:
        """Longest strict-prefix entry (see _best_match), counted + touched."""
        prompt = np.asarray(prompt, np.int32)
        with self._lock:
            best_tok, usable = self._best_match(model_id, prompt)
            best: PrefixEntry | None = None
            if best_tok is not None:
                ent = self._by_model[model_id][best_tok]
                if usable < ent.valid_len:
                    # partially usable entry: present it at the usable
                    # length (rows beyond it are junk the suffix prefill
                    # overwrites)
                    ent = PrefixEntry(ent.tokens[:usable], ent.k, ent.v,
                                      usable, ent.nbytes)
                best = ent
            if best is not None:
                self._recency.move_to_end((model_id, best_tok))
                # keep the per-model order LRU too: the entry cap below
                # evicts from its front
                self._by_model[model_id].move_to_end(best_tok)
                self.hits += 1
            else:
                self.misses += 1
        return best

    def insert(self, model_id: ModelId, tokens: np.ndarray, k, v,
               valid_len: int) -> None:
        tokens = np.asarray(tokens, np.int32)[:valid_len]
        # v is None for a one-sided (latent) cache
        nbytes = int(k.nbytes) + (0 if v is None else int(v.nbytes))
        if nbytes > self.capacity_bytes:
            return  # one entry over budget: don't thrash the whole cache
        tok_bytes = tokens.tobytes()
        with self._lock:
            model_entries = self._by_model.setdefault(model_id, OrderedDict())
            old = model_entries.pop(tok_bytes, None)
            if old is not None:
                self._total -= old.nbytes
                self._recency.pop((model_id, tok_bytes), None)
            while self._total + nbytes > self.capacity_bytes and self._recency:
                (ev_mid, ev_tok), _ = self._recency.popitem(last=False)
                ev = self._by_model.get(ev_mid, {}).pop(ev_tok, None)
                if ev is not None:
                    self._total -= ev.nbytes
            model_entries[tok_bytes] = PrefixEntry(tokens, k, v, valid_len,
                                                   nbytes)
            self._recency[(model_id, tok_bytes)] = None
            self._total += nbytes
            while len(model_entries) > _MAX_ENTRIES_PER_MODEL:
                ev_tok, ev = model_entries.popitem(last=False)
                self._total -= ev.nbytes
                self._recency.pop((model_id, ev_tok), None)

    def peek(self, model_id: ModelId, prompt: np.ndarray) -> int:
        """Usable row count of the best entry for ``prompt`` WITHOUT touching
        recency or hit/miss counters (0 = miss). A cross-host group's leader
        peeks under its op lock to form the envelope decision; the real
        lookup happens inside generate on every process."""
        prompt = np.asarray(prompt, np.int32)
        with self._lock:
            return self._best_match(model_id, prompt)[1]

    def note_forced_miss(self) -> None:
        """Stats for a miss decided upstream (group envelope forced_rows=0):
        the local lookup was bypassed, the miss still happened."""
        with self._lock:
            self.misses += 1

    def drop_model(self, model_id: ModelId) -> None:
        """Model unloaded/evicted: its prefix KV must go with it."""
        with self._lock:
            entries = self._by_model.pop(model_id, None)
            if not entries:
                return
            for tok_bytes, ent in entries.items():
                self._total -= ent.nbytes
                self._recency.pop((model_id, tok_bytes), None)

    @property
    def total_bytes(self) -> int:
        with self._lock:
            return self._total

    def __len__(self) -> int:
        with self._lock:
            return sum(len(d) for d in self._by_model.values())

    def clear(self) -> None:
        with self._lock:
            self._by_model.clear()
            self._recency.clear()
            self._total = 0


# =============================================================================
# Cross-request shared-prefix KV over the PAGED arena (ISSUE 9 / ROADMAP
# item 3). Unlike PrefixCache above — which stores its own dense K/V row
# blocks and serves the B=1 solo path — PagePrefixIndex stores no K/V at
# all: it is a radix index from token-prefix to the ARENA PAGES that
# already hold that prefix's K/V, so N concurrent same-prompt rows map the
# same physical pages read-only instead of each prefilling a private copy.
# =============================================================================


@dataclass
class SharedPrefixPlan:
    """One admission's shared-prefix decision, produced by
    ``PagePrefixIndex.lookup`` (and trimmed for viability by
    ``TPUModelRuntime.shared_prefix_plan``) and consumed by the continuous
    scheduler's reservation + prefill + CoW steps.

    ``kind == "shared"``: map ``pages`` (full page-aligned chunks of the
    prompt) read-only and prefill only the suffix. ``kind == "exact"``: the
    whole prompt is indexed — map ``pages`` plus ``boundary_page`` (the
    index-held copy of the partially-filled last page, when ``tail_len >
    0``) and skip prefill compute entirely; the first token is sampled from
    ``logits`` (the publisher's last-position prefill logits) under the new
    request's own seed, so sampling parity with a cold prefill holds
    token-for-token."""

    kind: str                        # "exact" | "shared"
    pages: list[int]                 # full-chunk pages, prompt order
    n_full: int                      # == len(pages)
    page_tokens: int = 0
    boundary_page: int | None = None  # exact only, tail_len > 0
    tail_len: int = 0                # prompt tokens inside the boundary page
    logits: np.ndarray | None = None  # (1, V) f32 — exact only

    @property
    def covered(self) -> int:
        """Prompt tokens whose K/V the mapped full pages already hold."""
        return self.n_full * self.page_tokens

    def mapped_pages(self) -> list[int]:
        out = list(self.pages)
        if self.kind == "exact" and self.boundary_page is not None:
            out.append(self.boundary_page)
        return out


class _RadixNode:
    """One full ``page_tokens``-token chunk of some indexed prompt. The
    node's page holds exactly that chunk's K/V; children extend the prefix
    by one more full chunk; ``tails`` terminate prompts mid-page."""

    __slots__ = ("page", "children", "tails", "last_used")

    def __init__(self, page: int = 0) -> None:
        self.page = page
        self.children: dict[bytes, _RadixNode] = {}
        self.tails: dict[bytes, _Tail] = {}
        self.last_used = 0


@dataclass
class _Tail:
    """Terminal entry for a prompt that ends mid-page (or page-aligned):
    the index-held pristine copy of the boundary page (``page`` — None when
    the prompt is page-aligned and there is nothing mid-page to hold) plus
    the publisher's last-position prefill logits, which is what lets an
    exact re-admission skip prefill compute entirely."""

    page: int | None
    logits: np.ndarray               # (1, V) f32
    tail_len: int
    last_used: int = 0
    nbytes: int = 0


class PagePrefixIndex:
    """Radix index token-prefix -> (arena page list, cached first-token
    logits) for ONE model's paged slot state (runtime/model_runtime.py
    SlotDecodeState.prefix_index). Single-threaded by construction: the
    model's continuous-scheduler thread owns the slot state's host mirrors
    and is the only caller, so there is no lock (same ownership rule as
    block_tables / free_pages).

    Refcount protocol: the index holds one reference per node/tail page it
    stores, mirrored into ``SlotDecodeState.page_refs`` by the CALLER
    (insert/evict return the page lists to incref/decref) — the index
    never touches the free-list itself, so the conservation invariant
    (every page free XOR trash XOR referenced) is enforceable in one
    place. Byte budget counts pinned pages (+ cached logits); eviction
    drops the coldest LEAF first, preferring pages with zero lane
    references (``page_refs == index refs``) so evicting actually frees
    arena memory, and ``reclaim`` lets admission pressure override the
    budget entirely rather than block a request behind cold cache pages."""

    def __init__(self, page_tokens: int, page_nbytes: int,
                 capacity_bytes: int) -> None:
        self.page_tokens = int(page_tokens)
        self.page_nbytes = int(page_nbytes)
        self.capacity_bytes = int(capacity_bytes)
        self._root = _RadixNode()
        self._held: dict[int, int] = {}   # page -> index refs (normally 1)
        self._clock = itertools.count(1)
        self._bytes = 0
        self.hits = 0
        self.exact_hits = 0
        self.misses = 0

    # -- read side -----------------------------------------------------------
    @property
    def total_bytes(self) -> int:
        return self._bytes

    def held_pages(self) -> dict[int, int]:
        """page -> index reference count (for conservation checks and the
        shared/cached page-split observability)."""
        return dict(self._held)

    def lookup(self, prompt: np.ndarray) -> SharedPrefixPlan | None:
        """Longest page-aligned indexed prefix of ``prompt`` — an exact
        terminal match (full skip) beats any partial one. Touches recency
        along the matched path and counts hit/miss."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        p, pt = prompt.shape[0], self.page_tokens
        stamp = next(self._clock)
        node, pages, i = self._root, [], 0
        while (i + 1) * pt <= p:
            child = node.children.get(prompt[i * pt:(i + 1) * pt].tobytes())
            if child is None:
                break
            child.last_used = stamp
            pages.append(child.page)
            node, i = child, i + 1
        tail = node.tails.get(prompt[i * pt:].tobytes())
        if tail is not None:
            tail.last_used = stamp
            self.hits += 1
            self.exact_hits += 1
            return SharedPrefixPlan(
                "exact", pages, i, page_tokens=pt, boundary_page=tail.page,
                tail_len=tail.tail_len, logits=tail.logits,
            )
        if i > 0 and i * pt >= p:
            # page-aligned prompt with no cached logits: at least one
            # suffix token must remain to prefill (the forward needs a
            # non-empty block — same strictness as PrefixCache._best_match)
            i -= 1
            pages.pop()
        if i == 0:
            self.misses += 1
            return None
        self.hits += 1
        return SharedPrefixPlan("shared", pages, i, page_tokens=pt)

    # -- write side ----------------------------------------------------------
    def insert(
        self,
        prompt: np.ndarray,
        full_pages: list[int],
        boundary_page: int | None,
        logits: np.ndarray | None,
        page_refs: np.ndarray,
    ) -> tuple[list[int], list[int]]:
        """Publish an admitted lane's prompt: ``full_pages`` are the lane's
        block-table entries covering the prompt's full page chunks (shared
        chunks dedup onto existing nodes — no double ref), ``boundary_page``
        is a PRISTINE COPY of the partially-filled last page (made by the
        caller before the lane's decode writes dirty the original).
        Returns ``(added, released)``: pages the index newly references
        (caller increfs) and pages budget eviction released (caller decrefs
        and recycles). A declined ``boundary_page`` is returned in neither
        list — the caller puts it back on the free-list."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        pt = self.page_tokens
        stamp = next(self._clock)
        added: list[int] = []
        node = self._root
        for i, pg in enumerate(full_pages):
            key = prompt[i * pt:(i + 1) * pt].tobytes()
            child = node.children.get(key)
            if child is None:
                child = _RadixNode(page=int(pg))
                node.children[key] = child
                self._held[child.page] = self._held.get(child.page, 0) + 1
                self._bytes += self.page_nbytes
                added.append(child.page)
            # an existing node keeps ITS page (it already holds this
            # chunk's K/V); the publisher's duplicate page stays private
            child.last_used = stamp
            node = child
        rem_key = prompt[len(full_pages) * pt:].tobytes()
        if logits is not None and rem_key not in node.tails:
            logits = np.asarray(logits, np.float32)
            tail_len = prompt.shape[0] - len(full_pages) * pt
            if boundary_page is not None or tail_len == 0:
                nbytes = int(logits.nbytes)
                if boundary_page is not None:
                    nbytes += self.page_nbytes
                    self._held[int(boundary_page)] = (
                        self._held.get(int(boundary_page), 0) + 1
                    )
                    added.append(int(boundary_page))
                node.tails[rem_key] = _Tail(
                    None if boundary_page is None else int(boundary_page),
                    logits, tail_len, stamp, nbytes,
                )
                self._bytes += nbytes
        released = self._evict(page_refs, self.capacity_bytes)
        return added, released

    def reclaim(self, page_refs: np.ndarray, want_pages: int,
                protect: frozenset = frozenset()) -> list[int]:
        """Admission pressure: release up to ``want_pages`` ZERO-LANE-REF
        pages regardless of the byte budget (dropping coldest leaves
        first), never touching ``protect`` (the pages the blocked
        request's own share plan maps). The cache must never win a page
        fight against a live admission."""
        return self._evict(
            page_refs, target_bytes=None, want_pages=want_pages,
            protect=protect, zero_ref_only=True,
        )

    def _leaf_candidates(self):
        """Yield every removable leaf: (node-or-tail marker, parent, key,
        last_used, pages). Rebuilt per eviction round — the index is
        budget-capped small, so clarity beats an intrusive heap."""
        stack = [(self._root, None, None)]
        while stack:
            node, parent, key = stack.pop()
            for k, t in node.tails.items():
                yield ("tail", node, k, t.last_used,
                       [] if t.page is None else [t.page])
            if (parent is not None and not node.children
                    and not node.tails):
                yield ("node", parent, key, node.last_used, [node.page])
            for k, child in node.children.items():
                stack.append((child, node, k))

    def _evict(self, page_refs, target_bytes, want_pages: int = 0,
               protect: frozenset = frozenset(),
               zero_ref_only: bool = False) -> list[int]:
        released: list[int] = []
        freed_pages = 0
        while True:
            if target_bytes is not None and self._bytes <= target_bytes \
                    and not want_pages:
                break
            if want_pages and freed_pages >= want_pages:
                break
            best = None
            for cand in self._leaf_candidates():
                kind, holder, key, last_used, pages = cand
                if any(pg in protect for pg in pages):
                    continue
                # zero lane refs: every reference on the page is the
                # index's own -> dropping it actually frees arena memory
                zero_ref = all(
                    int(page_refs[pg]) <= self._held.get(pg, 0)
                    for pg in pages
                )
                if zero_ref_only and not (zero_ref and pages):
                    continue
                rank = (0 if zero_ref else 1, last_used)
                if best is None or rank < best[0]:
                    best = (rank, cand)
            if best is None:
                break
            kind, holder, key, _, pages = best[1]
            if kind == "tail":
                tail = holder.tails.pop(key)
                self._bytes -= tail.nbytes
            else:
                holder.children.pop(key)
                self._bytes -= self.page_nbytes
            for pg in pages:
                n = self._held.get(pg, 0) - 1
                if n <= 0:
                    self._held.pop(pg, None)
                else:
                    self._held[pg] = n
                released.append(pg)
                freed_pages += 1
        return released
