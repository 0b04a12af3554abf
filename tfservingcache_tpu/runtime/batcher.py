"""Continuous (pipelined) micro-batching for the in-process runtime.

Reference-parity rationale: the reference delegates request batching to TF
Serving's ``--enable_batching`` (the sidecar never sees tensors); with
inference in-process, the batcher moves here. TPU-first motivation: one
batched MXU dispatch amortizes per-call host->device overhead — the dominant
warm-path cost for small models — and a power-of-two padded batch keeps the
jit cache small (runtime._pad_to_bucket already buckets the batch axis).

Continuous-batching design (no timed window): batches for one
(model, non-batch shape, filter) key are serialized on a per-key gate. The
first arrival becomes the leader of the next batch and acquires the gate;
while a previous batch occupies the device, later arrivals keep joining the
leader's pending batch, and the moment the gate frees the batch closes and
runs as ONE runtime.predict, outputs split back by each caller's row count.
The accumulation window is therefore exactly the device's own busy time:

  - strictly sequential traffic acquires an uncontended gate and runs
    immediately — no timed wait is ever inserted (the added latency is the
    gate bookkeeping itself, small but not literally zero);
  - saturating traffic coalesces into device-call-sized batches without any
    window-length tuning (the classic latency/throughput knob dissolves).

Whether coalescing wins over independent dispatch is an empirical, shape-
dependent question (config.py, ``batch_window_ms``: off by default).

``:generate`` does not come through the gate: ``ContinuousGenerateEngine``
below batches it at every decode-chunk boundary over the paged KV arena.

Calls are thread-blocking by design — they arrive on the protocol backend's
executor threads (protocol/local_backend.py), never on the event loop.

Models whose inputs have no named "batch" axis fall through unbatched.
"""

from __future__ import annotations

import collections
import secrets
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping

import numpy as np

from tfservingcache_tpu.runtime.base import (
    BaseRuntime,
    ModelNotLoadedError,
    RuntimeError_,
)
from tfservingcache_tpu.lab import faults as lab_faults
from tfservingcache_tpu.types import ModelId
from tfservingcache_tpu.utils.accounting import LEDGER
from tfservingcache_tpu.utils.flight_recorder import RECORDER
from tfservingcache_tpu.utils.lockcheck import lockchecked
from tfservingcache_tpu.utils.logging import get_logger
from tfservingcache_tpu.utils.tracing import TRACER, current_ids, host_span

log = get_logger("runtime.batcher")


# chunk and prefill-chunk sizes clamp to the runtime's compile buckets — its
# own bucketing function, not a copy that can drift
from tfservingcache_tpu.runtime.model_runtime import check_page_tokens
from tfservingcache_tpu.runtime.model_runtime import next_bucket as _next_bucket


@lockchecked
class _Gate:
    """A counted gate admitting up to ``limit`` concurrent holders.

    One mutex per key (round-2 design) serialized ALL device calls for a
    model: with the device/transport busy for RTT seconds, at most one batch
    was ever in flight, while the unbatched path pipelines ``clients``
    independent calls through the transport — the batcher *lost* throughput
    on any link whose round-trip dominates device time (the r2 31% and the
    r3 preview's 3x REST regression). A bounded semaphore keeps the
    accumulate-while-busy behavior (leaders still block once ``limit``
    batches are in flight, and arrivals join the blocked leader's batch)
    while letting ``limit`` batches overlap host codec + transfer + compute."""

    _tpusc_guarded = {"in_use": "_count"}

    def __init__(self, limit: int) -> None:
        self._sem = threading.BoundedSemaphore(limit)
        self._count = threading.Lock()
        self.in_use = 0

    def __enter__(self) -> "_Gate":
        self._sem.acquire()
        with self._count:
            self.in_use += 1
        return self

    def __exit__(self, *exc) -> None:
        with self._count:
            self.in_use -= 1
        self._sem.release()


@lockchecked
class _GateMap:
    """MicroBatcher's per-key device gates with bounded growth: bound how
    many batches per key are in flight so
    arrivals during a saturated device accumulate into the next batch.
    Pruning keeps only in-use gates; losing an idle gate only costs a
    coalescing opportunity (or briefly exceeds the in-flight bound), never
    correctness."""

    _tpusc_guarded = {"_gates": "_lock"}

    def __init__(self, max_entries: int = 4096, limit: int = 4) -> None:
        self._lock = threading.Lock()
        self._gates: dict[tuple, _Gate] = {}
        self._max = max_entries
        self._limit = max(1, limit)

    def get(self, key: tuple) -> _Gate:
        with self._lock:
            gate = self._gates.get(key)
            if gate is None:
                if len(self._gates) > self._max:
                    self._gates = {
                        k: g for k, g in self._gates.items() if g.in_use
                    }
                gate = self._gates.setdefault(key, _Gate(self._limit))
            return gate


@dataclass
class _Slot:
    inputs: Mapping[str, np.ndarray]
    rows: int
    done: threading.Event = field(default_factory=threading.Event)
    result: dict[str, np.ndarray] | None = None
    error: BaseException | None = None


@dataclass
class _Pending:
    slots: list[_Slot] = field(default_factory=list)
    rows: int = 0
    closed: bool = False                  # no further joiners


@lockchecked
class MicroBatcher:
    # Guarded-field registry (tools/tpusc_check TPUSC001 + TPUSC_LOCKCHECK=1).
    _tpusc_guarded = {
        "_pending": "_lock",
        "_axes_cache": "_lock",
        "_out_axes_cache": "_lock",
    }

    def __init__(
        self,
        runtime: BaseRuntime,
        max_batch: int = 64,
        wait_timeout_s: float = 600.0,
        metrics=None,
        max_inflight: int = 4,
    ) -> None:
        self.runtime = runtime
        self.max_batch = max_batch
        # generous: a follower may sit behind the leader's cold jit compile
        self.wait_timeout_s = wait_timeout_s
        self.metrics = metrics
        self._lock = threading.Lock()
        self._pending: dict[tuple, _Pending] = {}
        self._gates = _GateMap(limit=max_inflight)
        # signature() results are static per loaded model — cache the derived
        # axis maps so the hot path doesn't rebuild spec dicts per request
        self._axes_cache: dict[ModelId, dict[str, int] | None] = {}
        self._out_axes_cache: dict[ModelId, dict[str, int | None]] = {}
        # observability
        self.batches = 0
        self.batched_requests = 0

    # -- key/axis helpers ---------------------------------------------------
    def _batch_axes(self, model_id: ModelId) -> dict[str, int] | None:
        """Input name -> axis index of its named "batch" axis; None when any
        input OR output lacks one. An output with no batch axis is reduced
        OVER the batch (a scalar score, a pooled aggregate): coalescing would
        compute it across other callers' rows — wrong answers and a
        cross-request leak — so such models always run solo."""
        with self._lock:
            if model_id in self._axes_cache:
                return self._axes_cache[model_id]
        input_spec, output_spec, _ = self.runtime.signature(model_id)
        axes: dict[str, int] | None = {}
        for name, spec in input_spec.items():
            ax = [i for i, n in spec.dynamic_axes() if n == "batch"]
            if not ax:
                axes = None
                break
            axes[name] = ax[0]
        if axes is not None:
            for spec in output_spec.values():
                if not any(n == "batch" for _, n in spec.dynamic_axes()):
                    axes = None
                    break
        out_axes: dict[str, int | None] = {}
        for name, spec in output_spec.items():
            batch_axes = [a for a, n in spec.dynamic_axes() if n == "batch"]
            out_axes[name] = batch_axes[0] if batch_axes else None
        with self._lock:
            if len(self._axes_cache) > 4096:  # bound growth across tenants
                self._axes_cache.clear()
                self._out_axes_cache.clear()
            self._axes_cache[model_id] = axes
            self._out_axes_cache[model_id] = out_axes
        return axes

    def _key(
        self,
        model_id: ModelId,
        inputs: Mapping[str, np.ndarray],
        axes: Mapping[str, int],
        output_filter: list[str] | None,
    ) -> tuple | None:
        """Batchable only when every input's batch-axis row count agrees and
        all non-batch dims match across joiners (exact-shape coalescing)."""
        if set(inputs) != set(axes):
            return None  # wrong input set: let runtime.predict raise cleanly
        rows = None
        sig = []
        for name in sorted(inputs):
            arr = np.asarray(inputs[name])
            ax = axes.get(name)
            if ax is None or arr.ndim <= ax:
                return None
            if rows is None:
                rows = arr.shape[ax]
            elif arr.shape[ax] != rows:
                return None
            rest = tuple(d for i, d in enumerate(arr.shape) if i != ax)
            sig.append((name, str(arr.dtype), rest))
        return (model_id, tuple(sig), tuple(output_filter or ()))

    def _gate(self, key: tuple) -> _Gate:
        return self._gates.get(key)

    # -- core ---------------------------------------------------------------
    def predict(
        self,
        model_id: ModelId,
        inputs: Mapping[str, np.ndarray],
        output_filter: list[str] | None = None,
    ) -> dict[str, np.ndarray]:
        axes = self._batch_axes(model_id)
        key = self._key(model_id, inputs, axes, output_filter) if axes else None
        if key is None:
            return self.runtime.predict(model_id, inputs, output_filter)

        first = sorted(inputs)[0]
        rows = int(np.asarray(inputs[first]).shape[axes[first]])
        if rows >= self.max_batch:
            # already at/over the cap on its own: run solo, never join a batch
            return self.runtime.predict(model_id, inputs, output_filter)
        slot = _Slot(inputs=inputs, rows=rows)
        with self._lock:
            pend = self._pending.get(key)
            if pend is not None and pend.rows + rows > self.max_batch:
                # max_batch is a hard cap: the full batch keeps its leader,
                # this request starts (and leads) a fresh one
                pend.closed = True
                self._pending.pop(key, None)
                pend = None
            leader = pend is None
            if leader:
                pend = _Pending()
                self._pending[key] = pend
            pend.slots.append(slot)
            pend.rows += rows
            if pend.rows >= self.max_batch:
                pend.closed = True
                self._pending.pop(key, None)
        if self.metrics is not None:
            self.metrics.batcher_queue_depth.labels("predict").inc()

        if not leader:
            if not slot.done.wait(self.wait_timeout_s):
                raise TimeoutError(f"batched predict for {model_id} timed out")
            if slot.error is not None:
                raise slot.error
            assert slot.result is not None
            return slot.result

        # Leader: acquire the per-key gate. If a previous batch is on the
        # device this blocks, and every arrival in the meantime joins OUR
        # pend — the accumulation window IS the device's busy time. On an
        # idle gate we pass straight through: no timed wait, no added
        # latency for sequential traffic.
        with self._gate(key):
            with self._lock:
                if not pend.closed:
                    pend.closed = True
                    self._pending.pop(key, None)
            slots = pend.slots
            # the batch leaves the queue for the device the moment its leader
            # holds the gate — success or failure, these are no longer queued
            if self.metrics is not None:
                self.metrics.batcher_queue_depth.labels("predict").dec(len(slots))
            try:
                if len(slots) == 1:
                    out = self.runtime.predict(model_id, slot.inputs, output_filter)
                    slot.result = out
                    return out
                with TRACER.span(
                    "microbatch", model=str(model_id), requests=len(slots), rows=pend.rows
                ):
                    cat = {
                        name: np.concatenate(
                            [np.asarray(s.inputs[name]) for s in slots], axis=axes[name]
                        )
                        for name in slots[0].inputs
                    }
                    out = self.runtime.predict(model_id, cat, output_filter)
                    self.batches += 1
                    self.batched_requests += len(slots)
                    if self.metrics is not None:
                        self.metrics.coalesced_batches.labels("predict").inc()
                        self.metrics.coalesced_requests.labels("predict").inc(len(slots))
                    self._scatter(model_id, slots, out)
                assert slot.result is not None
                return slot.result
            except BaseException as e:
                for s in slots:
                    if s is not slot and s.result is None and s.error is None:
                        s.error = e
                        s.done.set()
                raise
            finally:
                for s in slots:
                    if s is not slot:
                        s.done.set()

    def _scatter(self, model_id: ModelId, slots: list[_Slot], out: dict[str, np.ndarray]) -> None:
        """Split batched outputs back per caller by row ranges.

        `_batch_axes` guarantees every output of a batchable model declares a
        batch axis, so a missing axis or a batch-dim length that disagrees
        with the total row count means the model's spec lies about its actual
        output shape. That MUST fail the whole batch: silently handing each
        caller the full concatenated array would leak other callers' rows."""
        with self._lock:
            out_axes = dict(self._out_axes_cache.get(model_id, {}))
        offsets = []
        start = 0
        for s in slots:
            offsets.append((start, start + s.rows))
            start += s.rows

        for name, arr in out.items():
            ax = out_axes.get(name)
            a = np.asarray(arr)
            if ax is None or a.ndim <= ax or a.shape[ax] != start:
                raise ValueError(
                    f"batched output {name!r} of {model_id} has shape {a.shape}, "
                    f"expected batch axis {ax} of length {start}; refusing to "
                    f"scatter (would leak rows across requests)"
                )

        for i, s in enumerate(slots):
            lo, hi = offsets[i]
            s.result = {
                name: np.take(arr, range(lo, hi), axis=out_axes[name])
                for name, arr in out.items()
            }


# priority classes for the continuous engine's SLO-aware admission
# (REST/gRPC `priority`, default normal): rank order is what admission and
# preemption compare — smaller rank wins pages
_PRIORITY_RANKS = {"high": 0, "normal": 1, "low": 2}


@dataclass
class _ContinuousReq:
    """One ROW of a continuous generate (multi-row requests split into
    per-row units so each row admits and retires independently)."""

    prompt: np.ndarray                    # (P,) true prompt tokens
    max_new: int
    temperature: float
    top_k: int
    enqueue_t: float = field(default_factory=time.monotonic)
    done: threading.Event = field(default_factory=threading.Event)
    tokens: list[int] = field(default_factory=list)
    error: BaseException | None = None
    admitted_t: float | None = None
    first_tok_t: float | None = None
    finish_t: float | None = None
    prefix_hit: bool = False
    prefill_s: float = 0.0                # slot_prefill wall time (phase clock)
    # crash-recovery budget consumed (scheduler-thread only): each engine
    # crash that requeues this row bumps it; past the engine's
    # max_recoveries the row fails instead — a prompt that deterministically
    # crashes the engine must not respawn scheduler threads forever
    recoveries: int = 0
    # conversation KV lifecycle (ISSUE 18): rows carrying a conversation id
    # park their decode state at retirement and resume from a parked
    # ancestor at admission (suffix-only prefill). None = park/resume off
    # for this row.
    conversation_id: str | None = None
    # tokens actually run through prefill across this row's life (every
    # admission, including crash-recovery replays) — the O(new tokens)
    # evidence surface: a resumed row's total stays ~suffix-sized where a
    # cold replay pays the whole history again
    prefill_tokens: int = 0
    # SLO-aware engine (ISSUE 19). priority class -> rank (high=0, normal=1,
    # low=2); admission picks min (rank, seq), so all-normal traffic
    # degenerates to today's exact FIFO (seq is engine-monotonic and
    # survives preemption/crash requeues).
    priority: str = "normal"
    rank: int = 1
    seq: int = 0
    # per-token stream callback (single-row requests only; exceptions are
    # swallowed once and the callback dropped — a broken client must not
    # kill the scheduler thread)
    on_token: Callable[[int], None] | None = None
    # times this row was preempted off a lane (bounded by
    # engine.preempt_limit so a page-starved class can't be parked forever)
    preemptions: int = 0
    # ParkedConversation from a preemption park — checked at re-admission
    # BEFORE the conversation tier, giving the O(new tokens) resume without
    # requiring the row to carry a conversation_id
    preempt_parked: Any = None
    # chunked-prefill carry (serving.prefill_chunk_tokens > 0): tokens of
    # pf_prompt written so far (None = not PREFILLING), the full prompt
    # being written (includes crash-recovered emitted tokens), and the
    # first-token seed drawn at admission
    pf_pos: int | None = None
    pf_prompt: np.ndarray | None = None
    pf_seed: int = 0


@dataclass
class _Flight:
    """A dispatched decode chunk the scheduler has not emitted yet, with what
    its boundary's ring entry says of it (worked out from the mirrors it was
    launched with). ``handle`` is the runtime's (``slot_decode_chunk_launch``)
    for a chunk still to fetch; ``toks`` is set instead where the dispatch
    came back fetched (a speculation round, whose per-row ``accept`` counts
    ride along, or a runtime with no split)."""

    chunk: int
    reqs: list                      # the lanes' rows at the launch
    handle: Any = None
    toks: Any = None
    accept: Any = None
    ahead: int = 0                  # 1: launched before the chunk before it was fetched
    path: str | None = None
    write_lanes: int = 0
    window_pages: float = 0.0
    shared_pages: float = 0.0
    state_lanes: int = 0
    launch_s: float = 0.0
    uploads: int = 0
    drafted: int = 0


@lockchecked
class _ContinuousScheduler:
    """One model's decode loop: a dedicated thread that admits pending rows
    into free slot lanes at chunk boundaries, dispatches the compiled
    decode-chunk program over the slot array, and retires rows the moment
    they hit EOS or their own max_new_tokens — freeing the lane for the
    next pending row instead of waiting for a batch drain."""

    _tpusc_guarded = {"pending": "cv", "stopped": "cv"}

    def __init__(self, engine: "ContinuousGenerateEngine", model_id: ModelId) -> None:
        self.engine = engine
        self.model_id = model_id
        self.cv = threading.Condition()
        self.pending: collections.deque[_ContinuousReq] = collections.deque()
        self.stopped = False
        # speculative decoding (ISSUE 16): set when the configured draft
        # pair turned out structurally incompatible (family/vocab) —
        # permanent for this scheduler, so the warning logs once and every
        # later boundary decodes plain without re-raising. Scheduler-thread
        # only, like `lanes`/`state`.
        self._spec_broken = False
        # the chunk launched AHEAD of the last boundary's fetch, which the
        # next boundary fetches (``_chain``); scheduler-thread only
        self._flight: _Flight | None = None
        self.thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"tpusc-cdecode-{model_id.name}",
        )
        self.thread.start()

    def submit(self, reqs: list[_ContinuousReq]) -> None:
        with self.cv:
            if self.stopped:
                raise RuntimeError_("continuous generate engine is closed")
            self.pending.extend(reqs)
            if self.engine.metrics is not None:
                self.engine.metrics.batcher_queue_depth.labels("generate").inc(
                    len(reqs)
                )
            self.cv.notify()

    def _fail(self, reqs: list[_ContinuousReq], err: BaseException) -> None:
        for r in reqs:
            if r.error is None and not r.done.is_set():
                r.error = err
                r.done.set()

    def _triage(
        self,
        inflight: list[_ContinuousReq],
        queued: list[_ContinuousReq],
        err: BaseException,
    ) -> list[_ContinuousReq]:
        """Crash triage: split casualties into survivors (requeued into the
        replacement scheduler — interrupted rows first, so FIFO order is
        preserved across the respawn) and doomed rows (recovery off, or past
        the per-row recovery budget). Each survivor counts once in
        ``tpusc_requests_recovered_total`` — reason ``mid_decode`` for rows
        whose partial decode is re-prefilled, ``queued`` for rows that only
        change queues."""
        eng = self.engine
        if queued and eng.metrics is not None:
            # the drained rows' queue-depth contribution: survivors re-count
            # at re-submit, so without this the gauge double-counts them
            # (and doomed rows would leak it forever)
            eng.metrics.batcher_queue_depth.labels("generate").dec(len(queued))
        if not eng.recovery:
            self._fail(inflight + queued, err)
            return []
        survivors: list[_ContinuousReq] = []
        doomed: list[_ContinuousReq] = []
        for reason, rows in (("mid_decode", inflight), ("queued", queued)):
            for r in rows:
                if r.done.is_set():
                    continue
                r.recoveries += 1
                if r.recoveries > eng.max_recoveries:
                    doomed.append(r)
                    continue
                # a row caught mid chunked-prefill restarts from chunk 0 on
                # the fresh scheduler (the crashed state's pages are gone);
                # stale carry would make re-admission treat it as PREFILLING
                r.pf_pos = None
                r.pf_prompt = None
                survivors.append(r)
                if eng.metrics is not None:
                    eng.metrics.requests_recovered.labels(reason).inc()
        if doomed:
            self._fail(doomed, err)
        return survivors

    def _resolve_draft_id(self, rt, name: str) -> ModelId | None:
        """Map the spec_draft_model knob ("name" or "name@version") to a
        RESIDENT ModelId, newest version first for a bare name. None when
        nothing resident matches — the scheduler just retries next boundary
        (the backend ensure-loads the draft on the generate path, so the
        first boundary after that load attaches)."""
        if "@" in name:
            base, _, ver = name.rpartition("@")
            try:
                want = ModelId(base, int(ver))
            except ValueError:
                return None
            return want if rt.is_loaded(want) else None
        best = None
        for mid in rt.resident_models():
            if mid.name == name and (best is None or mid.version > best.version):
                best = mid
        return best

    def _spec_setup(self, rt, state, lanes) -> None:
        """Attach (or detach) the configured draft model on this scheduler's
        slot state. Attach only happens with every lane idle: rows admitted
        while the draft is attached reserve + prefill BOTH arenas, so a
        mid-flight attach would leave live lanes with no draft pages and the
        draft-side page census would see active lanes mapping trash."""
        eng = self.engine
        st_draft = getattr(state, "spec_draft", None)
        if st_draft is not None:
            # keep the pair only while the draft stays resident; on
            # eviction detach and fall back to plain chunks (re-attach
            # happens at the next all-idle boundary if it reloads)
            if not rt.is_loaded(state.spec_draft_id):
                state.spec_draft = None
                state.spec_draft_id = None
                state.spec_tokens = 0
            return
        if self._spec_broken or state is None:
            return
        if not hasattr(rt, "slot_attach_draft"):
            return
        name = eng.spec_draft_model
        if name is None:
            name = str(
                getattr(getattr(rt, "cfg", None), "spec_draft_model", "") or ""
            )
        if not name:
            return
        if any(l is not None for l in lanes):
            return
        draft_id = self._resolve_draft_id(rt, name)
        if draft_id is None or draft_id == self.model_id:
            return
        spec = eng.spec_tokens
        if spec is None:
            spec = int(getattr(getattr(rt, "cfg", None), "spec_tokens", 4) or 4)
        try:
            rt.slot_attach_draft(state, draft_id, spec)
            log.info(
                "continuous spec attach model=%s draft=%s spec_tokens=%d",
                self.model_id, draft_id, state.spec_tokens,
            )
        except ModelNotLoadedError:
            # evicted between resolve and attach: transient, retry later
            pass
        except RuntimeError_ as e:
            self._spec_broken = True
            log.warning(
                "continuous spec disabled model=%s draft=%s: %s",
                self.model_id, draft_id, e,
            )

    def _loop(self) -> None:
        rt = self.engine.runtime
        lanes: list[_ContinuousReq | None] = [None] * self.engine.slots
        state = None
        while True:
            with self.cv:
                while (
                    not self.pending
                    and not any(l is not None for l in lanes)
                    and self._flight is None
                    and not self.stopped
                ):
                    self.cv.wait()
                if self.stopped:
                    doomed = [l for l in lanes if l is not None]
                    doomed += list(self.pending)
                    self.pending.clear()
                    break
            try:
                with host_span("boundary"):
                    state = self._step(rt, state, lanes)
            except BaseException as e:  # noqa: BLE001 - triage the in-flight rows
                # eviction mid-decode (ModelNotLoadedError) or a device
                # failure: the slot state may hold poisoned K/V, so it is
                # always dropped. With recovery on (the default), in-flight
                # and queued rows move to a FRESH scheduler thread where
                # admission re-prefills prompt + tokens-emitted-so-far —
                # the prefix cache makes the replay cheap and greedy streams
                # stay token-identical. Rows past their recovery budget, and
                # every row when recovery is off, get the error as before.
                with self.cv:
                    inflight = [l for l in lanes if l is not None]
                    queued = list(self.pending)
                    self.pending.clear()
                lanes = [None] * self.engine.slots
                # a chunk in flight dies with the state: its tokens were
                # never emitted, recovery re-prefills prompt + emitted
                self._flight = None
                survivors = self._triage(inflight, queued, e)
                RECORDER.dump(
                    "engine_crash", model=str(self.model_id),
                    error=repr(e),
                    failed_rows=len(inflight) + len(queued) - len(survivors),
                    recovered_rows=len(survivors),
                )
                try:
                    rt.drop_slot_state(self.model_id)
                except Exception:  # noqa: BLE001 - best-effort cleanup
                    pass
                state = None
                self.engine._set_active(self.model_id, 0)
                self.engine._set_pages(self.model_id, 0, 0)
                if survivors:
                    if self.engine._respawn(self, survivors) is not None:
                        # the replacement scheduler owns the model (and the
                        # survivors) from here; this thread is done
                        return
                    # engine closing mid-crash: nowhere to requeue
                    self._fail(survivors, e)
        if self._flight is not None:
            # a launched chunk is fetched or its state dropped: the state
            # outlives this engine and its mirrors must not trail the device
            try:
                rt.slot_decode_chunk_fetch(state, self._flight.handle)
            except Exception:  # noqa: BLE001 - best-effort at close
                log.debug("fetch of the chunk in flight failed at close",
                          exc_info=True)
            self._flight = None
        self._fail(doomed, RuntimeError_("continuous generate engine closed"))
        self.engine._set_active(self.model_id, 0)
        self.engine._set_pages(self.model_id, 0, 0)

    def _step(self, rt, state, lanes):
        """One chunk boundary: admit into free lanes, then advance all
        active lanes by one compiled chunk. Called only from self.thread."""
        eng = self.engine
        # scenario-lab hook (lab/faults.py): kill_engine raises here — the
        # same path an organic device failure takes through _loop's triage —
        # and freeze_scheduler sleeps this thread, aging the queue. Disarmed
        # (every production default) this is one bool read.
        lab_faults.fire("engine_step", model=str(self.model_id))
        step_t0 = time.monotonic()
        eos = getattr(rt, "eos_id_of", lambda _m: None)(self.model_id)
        # a chunk the last boundary launched ahead is this boundary's: no
        # admission while it is in flight (the mirrors trail the device until
        # its fetch); ``_chain`` launched it because none was possible and
        # launches no further one once a queued row can be admitted, so the
        # boundary after this one admits it
        flight = self._flight
        free = [] if flight is not None else [
            i for i, l in enumerate(lanes) if l is None]
        if state is not None and flight is None:
            # draft attach/detach happens at the boundary, before admission,
            # so every row admitted below sees the final spec configuration
            # (page budgets include draft headroom iff the draft is on)
            self._spec_setup(rt, state, lanes)
        admitted_any = False
        admitted_n = 0
        retired_n = 0
        prefix_hits_n = 0
        prefill_s_sum = 0.0
        tokens_in_n = 0
        with host_span("admit"):
            while free:
                with self.cv:
                    if not self.pending:
                        break
                    # admission orders by (priority rank, submit seq): strict
                    # class precedence, FIFO inside a class. With every queued
                    # row the same class this is min-seq = the leftmost row —
                    # exactly the old popleft, so priority-free traffic keeps
                    # its byte-identical admission order. O(n) scan; the queue
                    # is bounded by client concurrency.
                    best = 0
                    for qi in range(1, len(self.pending)):
                        r = self.pending[qi]
                        b = self.pending[best]
                        if (r.rank, r.seq) < (b.rank, b.seq):
                            best = qi
                    req = self.pending[best]
                    del self.pending[best]
                    if eng.metrics is not None:
                        eng.metrics.batcher_queue_depth.labels("generate").dec()
                reserved_idx = None
                d_st = None
                d_pk = d_pv = None
                try:
                    if state is None:
                        if eng.page_tokens is None and \
                                eng.share_prefix_bytes is None and \
                                eng.arena_dtype is None and \
                                eng.paged_kernel is None:
                            # no engine-level override: the runtime's ServingConfig
                            # decides (and stub runtimes keep their 2-arg surface)
                            state = rt.slot_decode_state(self.model_id, eng.slots)
                        else:
                            kw = {}
                            if eng.page_tokens is not None:
                                kw["page_tokens"] = eng.page_tokens
                                kw["arena_pages"] = eng.arena_pages
                            if eng.share_prefix_bytes is not None:
                                kw["share_prefix_bytes"] = eng.share_prefix_bytes
                            if eng.arena_dtype is not None:
                                kw["arena_dtype"] = eng.arena_dtype
                            if eng.paged_kernel is not None:
                                kw["paged_kernel"] = eng.paged_kernel
                            state = rt.slot_decode_state(
                                self.model_id, eng.slots, **kw
                            )
                        # fresh state: every lane is idle, so the draft (if
                        # configured and resident) can attach right away
                        self._spec_setup(rt, state, lanes)
                    d_st = getattr(state, "spec_draft", None)
                    prompt = req.prompt
                    remaining = req.max_new - len(req.tokens)
                    if req.tokens:
                        # crash-recovered row (tokens were emitted before the
                        # old scheduler died): re-prefill prompt + emitted
                        # tokens, so the next sampled token continues the stream
                        # exactly where it broke — greedy output is identical to
                        # an uninterrupted decode, and a shared-prefix hit on
                        # the original prompt makes the replay cheap
                        prompt = np.concatenate(
                            [prompt, np.asarray(req.tokens, np.int32)]
                        )
                    p = prompt.shape[0]
                    if p + remaining > state.max_seq:
                        req.error = RuntimeError_(
                            f"prompt {p} + max_new_tokens {remaining} exceeds "
                            f"max_seq {state.max_seq}"
                        )
                        req.done.set()
                        continue
                    plan = None
                    kind = None
                    resume = None   # (parked, covered, n_pages) when resuming
                    share = getattr(state, "prefix_index", None) is not None
                    # admission is gated on free PAGES, not just free lanes:
                    # the row's whole prompt + max_new budget is reserved up
                    # front so a mid-decode row can never starve for a page.
                    # With a draft attached the budget grows by spec_tokens
                    # of headroom — a verify round started one token short
                    # of max_new still writes K/V rows at pos..pos+spec, and
                    # those writes must land on pages this row owns (never
                    # shared/trash), so the overshoot is reserved up front
                    # and handed back through release_pages at retirement.
                    headroom = state.spec_tokens if d_st is not None else 0
                    budget = min(p + remaining + headroom,
                                 state.pages_per_slot * state.page_tokens)
                    need = state.pages_needed(budget)
                    if need > state.arena_pages:
                        req.error = RuntimeError_(
                            f"request needs {need} KV pages "
                            f"({budget} tokens) but the arena has only "
                            f"{state.arena_pages}"
                        )
                        req.done.set()
                        continue
                    idx = free[-1]  # the lane free.pop() will hand out below
                    shared_pages = ()
                    cow_headroom = 0
                    if req.preempt_parked is not None and \
                            hasattr(rt, "plan_conversation_resume"):
                        # preempted row coming back: its own parked pages
                        # beat both the conversation tier and the radix
                        # index — they cover prompt + every emitted token,
                        # so the resume prefill is O(1) (the single row the
                        # park could not cover)
                        rplan = rt.plan_conversation_resume(
                            state, prompt, req.preempt_parked
                        )
                        if rplan is not None:
                            resume = (req.preempt_parked, rplan[0], rplan[1])
                    if resume is None and req.conversation_id and \
                            eng.conversation_tier is not None and \
                            hasattr(rt, "plan_conversation_resume"):
                        # resume beats cold prefill AND the shared-prefix
                        # plan: parked pages cover the whole history (prompt
                        # + prior turns' emitted tokens), where the radix
                        # index at best covers what is still arena-resident.
                        # The lookup PEEKS, so a lane that crashes mid-decode
                        # can resume again from the same ancestor.
                        parked, _outcome = eng.conversation_tier.get(
                            req.conversation_id, str(self.model_id)
                        )
                        if parked is not None:
                            rplan = rt.plan_conversation_resume(
                                state, prompt, parked
                            )
                            if rplan is not None:
                                resume = (parked, rplan[0], rplan[1])
                    if share and resume is None:
                        plan = rt.shared_prefix_plan(state, prompt)
                        if plan is not None:
                            # map the indexed prefix read-only; reserve only
                            # the private remainder. An exact hit with a
                            # mid-page tail also needs one CoW page in hand
                            # — its first decode write lands in the shared
                            # boundary page.
                            shared_pages = plan.mapped_pages()
                            if plan.kind == "exact" and plan.tail_len > 0:
                                cow_headroom = 1
                    ok = state.reserve_pages(
                        idx, budget, shared_pages, cow_headroom
                    )
                    if not ok and share:
                        # page pressure: cold index-only prefix pages must
                        # lose the fight to a live admission (protecting the
                        # plan's own mapped pages), else sharing would turn
                        # the blocks-never-fails queue into a deadlock
                        want = (max(0, need - len(shared_pages)) + cow_headroom
                                - len(state.free_pages))
                        if want > 0 and rt.reclaim_prefix_pages(
                            state, want, shared_pages
                        ):
                            ok = state.reserve_pages(
                                idx, budget, shared_pages, cow_headroom
                            )
                    if ok and d_st is not None:
                        # the draft arena mirrors the reservation (its rows
                        # for pos..pos+spec are written every round). No
                        # shared pages: the draft state has no prefix index,
                        # every draft page is private by construction. The
                        # cap keeps a shorter draft max_seq from deadlocking
                        # (the auto-sized draft arena always covers slots x
                        # pages_per_slot, so a capped reservation succeeds
                        # whenever the lane itself is free).
                        d_budget = min(
                            budget, d_st.pages_per_slot * d_st.page_tokens
                        )
                        if not d_st.reserve_pages(idx, d_budget):
                            state.release_pages(idx)
                            ok = False
                    if not ok and hasattr(rt, "park_lane"):
                        # priority preemption (ISSUE 19): a higher-class
                        # arrival that still can't reserve parks the
                        # lowest-class decoding lane's KV (pages are COPIES
                        # through the PR 18 codec, so the conservation
                        # census stays exact), requeues it for an
                        # O(new tokens) parked-KV resume, and retries the
                        # reservation. One victim may not free enough —
                        # keep hunting until the reserve succeeds or no
                        # preemptible lane remains.
                        while not ok:
                            vidx = self._pick_victim(lanes, req)
                            if vidx is None or not self._preempt(
                                rt, state, lanes, vidx
                            ):
                                break
                            # the victim's lane frees too — at the FRONT of
                            # the free list, so free[-1] (the lane reserved
                            # as `idx` above) is untouched
                            free.insert(0, vidx)
                            ok = state.reserve_pages(
                                idx, budget, shared_pages, cow_headroom
                            )
                            if ok and d_st is not None:
                                d_budget = min(
                                    budget,
                                    d_st.pages_per_slot * d_st.page_tokens,
                                )
                                if not d_st.reserve_pages(idx, d_budget):
                                    state.release_pages(idx)
                                    ok = False
                                    break
                    if not ok:
                        # arena exhausted: the queue BLOCKS, never fails —
                        # the row goes back to the FRONT (FIFO preserved)
                        # and retirements below recycle pages for the next
                        # chunk boundary's retry. Can't deadlock: with no
                        # active lanes every page is free or reclaimable
                        # from the prefix index, and need <= arena_pages
                        # was checked above.
                        with self.cv:
                            self.pending.appendleft(req)
                            if eng.metrics is not None:
                                eng.metrics.batcher_queue_depth.labels(
                                    "generate"
                                ).inc()
                        RECORDER.dump(
                            "page_exhaustion", model=str(self.model_id),
                            needed_pages=need, free_pages=len(state.free_pages),
                            arena_pages=state.arena_pages,
                        )
                        break
                    reserved_idx = idx
                    pf0 = time.monotonic()
                    seed = secrets.randbits(31)
                    if (
                        eng.prefill_chunk_tokens > 0
                        and resume is None and plan is None and d_st is None
                        and p > eng.prefill_chunk_tokens
                        and hasattr(rt, "slot_prefill_chunk")
                    ):
                        # chunked-prefill interleaving (ISSUE 19): pages are
                        # reserved but NOTHING is written yet — the lane enters
                        # its PREFILLING state and _prefill_phase advances it
                        # one fixed-size chunk per boundary while other lanes
                        # keep decoding between chunks. pos holds the past-
                        # reservation sentinel so the decode chunk's frozen
                        # rewrite of this inactive lane hits the trash-page
                        # redirect, never the reserved rows the chunks fill.
                        # Resume/shared hits and spec-draft engines keep the
                        # single-dispatch path (their prefill is already the
                        # short suffix, or the draft arena must mirror it).
                        idx = free.pop()
                        req.pf_prompt = prompt
                        req.pf_pos = 0
                        req.pf_seed = seed
                        now = time.monotonic()
                        req.admitted_t = now
                        state.active[idx] = False
                        state.pos[idx] = state.pages_per_slot * state.page_tokens
                        state.temps[idx] = req.temperature
                        state.topks[idx] = req.top_k
                        lanes[idx] = req
                        eng.admitted += 1
                        admitted_any = True
                        admitted_n += 1
                        if eng.metrics is not None:
                            eng.metrics.gen_admission_wait.labels(
                                "continuous"
                            ).observe(max(0.0, now - req.enqueue_t))
                        continue
                    with host_span("prefill"):
                        if resume is not None:
                            # O(new tokens) turn resume: parked pages re-import into
                            # the lane's private reservation, only the suffix past
                            # the common history prefix runs through prefill
                            tok, pk, pv, last = rt.slot_resume_prefill(
                                self.model_id, state, reserved_idx, prompt,
                                resume[0], resume[1], resume[2],
                                req.temperature, req.top_k, seed,
                            )
                            kind = "resume"
                            hit = True
                            req.preempt_parked = None
                        elif share:
                            tok, pk, pv, kind, last = rt.slot_prefill_shared(
                                self.model_id, state, prompt, req.temperature,
                                req.top_k, seed, plan,
                            )
                            hit = kind != "miss"
                        else:
                            tok, pk, pv, hit = rt.slot_prefill(
                                self.model_id, prompt, req.temperature,
                                req.top_k, seed=seed,
                            )
                            last = None
                        if d_st is not None:
                            # greedy draft prefill (temperature 0, sampled token
                            # ignored — only the draft's K/V rows matter). Runs even
                            # on an exact target prefix hit: the draft arena has no
                            # prefix index to skip into.
                            _, d_pk, d_pv, _ = rt.slot_prefill(
                                state.spec_draft_id, prompt, 0.0, 0, seed=seed,
                            )
                except BaseException as e:  # noqa: BLE001
                    # the req is already out of `pending` and not yet in `lanes`
                    # — without this the _loop doom sweep would miss it and its
                    # waiter would block until timeout
                    if reserved_idx is not None:
                        state.release_pages(reserved_idx)
                        if d_st is not None:
                            d_st.release_pages(reserved_idx)
                    self._fail([req], e)
                    raise
                now = time.monotonic()
                req.prefill_s = now - pf0
                req.admitted_t = now
                if req.first_tok_t is None:
                    # a recovered row keeps its ORIGINAL first-token stamp —
                    # TTFT is a client-experienced clock, and the client saw
                    # its first token before the crash
                    req.first_tok_t = now
                req.prefix_hit = hit
                self._emit(req, int(tok))
                if kind == "exact":
                    pass  # zero prefill compute
                elif kind == "resume":
                    req.prefill_tokens += p - resume[1]
                elif kind == "shared":
                    req.prefill_tokens += p - plan.covered
                else:
                    req.prefill_tokens += p
                eng.admitted += 1
                admitted_any = True
                admitted_n += 1
                prefill_s_sum += req.prefill_s
                tokens_in_n += p
                if hit:
                    prefix_hits_n += 1
                    if eng.metrics is not None:
                        # exact = radix full-skip (zero prefill compute);
                        # resume = parked-conversation re-import (suffix-only
                        # prefill over re-imported pages); shared = radix
                        # partial hit AND legacy dense-cache reuse (both paid
                        # only a suffix prefill)
                        eng.metrics.gen_prefix_hits.labels(
                            "continuous",
                            kind if kind in ("exact", "resume") else "shared",
                        ).inc()
                if eng.metrics is not None:
                    eng.metrics.gen_admission_wait.labels("continuous").observe(
                        max(0.0, now - req.enqueue_t)
                    )
                if (eos is not None and int(tok) == eos) or remaining <= 1:
                    # done at prefill: the lane was never consumed
                    self._retire_pages(state, reserved_idx, req)
                    req.finish_t = now
                    req.done.set()
                    retired_n += 1
                    continue
                idx = free.pop()
                if pk is None:
                    # exact shared-prefix hit: the prompt's K/V already lives in
                    # the mapped pages — nothing to insert. Its first decode
                    # write (pos = p) lands mid-way into the SHARED boundary
                    # page, so that one page is CoW'd now, while the headroom
                    # page reserved for it is guaranteed free (same scheduler
                    # turn, nothing ran in between).
                    if plan is not None and plan.tail_len > 0:
                        rt.slot_cow(state, idx, plan.n_full)
                elif kind == "resume":
                    # suffix-only insert over the re-imported pages: rows below
                    # the resume boundary already hold the parked bytes (the
                    # lane owns them privately — no trash redirect needed for
                    # correctness, but the suffix prefill only produced junk
                    # there, same as the shared case)
                    rt.slot_admit(state, idx, pk, pv, base_tokens=resume[1])
                elif plan is not None and kind == "shared":
                    # suffix-only insert: rows below the shared boundary stay in
                    # the read-only mapped pages, the jit redirects them to trash
                    rt.slot_admit(state, idx, pk, pv, base_tokens=plan.covered)
                else:
                    rt.slot_admit(state, idx, pk, pv)
                if share and pk is not None:
                    # publish this lane's prompt pages so later same-prefix
                    # admissions share them (exact hits are already indexed)
                    rt.shared_prefix_publish(state, idx, prompt, last)
                if d_pk is not None:
                    # the draft lane rides the same index: its prompt K/V lands
                    # on the pages reserved above, all private
                    rt.slot_admit(d_st, idx, d_pk, d_pv)
                state.tok[idx] = int(tok)
                state.pos[idx] = p
                state.active[idx] = True
                state.temps[idx] = req.temperature
                state.topks[idx] = req.top_k
                lanes[idx] = req
        if admitted_any:
            eng._set_active(
                self.model_id, sum(l is not None for l in lanes)
            )
        pf_chunks = 0
        if eng.prefill_chunk_tokens > 0 and state is not None and flight is None:
            # chunked-prefill interleave: every PREFILLING lane advances
            # exactly ONE chunk per boundary, so a long prompt's prefill is
            # spread across boundaries instead of monopolizing one dispatch
            with host_span("prefill"):
                pf_chunks, pf_toks, pf_s, pf_retired = self._prefill_phase(
                    rt, state, lanes, eos
                )
            retired_n += pf_retired
            prefill_s_sum += pf_s
            tokens_in_n += pf_toks
            if pf_retired:
                eng._set_active(
                    self.model_id, sum(l is not None for l in lanes)
                )
        self._update_page_gauge(state)
        if flight is None and not any(
            l is not None and l.pf_pos is None for l in lanes
        ):
            if admitted_n or retired_n or pf_chunks:
                # prefill-only boundary (every admitted row finished at its
                # first token, or every occupied lane is still PREFILLING):
                # still a ring entry, with no chunk dispatched
                self._record_step(
                    state, 0, 0, admitted_n, retired_n, 0, step_t0,
                    prefix_hits_n, prefill_s_sum, tokens_in_n,
                )
            return state
        chunk_t0 = time.monotonic()
        # host time of the launches this boundary made, what they had to send
        launch_s, uploads = 0.0, 0
        with host_span("decode_chunk"):
            if flight is None:
                flight = self._dispatch(rt, state, lanes)
                launch_s, uploads = flight.launch_s, flight.uploads
            # the next chunk goes up BEFORE this one is fetched where the next
            # boundary is foreseen decode-only: the device then runs on while
            # the host fetches, emits and comes round
            nxt = self._chain(rt, state, lanes, flight)
            if nxt is not None:
                # held from here on, before the emission can wake a client:
                # ``_flight`` is None only when every launched chunk has its
                # ring entry
                self._flight = nxt
                launch_s += nxt.launch_s
                uploads += nxt.uploads
            toks = flight.toks
            if toks is None:
                toks = rt.slot_decode_chunk_fetch(state, flight.handle)
        chunk, accept = flight.chunk, flight.accept
        eng.chunks += 1
        now = time.monotonic()
        wasted = 0
        active_rows = 0
        accepted = int(accept.sum()) if accept is not None else 0
        with host_span("emit"):
            for idx, req in enumerate(flight.reqs):
                if req is None or req.pf_pos is not None:
                    continue
                active_rows += 1
                # spec rounds emit a VARIABLE per-row prefix (the accepted
                # draft run + the verify's correction token); plain chunks
                # emit exactly `chunk` tokens per live lane
                n_emit = chunk if accept is None else int(accept[idx])
                if lanes[idx] is not req:
                    # the row met EOS in the chunk before, found at that
                    # chunk's fetch with this one already launched: the
                    # device computed these steps for a finished request
                    wasted += n_emit
                    continue
                for j in range(n_emit):
                    t = int(toks[idx, j])
                    self._emit(req, t)
                    if (eos is not None and t == eos) or len(req.tokens) >= req.max_new:
                        # retire NOW: steps the chunk computed past this point
                        # were for a finished request — the waste continuous
                        # batching exists to bound (< chunk). Under spec this
                        # also drops accepted tokens past a mid-round EOS.
                        wasted += n_emit - (j + 1)
                        state.active[idx] = False
                        lanes[idx] = None
                        self._retire_pages(state, idx, req)
                        req.finish_t = now
                        req.done.set()
                        retired_n += 1
                        break
        emit_s = time.monotonic() - now
        if eng.metrics is not None:
            eng.metrics.gen_sample_steps.labels(flight.path).inc(chunk)
            eng.metrics.gen_kv_write_steps.labels(
                str(flight.write_lanes)).inc(chunk)
            eng.metrics.gen_chunks.labels(
                "ahead" if flight.ahead else "boundary").inc()
            if wasted:
                eng.metrics.gen_wasted_steps.labels("continuous").inc(wasted)
        if accept is not None and hasattr(rt, "_spec_observe"):
            # acceptance health + cumulative counters: one verify round per
            # active lane this boundary
            rt._spec_observe(
                self.model_id, state.spec_draft_id, accepted, active_rows,
                engine="continuous",
            )
        eng._set_active(self.model_id, sum(l is not None for l in lanes))
        self._update_page_gauge(state)
        self._record_step(
            state, chunk, active_rows, admitted_n, retired_n, wasted, step_t0,
            prefix_hits_n, prefill_s_sum, tokens_in_n,
            drafted=flight.drafted, accepted=accepted,
            emitted=accepted if accept is not None else None,
            chunk_s=now - chunk_t0, emit_s=emit_s,
            write_lanes=flight.write_lanes,
            launch_s=launch_s, uploads=uploads,
            window_pages=flight.window_pages, ahead=flight.ahead,
            shared_pages=flight.shared_pages,
            state_lanes=flight.state_lanes,
        )
        self._flight = nxt
        return state

    def _dispatch(self, rt, state, lanes) -> _Flight:
        """A boundary's own dispatch, after its admissions: a speculation
        round where the draft is attached and healthy (it comes back
        fetched), else one plain chunk, launched."""
        eng = self.engine
        # chunk clamped to the pow2 cover of the largest remaining budget:
        # when every active row needs < chunk_tokens more, a smaller
        # compiled chunk (log2-bounded program count) trims the overshoot.
        # PREFILLING lanes are excluded everywhere below — the decode jit
        # freezes them (active=False) and their emit rows are junk.
        max_remaining = max(
            l.max_new - len(l.tokens)
            for l in lanes if l is not None and l.pf_pos is None
        )
        chunk = max(1, min(eng.chunk_tokens, _next_bucket(max_remaining)))
        d_st = getattr(state, "spec_draft", None)
        use_spec = (
            d_st is not None
            and rt.is_loaded(state.spec_draft_id)
            and getattr(rt, "_spec_admit", lambda *_a: False)(
                self.model_id, state.spec_draft_id
            )
            # a round with zero greedy lanes is pure draft overhead (every
            # sampled row forces accept=1), so it falls back to plain decode
            and any(
                l is not None and float(state.temps[i]) <= 0.0
                for i, l in enumerate(lanes)
            )
        )
        spec_span = state.spec_tokens if use_spec else 0
        if getattr(state, "page_refs", None) is not None:
            # copy-on-write safety net: no lane may write into a page it
            # doesn't solely own. Admission already CoW'd the only shareable
            # write target (the exact-hit boundary page) and a chunk only
            # advances into the lane's own private reservation, so this
            # never fires in the designed protocol — it is the refcount
            # invariant's last line of defense, not a fast path. A spec
            # round writes K/V rows at pos..pos+spec in one dispatch, so
            # the net covers every page that span touches, not just pos's.
            for cidx, creq in enumerate(lanes):
                if creq is None:
                    continue
                first = int(state.pos[cidx]) // state.page_tokens
                last = min(
                    (int(state.pos[cidx]) + spec_span) // state.page_tokens,
                    state.pages_per_slot - 1,
                )
                for slot in range(first, last + 1):
                    pg = int(state.block_tables[cidx, slot])
                    if pg and int(state.page_refs[pg]) > 1:
                        rt.slot_cow(state, cidx, slot)
        if use_spec:
            path = self._sample_path(state)
            try:
                toks, accept = rt.slot_decode_spec_round(state)
            except ModelNotLoadedError as e:
                if not rt.is_loaded(self.model_id):
                    raise
                # the draft was evicted between the residency check and
                # the round: detach and decode plain — target lanes are
                # untouched (the round failed before any state update)
                log.info(
                    "continuous spec detach model=%s (%s)", self.model_id, e,
                )
                state.spec_draft = None
                state.spec_draft_id = None
                state.spec_tokens = 0
            else:
                # ring/ledger semantics: a spec round can emit up to spec+1
                # tokens per lane in one dispatch — that is its "chunk"; the
                # draft scan and the verify pass write every lane's rows
                rows = sum(l is not None and l.pf_pos is None for l in lanes)
                return _Flight(
                    chunk=state.spec_tokens + 1, reqs=list(lanes), toks=toks,
                    accept=accept, write_lanes=state.slots,
                    drafted=spec_span * rows, path=path,
                )
        return self._launch(rt, state, lanes, chunk, state.pos)

    def _sample_path(self, state) -> str | None:
        """What the sampler will pay for, from the mirrors a dispatch takes
        (the emission loop clears ``active`` for the rows it retires)."""
        if self.engine.metrics is None:
            return None
        from tfservingcache_tpu.models.generation import sample_path

        return sample_path(
            state.active, state.temps, state.topks,
            dict(state.cfg_key)["vocab_size"],
        )

    def _launch(self, rt, state, lanes, chunk: int, pos, ahead: int = 0) -> _Flight:
        """Launch one plain chunk of ``chunk`` steps from the lanes' positions
        ``pos`` (the mirror, or what the host foresees where a chunk in flight
        has the device ahead of it). A runtime without the split decodes the
        chunk whole and it comes back fetched."""
        from tfservingcache_tpu.models.generation import (
            kv_write_lanes,
            shared_pages_read,
            state_write_lanes,
            window_pages_read,
        )

        # what the sampler and the KV write will pay for
        path = self._sample_path(state)
        write_lanes = kv_write_lanes(state.active)
        # what a window layer's call will read a live lane (0 with no such layer)
        window = getattr(state, "window_tokens", 0)
        window_pages = window_pages_read(
            pos, state.active, chunk, window, state.page_tokens
        ) if window else 0.0
        # what the calls over a shared global layer will read (0 with none)
        readers = getattr(state, "shared_readers", 0)
        shared_pages = shared_pages_read(
            pos, state.active, chunk, readers, state.page_tokens
        ) if readers else 0.0
        # the lanes whose lane state a step will read and write (0 with none)
        state_lanes = state_write_lanes(state.active, dict(state.cfg_key)) \
            if getattr(state, "lane_state", None) is not None else 0
        t0 = time.monotonic()
        handle = toks = None
        if hasattr(rt, "slot_decode_chunk_launch"):
            handle = rt.slot_decode_chunk_launch(state, chunk)
        else:
            toks = rt.slot_decode_chunk(state, chunk)
        return _Flight(
            chunk=chunk, reqs=list(lanes), handle=handle, toks=toks,
            ahead=ahead, path=path, write_lanes=write_lanes,
            window_pages=window_pages, shared_pages=shared_pages,
            state_lanes=state_lanes,
            # the launch path ended at ``launched_t`` (a runtime that keeps
            # no such clock leaves an older time there and records 0)
            launch_s=max(0.0, getattr(state, "launched_t", 0.0) - t0),
            # what the launch had to send the device
            uploads=getattr(state, "uploads", 0),
        )

    def _chain(self, rt, state, lanes, cur: _Flight) -> _Flight | None:
        """Launch the chunk AFTER ``cur`` now, with ``cur`` still to fetch,
        if the boundary between them is foreseen decode-only. Its operands
        are ``cur``'s own outputs on the device and residents the host did
        not touch, and the host knows every live lane gets exactly
        ``cur.chunk`` tokens, so it needs nothing from ``cur``'s fetch. None
        (the next boundary is then today's) where the runtime has no split,
        the operands are not resident (a mesh), a draft is attached, a lane
        is PREFILLING or ends at ``max_new`` inside ``cur`` (its retirement
        rewrites ``active`` and the tables), a queued row could be admitted,
        or a lane would write a page it does not own alone. A lane that meets
        EOS inside ``cur`` is found only at its fetch: the chunk launched
        here computes for it on its own pages, and its tokens are dropped."""
        if cur.handle is None or not getattr(state, "resident", None) \
                or getattr(state, "spec_draft", None) is not None:
            return None
        left = []
        for req in lanes:
            if req is None:
                continue
            n = req.max_new - len(req.tokens) - cur.chunk
            if req.pf_pos is not None or n <= 0:
                return None
            left.append(n)
        if not left or self._could_admit(rt, state, lanes):
            return None
        pos = state.pos + cur.chunk * state.active
        refs = getattr(state, "page_refs", None)
        if refs is not None:
            # the boundary's copy-on-write net, one chunk on
            slot = np.minimum(pos // state.page_tokens, state.pages_per_slot - 1)
            pages = state.block_tables[np.arange(len(slot)), slot][state.active]
            if (refs[pages[pages > 0]] > 1).any():
                return None
        chunk = max(1, min(self.engine.chunk_tokens, _next_bucket(max(left))))
        return self._launch(rt, state, lanes, chunk, pos, ahead=1)

    def _could_admit(self, rt, state, lanes) -> bool:
        """Whether the next boundary would find a queued row it can admit
        (or must refuse): the first in admission's order, a free lane, and
        the pages of its budget free or to be had from the prefix index or a
        preemption."""
        with self.cv:
            if not self.pending:
                return False
            req = min(self.pending, key=lambda r: (r.rank, r.seq))
        if all(l is not None for l in lanes):
            return False
        if getattr(state, "prefix_index", None) is not None:
            return True
        # prompt + emitted + what is left of max_new
        tokens = req.prompt.shape[0] + req.max_new
        need = state.pages_needed(
            min(tokens, state.pages_per_slot * state.page_tokens))
        if tokens > state.max_seq or need > state.arena_pages \
                or need <= len(state.free_pages):
            return True
        return hasattr(rt, "park_lane") and \
            self._pick_victim(lanes, req) is not None

    def _record_step(
        self, state, chunk, active, admitted, retired, wasted, step_t0,
        prefix_hits=0, prefill_s=0.0, tokens_in=0,
        drafted=0, accepted=0, emitted=None, chunk_s=0.0, emit_s=0.0,
        write_lanes=0, launch_s=0.0, uploads=0, window_pages=0.0, ahead=0,
        shared_pages=0.0, state_lanes=0,
    ) -> None:
        """One flight-recorder ring entry per chunk boundary (``step_ms``
        split into the prefill clocks ``_step`` already keeps, the decode
        chunk and the emission loop; the rest is the engine's own;
        ``launch_s`` is the part of ``chunk_s`` the boundary's launches took,
        ``uploads`` the operands they sent, ``ahead`` whether the chunk it
        fetched had been launched before the last one's fetch), plus the
        oldest-queued-age gauge (`gen_admission_wait` only observes at
        admission — a row starved behind page exhaustion is invisible there
        until it finally admits; this gauge shows it starving)."""
        eng = self.engine
        with self.cv:
            depth = len(self.pending)
            oldest_t = self.pending[0].enqueue_t if depth else None
        wait_ms = (
            0.0 if oldest_t is None
            else max(0.0, (time.monotonic() - oldest_t) * 1e3)
        )
        if eng.metrics is not None:
            eng.metrics.gen_oldest_queued_age.labels("continuous").set(
                wait_ms / 1e3
            )
        # an expert model's routing numbers came back with the chunk's
        # tokens (plain chunks only: a spec round runs the verify step)
        moe_stats = (0.0, 0.0, 0.0)
        if chunk and drafted == 0 and getattr(state, "moe_stats", None):
            moe_stats = state.moe_stats
            if eng.metrics is not None:
                label = eng.metrics.model_label(
                    self.model_id.name, self.model_id.version)
                eng.metrics.moe_assignments.labels(label).inc(
                    active * chunk * dict(state.cfg_key).get("top_k", 1))
                eng.metrics.moe_expert_rows.labels(label).observe(moe_stats[1])
        shared = state.page_stats()["shared"]
        now = time.monotonic()
        # cost ledger: the whole boundary's wall time lands on this tenant
        # (each scheduler thread is single-model); the prefill clock sum is
        # carved out, the remainder is decode+bookkeeping. tokens_out = one
        # prefill token per admission + the chunk tokens that reached live
        # rows (wasted overshoot excluded — waste is the ENGINE's cost).
        LEDGER.note_step(
            str(self.model_id), "continuous",
            prefill_s=prefill_s,
            decode_s=max(0.0, (now - step_t0) - prefill_s),
            tokens_in=tokens_in,
            # spec rounds pass the true emitted total (variable per-row
            # acceptance); plain chunks emit exactly chunk per live lane
            tokens_out=admitted + max(
                0, (active * chunk if emitted is None else emitted) - wasted
            ),
            queue_depth=depth,
        )
        RECORDER.record(
            str(self.model_id), "continuous",
            step_ms=(now - step_t0) * 1e3,
            chunk=chunk, active=active, admitted=admitted, retired=retired,
            pages_used=state.arena_pages - len(state.free_pages),
            pages_free=len(state.free_pages),
            wasted=wasted, queue_depth=depth, oldest_wait_ms=wait_ms,
            pages_shared=shared, prefix_hits=prefix_hits,
            drafted=drafted, accepted=accepted,
            prefill_ms=prefill_s * 1e3, chunk_ms=chunk_s * 1e3,
            emit_ms=emit_s * 1e3,
            experts_hit=moe_stats[0], expert_rows_max=moe_stats[1],
            expert_rows_local=moe_stats[2], write_lanes=write_lanes,
            launch_ms=launch_s * 1e3, uploads=uploads,
            window_pages=window_pages, ahead=ahead,
            shared_pages=shared_pages, state_lanes=state_lanes,
        )

    def _retire_pages(self, state, idx: int, req: _ContinuousReq) -> None:
        """Recycle a finishing row's pages and record its page-granularity
        waste: reserved capacity minus the tokens that actually occupied it
        (prompt + emitted; the internal-fragmentation cost of fixed pages
        plus the unconsumed max_new headroom)."""
        eng = self.engine
        if eng.metrics is not None:
            cap = state.lane_capacity(idx)
            used = req.prompt.shape[0] + len(req.tokens)
            eng.metrics.gen_kv_page_waste.observe(max(0, cap - min(used, cap)))
        if (
            req.conversation_id
            and eng.conversation_tier is not None
            and hasattr(eng.runtime, "park_lane")
        ):
            # park BEFORE release: export needs the lane's page mapping.
            # History = prompt + all-but-last emitted token: the decode step
            # that emits token j writes the KV row for token j-1, so the
            # last emitted token's row was never written (mid-chunk EOS
            # leaves garbage beyond it). The next turn's prompt extends
            # exactly this sequence, so the match walk re-covers every row.
            try:
                if len(req.tokens) > 1:
                    history = np.concatenate(
                        [req.prompt, np.asarray(req.tokens[:-1], np.int32)]
                    )
                else:
                    history = req.prompt
                parked = eng.runtime.park_lane(state, idx, history)
                if parked is not None:
                    eng.conversation_tier.put(req.conversation_id, parked)
            except Exception:  # noqa: BLE001 - parking is best-effort
                log.warning(
                    "conversation park failed for %s", req.conversation_id,
                    exc_info=True,
                )
        state.release_pages(idx)
        d_st = getattr(state, "spec_draft", None)
        if d_st is not None:
            # the draft lane retires with its target: whole-page overshoot
            # from the last verify round hands back through the same
            # free-list, keeping the draft-side conservation census exact
            d_st.release_pages(idx)

    @staticmethod
    def _emit(req: _ContinuousReq, tok: int) -> None:
        """Append one emitted token and fire the row's stream callback (the
        SSE / gRPC-stream frame writers hang off it). A callback that raises
        is dropped after one failure — a dead client connection must not
        take the scheduler thread (and every other lane) down with it."""
        req.tokens.append(tok)
        cb = req.on_token
        if cb is not None:
            try:
                cb(tok)
            except Exception:  # noqa: BLE001 - client callback, not engine state
                req.on_token = None

    def _prefill_phase(
        self, rt, state, lanes, eos
    ) -> tuple[int, int, float, int]:
        """Advance every PREFILLING lane by exactly ONE fixed-size chunk
        (scheduler-thread only; called between admission and the decode
        half). The final chunk samples the row's first token under the seed
        drawn at admission — the same split-then-sample as a monolithic
        prefill — then activates the lane for the next boundary's decode
        chunk (or retires it on immediate EOS / max_new == 1). Returns
        (chunks_run, tokens_written, prefill_seconds, retired)."""
        eng = self.engine
        chunk_size = eng.prefill_chunk_tokens
        chunks = 0
        toks_in = 0
        prefill_s = 0.0
        retired = 0
        for idx, req in enumerate(lanes):
            if req is None or req.pf_pos is None:
                continue
            prompt = req.pf_prompt
            p = prompt.shape[0]
            t0 = time.monotonic()
            n = min(chunk_size, p - req.pf_pos)
            last = rt.slot_prefill_chunk(
                self.model_id, state, idx,
                prompt[req.pf_pos:req.pf_pos + n], req.pf_pos, chunk_size,
            )
            req.pf_pos += n
            dt = time.monotonic() - t0
            req.prefill_s += dt
            prefill_s += dt
            toks_in += n
            chunks += 1
            if eng.metrics is not None:
                eng.metrics.gen_prefill_chunks.inc()
            if req.pf_pos < p:
                continue
            tok = rt.sample_first_token(
                last, req.temperature, req.top_k, req.pf_seed
            )
            now = time.monotonic()
            if req.first_tok_t is None:
                req.first_tok_t = now
            req.prefill_tokens += p
            req.pf_pos = None
            req.pf_prompt = None
            remaining = req.max_new - len(req.tokens)
            self._emit(req, int(tok))
            if getattr(state, "prefix_index", None) is not None:
                # same publish the monolithic cold path does, just at the
                # last chunk: later same-prefix admissions map these pages
                rt.shared_prefix_publish(state, idx, prompt, last)
            if (eos is not None and int(tok) == eos) or remaining <= 1:
                lanes[idx] = None
                self._retire_pages(state, idx, req)
                req.finish_t = now
                req.done.set()
                retired += 1
                continue
            state.tok[idx] = int(tok)
            state.pos[idx] = p
            state.active[idx] = True
        return chunks, toks_in, prefill_s, retired

    def _pick_victim(
        self, lanes, req: _ContinuousReq
    ) -> int | None:
        """The preemption target for ``req``: the decoding lane with the
        numerically largest rank strictly above the arrival's (low loses to
        normal loses to high), youngest submit last — matching admission's
        (rank, seq) order in reverse. PREFILLING lanes are exempt (nothing
        decodable to park yet) and so are lanes out of preemption budget."""
        eng = self.engine
        best = None
        for li, lreq in enumerate(lanes):
            if lreq is None or lreq.pf_pos is not None:
                continue
            if lreq.rank <= req.rank:
                continue
            if lreq.preemptions >= eng.preempt_limit:
                continue
            if best is None or (lreq.rank, lreq.seq) > (
                lanes[best].rank, lanes[best].seq
            ):
                best = li
        return best

    def _preempt(self, rt, state, lanes, vidx: int) -> bool:
        """Park one decoding lane's KV through the conversation codec and
        requeue the row (priority preemption). The parked pages are COPIES:
        release_pages hands the originals back through the normal free
        list, so the conservation census never sees a discrepancy. Returns
        False when the lane can't be parked (nothing valid to export yet) —
        the caller stops hunting victims then."""
        eng = self.engine
        victim = lanes[vidx]
        park_t0 = time.monotonic()
        try:
            # same validity rule as retirement parking: the decode step
            # that emits token j writes the KV row for token j-1, so the
            # last emitted token's row was never written
            if len(victim.tokens) > 1:
                history = np.concatenate(
                    [victim.prompt, np.asarray(victim.tokens[:-1], np.int32)]
                )
            else:
                history = victim.prompt
            parked = rt.park_lane(state, vidx, history)
        except Exception:  # noqa: BLE001 - lane left running on park failure
            log.warning(
                "preemption park failed for lane %d of %s",
                vidx, self.model_id, exc_info=True,
            )
            return False
        if parked is None:
            return False
        victim.preempt_parked = parked
        victim.preemptions += 1
        state.active[vidx] = False
        state.release_pages(vidx)
        d_st = getattr(state, "spec_draft", None)
        if d_st is not None:
            d_st.release_pages(vidx)
        lanes[vidx] = None
        with self.cv:
            self.pending.append(victim)
            if eng.metrics is not None:
                eng.metrics.batcher_queue_depth.labels("generate").inc()
        if eng.metrics is not None:
            eng.metrics.gen_preemptions.labels(victim.priority).inc()
        # flight-recorder phase note: every preemption decision leaves an
        # auditable per-victim stamp (park cost attributed like a phase)
        RECORDER.note_phases(
            str(self.model_id), "continuous",
            {"preempt_park": time.monotonic() - park_t0},
        )
        log.info(
            "preempted lane %d of %s (class=%s, %d tokens emitted, "
            "preemption %d/%d)",
            vidx, self.model_id, victim.priority, len(victim.tokens),
            victim.preemptions, eng.preempt_limit,
        )
        return True

    def _update_page_gauge(self, state) -> None:
        if state is not None:
            # DISTINCT pages only: a prefix page mapped by N lanes counts
            # once, and index-only ("cached") pages are excluded — they are
            # reclaimable on demand, so counting them would under-report
            # admission headroom (NodeStatus routes on it)
            ps = state.page_stats()
            self.engine._set_pages(
                self.model_id, ps["shared"] + ps["private"],
                state.arena_pages, ps["shared"],
            )


@lockchecked
class ContinuousGenerateEngine:
    """Iteration-level continuous batching for ``:generate`` (vLLM-/
    DeepServe-style), the one batching engine of this program.

    It keeps a fixed number of lanes per model over a paged KV arena
    (static shapes — one compiled decode-chunk program regardless of which
    lanes are live) and decides membership at every chunk boundary: pending
    rows admit into free lanes once their pages are reserved (prompt
    prefilled via the prefix-cache-aware slot prefill), finished rows
    retire immediately and hand their pages back.

    What it cannot take falls through to ``runtime.generate``, the solo
    decoder: explicitly seeded requests (a reproducible stream of their
    own), families not engine_ready, malformed params, and LOCKSTEP mesh
    runtimes (``runtime.mesh_lockstep`` — a cross-process group's device-op
    stream must not depend on a host scheduler thread). A single-process
    mesh runs here on its KV-head-sharded arena (ISSUE 20),
    greedy-parity-pinned against the single-device path by
    tests/test_mesh_parity.py.
    """

    # Guarded-field registry (tools/tpusc_check TPUSC001 + TPUSC_LOCKCHECK=1).
    _tpusc_guarded = {
        "_scheds": "_lock",
        "_active": "_lock",
        "_pages": "_lock",
        "_closed": "_lock",
        "_seq": "_lock",
    }

    def __init__(
        self,
        runtime: BaseRuntime,
        slots: int = 8,
        chunk_tokens: int = 8,
        wait_timeout_s: float = 600.0,
        metrics=None,
        page_tokens: int | None = None,
        arena_pages: int | None = None,
        share_prefix_bytes: int | None = None,
        arena_dtype: str | None = None,
        paged_kernel: bool | None = None,
        spec_draft_model: str | None = None,
        spec_tokens: int | None = None,
        recovery: bool = True,
        max_recoveries: int = 2,
        conversation_kv_bytes: int | None = None,
        conversation_kv_disk_bytes: int | None = None,
        conversation_kv_dir: str | None = None,
        prefill_chunk_tokens: int | None = None,
    ) -> None:
        self.runtime = runtime
        self.slots = max(1, int(slots))
        self.chunk_tokens = max(1, int(chunk_tokens))
        self.wait_timeout_s = wait_timeout_s
        self.metrics = metrics
        # arena knobs forwarded to slot_decode_state: None = defer to the
        # runtime's ServingConfig (kv_page_tokens / kv_arena_pages /
        # kv_share_prefix_bytes); page size >= 1, arena_pages 0 = auto-size,
        # share_prefix_bytes 0 = sharing off
        self.page_tokens = (
            None if page_tokens is None else check_page_tokens(page_tokens)
        )
        self.arena_pages = None if arena_pages is None else int(arena_pages)
        self.share_prefix_bytes = (
            None if share_prefix_bytes is None else int(share_prefix_bytes)
        )
        # same None-defers convention: kv_arena_dtype ("" = model dtype,
        # "int8" = quantized pages — byte-matched auto-size means MORE pages
        # for the same budget, so admission capacity grows with no batcher
        # change: reserve_pages just sees a longer free-list) and
        # kv_paged_kernel (fused Pallas decode vs gather+einsum reference)
        self.arena_dtype = None if arena_dtype is None else str(arena_dtype)
        self.paged_kernel = (
            None if paged_kernel is None else bool(paged_kernel)
        )
        # in-engine speculative decoding (ISSUE 16): None = defer to the
        # runtime's ServingConfig (serving.spec_draft_model /
        # serving.spec_tokens), "" = explicitly off.  The draft model is
        # named "name" (highest resident version) or "name@version"; each
        # scheduler attaches it to its slot state via slot_attach_draft and
        # replaces plain decode chunks with draft/verify rounds whenever the
        # health gate (_spec_admit) allows.
        self.spec_draft_model = (
            None if spec_draft_model is None else str(spec_draft_model)
        )
        self.spec_tokens = None if spec_tokens is None else int(spec_tokens)
        # transparent crash recovery (serving.generate_recovery): on an
        # engine-thread death the crashed scheduler's rows requeue into a
        # fresh scheduler thread instead of failing — admission re-prefills
        # a row's prompt + emitted tokens, so the client stream continues
        # where it broke. max_recoveries bounds the respawn budget PER ROW.
        self.recovery = bool(recovery)
        self.max_recoveries = max(0, int(max_recoveries))
        # conversation-grade KV lifecycle (ISSUE 18): a byte-budgeted host
        # tier (+ optional disk spill level) holding parked decode state
        # keyed by conversation id. None = defer to the runtime's
        # ServingConfig (serving.conversation_kv_bytes & friends), 0 =
        # explicitly off. The tier lives on the ENGINE, not the scheduler:
        # parked turns survive scheduler crashes and respawns.
        cfg = getattr(runtime, "cfg", None)
        ckv_bytes = (
            int(getattr(cfg, "conversation_kv_bytes", 0) or 0)
            if conversation_kv_bytes is None else int(conversation_kv_bytes)
        )
        ckv_disk = (
            int(getattr(cfg, "conversation_kv_disk_bytes", 0) or 0)
            if conversation_kv_disk_bytes is None
            else int(conversation_kv_disk_bytes)
        )
        ckv_dir = (
            str(getattr(cfg, "conversation_kv_dir", "/tmp/tpusc_conv_kv"))
            if conversation_kv_dir is None else str(conversation_kv_dir)
        )
        if ckv_bytes > 0:
            from tfservingcache_tpu.cache.conversation_kv import (
                ConversationKVTier,
            )
            self.conversation_tier = ConversationKVTier(
                ckv_bytes,
                disk_capacity_bytes=ckv_disk,
                disk_dir=ckv_dir,
                metrics=metrics,
            )
        else:
            self.conversation_tier = None
        # chunked prefill interleaving (ISSUE 19): None = defer to the
        # runtime's ServingConfig (serving.prefill_chunk_tokens), 0 =
        # explicitly off. Clamped UP to a pow2 so ONE compiled partial-
        # prefill program serves every chunk of every prompt (the final
        # chunk zero-pads into it).
        pf = (
            int(getattr(cfg, "prefill_chunk_tokens", 0) or 0)
            if prefill_chunk_tokens is None else int(prefill_chunk_tokens)
        )
        self.prefill_chunk_tokens = _next_bucket(pf) if pf > 0 else 0
        # priority preemption budget PER LANE: a row parked off its lane
        # this many times decodes to completion afterwards no matter what
        # class arrives — bounded starvation by construction
        self.preempt_limit = 2
        self._lock = threading.Lock()
        self._scheds: dict[ModelId, _ContinuousScheduler] = {}
        self._active: dict[ModelId, int] = {}
        # mid -> (used, total, shared); used counts DISTINCT pages and
        # excludes index-only cached pages (true admission headroom)
        self._pages: dict[ModelId, tuple[int, int, int]] = {}
        self._closed = False
        # engine-monotonic submit sequence — the FIFO half of admission's
        # (rank, seq) order; preserved across preemption/crash requeues
        self._seq = 0
        # observability (tests + drives)
        self.admitted = 0
        self.chunks = 0
        self.peak_active = 0  # high-water concurrent lanes

    def _set_active(self, model_id: ModelId, n: int) -> None:
        with self._lock:
            if n:
                self._active[model_id] = n
            else:
                self._active.pop(model_id, None)
            total = sum(self._active.values())
            if total > self.peak_active:
                self.peak_active = total
        if self.metrics is not None:
            # per-model series when model_labels is on (which model's lanes
            # are saturated), one all_models total otherwise
            label = self.metrics.model_label(model_id.name, model_id.version)
            value = n if self.metrics.model_labels else total
            self.metrics.gen_slots_active.labels(label).set(value)

    def _set_pages(self, model_id: ModelId, used: int, total: int,
                   shared: int = 0) -> None:
        with self._lock:
            if total:
                self._pages[model_id] = (used, total, shared)
            else:
                self._pages.pop(model_id, None)
            used_sum = sum(u for u, _, _ in self._pages.values())
            total_sum = sum(t for _, t, _ in self._pages.values())
            shared_sum = sum(s for _, _, s in self._pages.values())
        peak = RECORDER.observe_watermark("gen_kv_pages_used", float(used_sum))
        # cost ledger: this tenant's distinct-page level (feeds its
        # kv_page_seconds integral) and the cross-model arena occupancy
        # level (the conservation test's reference integral) — stamped at
        # the same boundary so Σ tenants tracks the arena exactly
        LEDGER.gauge_set(str(model_id), "kv_pages", used)
        LEDGER.note_arena(used_sum)
        if self.metrics is not None:
            self.metrics.gen_kv_pages_used.set(used_sum)
            self.metrics.gen_kv_pages_total.set(total_sum)
            self.metrics.gen_kv_pages_used_peak.set(peak)
            self.metrics.gen_kv_pages_shared.set(shared_sum)

    def _sched(self, model_id: ModelId) -> _ContinuousScheduler:
        with self._lock:
            if self._closed:
                raise RuntimeError_("continuous generate engine is closed")
            s = self._scheds.get(model_id)
            if s is not None and not s.thread.is_alive():
                # insurance: a scheduler whose thread died without managing
                # a respawn (recovery off, or a crash that raced close())
                # must not keep collecting rows into a corpse's queue
                self._scheds.pop(model_id, None)
                s = None
            if s is None:
                s = _ContinuousScheduler(self, model_id)
                self._scheds[model_id] = s
            return s

    def _respawn(
        self, old: _ContinuousScheduler, survivors: list[_ContinuousReq]
    ) -> "_ContinuousScheduler | None":
        """Crash recovery (called from ``old``'s dying thread): swap in a
        fresh scheduler for the model and requeue the surviving rows, FIFO
        order preserved. Returns None when the engine is closing or ``old``
        was already replaced — the caller then fails the rows instead of
        stranding them on a queue nobody drains."""
        with self._lock:
            if self._closed or self._scheds.get(old.model_id) is not old:
                return None
            fresh = _ContinuousScheduler(self, old.model_id)
            self._scheds[old.model_id] = fresh
        with old.cv:
            # a submit that raced the swap through a stale scheduler ref
            # may have landed rows on the corpse's queue: carry them over,
            # and stop the corpse so later stale submits raise cleanly
            old.stopped = True
            late = list(old.pending)
            old.pending.clear()
        if late and self.metrics is not None:
            # their original submit already counted them; fresh.submit
            # counts them again, so cancel one of the two
            self.metrics.batcher_queue_depth.labels("generate").dec(len(late))
        rows = survivors + late
        try:
            fresh.submit(rows)
        except RuntimeError_ as e:
            # closed between the swap and the submit
            fresh._fail(rows, e)
            return None
        log.warning(
            "continuous scheduler for %s respawned after crash: "
            "%d rows requeued (%d interrupted mid-decode)",
            old.model_id, len(rows),
            sum(1 for r in survivors if r.tokens),
        )
        return fresh

    def generate(
        self,
        model_id: ModelId,
        input_ids: np.ndarray,
        prompt_lengths: list[int] | None = None,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_k: int = 0,
        seed: int | None = None,
        return_stats: bool = False,
        conversation_id: str | None = None,
        priority: str = "normal",
        on_token: Callable[[int], None] | None = None,
    ) -> np.ndarray:
        """-> (rows, max_new_tokens) int32. A row that hit EOS early is
        zero-padded after it (the solo path has no EOS concept and always
        fills max_new_tokens — identical when the model declares no
        eos_id). ``return_stats`` additionally returns per-row timing dicts
        (ttft_s, admission_wait_s, tokens, prefill_tokens, priority,
        preemptions) — the streaming-TTFT surface of drives and tests.

        ``priority`` ("high" | "normal" | "low") orders admission by class
        then FIFO and arms preemption: a high-class arrival finding no free
        pages parks the lowest-class decoding lane. Ignored on the solo
        path (a solo dispatch has no queue to order).

        ``on_token`` streams each emitted token the moment the scheduler
        appends it (single-row requests only — multi-row token order is
        undefined across lanes, so the callback is dropped). On the solo
        path the full row is replayed through the callback after the
        dispatch returns, so stream framing works identically there.

        ``conversation_id`` opts the request into the conversation KV tier
        (ISSUE 18): on retirement the row's decode state parks under the id,
        and the next turn carrying the same id resumes with a suffix-only
        prefill. Multi-row calls get per-row ids (``"{id}#r{row}"``) so rows
        never alias each other's parked state. A no-op when the tier is
        disabled (conversation_kv_bytes = 0), or on the solo path."""
        pr = str(priority or "normal")
        rank = _PRIORITY_RANKS.get(pr)
        if rank is None:
            raise ValueError(
                f"unknown priority {priority!r} (expected high|normal|low)"
            )
        ids = np.asarray(input_ids, np.int32)
        ready = getattr(self.runtime, "engine_ready_of", lambda _m: False)(model_id)
        # mesh_lockstep (ISSUE 20): only CROSS-PROCESS groups (or meshes
        # with serving.mesh_fast_path off) fall back to the solo path — a
        # single-process mesh runs the engine on its sharded arena
        solo = (
            seed is not None
            or getattr(
                self.runtime, "mesh_lockstep",
                getattr(self.runtime, "mesh", None) is not None,
            )
            or ids.ndim != 2
            or not ids.size
            or not ready
        )
        lengths = None
        if not solo:
            rows, s = ids.shape
            if prompt_lengths is None:
                lengths = np.full((rows,), s, np.int32)
            else:
                lengths = np.asarray(prompt_lengths, np.int32)
                if (
                    lengths.shape != (rows,)
                    or (lengths < 1).any()
                    or (lengths > s).any()
                ):
                    solo = True  # runtime raises its own clean error
            if not solo and (
                max_new_tokens < 1
                or not np.isfinite(temperature)
                or temperature < 0.0
                or top_k < 0
            ):
                solo = True
        if not solo:
            # an oversized prompt fails loudly AT SUBMIT, in the caller's
            # thread, not after it queued behind other rows for a lane
            max_seq = getattr(self.runtime, "max_seq_of", lambda _m: None)(model_id)
            longest = int(lengths.max())
            if max_seq is not None and longest + max_new_tokens > max_seq:
                raise ValueError(
                    f"prompt {longest} + max_new_tokens {max_new_tokens} "
                    f"exceeds max_seq {max_seq}"
                )
        if solo:
            out = self.runtime.generate(
                model_id, ids, prompt_lengths=prompt_lengths,
                max_new_tokens=max_new_tokens, temperature=temperature,
                top_k=top_k,
                seed=seed if seed is not None else secrets.randbits(31),
            )
            if on_token is not None and out.ndim == 2 and out.shape[0] == 1:
                # stream framing parity on the solo path: replay the row
                # through the callback (all at once — a solo dispatch has
                # no per-token boundary to hook)
                for t in np.asarray(out)[0, :max_new_tokens].tolist():
                    try:
                        on_token(int(t))
                    except Exception:  # noqa: BLE001 - client callback
                        break
            return (out, None) if return_stats else out

        cid = str(conversation_id) if conversation_id else None
        with self._lock:
            seq0 = self._seq
            self._seq += rows
        reqs = [
            _ContinuousReq(
                prompt=ids[r, : lengths[r]].copy(),
                max_new=int(max_new_tokens),
                temperature=float(temperature),
                top_k=int(top_k),
                conversation_id=(
                    None if cid is None
                    else (cid if rows == 1 else f"{cid}#r{r}")
                ),
                priority=pr,
                rank=rank,
                seq=seq0 + r,
                on_token=on_token if rows == 1 else None,
            )
            for r in range(rows)
        ]
        self._sched(model_id).submit(reqs)
        deadline = time.monotonic() + self.wait_timeout_s
        for r in reqs:
            if not r.done.wait(max(0.0, deadline - time.monotonic())):
                raise TimeoutError(
                    f"continuous generate for {model_id} timed out"
                )
        for r in reqs:
            if r.error is not None:
                raise r.error
        out = np.zeros((rows, max_new_tokens), np.int32)
        for i, r in enumerate(reqs):
            t = np.asarray(r.tokens[:max_new_tokens], np.int32)
            out[i, : t.shape[0]] = t
        # phase clocks (queue -> prefill -> decode -> respond), observed from
        # the CALLER's thread once every row is done: queue ends where the
        # scheduler starts the row's prefill, decode runs first token ->
        # finish, respond is the wait for batch-mates plus output assembly.
        # The worst row's attribution lands on the trace root — the request
        # was as slow as its slowest row.
        end_t = time.monotonic()
        ids_ctx = current_ids()
        worst: dict[str, float] = {}
        for r in reqs:
            admitted = r.admitted_t or r.enqueue_t
            finish = r.finish_t or admitted
            phases = {
                "queue": max(0.0, admitted - r.enqueue_t - r.prefill_s),
                "prefill": r.prefill_s,
                "decode": max(0.0, finish - (r.first_tok_t or admitted)),
                "respond": max(0.0, end_t - finish),
            }
            if self.metrics is not None:
                for ph, v in phases.items():
                    self.metrics.observe_phase(ph, "continuous", r.priority, v)
            for ph, v in phases.items():
                if v > worst.get(ph, -1.0):
                    worst[ph] = v
            RECORDER.note_phases(
                str(model_id), "continuous", phases,
                trace_id=ids_ctx[0] if ids_ctx else None,
            )
        # span annotation from the CALLER's thread (the scheduler thread has
        # no ambient trace — a span opened there would be an orphan root)
        TRACER.annotate(
            gen_engine="continuous",
            gen_admission_wait_ms=round(
                1e3 * max(
                    (r.admitted_t or r.enqueue_t) - r.enqueue_t for r in reqs
                ), 3,
            ),
            gen_prefix_hits=sum(1 for r in reqs if r.prefix_hit),
        )
        # priority + TTFT stamped on the ROOT (not the request span) so
        # /monitoring/traces and tools/slo_report.py --classes read the
        # same per-class attribution the class-labeled phase histogram
        # aggregates (ISSUE 20 satellite)
        TRACER.annotate_root(
            priority=pr,
            ttft_ms=round(
                1e3 * max(
                    (r.first_tok_t or r.enqueue_t) - r.enqueue_t for r in reqs
                ), 3,
            ),
            **{f"phase_{ph}_ms": round(v * 1e3, 3) for ph, v in worst.items()},
        )
        if return_stats:
            stats = [
                {
                    "ttft_s": (r.first_tok_t or r.enqueue_t) - r.enqueue_t,
                    "admission_wait_s": (r.admitted_t or r.enqueue_t)
                    - r.enqueue_t,
                    "tokens": len(r.tokens[:max_new_tokens]),
                    "prefill_tokens": r.prefill_tokens,
                    "priority": r.priority,
                    "preemptions": r.preemptions,
                }
                for r in reqs
            ]
            return out, stats
        return out

    def close(self) -> None:
        with self._lock:
            self._closed = True
            scheds = list(self._scheds.values())
            self._scheds.clear()
        for s in scheds:
            with s.cv:
                s.stopped = True
                s.cv.notify_all()
        for s in scheds:
            s.thread.join(timeout=5.0)
        if self.conversation_tier is not None:
            self.conversation_tier.close()
