"""Priority work scheduler — the single place device-sized batches form.

Rebuild of the reference beacon_processor
(/root/reference/beacon_node/beacon_processor/src/lib.rs): a manager loop
over per-work-type bounded queues with an explicit priority order
(lib.rs:950-977), a capped worker pool, and opportunistic batch formation
for attestations/aggregates (lib.rs:977-1010).

TPU-first deltas from the reference:
- The reference drains at most 64 queued attestations into one batch
  (lib.rs:196-203) because its batch verifier is CPU-bound.  Here the batch
  cap defaults to 2048 lanes and adds a time-based flush, because the device
  batch-pairing kernel wants large, padded, bucketed batches (SURVEY.md §7:
  "raise the 64-item cap, add time-based flush").
- Queues are deques of work events; batch formation concatenates event
  payloads so the BLS backend sees one contiguous lane batch.

Concurrency model: asyncio manager + thread-pool executor for CPU/device
work (the reference's tokio manager + blocking worker pool,
task_executor::spawn_blocking).
"""

from __future__ import annotations

import asyncio
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from enum import Enum, auto
from typing import Any, Awaitable, Callable

from lighthouse_tpu.common import env as envreg
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import REGISTRY, record_swallowed
from lighthouse_tpu.ops import faults
from lighthouse_tpu.processor.admission import (
    ACCEPTED,
    Admission,
    AdmissionController,
)


class WorkType(Enum):
    """Work kinds (reference Work enum, lib.rs:552-618)."""

    # highest priority: chain structure
    CHAIN_SEGMENT = auto()
    CHAIN_SEGMENT_BACKFILL = auto()
    RPC_BLOCK = auto()
    RPC_BLOBS = auto()
    # delayed re-imports
    DELAYED_IMPORT_BLOCK = auto()
    # gossip block parts
    GOSSIP_BLOCK = auto()
    GOSSIP_BLOB_SIDECAR = auto()
    # API priorities
    API_REQUEST_P0 = auto()
    API_REQUEST_P1 = auto()
    # aggregates before unaggregated attestations
    GOSSIP_AGGREGATE = auto()
    GOSSIP_AGGREGATE_BATCH = auto()
    GOSSIP_ATTESTATION = auto()
    GOSSIP_ATTESTATION_BATCH = auto()
    # remaining gossip
    GOSSIP_SYNC_SIGNATURE = auto()
    GOSSIP_SYNC_CONTRIBUTION = auto()
    GOSSIP_VOLUNTARY_EXIT = auto()
    GOSSIP_PROPOSER_SLASHING = auto()
    GOSSIP_ATTESTER_SLASHING = auto()
    GOSSIP_BLS_TO_EXECUTION_CHANGE = auto()
    GOSSIP_LIGHT_CLIENT_UPDATE = auto()
    # Req/Resp serving
    STATUS = auto()
    BLOCKS_BY_RANGE_REQUEST = auto()
    BLOCKS_BY_ROOT_REQUEST = auto()
    BLOBS_BY_RANGE_REQUEST = auto()
    BLOBS_BY_ROOT_REQUEST = auto()
    LIGHT_CLIENT_BOOTSTRAP_REQUEST = auto()
    UNKNOWN_BLOCK_ATTESTATION = auto()
    UNKNOWN_BLOCK_AGGREGATE = auto()


# Manager poll order (reference lib.rs:950-977): chain segments, then rpc
# blocks, delayed imports, gossip blocks/blobs, P0 API, aggregates,
# attestations, then everything else.
PRIORITY_ORDER: tuple[WorkType, ...] = (
    WorkType.CHAIN_SEGMENT,
    WorkType.RPC_BLOCK,
    WorkType.RPC_BLOBS,
    WorkType.CHAIN_SEGMENT_BACKFILL,
    WorkType.DELAYED_IMPORT_BLOCK,
    WorkType.GOSSIP_BLOCK,
    WorkType.GOSSIP_BLOB_SIDECAR,
    WorkType.API_REQUEST_P0,
    WorkType.GOSSIP_AGGREGATE,
    WorkType.GOSSIP_ATTESTATION,
    WorkType.UNKNOWN_BLOCK_AGGREGATE,
    WorkType.UNKNOWN_BLOCK_ATTESTATION,
    WorkType.GOSSIP_SYNC_CONTRIBUTION,
    WorkType.GOSSIP_SYNC_SIGNATURE,
    WorkType.API_REQUEST_P1,
    WorkType.GOSSIP_ATTESTER_SLASHING,
    WorkType.GOSSIP_PROPOSER_SLASHING,
    WorkType.GOSSIP_VOLUNTARY_EXIT,
    WorkType.GOSSIP_BLS_TO_EXECUTION_CHANGE,
    WorkType.GOSSIP_LIGHT_CLIENT_UPDATE,
    WorkType.STATUS,
    WorkType.BLOCKS_BY_RANGE_REQUEST,
    WorkType.BLOCKS_BY_ROOT_REQUEST,
    WorkType.BLOBS_BY_RANGE_REQUEST,
    WorkType.BLOBS_BY_ROOT_REQUEST,
    WorkType.LIGHT_CLIENT_BOOTSTRAP_REQUEST,
)

# queues that drop the OLDEST item when full (gossip floods); everything
# else rejects the newest with a backoff hint (reference
# FifoQueue/LifoQueue split).  Either way the discard is accounted in
# processor_shed_total{work_type,reason} — overload may degrade service,
# never the books.
_LIFO_TYPES = {
    WorkType.GOSSIP_ATTESTATION,
    WorkType.GOSSIP_AGGREGATE,
    WorkType.GOSSIP_SYNC_SIGNATURE,
    WorkType.GOSSIP_SYNC_CONTRIBUTION,
}

# lanes the degradation ladder must never shed AND the scheduler must
# never starve: chain structure always lands.  One worker slot is
# reserved for these — a saturated attestation plane can occupy at most
# max_workers - 1 slots (the reserve is how GOSSIP_BLOCK/CHAIN_SEGMENT
# stay verifiably live during a flood drill).
_PROTECTED_TYPES = frozenset({
    WorkType.CHAIN_SEGMENT,
    WorkType.CHAIN_SEGMENT_BACKFILL,
    WorkType.RPC_BLOCK,
    WorkType.RPC_BLOBS,
    WorkType.DELAYED_IMPORT_BLOCK,
    WorkType.GOSSIP_BLOCK,
    WorkType.GOSSIP_BLOB_SIDECAR,
})

# longest a deadline flush may be held for coalescing while the dispatch
# thread is busy: bounds queue wait for sub-max batches when back-to-back
# flights of another work type keep the thread saturated
_COALESCE_HOLD_MAX_S = 0.5

# work types eligible for batch formation: (batch type, per-event lanes)
_BATCHABLE = {
    WorkType.GOSSIP_ATTESTATION: WorkType.GOSSIP_ATTESTATION_BATCH,
    WorkType.GOSSIP_AGGREGATE: WorkType.GOSSIP_AGGREGATE_BATCH,
}


def queue_wait_histogram():
    """The beacon_processor_queue_wait_seconds family (this module is
    its sole owner; the firehose driver reads quantiles through here)."""
    return REGISTRY.histogram(
        "beacon_processor_queue_wait_seconds",
        "enqueue->dequeue wait per work event, by work type")


def _with_ingest_stall(batch_fn, payloads):
    """Batch-callable wrapper run ON the dispatch/worker thread: honors
    an active slow-consumer ingest storm (ops/faults.IngestPlan
    mode=stall, armable via LHTPU_INGEST_FAULT_MODE) so chaos drills can
    wedge the REAL consumer, not just a bench harness."""
    stall = faults.consumer_stall_s()
    if stall:
        time.sleep(stall)
    return batch_fn(payloads)


def _record_inflight(n: int) -> None:
    """Mirror the dispatch-thread occupancy into the
    bls_pipeline_inflight_batches gauge (owned by ops/dispatch_pipeline;
    lazy import keeps this module importable without jax)."""
    try:
        from lighthouse_tpu.ops.dispatch_pipeline import record_inflight

        record_inflight(n)
    except (ImportError, AttributeError, KeyError, TypeError,
            ValueError) as e:
        record_swallowed("beacon_processor.record_inflight", e)


def default_queue_lengths(active_validator_count: int) -> dict[WorkType, int]:
    """Queue bounds scaled from the active validator count
    (reference lib.rs:96-183: attestation queue = validators/32, etc.)."""
    n = max(active_validator_count, 1024)
    return {
        WorkType.GOSSIP_ATTESTATION: max(4096, n // 32),
        WorkType.GOSSIP_AGGREGATE: 4096,
        WorkType.GOSSIP_SYNC_SIGNATURE: max(2048, n // 64),
        WorkType.GOSSIP_SYNC_CONTRIBUTION: 1024,
        WorkType.GOSSIP_BLOCK: 1024,
        WorkType.GOSSIP_BLOB_SIDECAR: 1024,
        WorkType.RPC_BLOCK: 1024,
        WorkType.RPC_BLOBS: 1024,
        WorkType.CHAIN_SEGMENT: 64,
        WorkType.CHAIN_SEGMENT_BACKFILL: 64,
        WorkType.API_REQUEST_P0: 1024,
        WorkType.API_REQUEST_P1: 1024,
        WorkType.UNKNOWN_BLOCK_ATTESTATION: 4096,
        WorkType.UNKNOWN_BLOCK_AGGREGATE: 1024,
    }


@dataclass
class WorkEvent:
    """One unit of work.

    `process` runs on a worker (sync callables go to the thread pool,
    async callables are awaited).  For batchable types, `process_batch`
    receives a list of payloads when the manager forms a batch
    (reference Work::GossipAttestation {process_individual, process_batch},
    lib.rs:552-557).
    """

    work_type: WorkType
    process: Callable[[], Any] | Callable[[], Awaitable[Any]] | None = None
    payload: Any = None
    process_batch: Callable[[list[Any]], Any] | None = None
    drop_during_sync: bool = False
    enqueued_at: float = field(default_factory=time.monotonic)


@dataclass
class ProcessorMetrics:
    enqueued: dict[WorkType, int] = field(default_factory=dict)
    processed: dict[WorkType, int] = field(default_factory=dict)
    dropped: dict[WorkType, int] = field(default_factory=dict)
    # (work_type, reason) -> count; the in-process mirror of the labeled
    # processor_shed_total family.  Invariant the firehose drill holds:
    # enqueued == processed + shed + still-queued, per work type.
    shed: dict[tuple[WorkType, str], int] = field(default_factory=dict)
    batches_formed: int = 0
    batch_lanes: int = 0
    # submit() races from producer threads: a bare read-modify-write
    # would lose counts exactly when the books matter most (under flood)
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False)

    def bump(self, table: dict, wt: WorkType, by: int = 1):
        with self._lock:
            table[wt] = table.get(wt, 0) + by

    def bump_shed(self, wt: WorkType, reason: str, by: int = 1):
        with self._lock:
            key = (wt, reason)
            self.shed[key] = self.shed.get(key, 0) + by

    def shed_total(self, wt: WorkType | None = None) -> int:
        return sum(n for (w, _r), n in self.shed.items()
                   if wt is None or w is wt)


class BeaconProcessor:
    """Manager + worker pool (reference BeaconProcessor::spawn_manager,
    lib.rs:758)."""

    def __init__(
        self,
        max_workers: int = 4,
        max_batch: int = 2048,
        batch_flush_ms: float = 50.0,
        queue_lengths: dict[WorkType, int] | None = None,
        work_journal: Callable[[str], None] | None = None,
        dispatch_wedge_s: float | None = None,
        dispatch_restart_max: int | None = None,
        dispatch_restart_window_s: float | None = None,
    ):
        self.max_workers = max(2, max_workers)
        self.max_batch = max_batch
        self.batch_flush_ms = batch_flush_ms
        self._lengths = queue_lengths or default_queue_lengths(0)
        self._queues: dict[WorkType, deque[WorkEvent]] = {
            wt: deque() for wt in WorkType}
        self.metrics = ProcessorMetrics()
        # test hook: emits one token per scheduling decision (reference
        # work_journal_tx, lib.rs:925-935)
        self._journal = work_journal
        self._idle = asyncio.Semaphore(self.max_workers)
        self._wakeup = asyncio.Event()
        self._stopped = False
        self._manager_task: asyncio.Task | None = None
        self._sweeper_task: asyncio.Task | None = None
        # True while the manager holds popped-but-unscheduled work
        # (parked on _idle.acquire); read by drain()
        self._manager_holding = False
        self._executor = ThreadPoolExecutor(max_workers=self.max_workers)
        # ONE dedicated dispatch thread for device batches: batch work
        # from every batchable type serializes here back-to-back, so the
        # device stays saturated while the manager keeps draining queues
        # on the loop and the general pool serves per-event work.  The
        # thread count is the contract — two concurrent device batch
        # dispatches would interleave their host/device stages.
        self._dispatch_executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="bp-dispatch")
        # --- dispatch-thread supervisor: a wedged or dead dispatch
        # thread must not stall batch verification forever.  Each batch
        # awaits its executor future under a wedge deadline; on timeout
        # (or a dead executor) the batch re-runs on the general worker
        # pool (the synchronous path) and the dispatch executor is
        # replaced — storm-limited so a persistently wedging device
        # pins batch work to the synchronous path instead of spawning
        # threads unboundedly.  Restart bookkeeping is mutated only on
        # the event loop.
        # explicit zeros are honored: wedge 0 disables the supervisor,
        # restart-max 0 means never restart (sync-only recovery)
        self.dispatch_wedge_s = (
            dispatch_wedge_s if dispatch_wedge_s is not None
            else envreg.get_float("LHTPU_DISPATCH_WEDGE_S", 600.0))
        self.dispatch_restart_max = (
            dispatch_restart_max if dispatch_restart_max is not None
            else envreg.get_int("LHTPU_DISPATCH_RESTART_MAX", 3))
        self.dispatch_restart_window_s = (
            dispatch_restart_window_s
            if dispatch_restart_window_s is not None
            else envreg.get_float("LHTPU_DISPATCH_RESTART_WINDOW_S", 300.0))
        self._dispatch_restarts: deque[float] = deque()  # restart stamps
        self._dispatch_generation = 0
        self.dispatch_restart_count = 0  # lifetime total (test surface)
        # batches currently on (or queued for) the dispatch thread;
        # mutated only on the event loop
        self._dispatch_inflight = 0
        self._inflight: set[asyncio.Task] = set()
        # first-seen timestamps for batch flush decisions (the flush
        # deadline is computed at sweep time so the ladder's
        # coalesce-harder rung can stretch it for already-queued work)
        self._batch_first_seen: dict[WorkType, float] = {}
        # --- admission control: per-WorkType watermarks + the
        # degradation ladder over the flood lanes (processor/admission).
        # Swept from the manager loop; drills call sweep_now() directly.
        self.admission = AdmissionController(
            governed=(WorkType.GOSSIP_ATTESTATION, WorkType.GOSSIP_AGGREGATE),
            shed_order=(WorkType.GOSSIP_ATTESTATION,
                        WorkType.GOSSIP_AGGREGATE))
        self.admit_sweep_s = envreg.get_float("LHTPU_ADMIT_SWEEP_S", 0.05)
        # unprotected (flood-lane) work currently scheduled; the manager
        # keeps this strictly below max_workers so one slot always
        # remains for _PROTECTED_TYPES.  Mutated only on the event loop.
        self._unprotected_inflight = 0
        self._shed_counter = REGISTRY.counter(
            "processor_shed_total",
            "work events discarded by admission control / queue policy, "
            "by work type and reason")
        # sheds awaiting their aggregated trace event (flushed per sweep)
        self._shed_pending: dict[tuple[WorkType, str], int] = {}
        # labeled registry families (one series per WorkType label);
        # ProcessorMetrics above stays as the in-process test surface
        self._wait_hist = queue_wait_histogram()
        self._batch_hist = REGISTRY.histogram(
            "beacon_processor_batch_size_lanes",
            "lanes per formed device batch, by work type",
            buckets=(1, 8, 32, 64, 128, 256, 512, 1024, 2048, 4096))
        self._event_counter = REGISTRY.counter(
            "beacon_processor_events_total",
            "work events by work type and outcome "
            "(enqueued/dropped/processed)")
        # labeled children memoized per (family, type[, outcome]):
        # submit()/dequeue run once per gossip event at flood scale, so
        # the per-call cost must stay one observe()/inc()
        self._label_memo: dict[tuple, Any] = {}
        # the books go LIVE: enqueued == processed + shed + queued is a
        # registered invariant monitor (weakref-backed; the newest
        # processor instance owns the "processor_books" name)
        from lighthouse_tpu.common import monitors as _monitors

        _monitors.register_processor_books(self)

    def _labeled(self, family, wt: WorkType, outcome: str | None = None,
                 reason: str | None = None):
        key = (family.name, wt, outcome, reason)
        child = self._label_memo.get(key)
        if child is None:
            labels = {"work_type": wt.name.lower()}
            if outcome is not None:
                labels["outcome"] = outcome
            if reason is not None:
                labels["reason"] = reason
            child = self._label_memo[key] = family.labels(**labels)
        return child

    def _account_shed(self, wt: WorkType, reason: str, n: int = 1) -> None:
        """EVERY discard of queued (or submitted) work funnels through
        here: the labeled processor_shed_total series, the in-process
        mirrors, and (aggregated per sweep) a trace event.  The firehose
        acceptance criterion — zero unaccounted drops — is this helper
        being the only discard path.

        Tracing is deferred: a span per shed event would take the
        tracer's process-wide lock once per gossip message exactly when
        tens of thousands/s are being shed, so sheds accumulate in
        ``_shed_pending`` and ``sweep_now`` emits ONE span per
        (work_type, reason) carrying the count since the last sweep."""
        self.metrics.bump(self.metrics.dropped, wt, n)
        self.metrics.bump_shed(wt, reason, n)
        self._labeled(self._event_counter, wt, "dropped").inc(n)
        self._labeled(self._shed_counter, wt, reason=reason).inc(n)
        with self.metrics._lock:
            key = (wt, reason)
            self._shed_pending[key] = self._shed_pending.get(key, 0) + n

    def _trace_pending_sheds(self) -> None:
        from lighthouse_tpu.common import flight_recorder as flight

        with self.metrics._lock:
            pending, self._shed_pending = self._shed_pending, {}
        for (wt, reason), n in pending.items():
            with tracing.span("beacon_processor.shed",
                              work_type=wt.name.lower(), reason=reason,
                              count=n):
                pass
            # aggregated per sweep (never per message): the black box
            # shows WHAT was shed in the window before a trip
            flight.emit("shed", plane="processor",
                        work_type=wt.name.lower(), reason=reason, count=n)

    def shed_queue(self, wt: WorkType, reason: str = "purged") -> int:
        """Discard EVERYTHING queued on one lane, accounted under
        ``reason`` — the operator's backlog purge (a poisoned or stale
        backlog after a storm is often worth less than the fresh traffic
        behind it).  Returns the number of events shed."""
        q = self._queues[wt]
        n = 0
        while True:
            try:
                q.popleft()
            except IndexError:
                break
            n += 1
        if n:
            self._account_shed(wt, reason, n)
        self._batch_first_seen.pop(wt, None)
        return n

    # -- submission (any task/thread) -------------------------------------

    def submit(self, event: WorkEvent) -> Admission:
        """Enqueue work.  Returns a truthy :class:`Admission` when the
        event was queued; a falsy one (with ``reason`` and, for
        reject-newest lanes, a ``retry_after_s`` backoff hint) when it
        was shed.  A LIFO gossip lane over its limit still accepts the
        newest event and sheds its OLDEST instead — that drop is
        accounted but the submitted event lands, so the call returns
        accepted."""
        wt = event.work_type
        q = self._queues[wt]
        limit = self._lengths.get(wt, 1024)
        self.metrics.bump(self.metrics.enqueued, wt)
        self._labeled(self._event_counter, wt, "enqueued").inc()
        reason = self.admission.shed_reason(wt)
        if reason is not None:
            # degradation-ladder shed: refused at the door, before any
            # queue state is touched
            self._account_shed(wt, reason)
            self._wakeup.set()
            return Admission(False, reason=reason)
        if len(q) >= limit:
            if wt in _LIFO_TYPES:
                try:
                    q.popleft()  # drop oldest, keep newest
                except IndexError:
                    # racing producers both saw a full queue and the
                    # manager drained it first — nothing was discarded,
                    # so nothing is accounted (a phantom shed would
                    # break the zero-unaccounted-drops books the other
                    # way: shed counted with no event missing)
                    pass
                else:
                    self._account_shed(wt, "queue_full_drop_oldest")
            else:
                self._account_shed(wt, "queue_full_reject_newest")
                self._wakeup.set()
                return Admission(
                    False, reason="queue_full_reject_newest",
                    retry_after_s=self.admission.retry_after_s(
                        len(q), limit))
        q.append(event)
        # deliberately lock-free, like the deques (module docstring):
        # the worst interleaving with the manager's pop is a batch
        # window stamped one flush interval early/late, self-healing on
        # the next sweep — a lock here would sit on every submit
        if wt in _BATCHABLE and wt not in self._batch_first_seen:
            self._batch_first_seen[wt] = time.monotonic()  # lhlint: allow(LH1003) — benign by design: single GIL-atomic setitem, staleness bounded by the flush interval
        self._wakeup.set()
        return ACCEPTED

    def queue_len(self, wt: WorkType) -> int:
        return len(self._queues[wt])

    # -- manager loop ------------------------------------------------------

    async def start(self):
        if self._manager_task is None:
            self._stopped = False
            self._manager_task = asyncio.ensure_future(self._manager())
            self._sweeper_task = asyncio.ensure_future(self._sweeper())

    async def stop(self, drain: bool = True):
        if drain:
            await self.drain()
        self._stopped = True
        self._wakeup.set()
        if self._manager_task is not None:
            await self._manager_task
            self._manager_task = None
        if self._sweeper_task is not None:
            self._sweeper_task.cancel()
            try:
                await self._sweeper_task
            except asyncio.CancelledError:
                pass
            self._sweeper_task = None

    async def _sweeper(self):
        """Dedicated ladder-sweep cadence.  The manager loop cannot own
        it: it parks on an unbounded ``_idle.acquire()`` whenever every
        worker is busy — which is exactly the overload moment the ladder
        must keep observing (a wedged dispatch batch would otherwise
        freeze escalation for the whole wedge deadline)."""
        while not self._stopped:
            self.sweep_now()
            await asyncio.sleep(self.admit_sweep_s or 0.05)

    async def drain(self):
        """Wait until every queue is empty and all workers are idle.
        ``_manager_holding`` covers the window where the manager has
        POPPED work but is still parked on ``_idle.acquire()`` — queues
        and inflight are both empty there, yet work exists; returning
        then would break every books-balance assertion built on
        drain."""
        while True:
            busy = (any(self._queues[wt] for wt in WorkType)
                    or self._inflight or self._manager_holding)
            if not busy:
                return
            await asyncio.sleep(0.002)

    async def _manager(self):
        while not self._stopped:
            event_or_batch = self._next_work()
            if event_or_batch is None:
                self._wakeup.clear()
                # re-check with a timeout so batch flush deadlines fire
                try:
                    await asyncio.wait_for(
                        self._wakeup.wait(), timeout=self.batch_flush_ms / 1000.0)
                except asyncio.TimeoutError:
                    pass
                continue
            first = (event_or_batch[0] if isinstance(event_or_batch, list)
                     else event_or_batch)
            unprotected = first.work_type not in _PROTECTED_TYPES
            self._manager_holding = True
            try:
                await self._idle.acquire()
                if unprotected:
                    self._unprotected_inflight += 1
                task = asyncio.ensure_future(
                    self._run_work(event_or_batch, unprotected))
                self._inflight.add(task)
                task.add_done_callback(self._inflight.discard)
            finally:
                self._manager_holding = False

    def sweep_now(self) -> int:
        """One admission-ladder observation over the governed queue
        depths (the dedicated _sweeper task runs this at
        LHTPU_ADMIT_SWEEP_S cadence; drills/tests call it directly).
        Also flushes the aggregated shed trace events accumulated since
        the last sweep."""
        self._trace_pending_sheds()
        return self.admission.sweep({
            wt: (len(self._queues[wt]), self._lengths.get(wt, 1024))
            for wt in self.admission.governed})

    def _journal_emit(self, token: str):
        if self._journal is not None:
            self._journal(token)

    def _next_work(self):
        """Pick the highest-priority queue with work; form batches
        opportunistically for attestations/aggregates.

        Priority isolation: unprotected (flood-lane) work is only
        scheduled while at least one worker slot stays free for
        _PROTECTED_TYPES, so a saturated attestation plane can never
        occupy the slot a gossip block or chain segment needs."""
        now = time.monotonic()
        reserve_busy = (
            self._unprotected_inflight >= max(1, self.max_workers - 1))
        flush_s = (self.batch_flush_ms / 1000.0
                   * self.admission.flush_factor())
        for wt in PRIORITY_ORDER:
            q = self._queues[wt]
            if not q:
                continue
            if reserve_busy and wt not in _PROTECTED_TYPES:
                continue
            if wt in _BATCHABLE:
                n = len(q)
                first_seen = self._batch_first_seen.get(wt)
                deadline = (now if first_seen is None
                            else first_seen + flush_s)
                # cross-batch coalescing: while a batch is in flight on
                # the dispatch thread, deadline flushes HOLD — events
                # arriving during the flight merge into one next sweep
                # (bounded by max_batch) instead of trickling out as
                # many small batches queued behind the device.  A full
                # queue still forms immediately: a max_batch sweep is
                # already maximal and keeps the device fed back-to-back.
                # The hold is time-bounded (_COALESCE_HOLD_MAX_S past
                # the deadline): under a sustained flood of another
                # work type the dispatch thread may never go idle, and
                # a sub-max queue must not be starved forever.
                if n >= self.max_batch or (now >= deadline and (
                        self._dispatch_inflight == 0
                        or now - deadline >= _COALESCE_HOLD_MAX_S)):
                    take = min(n, self.max_batch)
                    events = [q.popleft() for _ in range(take)]
                    if not q:
                        self._batch_first_seen.pop(wt, None)
                    # non-empty remainder keeps its (already expired)
                    # window, so it flushes on the next sweep — same
                    # behaviour the absolute-deadline bookkeeping had
                    wait_child = self._labeled(self._wait_hist, wt)
                    for e in events:
                        wait_child.observe(now - e.enqueued_at)
                    if take == 1:
                        self._journal_emit(wt.name)
                        return events[0]
                    self.metrics.batches_formed += 1
                    self.metrics.batch_lanes += take
                    self._labeled(self._batch_hist, wt).observe(take)
                    self._journal_emit(f"{_BATCHABLE[wt].name}({take})")
                    return events
                # not enough lanes yet and deadline pending: let lower
                # priorities run while the batch accumulates
                continue
            self._journal_emit(wt.name)
            event = q.popleft()
            self._labeled(self._wait_hist, wt).observe(
                now - event.enqueued_at)
            return event
        return None

    async def _run_work(self, work, unprotected: bool = False):
        try:
            if isinstance(work, list):
                await self._run_batch(work)
            else:
                await self._run_one(work)
        finally:
            if unprotected:
                self._unprotected_inflight -= 1
            self._idle.release()
            self._wakeup.set()

    async def _run_one(self, event: WorkEvent):
        fn = event.process
        if fn is None:
            if event.process_batch is not None:
                # a deadline flush can hand over a single batchable
                # event; it must still ride the dispatch thread as a
                # 1-lane batch, not be dropped for lacking `process`
                await self._run_batch([event])
            return
        wt_label = event.work_type.name.lower()
        try:
            with tracing.span("beacon_processor.work", work_type=wt_label):
                if asyncio.iscoroutinefunction(fn):
                    await fn()
                else:
                    loop = asyncio.get_running_loop()
                    res = await loop.run_in_executor(self._executor, fn)
                    if asyncio.iscoroutine(res):
                        await res
        except Exception as e:  # worker panics must not kill the manager
            record_swallowed("beacon_processor.worker", e)
        self.metrics.bump(self.metrics.processed, event.work_type)
        self._labeled(self._event_counter, event.work_type,
                      "processed").inc()

    async def _run_batch(self, events: list[WorkEvent]):
        wt = events[0].work_type
        batch_fn = events[0].process_batch
        if batch_fn is None:
            for e in events:
                await self._run_one(e)
            return
        payloads = [e.payload for e in events]
        self._dispatch_inflight += 1
        _record_inflight(self._dispatch_inflight)
        try:
            with tracing.span("beacon_processor.batch",
                              work_type=wt.name.lower(),
                              lanes=len(events)):
                await self._dispatch_batch(batch_fn, payloads)
        except Exception as e:  # batch panics must not kill the manager
            record_swallowed("beacon_processor.batch", e)
        finally:
            self._dispatch_inflight -= 1
            _record_inflight(self._dispatch_inflight)
        self.metrics.bump(self.metrics.processed, wt, len(events))
        self._labeled(self._event_counter, wt, "processed").inc(len(events))

    # -- dispatch-thread supervisor ----------------------------------------

    async def _dispatch_batch(self, batch_fn, payloads):
        """Run one batch on the dedicated dispatch thread under the wedge
        deadline; recover through the synchronous worker-pool path when
        the thread is dead or wedged.

        Recovery RE-RUNS the batch callable: batch handlers must
        tolerate re-execution INCLUDING concurrent execution — the
        abandoned thread, if merely slow rather than dead, may still be
        inside the same batch while the synchronous copy runs.  That is
        the same contract concurrent gossip/RPC copies of one block
        already impose (verification is idempotent; dup gates and
        observed-caches absorb the replay, and the verify paths are
        thread-safe per tests/test_lock_contracts.py)."""
        loop = asyncio.get_running_loop()
        if self._restart_budget_exhausted():
            # PINNED: the storm limiter is saturated, so the current
            # dispatch executor is presumed wedged-and-unreplaceable —
            # go straight to the synchronous path instead of queueing
            # behind it for another full wedge deadline per batch
            await loop.run_in_executor(self._executor, _with_ingest_stall,
                                       batch_fn, payloads)
            return
        gen = self._dispatch_generation
        try:
            fut = loop.run_in_executor(
                self._dispatch_executor, _with_ingest_stall, batch_fn,
                payloads)
        except RuntimeError as e:
            # executor shut down / thread unspawnable: a DEAD dispatch
            # thread — replace it and serve this batch synchronously
            self._recover_dispatch("dead", gen, e)
            await loop.run_in_executor(self._executor, _with_ingest_stall,
                                       batch_fn, payloads)
            return
        wedge = self.dispatch_wedge_s
        if not wedge or wedge <= 0:
            await fut
            return
        try:
            await asyncio.wait_for(fut, timeout=wedge)
        except asyncio.TimeoutError:
            # WEDGED: the thread has been inside one batch past the
            # deadline.  Abandon it (the cancelled future detaches; the
            # thread keeps its GIL turns until it dies with the old
            # executor), restart, and drain this batch synchronously.
            self._recover_dispatch("wedged", gen, None)
            await loop.run_in_executor(self._executor, _with_ingest_stall,
                                       batch_fn, payloads)

    def _restart_budget_exhausted(self) -> bool:
        """True while the restart-storm limiter is saturated (prunes
        stamps older than the window first)."""
        now = time.monotonic()
        while (self._dispatch_restarts
               and now - self._dispatch_restarts[0]
               > self.dispatch_restart_window_s):
            self._dispatch_restarts.popleft()
        return len(self._dispatch_restarts) >= self.dispatch_restart_max

    def _recover_dispatch(self, reason: str, gen: int,
                          exc: BaseException | None) -> None:
        """Replace the dispatch executor (restart-storm-limited) and
        account the fault.  ``gen`` is the generation the failing batch
        was submitted under: if another batch already triggered the
        restart, this one only falls back synchronously."""
        restarted = False
        if gen == self._dispatch_generation:
            if not self._restart_budget_exhausted():
                self._dispatch_restarts.append(time.monotonic())
                self._dispatch_generation += 1
                self.dispatch_restart_count += 1
                old = self._dispatch_executor
                self._dispatch_executor = ThreadPoolExecutor(
                    max_workers=1,
                    thread_name_prefix=(
                        f"bp-dispatch-{self._dispatch_generation}"))
                old.shutdown(wait=False)  # abandon the wedged thread
                restarted = True
            # else: storm limiter — queued batches keep timing out onto
            # the synchronous path until the window drains
        try:
            REGISTRY.counter(
                "beacon_processor_dispatch_restarts_total",
                "dispatch-thread supervisor interventions, by reason and "
                "action",
            ).labels(reason=reason,
                     action="restarted" if restarted else "sync_only").inc()
        except (AttributeError, KeyError, TypeError, ValueError) as e:
            record_swallowed("beacon_processor.dispatch_restart_counter", e)
        # a wedged/dead dispatch thread is a trip condition: the black
        # box dumps with the batches and faults that preceded the wedge
        from lighthouse_tpu.common import flight_recorder as flight

        flight.trip("dispatch_wedge", wedge=reason,
                    restarted=restarted,
                    generation=self._dispatch_generation,
                    inflight=self._dispatch_inflight)
        if exc is not None:
            record_swallowed(f"beacon_processor.dispatch_{reason}", exc)
