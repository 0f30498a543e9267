"""Black-box flight recorder: the story BEHIND a counter increment.

Ten PRs of hardening left every failure *counted* — breaker trips,
ladder escalations, shed totals, quarantines, store repairs — but a
counter is a verdict, not a story.  When a breaker opens in production
the operator needs the ordered sequence of events that led up to it:
which faults fired, which rungs escalated, what was shed, which peers
were downscored.  This module is that black box: a bounded, lock-cheap
ring of structured events that every plane (BLS supervisor, admission
ladder, dispatch supervisor, epoch breaker, store repair, rpc
quarantine, sync accounting, fault injection) emits into, and that
auto-dumps to disk as JSON the moment a TRIP CONDITION fires:

==================  ==========================================================
trip reason         fired by
==================  ==========================================================
bls_breaker_open    a BLS device backend's circuit breaker opening
                    (crypto/bls/api._note_transition)
epoch_breaker_open  the shared epoch/shuffle breaker opening
                    (state_transition/epoch_processing._breaker_fault)
dispatch_wedge      the beacon-processor dispatch-thread supervisor
                    replacing a wedged/dead dispatch thread
store_corruption    the startup integrity sweep repairing/dropping a
                    corrupt meta record (store/hot_cold)
peer_quarantine     a peer crossing into its rpc quarantine window
                    (network/rpc.RequestDiscipline)
books_violation     a registered invariant monitor breaching
                    (common/monitors)
deep_reorg          a canonical-head rewrite at or beyond
                    LHTPU_REORG_TRIP_DEPTH (chain/chain_health)
finality_stall      finality lag reaching LHTPU_FINALITY_STALL_EPOCHS,
                    once per stall episode (chain/chain_health)
==================  ==========================================================

The ring keeps the newest ``LHTPU_FLIGHT_CAPACITY`` events (overflow
rotates the oldest out, counted in ``flight_evicted_total``); a trip
snapshots the whole ring into ``last_dump``, writes it atomically to
``LHTPU_FLIGHT_DIR`` (newest ``LHTPU_FLIGHT_DUMPS`` files kept), and the
HTTP surface serves it at ``GET /lighthouse/observatory/flight``.

Slow requests (``note_root``, called by ``common/tracing`` whenever a root
span closes; nothing below a root is judged, a stage is slow only as part
of its request): ``LHTPU_FLIGHT_SPAN_MS`` is the floor, not the rule.  Each
root name keeps its last 64 durations, and once it has 8 a closure is slow
only when it is over the floor and over one and a half times the second
longest of them: a request that takes what its kind takes leaves the ring
to the breaker and ladder events it exists for, on whichever of its levels
it falls (the one slot in 32 with an epoch transition shares a name with
the 31 without), and a stall that keeps coming back is told twice.  A root that closes slow files one
``slow_request`` with its stage table (per span name the ms, off-CPU ms
and collector ms), counted in ``slow_requests_total{root}`` and logged
once at WARN; until a name has 8 closures the floor alone decides and an
over-the-floor root files a ``slow_span``, as every such span once did.

Cost model: ``emit`` is one small dict + one lock-protected deque append
+ one memoized counter inc — cheap enough to ride the supervisor/ladder
transition paths, which are themselves rare relative to the work they
govern.  Hot per-message paths (gossip shed) emit AGGREGATED events per
sweep, never per message.  ``LHTPU_OBS_ARMED=0`` disarms the whole
observatory plane (recorder, slow-span capture, SLO scoring, monitor
sweeps) for overhead A/B runs.

Stdlib-only (no jax, no numpy): importable from ops/faults and the env
registry layer without dragging in the device stack.
"""

from __future__ import annotations

import json
import os
import statistics
import tempfile
import threading
import time
from collections import deque

from lighthouse_tpu.common import env as envreg
from lighthouse_tpu.common.metrics import (
    REGISTRY,
    record_evicted,
    record_swallowed,
)

#: documented trip reasons (``trip`` accepts any string so drills can
#: add ad-hoc conditions)
TRIP_REASONS = ("bls_breaker_open", "epoch_breaker_open", "dispatch_wedge",
                "store_corruption", "peer_quarantine", "books_violation",
                "deep_reorg", "finality_stall")


#: the slow-request rule: a root name's last _BASELINE_LEN closures are its
#: baseline; once it holds _BASELINE_MIN, slow is over _SLOW_FACTOR times the
#: second longest of them (a level is what two closures reached); at most
#: _BASELINE_NAMES names are kept (oldest out)
_BASELINE_LEN, _BASELINE_MIN, _SLOW_FACTOR, _BASELINE_NAMES = 64, 8, 1.5, 1024


def _jsonable(v):
    if isinstance(v, (bytes, bytearray, memoryview)):
        return "0x" + bytes(v).hex()
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple, set, frozenset)):
        return [_jsonable(x) for x in v]
    return str(v)


class FlightRecorder:
    """Bounded event ring + trip-triggered JSON dumps.

    Thread model: ``emit`` takes one short lock (seq + append); ``trip``
    snapshots under the same lock and does its disk I/O outside it.
    Counter children are memoized so steady-state emits cost one
    ``inc()``.
    """

    def __init__(self, capacity: int | None = None,
                 dump_dir: str | None = None,
                 max_dumps: int | None = None):
        cap = (capacity if capacity is not None
               else envreg.get_int("LHTPU_FLIGHT_CAPACITY", 512) or 512)
        self.capacity = max(16, int(cap))
        self._ring: deque[dict] = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._seq = 0
        self.enabled = envreg.get_bool("LHTPU_OBS_ARMED", True) is not False
        # programmatic fallback below the env knob: a datadir-owning
        # client points this at <datadir>/flight so N nodes on one host
        # never race one dump directory (set_default_dump_dir)
        self._default_dump_dir: str | None = None
        self.dump_dir = (dump_dir if dump_dir is not None
                         else envreg.get("LHTPU_FLIGHT_DIR"))
        md = (max_dumps if max_dumps is not None
              else envreg.get_int("LHTPU_FLIGHT_DUMPS", 8) or 8)
        self.max_dumps = max(1, int(md))
        self.span_floor_ms = max(0.0, envreg.get_float(
            "LHTPU_FLIGHT_SPAN_MS", 50.0) or 0.0)
        self.evicted = 0
        self.trip_count = 0
        self.last_dump: dict | None = None
        self._dump_paths: deque[str] = deque()
        self._counter_memo: dict = {}
        # leaf locks (never nested with self._lock, which is held on
        # the emit path when the memoized counters get built): one for
        # the labeled-child memo, one for the dump-rotation deque —
        # both are touched from every producer thread in the process
        self._memo_lock = threading.Lock()
        self._dump_lock = threading.Lock()
        # root span name -> its last durations (_judge).  Reentrant: the
        # collector can strike inside the hold and run anything
        self._baselines: dict[str, deque] = {}
        self._baseline_lock = threading.RLock()

    # -- accounting helpers (memoized labeled children) ---------------------

    def _count(self, key, make) -> None:
        """Increment the counter child ``make()`` builds, held under
        ``key`` from then on (family names stay literals at the callers:
        lhlint LH501)."""
        child = self._counter_memo.get(key)
        if child is None:
            with self._memo_lock:
                child = self._counter_memo.get(key)
                if child is None:
                    try:
                        child = make()
                    except Exception as e:
                        record_swallowed("flight.counter", e)
                        return
                    self._counter_memo[key] = child
        child.inc()

    def _count_event(self, kind: str) -> None:
        self._count(("event", kind), lambda: REGISTRY.counter(
            "flight_events_total",
            "flight-recorder events by kind").labels(kind=kind))

    def _count_evicted(self) -> None:
        self._count("evicted", lambda: REGISTRY.counter(
            "flight_evicted_total",
            "flight-recorder events rotated out by the ring bound"))

    def _count_trip(self, reason: str) -> None:
        self._count(("trip", reason), lambda: REGISTRY.counter(
            "flight_trips_total",
            "flight-recorder trip conditions fired, by reason",
        ).labels(reason=reason))

    # -- the ring ------------------------------------------------------------

    def emit(self, kind: str, **fields) -> None:
        """File one structured event into the ring (no-op when
        disarmed).  ``fields`` are coerced to JSON-able values at dump
        time, not here — emit stays on the cheap path."""
        if not self.enabled:
            return
        evt = {"kind": kind, "t": time.time()}
        evt.update(fields)
        with self._lock:
            self._seq += 1
            evt["seq"] = self._seq
            if len(self._ring) == self.capacity:
                self.evicted += 1
                evicted = True
            else:
                evicted = False
            self._ring.append(evt)
        self._count_event(kind)
        if evicted:
            self._count_evicted()

    def snapshot(self) -> list[dict]:
        """Ordered copy of the current ring (oldest first)."""
        with self._lock:
            return [dict(e) for e in self._ring]

    def tail(self, n: int) -> list[dict]:
        """Copy of the newest ``n`` events (oldest first) — the scrape
        surface; copies n events under the lock, not the whole ring."""
        with self._lock:
            take = min(n, len(self._ring))
            it = reversed(self._ring)
            out = [dict(next(it)) for _ in range(take)]
        out.reverse()
        return out

    @property
    def seq(self) -> int:
        """The current sequence watermark (the newest event's seq; 0
        before any emit) — clients hand it back as a cursor."""
        with self._lock:
            return self._seq

    def events_since(self, seq: int) -> list[dict]:
        """Events newer than the ``seq`` cursor (oldest first).  Walks
        the ring newest-first and stops at the watermark, so a repeat
        scrape costs O(new events), not O(capacity)."""
        with self._lock:
            out = []
            for e in reversed(self._ring):
                if e["seq"] <= seq:
                    break
                out.append(dict(e))
        out.reverse()
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.evicted = 0

    # -- trips ---------------------------------------------------------------

    def trip(self, reason: str, **fields) -> dict | None:
        """A trip condition fired: file the trip event, snapshot the
        whole ring into ``last_dump``, and write the black box to disk
        (atomic tmp+rename; newest ``max_dumps`` files kept).  Returns
        the dump dict (None when disarmed)."""
        if not self.enabled:
            return None
        self.emit("trip", reason=reason, **fields)
        with self._lock:
            self.trip_count += 1
            ordinal = self.trip_count   # captured under the lock: two
            #                             concurrent trips get distinct
            #                             dump filenames
            events = [dict(e) for e in self._ring]
        dump = {
            "reason": reason,
            "tripped_at": time.time(),
            "trip_fields": {k: _jsonable(v) for k, v in fields.items()},
            "event_count": len(events),
            "events": [{k: _jsonable(v) for k, v in e.items()}
                       for e in events],
        }
        self.last_dump = dump
        self._count_trip(reason)
        self._write_dump(dump, ordinal)
        return dump

    def _resolve_dump_dir(self) -> str:
        if self.dump_dir:
            return self.dump_dir
        return os.path.join(tempfile.gettempdir(), "lighthouse_flight")

    def _write_dump(self, dump: dict, ordinal: int) -> None:
        try:
            d = self._resolve_dump_dir()
            os.makedirs(d, exist_ok=True)
            name = (f"flight-{os.getpid()}-{ordinal:06d}-"
                    f"{dump['reason']}.json")
            path = os.path.join(d, name)
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(dump, fh, indent=1)
            os.replace(tmp, path)
            dump["path"] = path
            stale: list[str] = []
            with self._dump_lock:
                self._dump_paths.append(path)
                while len(self._dump_paths) > self.max_dumps:
                    stale.append(self._dump_paths.popleft())
            for old in stale:   # unlink outside the lock: disk I/O
                try:
                    os.remove(old)
                except OSError:
                    pass  # already gone: pruning is best-effort
        except OSError as e:
            # a full disk must not turn the black box into a crash: the
            # in-memory last_dump (and the HTTP surface) still carry it
            record_swallowed("flight.dump_write", e)

    # -- slow-request capture (called by common/tracing on a root's close) ---

    def _judge(self, name: str, duration_ms: float) -> list | None:
        """File one closure of root ``name`` into its baseline (the last
        ``_BASELINE_LEN``) and return those before it, or None while they
        are fewer than ``_BASELINE_MIN``."""
        evicted = 0
        with self._baseline_lock:
            past = self._baselines.get(name)
            if past is None:
                past = self._baselines[name] = deque(maxlen=_BASELINE_LEN)
                while len(self._baselines) > _BASELINE_NAMES:
                    del self._baselines[next(iter(self._baselines))]
                    evicted += 1
            before = list(past) if len(past) >= _BASELINE_MIN else None
            past.append(duration_ms)
        if evicted:
            record_evicted("flight_baseline", evicted)
        return before

    def note_root(self, root, duration_ms: float, slot: int | None) -> None:
        """A root span (``tracing.Span``) closed.  Every closure feeds its
        name's baseline; one over the latency floor
        (``LHTPU_FLIGHT_SPAN_MS``) is slow when it is also over one and a
        half times the second longest of the name's last closures (its
        upper level, wherever the name has more than one), and files ONE
        ``slow_request`` with its stage table.  While the name has no baseline the floor alone
        decides, and the root is filed as a ``slow_span``."""
        if not self.enabled:
            return
        before = self._judge(root.name, duration_ms)
        if duration_ms < self.span_floor_ms:
            return
        if before is None:
            fields = {"name": root.name, "ms": round(duration_ms, 3)}
            if slot is not None:
                fields["slot"] = int(slot)
            if root.attrs:
                fields["attrs"] = {k: _jsonable(v)
                                   for k, v in root.attrs.items()}
            self.emit("slow_span", **fields)
            return
        before.sort()
        level = before[-2]
        if duration_ms > _SLOW_FACTOR * level:
            self._slow_request(root, duration_ms, statistics.median(before),
                               level, slot)

    def _slow_request(self, root, duration_ms: float, median: float,
                      level: float, slot) -> None:
        """One event, one count and one WARN line for a root that closed
        slow: its stage table says where the time went and, per span name,
        how much of it was off the CPU and in the collector."""
        stages, leaf_ms = {}, 0.0

        def walk(sp):
            nonlocal leaf_ms
            ms = sp.duration_ms()
            row = stages.setdefault(sp.name, dict.fromkeys(
                ("n", "ms", "offcpu_ms", "gc_ms"), 0))
            row["n"] += 1
            row["ms"] += ms
            row["offcpu_ms"] += sp.offcpu_s() * 1000.0
            row["gc_ms"] += sp.gc_s * 1000.0
            children = list(sp.children)
            if not children and sp is not root:
                leaf_ms += ms
            for child in children:
                walk(child)

        walk(root)
        fields = {
            "root": root.name, "ms": round(duration_ms, 3),
            "median_ms": round(median, 3), "level_ms": round(level, 3),
            "slot": None if slot is None else int(slot),
            # share of the root its leaf spans cover: what the table explains
            "covered_pct": round(100.0 * leaf_ms / duration_ms, 1),
            "stages": {name: {k: (round(v, 3) if isinstance(v, float) else v)
                              for k, v in row.items()
                              if v or k in ("n", "ms")}
                       for name, row in stages.items()},
        }
        if root.attrs:
            fields["attrs"] = {k: _jsonable(v) for k, v in root.attrs.items()}
        self.emit("slow_request", **fields)
        self._count(("slow_request", root.name), lambda: REGISTRY.counter(
            "slow_requests_total",
            "root spans that closed over the latency floor and over one "
            "and a half times the second longest of their name's last "
            "closures, by root span name").labels(root=root.name))
        from lighthouse_tpu.common.logging import Logger

        Logger("flight").warn(
            "slow request", **{k: fields[k] for k in (
                "root", "ms", "median_ms", "level_ms", "slot",
                "covered_pct")},
            stages=json.dumps(fields["stages"], separators=(",", ":")))

    def reconfigure(self) -> None:
        """Re-read the LHTPU_FLIGHT_* / LHTPU_OBS_ARMED knobs (tests
        mutate os.environ after import).  A changed capacity rebuilds
        the ring in place, keeping the newest events."""
        self.enabled = envreg.get_bool("LHTPU_OBS_ARMED", True) is not False
        self.dump_dir = (envreg.get("LHTPU_FLIGHT_DIR")
                         or self._default_dump_dir)
        self.span_floor_ms = max(0.0, envreg.get_float(
            "LHTPU_FLIGHT_SPAN_MS", 50.0) or 0.0)
        self.max_dumps = max(1, envreg.get_int("LHTPU_FLIGHT_DUMPS", 8) or 8)
        cap = max(16, envreg.get_int("LHTPU_FLIGHT_CAPACITY", 512) or 512)
        with self._lock:
            # check INSIDE the hold: a concurrent reconfigure between a
            # bare check and the rebuild would rebuild the ring twice
            if cap != self.capacity:
                self.capacity = cap
                self._ring = deque(self._ring, maxlen=cap)


RECORDER = FlightRecorder()


def emit(kind: str, **fields) -> None:
    """Module-level convenience: file one event into the process
    recorder (the emit funnel the LH605 lint pass recognizes)."""
    RECORDER.emit(kind, **fields)


def set_default_dump_dir(path: str) -> None:
    """Point the recorder's dump directory at a node-scoped default
    (``<datadir>/flight``) unless LHTPU_FLIGHT_DIR pins it explicitly.
    Survives reconfigure(): the env knob stays the override, this stays
    the fallback — N nodes on one host each dump under their own
    datadir instead of racing one shared directory."""
    RECORDER._default_dump_dir = path
    if not envreg.get("LHTPU_FLIGHT_DIR"):
        RECORDER.dump_dir = path


def trip(reason: str, **fields) -> dict | None:
    """Module-level convenience: fire one trip condition."""
    return RECORDER.trip(reason, **fields)


def observatory_view(since_seq: int | None = None) -> dict:
    """The GET /lighthouse/observatory/flight payload: the last trip's
    black box (if any) plus the live ring tail.  With a ``since_seq``
    cursor the tail is every event newer than that watermark instead of
    the fixed newest-32 window; ``seq`` in the payload is the cursor to
    hand back on the next scrape."""
    r = RECORDER
    tail = r.tail(32) if since_seq is None else r.events_since(since_seq)
    return {
        "armed": r.enabled,
        "capacity": r.capacity,
        "events": len(r),
        "evicted": r.evicted,
        "trips": r.trip_count,
        "seq": r.seq,
        "last_dump": r.last_dump,
        "tail": [{k: _jsonable(v) for k, v in e.items()} for e in tail],
    }
