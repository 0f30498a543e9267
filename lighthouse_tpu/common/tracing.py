"""Cross-layer tracing: lightweight spans filed into per-slot timelines.

The offload path spans many layers (gossip arrival -> beacon_processor
queue -> device batch -> fork choice -> head update) and the per-layer
metrics in common/metrics.py cannot show how ONE block's time divided
between them.  This module is the connective tissue: a `span(name,
**attrs)` context manager / decorator records nested wall-time spans via
`contextvars` (so concurrent threads and asyncio tasks never cross-link),
and finished root spans are filed into a bounded in-memory ring of
per-slot timelines served by `GET /lighthouse/tracing/{slot}` (the
Lighthouse block-delay breakdown analogue).

Costs are bounded by construction: a span is one small object and, at
each end, one read of `perf_counter()` and (unless its name runs brief,
below) one of `thread_time()`; the ring keeps the newest `capacity` slots
and at most `max_spans_per_slot` root spans per slot — overflow rotates the
OLDEST root out (newest-wins), so a long-lived process's UNSLOTTED
timeline shows recent device-plane activity, not frozen startup content.
Tracing is always on — per-span cost is far below a single host<->device
crossing, the thing being measured.

A span also says WHY it took what it took, as far as the process can see:
the opening thread's CPU seconds against the wall (`cpu_s`; `offcpu_ms` in
the JSON: descheduled, blocked, or waiting for the GIL) and the collector's
pauses that struck inside it (`gc_s`).  Both are inclusive of children,
like the duration, and of the opening thread only: a span that hands its
work to another thread and waits (`bls.verify` under the supervisor's
watchdog) reads the wait as off-CPU and the worker's spans, nested under
it, carry the worker's own readings.  The CPU clock is a system call (one
that enters a user-space kernel on some hosts: 16 us where the span's own
work is 3), so a span reads it only where the reading can say something: a
name whose closures run under `_EVIDENCE_MIN_S` on average takes none after
its first `_EVIDENCE_ALWAYS` (`cpu_s` stays None), and its time off the CPU
shows in the spans around it.
:func:`install_host_probes` hooks the collector (`gc.callbacks`): a
collection of generation 1 or 2 inside a span is drawn as a `host.gc` span
where it struck, every pause is added to the innermost open span, and
`host_gc_pause_seconds_total` / `host_gc_collections_total{generation}`
advance whenever a root span closes.

Two hooks make a span the one timing primitive of the data plane:
``span(..., observe=fn)`` hands the span's duration (seconds) to ``fn`` on
exit, so a stage histogram is fed by the same ``with`` that draws the span;
and :func:`set_annotator` installs a factory (the data plane passes
``jax.profiler.TraceAnnotation``, from ``compile_cache.configure()``) whose
context is entered and exited with every span, which puts the program's
spans on the ``/host:CPU`` plane of any live profiler trace, on the device
trace's clock.  This module imports no jax: a host-only process never
installs one and pays nothing.
"""

from __future__ import annotations

import contextvars
import functools
import gc
import inspect
import json
import threading
import time
from collections import OrderedDict, deque

from lighthouse_tpu.common import flight_recorder as flight
from lighthouse_tpu.common.metrics import REGISTRY, record_swallowed

# Roots that finish with no slot (device-plane work outside any block
# context) are filed here so they stay inspectable.
UNSLOTTED = -1

_current: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "lhtpu_current_span", default=None)
_slot_ctx: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "lhtpu_current_slot", default=None)

# factory(name, **scalar_attrs) -> context manager, entered/exited with
# every span while set (see set_annotator)
_annotator = None

_perf = time.perf_counter
# The thread's CPU clock.  A system call at each end of a span that reads
# it: 0.3 us in the sandbox, 5.4 us back to back and ~16 us after a body on
# the benchmark's machines (gVisor), where it moves in 10 ms ticks.
_thread_cpu = time.thread_time
# A name's closures under this on average read no CPU clock (after the
# first _EVIDENCE_ALWAYS, which every name reads): two calls would cost the
# dearest host over a hundredth of the span, for a reading under a fifth of
# its tick.  The mean is over the last _MEAN_OVER closures or so.
_EVIDENCE_MIN_S, _EVIDENCE_ALWAYS, _MEAN_OVER = 0.002, 8, 64
# span name -> [closures counted (to _MEAN_OVER), their mean seconds, the
# name's child of span_offcpu_seconds or None]; names are literals of the
# code, so it is bounded by the code
_names: dict[str, list] = {}


def set_annotator(factory) -> None:
    """Install (or, with None, remove) the annotator: ``factory(name,
    **scalar_attrs)`` must return a context manager.  It is entered right
    after a span starts and exited right before it ends, so annotations
    nest exactly as the spans do."""
    global _annotator
    _annotator = factory


def _jsonable(v):
    if isinstance(v, (bytes, bytearray, memoryview)):
        return "0x" + bytes(v).hex()
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


class Span:
    """One timed region.  `start`/`end` are perf_counter seconds;
    `wall_start` is epoch time so timelines can be correlated with logs.
    `cpu_s` is the opening thread's CPU seconds between start and end (None
    where the span read no CPU clock: open still, or a name that runs
    brief), `gc_s` the collector's pauses inside the span; both inclusive
    of children and set when the span closes (class-level defaults until
    then: a span is built on the hot path)."""

    cpu_s: float | None = None
    gc_s: float = 0.0

    def __init__(self, name: str, attrs: dict | None = None,
                 start: float = 0.0, end: float | None = None,
                 wall_start: float = 0.0,
                 children: list["Span"] | None = None):
        self.name = name
        self.attrs = {} if attrs is None else attrs
        self.start = start
        self.end = end
        self.wall_start = wall_start
        self.children = [] if children is None else children

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, {self.duration_ms():.3f} ms, "
                f"{len(self.children)} children)")

    def duration_s(self) -> float:
        end = self.end if self.end is not None else time.perf_counter()
        return end - self.start

    def duration_ms(self) -> float:
        return self.duration_s() * 1000.0

    def offcpu_s(self) -> float:
        """Wall seconds of a closed span its thread was not on the CPU (0
        where the span read no CPU clock).  Not clamped: where the thread's
        CPU clock moves in ticks (10 ms on the benchmark's machines) one
        span's reading is good to a tick and can be negative, and the sum
        over many spans stays unbiased."""
        if self.cpu_s is None or self.end is None:
            return 0.0
        return self.end - self.start - self.cpu_s

    def to_dict(self, base: float | None = None) -> dict:
        base = self.start if base is None else base
        d: dict = {
            "name": self.name,
            "offset_ms": round((self.start - base) * 1000.0, 3),
            "duration_ms": round(self.duration_ms(), 3),
        }
        # the evidence, only where there is any
        offcpu_ms = round(self.offcpu_s() * 1000.0, 3)
        if abs(offcpu_ms) >= 0.005:  # under it: the readings' own order
            d["offcpu_ms"] = offcpu_ms
        if self.gc_s:
            d["gc_ms"] = round(self.gc_s * 1000.0, 3)
        if self.attrs:
            d["attrs"] = {k: _jsonable(v) for k, v in self.attrs.items()}
        if self.children:
            d["children"] = [c.to_dict(base) for c in self.children]
        return d


class _SlotTimeline:
    def __init__(self, slot: int, max_spans: int):
        self.slot = slot
        self.max_spans = max_spans
        self.spans: deque[Span] = deque(maxlen=max_spans)
        self.dropped = 0  # oldest roots rotated out by the bound

    def to_dict(self) -> dict:
        roots = list(self.spans)
        return {
            "slot": self.slot,
            "dropped_spans": self.dropped,
            "spans": [
                dict(r.to_dict(), wall_start=round(r.wall_start, 3))
                for r in roots
            ],
        }


class Tracer:
    """Bounded ring of per-slot timelines (newest `capacity` slots)."""

    def __init__(self, capacity: int = 64, max_spans_per_slot: int = 256):
        self.capacity = capacity
        self.max_spans_per_slot = max_spans_per_slot
        self._ring: OrderedDict[int, _SlotTimeline] = OrderedDict()
        self._lock = threading.Lock()
        self.enabled = True
        self._dropped = None  # tracing_spans_dropped_total, held once
        # root-span sinks (the SLO engine stitches slot timelines out of
        # finished roots); called OUTSIDE the ring lock, exceptions
        # swallowed-but-accounted — a broken sink must not break tracing
        self._sinks: list = []

    def add_sink(self, fn) -> None:
        """Register ``fn(root_span, slot)`` to observe every finished
        root span (idempotent per callable)."""
        if fn not in self._sinks:
            self._sinks.append(fn)

    def remove_sink(self, fn) -> None:
        if fn in self._sinks:
            self._sinks.remove(fn)

    def span(self, name: str, slot: int | None = None, **attrs) -> "span":
        return span(name, slot=slot, tracer=self, **attrs)

    def record_root(self, sp: Span, slot: int | None) -> None:
        if not self.enabled:
            return
        key = UNSLOTTED if slot is None else int(slot)
        with self._lock:
            tl = self._ring.get(key)
            if tl is None:
                tl = _SlotTimeline(key, self.max_spans_per_slot)
                self._ring[key] = tl
                while len(self._ring) > self.capacity:
                    self._ring.popitem(last=False)
            else:
                self._ring.move_to_end(key)
            if len(tl.spans) == tl.max_spans:
                # newest-wins: deque(maxlen) rotates the oldest root out
                tl.dropped += 1
                if self._dropped is None:
                    self._dropped = REGISTRY.counter(
                        "tracing_spans_dropped_total",
                        "root spans rotated out by the per-slot bound")
                self._dropped.inc()
            tl.spans.append(sp)
        # snapshot: add_sink/remove_sink mutate the list from other
        # threads, and index-based iteration over a shifting list can
        # skip a live sink or call a just-removed one
        for sink in tuple(self._sinks):
            try:
                sink(sp, key)
            except Exception as e:
                record_swallowed("tracing.root_sink", e)

    def timeline(self, slot: int) -> dict | None:
        with self._lock:
            tl = self._ring.get(int(slot))
            return tl.to_dict() if tl is not None else None

    def slots(self) -> list[int]:
        with self._lock:
            return sorted(self._ring)

    def to_json(self, slot: int) -> str:
        tl = self.timeline(slot)
        return json.dumps(tl if tl is not None else {"slot": int(slot),
                                                     "spans": []})

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


TRACER = Tracer()


# -- the host runtime's own evidence -------------------------------------------

_OFFCPU_BUCKETS = (0.0001, 0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                   1.0, 2.5)


def _offcpu_child(name: str):
    return REGISTRY.histogram(
        "span_offcpu_seconds",
        "wall seconds of a stage span (one opened with observe=) its "
        "thread was not on the CPU: descheduled, blocked, or waiting "
        "for the interpreter lock; a name whose closures run under two "
        "milliseconds on average reads no CPU clock and adds nothing",
        buckets=_OFFCPU_BUCKETS).labels(span=name)


class _HostProbes:
    """The collector's hook.

    The callback does plain additions and (generation 1 or 2) opens and
    closes one span; the registry's counters advance in :meth:`flush`,
    which the span primitive calls whenever a root closes.  Nothing the
    callback runs takes a lock of this package: a collection can strike
    between any two bytecodes, inside the recorder's and the tracer's
    critical sections too, and a ``host.gc`` span is never a root, feeds
    no histogram and is not the recorder's to judge."""

    def __init__(self):
        self._lock = threading.Lock()
        self._open: list = []      # (start, host.gc span or None), a stack
        self.pause_s = [0.0, 0.0, 0.0]
        self.collections = [0, 0, 0]
        self._flushed = ([0.0, 0.0, 0.0], [0, 0, 0])
        pauses = REGISTRY.counter(
            "host_gc_pause_seconds_total",
            "seconds the cyclic collector held the interpreter, by "
            "generation collected")
        runs = REGISTRY.counter(
            "host_gc_collections_total",
            "collections of the cyclic collector, by generation")
        # the children exist from here on: a still family reads 0, not absent
        self._pause_children = [pauses.labels(generation=g) for g in range(3)]
        self._run_children = [runs.labels(generation=g) for g in range(3)]

    def on_gc(self, phase: str, info: dict) -> None:
        try:
            if phase == "start":
                cm = None
                if info["generation"] >= 1 and _current.get() is not None:
                    # a generation-0 pass is microseconds and only adds up;
                    # so does a pass outside any span, which has nowhere
                    # to nest and is no request of anybody's
                    cm = span("host.gc", generation=info["generation"])
                    cm.__enter__()
                self._open.append((_perf(), cm))
                return
            t0, cm = self._open.pop()
            pause = _perf() - t0
            gen = info["generation"]
            self.pause_s[gen] += pause
            self.collections[gen] += 1
            if cm is not None:
                cm._span.attrs["collected"] = info["collected"]
                cm.__exit__(None, None, None)
            sp = _current.get()
            if sp is not None:
                sp.gc_s += pause
        except Exception as e:
            record_swallowed("tracing.gc_hook", e)

    def flush(self) -> None:
        with self._lock:
            flushed_s, flushed_n = self._flushed
            for gen in range(3):
                moved = self.collections[gen] - flushed_n[gen]
                if moved:
                    self._run_children[gen].inc(moved)
                    flushed_n[gen] += moved
                    grown = self.pause_s[gen] - flushed_s[gen]
                    self._pause_children[gen].inc(grown)
                    flushed_s[gen] += grown


_host: _HostProbes | None = None


def install_host_probes() -> None:
    """Hook the collector (idempotent; the data plane calls it from
    ``compile_cache.configure()``, beside the annotator).  A process that
    never calls it pays nothing at a root."""
    global _host
    if _host is None:
        _host = _HostProbes()
        gc.callbacks.append(_host.on_gc)


class span:
    """Context manager AND decorator for one traced region.

        with span("block_import", slot=7, source="gossip"):
            with span("signature_verify"):
                ...

        @span("bls.verify_pipeline")
        def verify(...): ...

    Nesting rides on contextvars, so spans opened by concurrent threads
    or asyncio tasks attach to THEIR enclosing span, never each other's.
    A root span (no enclosing span in this context) is filed into the
    tracer's ring under its `slot` (explicit, else inherited from the
    nearest enclosing span that set one, else UNSLOTTED).

    ``observe`` is called once on exit with the span's duration in
    seconds (also when the body raised): call sites pass their owner
    module's stage-histogram helper, so one ``with`` both draws the span
    and feeds the metric.
    """

    _annotation = None  # the annotator's context while the span is open

    def __init__(self, name: str, slot: int | None = None,
                 tracer: Tracer | None = None, observe=None, **attrs):
        self.name = name
        self.slot = slot
        self.attrs = attrs
        self.observe = observe
        self.tracer = tracer if tracer is not None else TRACER

    def __enter__(self) -> Span:
        attrs = dict(self.attrs)
        slot = self.slot
        if slot is not None:
            attrs.setdefault("slot", int(slot))
        sp = self._span = Span(self.name, attrs)
        sp.wall_start = time.time()
        stat = _names.get(self.name)
        if stat is None:
            stat = _names[self.name] = [0, 0.0, None]
        # the thread's CPU just outside the wall readings, unless the name
        # runs brief (see _EVIDENCE_MIN_S)
        cpu0 = (None if stat[0] >= _EVIDENCE_ALWAYS
                and stat[1] < _EVIDENCE_MIN_S else _thread_cpu())
        sp.start = _perf()
        # (parent, the name's record, the reading, the context tokens)
        self._open = (_current.get(), stat, cpu0, _current.set(sp),
                      None if slot is None else _slot_ctx.set(int(slot)))
        if _annotator is not None:
            try:
                self._annotation = _annotator(self.name, **{
                    k: v for k, v in attrs.items()
                    if isinstance(v, (str, int, float, bool))})
                self._annotation.__enter__()
            except Exception as e:
                self._annotation = None
                record_swallowed("tracing.annotator", e)
        return sp

    def __exit__(self, exc_type, exc, tb):
        sp = self._span
        if self._annotation is not None:
            try:
                self._annotation.__exit__(exc_type, exc, tb)
            except Exception as e:
                record_swallowed("tracing.annotator", e)
            self._annotation = None
        end = sp.end = _perf()
        parent, stat, cpu0, token, slot_token = self._open
        if cpu0 is not None:
            sp.cpu_s = _thread_cpu() - cpu0
        if exc_type is not None:
            sp.attrs.setdefault("error", exc_type.__name__)
        slot = self.slot if self.slot is not None else _slot_ctx.get()
        _current.reset(token)
        if slot_token is not None:
            _slot_ctx.reset(slot_token)
        dur_s = end - sp.start
        # the name's running mean (threads race here: a closure lost or
        # counted twice moves a mean nobody needs exact)
        n = stat[0] = min(stat[0] + 1, _MEAN_OVER)
        stat[1] += (dur_s - stat[1]) / n
        if parent is not None:
            # a parent on another thread may have closed already (a worker
            # the watchdog abandoned that finishes late): harmless, the
            # tree it appends to is filed and read-only users copy it
            if sp.gc_s:
                parent.gc_s += sp.gc_s
            parent.children.append(sp)
        else:
            self.tracer.record_root(sp, slot)
            # a root is a request: the flight recorder judges it against
            # its name's own baseline (nothing below a root is judged)
            flight.RECORDER.note_root(sp, dur_s * 1000.0, slot)
            if _host is not None:
                _host.flush()
        if self.observe is not None:
            try:
                self.observe(dur_s)
                if cpu0 is not None:
                    child = stat[2]
                    if child is None:
                        child = stat[2] = _offcpu_child(sp.name)
                    child.observe(sp.offcpu_s())
            except Exception as e:
                record_swallowed("tracing.observe", e)
        return False

    def __call__(self, fn):
        if inspect.iscoroutinefunction(fn):
            @functools.wraps(fn)
            async def awrapped(*args, **kwargs):
                with span(self.name, slot=self.slot, tracer=self.tracer,
                          observe=self.observe, **self.attrs):
                    return await fn(*args, **kwargs)
            return awrapped

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with span(self.name, slot=self.slot, tracer=self.tracer,
                      observe=self.observe, **self.attrs):
                return fn(*args, **kwargs)
        return wrapped


def current_span() -> Span | None:
    return _current.get()


def add_attrs(**attrs) -> None:
    """Annotate the innermost open span (no-op outside any span) — for
    values only known mid-region, e.g. a batch size discovered after
    queue drain."""
    sp = _current.get()
    if sp is not None:
        sp.attrs.update(attrs)
