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

Costs are bounded by construction: a span is one small object + two
`perf_counter()` reads; the ring keeps the newest `capacity` slots and at
most `max_spans_per_slot` root spans per slot — overflow rotates the
OLDEST root out (newest-wins), so a long-lived process's UNSLOTTED
timeline shows recent device-plane activity, not frozen startup content.
Tracing is always on — per-span cost is far below a single host<->device
crossing, the thing being measured.

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
import inspect
import json
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field

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


@dataclass
class Span:
    """One timed region.  `start`/`end` are perf_counter seconds;
    `wall_start` is epoch time so timelines can be correlated with logs."""

    name: str
    attrs: dict = field(default_factory=dict)
    start: float = 0.0
    end: float | None = None
    wall_start: float = 0.0
    children: list["Span"] = field(default_factory=list)

    def duration_s(self) -> float:
        end = self.end if self.end is not None else time.perf_counter()
        return end - self.start

    def duration_ms(self) -> float:
        return self.duration_s() * 1000.0

    def to_dict(self, base: float | None = None) -> dict:
        base = self.start if base is None else base
        d: dict = {
            "name": self.name,
            "offset_ms": round((self.start - base) * 1000.0, 3),
            "duration_ms": round(self.duration_ms(), 3),
        }
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
                REGISTRY.counter(
                    "tracing_spans_dropped_total",
                    "root spans rotated out by the per-slot bound").inc()
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

    def __init__(self, name: str, slot: int | None = None,
                 tracer: Tracer | None = None, observe=None, **attrs):
        self.name = name
        self.slot = slot
        self.attrs = attrs
        self.observe = observe
        self.tracer = tracer if tracer is not None else TRACER

    def __enter__(self) -> Span:
        attrs = dict(self.attrs)
        if self.slot is not None:
            attrs.setdefault("slot", int(self.slot))
        self._span = Span(name=self.name, attrs=attrs,
                          start=time.perf_counter(), wall_start=time.time())
        self._parent = _current.get()
        self._token = _current.set(self._span)
        self._slot_token = (_slot_ctx.set(int(self.slot))
                            if self.slot is not None else None)
        self._annotation = None
        if _annotator is not None:
            try:
                self._annotation = _annotator(self.name, **{
                    k: v for k, v in attrs.items()
                    if isinstance(v, (str, int, float, bool))})
                self._annotation.__enter__()
            except Exception as e:
                self._annotation = None
                record_swallowed("tracing.annotator", e)
        return self._span

    def __exit__(self, exc_type, exc, tb):
        sp = self._span
        if self._annotation is not None:
            try:
                self._annotation.__exit__(exc_type, exc, tb)
            except Exception as e:
                record_swallowed("tracing.annotator", e)
        sp.end = time.perf_counter()
        if exc_type is not None:
            sp.attrs.setdefault("error", exc_type.__name__)
        slot = self.slot if self.slot is not None else _slot_ctx.get()
        _current.reset(self._token)
        if self._slot_token is not None:
            _slot_ctx.reset(self._slot_token)
        # closures above the flight recorder's latency floor become
        # black-box events (sub-floor spans pay one float compare)
        dur_ms = (sp.end - sp.start) * 1000.0
        if dur_ms >= flight.RECORDER.span_floor_ms:
            flight.RECORDER.note_span(sp.name, dur_ms, slot, sp.attrs)
        if self._parent is not None:
            self._parent.children.append(sp)
        else:
            self.tracer.record_root(sp, slot)
        if self.observe is not None:
            try:
                self.observe(sp.duration_s())
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
