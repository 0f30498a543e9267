"""common/tracing: span nesting, context isolation, ring bounds, JSON."""

import asyncio
import json
import subprocess
import sys
import threading

import pytest

from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.tracing import (
    UNSLOTTED,
    Tracer,
    add_attrs,
    current_span,
    span,
)


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


class TestSpanNesting:
    def test_nested_spans_build_one_tree(self):
        t = Tracer()
        with t.span("root", slot=7, source="gossip"):
            with t.span("child_a"):
                with t.span("grandchild"):
                    pass
            with t.span("child_b"):
                pass
        tl = t.timeline(7)
        assert tl is not None and tl["slot"] == 7
        (root,) = tl["spans"]
        assert root["name"] == "root"
        assert root["attrs"]["source"] == "gossip"
        assert [c["name"] for c in root["children"]] == ["child_a",
                                                         "child_b"]
        assert root["children"][0]["children"][0]["name"] == "grandchild"

    def test_durations_and_offsets_are_consistent(self):
        t = Tracer()
        with t.span("root", slot=1):
            with t.span("inner"):
                pass
        root = t.timeline(1)["spans"][0]
        inner = root["children"][0]
        assert root["offset_ms"] == 0.0
        assert inner["offset_ms"] >= 0.0
        assert root["duration_ms"] >= inner["duration_ms"] >= 0.0

    def test_decorator_sync_and_async(self):
        t = Tracer()

        @span("work", slot=3, tracer=t)
        def work(x):
            return x + 1

        @span("awork", slot=4, tracer=t)
        async def awork(x):
            return x * 2

        assert work(1) == 2
        assert _run(awork(2)) == 4
        assert t.timeline(3)["spans"][0]["name"] == "work"
        assert t.timeline(4)["spans"][0]["name"] == "awork"

    def test_exception_annotates_and_still_records(self):
        t = Tracer()
        try:
            with t.span("boom", slot=9):
                raise ValueError("x")
        except ValueError:
            pass
        root = t.timeline(9)["spans"][0]
        assert root["attrs"]["error"] == "ValueError"
        assert current_span() is None  # context restored

    def test_add_attrs_mid_span(self):
        t = Tracer()
        with t.span("batch", slot=2):
            add_attrs(lanes=128)
        assert t.timeline(2)["spans"][0]["attrs"]["lanes"] == 128
        add_attrs(ignored=True)  # no open span: must not raise

    def test_slot_inherited_from_enclosing_span(self):
        # a root finishing inside another trace context files under the
        # slot that context established
        t = Tracer()
        with t.span("outer", slot=11):
            with t.span("inner"):
                pass
        (root,) = t.timeline(11)["spans"]
        assert [c["name"] for c in root["children"]] == ["inner"]

    def test_unslotted_roots_are_kept(self):
        t = Tracer()
        with t.span("no_slot"):
            pass
        assert t.timeline(UNSLOTTED)["spans"][0]["name"] == "no_slot"


class TestContextIsolation:
    def test_threads_do_not_cross_link(self):
        t = Tracer()
        barrier = threading.Barrier(2, timeout=10)
        errors = []

        def worker(i):
            try:
                with t.span(f"thread_{i}", slot=i):
                    barrier.wait()  # both spans open simultaneously
                    with t.span(f"child_{i}"):
                        barrier.wait()
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(2)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errors
        for i in range(2):
            (root,) = t.timeline(i)["spans"]
            assert root["name"] == f"thread_{i}"
            assert [c["name"] for c in root["children"]] == [f"child_{i}"]

    def test_async_tasks_do_not_cross_link(self):
        t = Tracer()

        async def task(i):
            with t.span(f"task_{i}", slot=100 + i):
                await asyncio.sleep(0.01)  # interleave the two tasks
                with t.span(f"tchild_{i}"):
                    await asyncio.sleep(0.01)

        async def main():
            await asyncio.gather(task(0), task(1))

        _run(main())
        for i in range(2):
            (root,) = t.timeline(100 + i)["spans"]
            assert root["name"] == f"task_{i}"
            assert [c["name"] for c in root["children"]] == [f"tchild_{i}"]


class TestRingBounds:
    def test_slot_ring_evicts_oldest(self):
        t = Tracer(capacity=4)
        for s in range(10):
            with t.span("tick", slot=s):
                pass
        assert t.slots() == [6, 7, 8, 9]
        assert t.timeline(0) is None

    def test_per_slot_span_bound_rotates_newest_wins(self):
        t = Tracer(max_spans_per_slot=3)
        for i in range(5):
            with t.span(f"flood_{i}", slot=1):
                pass
        tl = t.timeline(1)
        assert [s["name"] for s in tl["spans"]] == [
            "flood_2", "flood_3", "flood_4"]
        assert tl["dropped_spans"] == 2

    def test_active_slot_not_evicted_by_churn(self):
        # re-recording into an existing slot refreshes its ring position
        t = Tracer(capacity=2)
        for s in (1, 2):
            with t.span("a", slot=s):
                pass
        with t.span("b", slot=1):
            pass
        with t.span("a", slot=3):
            pass
        assert t.slots() == [1, 3]


class TestTimelineJson:
    def test_to_json_round_trips(self):
        t = Tracer()
        with t.span("root", slot=5, root_hash=b"\x12\x34", n=3):
            with t.span("leaf"):
                pass
        parsed = json.loads(t.to_json(5))
        assert parsed["slot"] == 5
        root = parsed["spans"][0]
        assert root["attrs"]["root_hash"] == "0x1234"  # bytes -> hex
        assert root["attrs"]["n"] == 3
        assert root["wall_start"] > 0
        assert json.loads(t.to_json(999)) == {"slot": 999, "spans": []}


class TestObserve:
    def test_observe_called_once_with_the_duration(self):
        t = Tracer()
        seen = []
        with t.span("stage", observe=seen.append) as sp:
            pass
        assert seen == [sp.duration_s()]
        assert seen[0] == sp.end - sp.start >= 0.0

    def test_observe_called_when_the_body_raises(self):
        t = Tracer()
        seen = []
        with pytest.raises(ValueError):
            with t.span("stage", observe=seen.append):
                raise ValueError("boom")
        assert len(seen) == 1 and seen[0] >= 0.0

    def test_observe_rides_the_decorator_and_is_no_attr(self):
        t = Tracer()
        seen = []

        @t.span("decorated", observe=seen.append)
        def f():
            return current_span().attrs

        assert f() == {} and f() == {}
        assert len(seen) == 2

    def test_a_broken_observer_does_not_break_the_span(self):
        t = Tracer()

        def broken(seconds):
            raise RuntimeError("observer")

        with t.span("stage", observe=broken):
            pass
        assert t.timeline(UNSLOTTED)["spans"][0]["name"] == "stage"


class _Recorder:
    """An annotator factory that records enter/exit in order."""

    def __init__(self):
        self.events = []

    def __call__(self, name, **attrs):
        rec = self

        class _Ctx:
            def __enter__(self):
                rec.events.append(("enter", name, attrs))

            def __exit__(self, *exc):
                rec.events.append(("exit", name, attrs))

        return _Ctx()


@pytest.fixture
def annotator():
    before = tracing._annotator
    rec = _Recorder()
    tracing.set_annotator(rec)
    yield rec
    tracing.set_annotator(before)


class TestAnnotator:
    def test_entered_and_exited_in_order_with_the_spans_name(self, annotator):
        t = Tracer()
        with t.span("outer", slot=3, n=2, blob=b"\x00", label="x"):
            with t.span("inner"):
                pass
        # scalar attrs only reach the annotator (bytes stay in the tracer)
        outer = {"slot": 3, "n": 2, "label": "x"}
        assert annotator.events == [
            ("enter", "outer", outer), ("enter", "inner", {}),
            ("exit", "inner", {}), ("exit", "outer", outer)]

    def test_exited_when_the_body_raises(self, annotator):
        with pytest.raises(KeyError):
            with Tracer().span("stage"):
                raise KeyError("k")
        assert [e[0] for e in annotator.events] == ["enter", "exit"]

    def test_never_called_when_unset(self, annotator):
        tracing.set_annotator(None)
        with Tracer().span("stage"):
            pass
        assert annotator.events == []

    def test_a_broken_annotator_does_not_break_the_span(self):
        before = tracing._annotator

        def broken(name, **attrs):
            raise RuntimeError("annotator")

        tracing.set_annotator(broken)
        try:
            t = Tracer()
            with t.span("stage"):
                pass
            assert t.timeline(UNSLOTTED)["spans"][0]["name"] == "stage"
        finally:
            tracing.set_annotator(before)


def test_tracing_imports_no_jax():
    """Spans cost a host-only process no jax import: the annotator is
    installed by the data plane (compile_cache.configure), never here."""
    code = ("import sys; from lighthouse_tpu.common import tracing; "
            "assert tracing._annotator is None; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0
