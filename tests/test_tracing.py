"""common/tracing: span nesting, context isolation, ring bounds, JSON."""

import asyncio
import contextvars
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


# -- why a span took what it took: the host runtime's evidence ------------------

def _counter(name, **labels):
    from lighthouse_tpu.common.metrics import REGISTRY

    want = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    for line in REGISTRY.render().splitlines():
        if line.startswith(f"{name}{{{want}}} "):
            return float(line.rsplit(" ", 1)[1])
    return None


@pytest.fixture
def probes():
    tracing.install_host_probes()   # idempotent: conftest's configure() did
    return tracing._host


def _cpu_tick() -> float:
    """The step of this host's thread CPU clock, probed once: the smallest
    move seen in 30 ms of spinning.  Nanoseconds on Linux; 10 ms on gVisor
    (the benchmark's machines), where a 20 ms body reads 10, 20 or 30."""
    import time

    if not hasattr(_cpu_tick, "s"):
        step, t0, last = 1.0, time.perf_counter(), time.thread_time()
        while time.perf_counter() - t0 < 0.03 or step == 1.0:
            now = time.thread_time()
            if now != last:
                step, last = min(step, now - last), now
        _cpu_tick.s = step
    return _cpu_tick.s


class TestHostEvidence:
    @pytest.mark.parametrize("body", ["sleep", "spin"])
    def test_off_cpu_is_wall_less_the_threads_cpu(self, body):
        """20 ms asleep read as off the CPU, 20 ms of the thread's own CPU
        do not (whatever else the machine gives the thread to wait for:
        the suite runs six workers wide).  Where the CPU clock moves in
        ticks the body is five of them and a reading is good to one."""
        import time

        tick = _cpu_tick()
        body_s, slack = max(0.02, 5 * tick), 0.005 + tick
        t = Tracer()
        with t.span("stage", slot=2) as sp:
            if body == "sleep":
                time.sleep(body_s)
            else:
                t0 = time.thread_time()
                while time.thread_time() - t0 < body_s:
                    pass
        d = t.timeline(2)["spans"][0]
        assert d["duration_ms"] >= body_s * 1000
        assert sp.offcpu_s() == pytest.approx(
            sp.duration_s() - sp.cpu_s, abs=1e-9)
        if body == "sleep":
            assert d["offcpu_ms"] >= (body_s - slack) * 1000
            assert sp.cpu_s <= slack
        else:
            assert sp.cpu_s >= body_s - slack
            assert d.get("offcpu_ms", 0.0) <= \
                d["duration_ms"] - (body_s - slack) * 1000

    def test_a_name_that_runs_brief_stops_reading_the_cpu_clock(
            self, monkeypatch):
        """After its first eight closures a name under two milliseconds on
        average takes no reading (`cpu_s` None, no `offcpu_ms`, nothing to
        the histogram); one long closure does not bring the readings back,
        a name that runs long keeps them."""
        import time

        from lighthouse_tpu.common.metrics import REGISTRY

        reads = []
        real = time.thread_time
        monkeypatch.setattr(tracing, "_thread_cpu",
                            lambda: reads.append(1) or real())
        t, seen = Tracer(), []
        for _ in range(tracing._EVIDENCE_ALWAYS):
            with t.span("brief.stage", observe=seen.append) as sp:
                pass
            assert sp.cpu_s is not None
        assert len(reads) == 2 * tracing._EVIDENCE_ALWAYS
        for _ in range(20):
            with t.span("brief.stage", observe=seen.append) as sp:
                pass
        assert len(reads) == 2 * tracing._EVIDENCE_ALWAYS
        assert sp.cpu_s is None and sp.offcpu_s() == 0.0
        assert "offcpu_ms" not in sp.to_dict()
        assert len(seen) == tracing._EVIDENCE_ALWAYS + 20
        assert (f'span_offcpu_seconds_count{{span="brief.stage"}} '
                f'{tracing._EVIDENCE_ALWAYS}') in REGISTRY.render()
        # 10 ms once among 28 brief closures: the mean stays under 2 ms
        with t.span("brief.stage") as sp:
            time.sleep(0.01)
        with t.span("brief.stage") as sp:
            pass
        assert sp.cpu_s is None
        del reads[:]
        for _ in range(tracing._EVIDENCE_ALWAYS + 4):
            with t.span("long.stage") as sp:
                time.sleep(0.003)
        assert sp.cpu_s is not None
        assert len(reads) == 2 * (tracing._EVIDENCE_ALWAYS + 4)

    def test_a_full_collection_draws_host_gc_where_it_struck(self, probes):
        import gc

        t = Tracer()
        with t.span("flush"):
            pass
        runs = _counter("host_gc_collections_total", generation=2)
        pause = _counter("host_gc_pause_seconds_total", generation=2)
        with t.span("outer", slot=5):
            with tracing.span("inner") as inner:
                gc.collect()
        assert inner.gc_s > 0.0
        (child,) = [c for c in inner.children if c.name == "host.gc"]
        assert child.attrs["generation"] == 2 and "collected" in child.attrs
        assert inner.gc_s <= child.duration_s() <= inner.gc_s + 0.05
        outer = t.timeline(5)["spans"][0]
        assert outer["gc_ms"] >= outer["children"][0]["gc_ms"] > 0
        assert _counter("host_gc_collections_total", generation=2) == runs + 1
        assert _counter("host_gc_pause_seconds_total", generation=2) > pause

    def test_a_generation_0_pass_only_adds_up(self, probes):
        import gc

        t = Tracer()
        with t.span("flush"):
            pass
        runs = _counter("host_gc_collections_total", generation=0)
        with t.span("outer", slot=6) as sp:
            gc.collect(0)
        assert not [c for c in sp.children if c.name == "host.gc"]
        assert sp.gc_s > 0.0
        assert _counter("host_gc_collections_total", generation=0) >= runs + 1

    def test_every_label_child_is_there_before_anything_happens(self, probes):
        for gen in (0, 1, 2):
            assert _counter("host_gc_pause_seconds_total",
                            generation=gen) is not None
            assert _counter("host_gc_collections_total",
                            generation=gen) is not None

    def test_a_collection_inside_the_recorders_hold_returns(self, probes):
        """The collector can strike between any two bytecodes of
        `_judge`, on the thread that holds the baselines' lock, under an
        open span: the `host.gc` span it draws is not the recorder's to
        judge, and whatever else a collection runs may close a root."""
        import gc

        from lighthouse_tpu.common import flight_recorder as flight

        done = threading.Event()

        def close_root():
            with tracing.span("struck.root"):
                pass

        def strike():
            with tracing.span("struck.parent"):
                with flight.RECORDER._baseline_lock:
                    gc.collect()       # draws host.gc, judged by none
                    # and a root closing in the hold (a finalizer's, say)
                    contextvars.Context().run(close_root)
            done.set()

        th = threading.Thread(target=strike, daemon=True)
        th.start()
        assert done.wait(10.0), "a span closure blocked on the recorder"

    def test_a_stage_span_feeds_span_offcpu_seconds(self):
        import time

        from lighthouse_tpu.common.metrics import REGISTRY

        seen = []
        with Tracer().span("evidence.stage", observe=seen.append):
            time.sleep(0.01)
        with Tracer().span("evidence.plain"):
            pass
        text = REGISTRY.render()
        assert 'span_offcpu_seconds_count{span="evidence.stage"} 1' in text
        assert 'span="evidence.plain"' not in text
        (line,) = [ln for ln in text.splitlines() if ln.startswith(
            'span_offcpu_seconds_sum{span="evidence.stage"}')]
        assert 0.005 <= float(line.rsplit(" ", 1)[1]) <= seen[0]

    def test_a_coarse_cpu_clock_reads_negative_not_zero(self):
        """One tick of a 10 ms CPU clock charged to a 4 ms span: the span
        reads -6 ms, and with its neighbours the sum comes out right."""
        sp = tracing.Span("tick", start=1.0, end=1.004)
        sp.cpu_s = 0.010
        assert sp.offcpu_s() == pytest.approx(-0.006)
        assert sp.to_dict()["offcpu_ms"] == -6.0

    def test_no_evidence_no_keys(self):
        sp = tracing.Span("calm", start=1.0, end=1.5)
        sp.cpu_s = 0.5
        assert set(sp.to_dict()) == {"name", "offset_ms", "duration_ms"}
        sp.cpu_s, sp.gc_s = 0.25, 0.002
        d = sp.to_dict()
        assert d["offcpu_ms"] == 250.0 and d["gc_ms"] == 2.0
        sp.cpu_s = None        # a name that runs brief read no clock
        assert "offcpu_ms" not in sp.to_dict()


class TestOneTreeARequest:
    def test_the_watchdogs_worker_nests_under_the_callers_span(self):
        from lighthouse_tpu.ops import faults

        def work():
            with tracing.span("worker.stage"):
                return threading.current_thread().name

        tracing.TRACER.clear()
        with tracing.span("caller", slot=77):
            name = faults.run_with_deadline(work, 5.0, "lhtpu-test", "work")
        assert name == "lhtpu-test"
        (root,) = tracing.TRACER.timeline(77)["spans"]
        assert root["name"] == "caller"
        assert [c["name"] for c in root["children"]] == ["worker.stage"]
        unslotted = tracing.TRACER.timeline(UNSLOTTED)
        assert not unslotted or "worker.stage" not in [
            s["name"] for s in unslotted["spans"]]

    def test_outside_any_span_the_worker_is_its_own_root(self):
        from lighthouse_tpu.ops import faults

        def work():
            with tracing.span("worker.alone"):
                pass

        tracing.TRACER.clear()
        faults.run_with_deadline(work, 5.0, "lhtpu-test", "work")
        assert [s["name"] for s in tracing.TRACER.timeline(
            UNSLOTTED)["spans"]] == ["worker.alone"]

    def test_an_abandoned_workers_late_closure_raises_nothing(self):
        from lighthouse_tpu.ops import faults

        release, closed, errors = (threading.Event(), threading.Event(), [])

        def work():
            try:
                with tracing.span("worker.late"):
                    release.wait(5.0)
            except BaseException as e:   # noqa: BLE001 - the test's verdict
                errors.append(e)
            finally:
                closed.set()

        tracing.TRACER.clear()
        with pytest.raises(faults.WatchdogTimeout):
            with tracing.span("caller", slot=78):
                faults.run_with_deadline(work, 0.05, "lhtpu-test", "work")
        (root,) = tracing.TRACER.timeline(78)["spans"]
        assert root["attrs"]["error"] == "WatchdogTimeout"
        assert "children" not in root
        release.set()
        assert closed.wait(5.0) and not errors
        # the late span went into the closed, filed parent
        (root,) = tracing.TRACER.timeline(78)["spans"]
        assert [c["name"] for c in root["children"]] == ["worker.late"]


def test_the_dropped_counter_is_held_once(monkeypatch):
    from lighthouse_tpu.common.metrics import REGISTRY

    t = Tracer(max_spans_per_slot=2)
    looked_up = []
    real = REGISTRY.counter
    monkeypatch.setattr(REGISTRY, "counter",
                        lambda *a, **k: looked_up.append(a[0]) or real(*a, **k))
    for _ in range(6):
        with t.span("root", slot=1):
            pass
    assert t.timeline(1)["dropped_spans"] == 4
    assert looked_up.count("tracing_spans_dropped_total") == 1
