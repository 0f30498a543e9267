"""Flight recorder: ring semantics, concurrency, and the trip matrix.

The acceptance contract (ISSUE 11): every documented trip condition
produces a JSON black box naming its trigger, concurrent emitters lose
no events, and the ring bound is honored (overflow evicts oldest,
counted).  The trip matrix drives each condition through its OWNING
seam (supervisor breaker, epoch breaker, dispatch supervisor, store
sweep, rpc quarantine, invariant monitor) — never by calling ``trip``
directly — so a refactor that disconnects an emit point fails here.
"""

from __future__ import annotations

import json
import threading

import pytest

from lighthouse_tpu.common import flight_recorder as flight
from lighthouse_tpu.common import monitors
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls import api
from lighthouse_tpu.ops import faults
from lighthouse_tpu.testing import supervised_bls


@pytest.fixture(autouse=True)
def fresh_recorder(tmp_path, monkeypatch):
    """A fresh armed recorder per test, dumping into tmp_path."""
    rec = flight.FlightRecorder(capacity=256, dump_dir=str(tmp_path),
                                max_dumps=4)
    rec.enabled = True
    monkeypatch.setattr(flight, "RECORDER", rec)
    monitors.MONITORS.reset()
    yield rec
    monitors.MONITORS.reset()


# -- ring semantics -----------------------------------------------------------


def test_ring_bound_honored(fresh_recorder):
    rec = flight.FlightRecorder(capacity=32, dump_dir=None)
    for i in range(100):
        rec.emit("tick", i=i)
    assert len(rec) == 32
    assert rec.evicted == 68
    events = rec.snapshot()
    # newest-wins: the survivors are the last 32 emits, in order
    assert [e["i"] for e in events] == list(range(68, 100))


def test_concurrent_emitters_lose_no_events(fresh_recorder):
    rec = flight.FlightRecorder(capacity=4096, dump_dir=None)
    n_threads, per_thread = 8, 200

    def pump(t):
        for i in range(per_thread):
            rec.emit("load", thread=t, i=i)

    threads = [threading.Thread(target=pump, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    events = rec.snapshot()
    assert len(events) == n_threads * per_thread
    # sequence numbers are unique and dense
    seqs = {e["seq"] for e in events}
    assert len(seqs) == n_threads * per_thread


def test_trip_dumps_to_disk_and_prunes(fresh_recorder, tmp_path):
    rec = fresh_recorder
    for i in range(5):
        rec.emit("precursor", i=i)
    for k in range(6):  # max_dumps=4: the first two files are pruned
        dump = rec.trip("drill", ordinal=k)
    assert dump["reason"] == "drill"
    assert dump["event_count"] >= 6
    files = sorted(tmp_path.glob("flight-*.json"))
    assert len(files) == 4
    parsed = json.loads(files[-1].read_text())
    assert parsed["reason"] == "drill"
    assert parsed["events"][0]["kind"] in ("precursor", "trip")


def test_disarmed_recorder_is_inert(fresh_recorder):
    rec = fresh_recorder
    rec.enabled = False
    rec.emit("x")
    assert rec.trip("y") is None
    assert len(rec) == 0 and rec.last_dump is None


def test_slow_span_capture(fresh_recorder):
    import time

    from lighthouse_tpu.common import tracing

    fresh_recorder.span_floor_ms = 5.0
    with tracing.span("slow_thing", slot=9):
        time.sleep(0.02)
    with tracing.span("fast_thing", slot=9):
        pass
    kinds = [(e["kind"], e.get("name")) for e in fresh_recorder.snapshot()]
    assert ("slow_span", "slow_thing") in kinds
    assert ("slow_span", "fast_thing") not in kinds


def _closure(name, ms, children=(), slot=None):
    """One span tree closed through the recorder's hook the way
    ``tracing.span.__exit__`` does it: the root alone is judged."""
    from lighthouse_tpu.common.tracing import Span

    def build(name, ms, children):
        sp = Span(name, start=0.0, end=ms / 1000.0)
        sp.cpu_s = ms / 1000.0
        sp.children = [build(*c) for c in children]
        return sp

    root = build(name, ms, children)
    flight.RECORDER.note_root(root, root.duration_ms(), slot)
    return root


def _kinds(rec):
    return [(e["kind"], e.get("name") or e.get("root"))
            for e in rec.snapshot()]


def test_routine_closures_stop_filing_once_the_baseline_stands(fresh_recorder):
    """Twenty routine closures of one root over the floor: the first eight
    are filed by the floor alone, the rest, within one and a half times
    their kind's level, file nothing; nothing below a root is filed."""
    fresh_recorder.span_floor_ms = 50.0
    for i in range(20):
        _closure("req", 570.0 + 5 * (i % 7), [("req.stage", 500.0, ())])
    kinds = _kinds(fresh_recorder)
    assert kinds.count(("slow_span", "req")) == 8
    assert len(kinds) == 8


def test_an_outlier_root_files_one_slow_request(fresh_recorder, capsys):
    """A root three times its level files exactly one ``slow_request``:
    its stage table says where the time went and accounts for the root,
    it is counted, and logged once."""
    from lighthouse_tpu.common.metrics import REGISTRY

    fresh_recorder.span_floor_ms = 50.0
    stages = [("req.layout", 420.0, ()), ("req.wait", 90.0, ()),
              ("req.fold", 50.0, [("req.fold.fetch", 45.0, ())])]
    for i in range(20):
        _closure("req", 560.0 + i, stages)
    fresh_recorder.clear()
    counter = REGISTRY.counter("slow_requests_total").labels(root="req")
    before = counter.value
    slow = [("req.layout", 1560.0, ()), ("req.wait", 90.0, ()),
            ("req.fold", 50.0, [("req.fold.fetch", 45.0, ())])]
    _closure("req", 1710.0, slow, slot=12)
    (evt,) = fresh_recorder.snapshot()
    assert evt["kind"] == "slow_request" and evt["root"] == "req"
    assert evt["ms"] == 1710.0 and evt["median_ms"] == 569.5
    # the level is what two of the name's closures reached
    assert evt["level_ms"] == 578.0
    assert evt["slot"] == 12
    table = evt["stages"]
    assert list(table) == ["req", "req.layout", "req.wait", "req.fold",
                           "req.fold.fetch"]
    assert table["req.layout"] == {"n": 1, "ms": 1560.0}
    # the leaves are the request: layout + wait + the fold's fetch
    assert evt["covered_pct"] == pytest.approx(100 * 1695 / 1710, abs=0.1)
    assert evt["covered_pct"] >= 90.0
    assert counter.value == before + 1
    err = capsys.readouterr().err
    assert err.count("slow request") == 1 and "root=req" in err
    # and the next routine request files nothing again
    _closure("req", 575.0, stages)
    assert len(fresh_recorder.snapshot()) == 1


@pytest.mark.parametrize("levels", [
    (112.0, 1152.0),                 # epoch-boundary's state.root, slot by slot
    (60.0,) * 31 + (1900.0,),        # one slot in 32 crosses an epoch
    (30.0, 30.0, 30.0, 700.0),       # a name that straddles the floor
])
def test_a_name_on_several_levels_files_nothing_on_any(fresh_recorder, levels):
    """One name, closures on levels far more than one and a half times
    apart (``epoch-boundary``: the slot that crosses the epoch and the one
    that does not are both ``state.slot``): two of the name's last 64
    reached the upper level, so neither is slow; the sub-floor closures
    feed the baseline like the others."""
    fresh_recorder.span_floor_ms = 50.0
    for i in range(64):
        _closure("state.slot", levels[i % len(levels)] * (1 + 0.01 * (i % 5)))
    fresh_recorder.clear()
    for i in range(64, 64 + 2 * len(levels)):
        _closure("state.slot", levels[i % len(levels)] * (1 + 0.01 * (i % 5)))
    assert fresh_recorder.snapshot() == []
    # and an outlier of the upper level is still one
    _closure("state.slot", 2.0 * max(levels))
    assert _kinds(fresh_recorder) == [("slow_request", "state.slot")]


def test_one_earlier_outlier_is_no_level(fresh_recorder):
    """A second 4 s request after a first is as slow as the first was (a
    level is what two closures reached); a third among the last 64 is what
    the name does now."""
    fresh_recorder.span_floor_ms = 50.0
    for i in range(8):
        _closure("req", 570.0 + i)
    fresh_recorder.clear()
    _closure("req", 4075.0)
    _closure("req", 580.0)
    _closure("req", 4100.0)
    assert _kinds(fresh_recorder) == [("slow_request", "req")] * 2
    _closure("req", 4090.0)
    assert len(fresh_recorder.snapshot()) == 2


def test_a_slow_stage_under_a_routine_root_files_nothing(fresh_recorder):
    """A stage is slow as part of its request: below a root that took what
    its kind takes, nothing is the ring's."""
    fresh_recorder.span_floor_ms = 50.0
    for _ in range(10):
        _closure("req", 600.0, [("req.stage", 100.0, ())])
    fresh_recorder.clear()
    _closure("req", 700.0, [("req.stage", 200.0, ())], slot=3)
    assert fresh_recorder.snapshot() == []


def test_the_stage_table_carries_the_spans_evidence(fresh_recorder):
    """Through the real span primitive: a root that sleeps where it used
    to take a tenth of the time reads the sleep as off-CPU in its row."""
    import time

    from lighthouse_tpu.common import tracing

    fresh_recorder.span_floor_ms = 1.0
    for _ in range(9):
        with tracing.span("evidence.req"):
            with tracing.span("evidence.req.stage"):
                time.sleep(0.003)
    fresh_recorder.clear()
    with tracing.span("evidence.req"):
        with tracing.span("evidence.req.stage"):
            time.sleep(0.06)
    (evt,) = [e for e in fresh_recorder.snapshot()
              if e["kind"] == "slow_request"]
    row = evt["stages"]["evidence.req.stage"]
    assert row["ms"] >= 60.0 and row["offcpu_ms"] >= 45.0
    assert evt["covered_pct"] >= 90.0


def test_the_baselines_are_bounded(fresh_recorder, monkeypatch):
    monkeypatch.setattr(flight, "_BASELINE_NAMES", 4)
    fresh_recorder.span_floor_ms = 1.0
    for i in range(10):
        _closure(f"name-{i}", 5.0)
    assert list(fresh_recorder._baselines) == [f"name-{i}"
                                               for i in range(6, 10)]


# -- the trip matrix ----------------------------------------------------------


@pytest.fixture
def valid_sets():
    sk = bls.SecretKey.from_bytes(bytes([0] * 31 + [5]))
    msg = b"flight-recorder-trip".ljust(32, b"\x00")
    return [bls.SignatureSet(sk.sign(msg), [sk.public_key()], msg)]


def test_trip_bls_breaker_open(fresh_recorder, valid_sets):
    """An injected device fault opens the tpu breaker through the REAL
    supervisor path; the dump names the trigger and carries the
    supervisor_fault event that preceded it."""
    def raising_backend(sets, **kw):
        raise faults.InjectedFault("flight drill")

    prev = api._BACKENDS.get("tpu")
    api.register_backend("tpu", raising_backend)
    try:
        with supervised_bls(LHTPU_SUPERVISOR_FAILS="1",
                            LHTPU_SUPERVISOR_LADDER="tpu,reference"):
            assert bls.verify_signature_sets(valid_sets, backend="tpu")
    finally:
        if prev is None:
            api._BACKENDS.pop("tpu", None)
        else:
            api._BACKENDS["tpu"] = prev
        api.reset_supervisor()
    dump = fresh_recorder.last_dump
    assert dump is not None and dump["reason"] == "bls_breaker_open"
    kinds = {e["kind"] for e in dump["events"]}
    assert "supervisor_fault" in kinds
    assert any(e["kind"] == "breaker" and e.get("new") == "open"
               for e in dump["events"])


def test_trip_epoch_breaker_open(fresh_recorder, monkeypatch):
    from lighthouse_tpu.state_transition import epoch_processing as ep

    monkeypatch.setenv("LHTPU_SUPERVISOR_FAILS", "1")
    ep.reset_epoch_supervisor()
    ep._breaker_fault()
    dump = fresh_recorder.last_dump
    assert dump is not None and dump["reason"] == "epoch_breaker_open"
    ep.reset_epoch_supervisor()


def test_trip_dispatch_wedge(fresh_recorder):
    """A batch that outlives the wedge deadline trips through the real
    dispatch-thread supervisor."""
    import asyncio
    import time

    from lighthouse_tpu.processor import (
        BeaconProcessor,
        WorkEvent,
        WorkType,
    )

    bp = BeaconProcessor(max_workers=2, batch_flush_ms=5,
                         dispatch_wedge_s=0.05)

    async def main():
        await bp.start()
        bp.submit(WorkEvent(WorkType.GOSSIP_ATTESTATION, payload=1,
                            process_batch=lambda p: time.sleep(0.4)))
        await bp.drain()
        await bp.stop(drain=False)

    asyncio.run(main())
    dump = fresh_recorder.last_dump
    assert dump is not None and dump["reason"] == "dispatch_wedge"
    assert dump["trip_fields"]["wedge"] == "wedged"


def test_trip_store_corruption(fresh_recorder):
    from lighthouse_tpu.store import HotColdDB
    from lighthouse_tpu.store.migrations import K_HEAD
    from lighthouse_tpu.testing import Harness

    h = Harness(n_validators=8, real_crypto=False)
    db = HotColdDB(h.spec)
    db.hot.put(K_HEAD, b"torn-unenveloped-garbage")
    report = db._startup_repair(dirty=True)
    assert report.get("head") == "dropped"
    dump = fresh_recorder.last_dump
    assert dump is not None and dump["reason"] == "store_corruption"
    assert dump["trip_fields"]["report"]["head"] == "dropped"
    kinds = {e["kind"] for e in dump["events"]}
    assert "store_repair" in kinds


def test_trip_peer_quarantine(fresh_recorder, monkeypatch):
    from lighthouse_tpu.network.rpc import RequestDiscipline, RpcError

    monkeypatch.setenv("LHTPU_RPC_FAILS", "3")
    monkeypatch.setenv("LHTPU_RPC_DEADLINE_S", "0")
    d = RequestDiscipline()

    def failing_issue(dst):
        raise RpcError("refused")

    for _ in range(3):
        with pytest.raises(RpcError):
            d.execute("evil-peer", "/eth2/x/req/status/1", b"",
                      failing_issue)
    dump = fresh_recorder.last_dump
    assert dump is not None and dump["reason"] == "peer_quarantine"
    assert dump["trip_fields"]["peer"] == "evil-peer"
    # the failures that walked the ladder are in the story
    assert sum(1 for e in dump["events"]
               if e["kind"] == "rpc_fail") >= 2


def test_trip_books_violation(fresh_recorder):
    monitors.register("drill_books", lambda: {"deficit": 3})
    fired = monitors.sweep()
    assert len(fired) == 1
    dump = fresh_recorder.last_dump
    assert dump is not None and dump["reason"] == "books_violation"
    assert dump["trip_fields"]["monitor"] == "drill_books"


def test_observatory_view_shape(fresh_recorder):
    fresh_recorder.emit("a")
    fresh_recorder.trip("drill")
    view = flight.observatory_view()
    assert view["armed"] and view["trips"] == 1
    assert view["last_dump"]["reason"] == "drill"
    assert view["tail"][-1]["kind"] == "trip"


# -- cross-thread regression pins (the lhrace LH1001-1003 fixes) --------------
# Each test drives the exact shape the race pass flagged with 6 racing
# threads and asserts the post-fix invariant holds under contention.


def test_concurrent_first_emits_memoize_one_counter_child(fresh_recorder):
    """6 threads racing the FIRST emit of a kind: the double-checked
    ``_memo_lock`` admits exactly one memoized child and no increment
    lands on an orphaned duplicate (the check-then-act fix on
    ``_counter_memo``)."""
    from lighthouse_tpu.common.metrics import REGISTRY

    rec = flight.FlightRecorder(capacity=4096, dump_dir=None)
    kind = "memo-race-pin"
    child = REGISTRY.counter("flight_events_total").labels(kind=kind)
    start = child.value
    n_threads, per_thread = 6, 50
    barrier = threading.Barrier(n_threads)

    def pump():
        barrier.wait()
        for _ in range(per_thread):
            rec.emit(kind)

    threads = [threading.Thread(target=pump) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert child.value == start + n_threads * per_thread
    assert ("event", kind) in rec._counter_memo


def test_concurrent_trips_prune_dump_files_consistently(fresh_recorder,
                                                        tmp_path):
    """6 threads tripping at once: the ``_dump_lock`` keeps the
    rotation deque and the on-disk dump set in lockstep (the unlocked
    append/popleft pair used to drop or double-prune paths)."""
    import os

    rec = fresh_recorder      # max_dumps=4, dumping into tmp_path
    n_threads, per_thread = 6, 3
    barrier = threading.Barrier(n_threads)

    def tripper(t):
        barrier.wait()
        for i in range(per_thread):
            rec.trip("stress", thread=t, i=i)

    threads = [threading.Thread(target=tripper, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rec.trip_count == n_threads * per_thread
    assert len(rec._dump_paths) <= rec.max_dumps
    on_disk = sorted(p.name for p in tmp_path.glob("flight-*.json"))
    assert sorted(os.path.basename(p) for p in rec._dump_paths) == on_disk


def test_concurrent_reconfigure_rebuilds_ring_once(fresh_recorder,
                                                   monkeypatch):
    """6 threads re-reading a changed capacity knob: the check now sits
    INSIDE the lock hold, so the ring is rebuilt exactly once and no
    buffered event is lost to a double rebuild."""
    rec = fresh_recorder
    for i in range(10):
        rec.emit("keep", i=i)
    monkeypatch.setenv("LHTPU_FLIGHT_CAPACITY", "64")
    n_threads = 6
    barrier = threading.Barrier(n_threads)

    def reconf():
        barrier.wait()
        rec.reconfigure()

    threads = [threading.Thread(target=reconf) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rec.capacity == 64
    assert rec._ring.maxlen == 64
    kept = [e["i"] for e in rec.snapshot() if "i" in e]
    assert kept == list(range(10))
