"""Fault-injection matrix for the offload supervisor (PR 4).

The acceptance contract: under every injected device-fault class
(raise, hang past the watchdog deadline, corrupt verdict, compile
failure), `verify_signature_sets` returns the same verdict the
reference backend would produce, the health ladder records the expected
circuit-breaker transitions, and a healthy probe re-promotes the
benched backend.  Plus the dispatch-thread supervisor's
kill-and-recover races (in the style of tests/test_lock_contracts.py).

Every injected fault here fires BEFORE any real device dispatch (entry
hooks, chunk index 0 pre-dispatch, stub backends), so this file
compiles no XLA programs and adds no new jit shapes; the longest stall
is the test-tuned watchdog (fractions of a second).
"""

from __future__ import annotations

import asyncio
import os
import threading
import time

import numpy as np
import pytest

from lighthouse_tpu.common import env as envreg
from lighthouse_tpu.common.metrics import REGISTRY, record_swallowed
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls import api
from lighthouse_tpu.ops import faults
from lighthouse_tpu.ops.dispatch_pipeline import AsyncVerdict
from lighthouse_tpu.processor import BeaconProcessor, WorkEvent, WorkType
from lighthouse_tpu.testing import inject_fault, supervised_bls

# test-tuned supervisor knobs: watchdog far below the injected hang,
# backoff short enough to probe within the test
TUNED = dict(
    LHTPU_WATCHDOG_S="0.25",
    LHTPU_SUPERVISOR_AUDIT="1",
    LHTPU_SUPERVISOR_FAILS="1",
    LHTPU_SUPERVISOR_BACKOFF_S="0.05",
    LHTPU_SUPERVISOR_LADDER="tpu,reference",
)

HANG_S = 1.0  # injected stall; must exceed the watchdog, bound the test


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()
    api.reset_supervisor()


@pytest.fixture(scope="module")
def sets():
    """One valid and one invalid 2-set batch on a fixed key (module-
    scoped: reference verification costs ~0.5 s per call)."""
    sk = bls.SecretKey.from_bytes(bytes([0] * 31 + [3]))
    msgs = [b"offload-fault-a".ljust(32, b"\x00"),
            b"offload-fault-b".ljust(32, b"\x00")]
    valid = [bls.SignatureSet(sk.sign(m), [sk.public_key()], m)
             for m in msgs]
    invalid = [bls.SignatureSet(sk.sign(msgs[1]), [sk.public_key()],
                                msgs[0]),
               valid[1]]
    return valid, invalid


def _fault_count(backend: str, kind: str) -> float:
    return REGISTRY.counter("bls_supervisor_faults_total").labels(
        backend=backend, kind=kind).value


# --- the fault matrix --------------------------------------------------------
# paths: single-shot entry, chunked (fault at chunk index 0 of the real
# pipeline's chunk loop), sharded entry.  corrupt is a verdict-boundary
# fault, exercised separately below.

MATRIX = [
    ("raise", "single"), ("raise", "chunked"), ("raise", "sharded"),
    ("hang", "single"), ("hang", "chunked"), ("hang", "sharded"),
    ("compile", "single"), ("compile", "chunked"), ("compile", "sharded"),
]


@pytest.mark.parametrize("mode,path", MATRIX)
def test_fault_matrix_verdict_identity(sets, mode, path, monkeypatch):
    valid, _ = sets
    # time the reference rung's re-verification apart: it is host work
    # after the watchdog let go (~0.6 s of pure-Python pairing, longer on
    # a loaded host), not waiting on the stall
    reference = api._verify_signature_sets_reference
    reference_s = []

    def timed_reference(*args, **kw):
        t = time.perf_counter()
        try:
            return reference(*args, **kw)
        finally:
            reference_s.append(time.perf_counter() - t)

    monkeypatch.setattr(api, "_verify_signature_sets_reference",
                        timed_reference)
    backend = "sharded" if path == "sharded" else "tpu"
    site = {"single": "tpu", "chunked": "chunk", "sharded": "sharded"}[path]
    kwargs = {"chunk_size": 1} if path == "chunked" else {}
    ladder = "sharded,reference" if backend == "sharded" else "tpu,reference"
    expect_kind = "hang" if mode == "hang" else (
        "compile" if mode == "compile" else "raise")
    with supervised_bls(**dict(TUNED, LHTPU_SUPERVISOR_LADDER=ladder)):
        before = _fault_count(backend, expect_kind)
        with inject_fault(mode, sites={site}, hang_s=HANG_S):
            t0 = time.perf_counter()
            ok = bls.verify_signature_sets(valid, backend=backend, **kwargs)
            elapsed = time.perf_counter() - t0
        # verdict identity: recovery re-verified on the reference path
        assert ok is True
        # the health ladder benched the faulting backend
        assert bls.backend_health()[backend] == "open"
        assert _fault_count(backend, expect_kind) == before + 1
        if mode == "hang":
            # the caller never waits for the stall — only the watchdog
            # (0.25 s; half the stall leaves room for thread scheduling)
            assert len(reference_s) == 1
            waited = elapsed - reference_s[0]
            assert waited < HANG_S / 2, f"caller waited {waited:.3f}s"


@pytest.mark.parametrize("corrupt_value,use_invalid", [(True, True),
                                                       (False, False)])
def test_corrupt_verdict_caught_by_audit(sets, corrupt_value, use_invalid):
    """A device that silently returns garbage is caught by the audit:
    the reference verdict is returned and the circuit opens."""
    valid, invalid = sets
    batch = invalid if use_invalid else valid
    expected = False if use_invalid else True
    with supervised_bls(**TUNED):
        before = _fault_count("tpu", "corrupt")
        with inject_fault("corrupt", sites={"tpu"},
                          corrupt_value=corrupt_value):
            ok = bls.verify_signature_sets(batch, backend="tpu")
        assert ok is expected
        assert bls.backend_health()["tpu"] == "open"
        assert _fault_count("tpu", "corrupt") == before + 1


def test_ladder_degrades_across_both_device_rungs(sets):
    """tpu AND sharded faulting: the batch lands on the reference rung,
    both breakers open, and the recovery is counted."""
    valid, _ = sets
    with supervised_bls(**dict(TUNED,
                               LHTPU_SUPERVISOR_LADDER="tpu,sharded,"
                                                       "reference")):
        rec = REGISTRY.counter("bls_supervisor_recoveries_total").labels(
            backend="tpu")
        before = rec.value
        with inject_fault("raise", sites={"tpu", "sharded"}):
            assert bls.verify_signature_sets(valid, backend="tpu") is True
        health = bls.backend_health()
        assert health["tpu"] == "open" and health["sharded"] == "open"
        assert rec.value == before + 1


# --- circuit-breaker transition table ---------------------------------------


@pytest.fixture()
def stub_tpu():
    """Replace the real tpu backend with a controllable stub (no device
    work), restored afterwards."""
    calls = {"n": 0, "fail": False}

    def stub(sets_, **kw):
        calls["n"] += 1
        if isinstance(calls["fail"], BaseException):
            raise calls["fail"]
        if calls["fail"]:
            raise faults.InjectedFault("stub fault")
        return True  # O(1): must finish far inside the tuned watchdog

    had = "tpu" in api._BACKENDS
    old = api._BACKENDS.get("tpu")
    api._BACKENDS["tpu"] = stub
    yield calls
    if had:
        api._BACKENDS["tpu"] = old
    else:
        api._BACKENDS.pop("tpu", None)


def _expire_backoff(backend: str) -> None:
    """Time-travel a breaker's backoff to expiry (a reference recovery
    costs ~0.5 s, so real sleeps would race tiny backoffs)."""
    api._get_supervisor().breakers[backend].open_until = 0.0


def test_circuit_transition_table(sets, stub_tpu):
    """closed -> (threshold-1 faults) closed -> open -> benched ->
    half_open probe -> closed."""
    valid, _ = sets
    with supervised_bls(**dict(TUNED, LHTPU_SUPERVISOR_AUDIT="0",
                               LHTPU_SUPERVISOR_FAILS="2",
                               LHTPU_SUPERVISOR_BACKOFF_S="30")):
        assert bls.backend_health()["tpu"] == "closed"
        stub_tpu["fail"] = True
        # failure 1 of 2: breaker stays closed, verdict still correct
        assert bls.verify_signature_sets(valid, backend="tpu") is True
        assert bls.backend_health()["tpu"] == "closed"
        # failure 2 of 2: opens
        assert bls.verify_signature_sets(valid, backend="tpu") is True
        assert bls.backend_health()["tpu"] == "open"
        # benched: the stub is NOT called while the circuit is open
        n = stub_tpu["n"]
        assert bls.verify_signature_sets(valid, backend="tpu") is True
        assert stub_tpu["n"] == n
        # backoff expires -> half-open probe rides through and closes
        stub_tpu["fail"] = False
        _expire_backoff("tpu")
        assert bls.verify_signature_sets(valid, backend="tpu") is True
        assert stub_tpu["n"] == n + 1
        assert bls.backend_health()["tpu"] == "closed"


@pytest.mark.parametrize("exc", [
    ImportError("cannot import name 'enable_x64'"),
    AttributeError("module 'jax' has no attribute 'moved'"),
    NameError("name 'cache_guard' is not defined"),
    TypeError("shard_map() got an unexpected keyword 'check_rep'"),
])
def test_program_fault_is_loud_not_a_breaker_fault(sets, stub_tpu, exc):
    """A device module that does not import or trace is a fault of the
    PROGRAM: the seam re-raises it instead of serving from the reference
    rung with a counted 'device fault' (the silent step-down that hid
    the jax 0.9.0 breakage) — and leaves no half-open probe wedged."""
    valid, _ = sets
    with supervised_bls(**dict(TUNED, LHTPU_SUPERVISOR_AUDIT="0")):
        before = _fault_count("tpu", "raise")
        stub_tpu["fail"] = exc
        with pytest.raises(type(exc)):
            bls.verify_signature_sets(valid, backend="tpu")
        assert _fault_count("tpu", "raise") == before
        # the breaker still benched the rung; after the backoff a healthy
        # program is probed and re-promoted as after any fault
        stub_tpu["fail"] = False
        _expire_backoff("tpu")
        assert bls.verify_signature_sets(valid, backend="tpu") is True
        assert bls.backend_health()["tpu"] == "closed"


def test_auto_backend_probe_failure_propagates(monkeypatch):
    """A device probe that RAISES fails node start-up with that error;
    a platform that merely is not a TPU still resolves to reference."""
    import jax

    monkeypatch.delenv("LHTPU_BLS_BACKEND", raising=False)
    assert api.resolve_auto_backend() == "reference"   # the CPU suite

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        api.resolve_auto_backend()


def test_failed_probe_doubles_backoff(sets, stub_tpu):
    valid, _ = sets
    with supervised_bls(**dict(TUNED, LHTPU_SUPERVISOR_AUDIT="0",
                               LHTPU_SUPERVISOR_BACKOFF_S="20")):
        stub_tpu["fail"] = True
        assert bls.verify_signature_sets(valid, backend="tpu") is True
        breaker = api._get_supervisor().breakers["tpu"]
        assert breaker.state == "open"
        assert breaker.backoff_s == pytest.approx(20.0)
        # the probe fails: re-open with doubled backoff
        _expire_backoff("tpu")
        assert bls.verify_signature_sets(valid, backend="tpu") is True
        assert breaker.state == "open"
        assert breaker.backoff_s == pytest.approx(40.0)
        # a healthy probe resets state AND backoff
        stub_tpu["fail"] = False
        _expire_backoff("tpu")
        assert bls.verify_signature_sets(valid, backend="tpu") is True
        assert breaker.state == "closed"
        assert breaker.backoff_s == pytest.approx(20.0)


def test_supervisor_disabled_faults_propagate(sets):
    """LHTPU_SUPERVISOR=0 is the escape hatch: device backends are
    called raw and injected faults surface to the caller."""
    valid, _ = sets
    with supervised_bls(LHTPU_SUPERVISOR="0"):
        with inject_fault("raise", sites={"tpu"}):
            with pytest.raises(faults.InjectedFault):
                bls.verify_signature_sets(valid, backend="tpu")


# --- AsyncVerdict watchdog deadline ------------------------------------------


class _SlowRow:
    """np.asarray(...) on this object stalls like a wedged kernel."""

    def __init__(self, delay_s, values):
        self.delay_s = delay_s
        self.values = values

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.delay_s)
        return np.asarray(self.values)


def test_async_verdict_watchdog_deadline():
    v = AsyncVerdict(_SlowRow(1.0, [True]), 1)
    t0 = time.perf_counter()
    with pytest.raises(faults.WatchdogTimeout):
        v.commit(timeout=0.1)
    assert time.perf_counter() - t0 < 0.9


def test_async_verdict_commit_paths():
    marks = []
    v = AsyncVerdict(np.array([True, True]), 2, on_pass=lambda: marks.append(1))
    assert v.commit(timeout=0.5) is True and marks == [1]
    assert v.commit() is True  # memoized
    assert AsyncVerdict.immediate(False).commit() is False


def test_async_verdict_corrupt_inverts_and_skips_on_pass():
    marks = []
    v = AsyncVerdict(np.array([True]), 1, on_pass=lambda: marks.append(1))
    with inject_fault("corrupt", sites={"verdict"}):
        assert v.commit() is False
    assert marks == []
    # the dangerous direction: a False->True flip must NOT run on_pass
    # (it would mark signatures subgroup-checked off a falsified verdict)
    v2 = AsyncVerdict(np.array([False]), 1, on_pass=lambda: marks.append(2))
    with inject_fault("corrupt", sites={"verdict"}):
        assert v2.commit() is True
    assert marks == []


# --- fault plan plumbing -----------------------------------------------------


def test_env_driven_plan_and_max_fires():
    os.environ.update({"LHTPU_FAULT_MODE": "raise",
                       "LHTPU_FAULT_SITE": "tpu",
                       "LHTPU_FAULT_MAX_FIRES": "1"})
    try:
        faults.refresh_from_env()
        with pytest.raises(faults.InjectedFault):
            faults.fire("tpu")
        assert faults.fire("tpu") is None  # max_fires exhausted
        assert faults.fire("sharded") is None  # site mismatch
    finally:
        for k in ("LHTPU_FAULT_MODE", "LHTPU_FAULT_SITE",
                  "LHTPU_FAULT_MAX_FIRES"):
            os.environ.pop(k, None)
        faults.clear()


def test_malformed_env_plan_warns_once_and_disables(capsys):
    os.environ["LHTPU_FAULT_MODE"] = "raze"  # typo'd chaos knob
    faults._WARNED_ENV_PLAN = False
    try:
        assert faults.refresh_from_env() is None
        assert faults.fire("tpu") is None  # injection disabled, no raise
        assert faults.refresh_from_env() is None
        err = capsys.readouterr().err
        assert err.count("malformed LHTPU_FAULT_") == 1
    finally:
        del os.environ["LHTPU_FAULT_MODE"]
        faults._WARNED_ENV_PLAN = False
        faults.clear()


def test_fault_indices_select_chunks():
    with inject_fault("compile", sites={"chunk"}, indices={2}):
        assert faults.fire("chunk", index=0) is None
        assert faults.fire("chunk", index=1) is None
        with pytest.raises(faults.InjectedCompileFault):
            faults.fire("chunk", index=2)


def test_classify_fault_kinds():
    assert faults.classify(faults.WatchdogTimeout("x")) == "hang"
    assert faults.classify(faults.InjectedCompileFault("x")) == "compile"
    assert faults.classify(RuntimeError("XLA compilation failure")) \
        == "compile"
    assert faults.classify(ValueError("boom")) == "raise"


# --- satellite seams ---------------------------------------------------------


def test_record_swallowed_counts_and_logs_once(capsys):
    before = REGISTRY.counter("offload_swallowed_errors_total").labels(
        site="test.site").value
    record_swallowed("test.site", ValueError("x"))
    record_swallowed("test.site", ValueError("y"))
    after = REGISTRY.counter("offload_swallowed_errors_total").labels(
        site="test.site").value
    assert after == before + 2
    err = capsys.readouterr().err
    assert err.count("swallowed ValueError at test.site") == 1


def test_env_unparseable_warns_once(capsys):
    os.environ["LHTPU_WATCHDOG_S"] = "not-a-number"
    envreg._WARNED_UNPARSEABLE.discard("LHTPU_WATCHDOG_S")
    try:
        assert envreg.get_float("LHTPU_WATCHDOG_S", 7.0) == 7.0
        assert envreg.get_float("LHTPU_WATCHDOG_S", 7.0) == 7.0
        err = capsys.readouterr().err
        assert err.count("unparseable LHTPU_WATCHDOG_S") == 1
    finally:
        del os.environ["LHTPU_WATCHDOG_S"]
        envreg._WARNED_UNPARSEABLE.discard("LHTPU_WATCHDOG_S")


# --- dispatch-thread supervisor (kill-and-recover races) ---------------------


def _run(coro):
    return asyncio.run(coro)


def test_single_batchable_event_not_dropped():
    """Regression: a deadline flush handing over ONE batchable event
    (no `process` callable) must run it as a 1-lane batch on the
    dispatch thread, not silently drop it."""

    async def main():
        bp = BeaconProcessor(max_workers=2, batch_flush_ms=1)
        done = []
        bp.submit(WorkEvent(WorkType.GOSSIP_ATTESTATION, payload="only",
                            process_batch=lambda ps: done.append(list(ps))))
        await bp.start()
        await bp.stop()
        assert done == [["only"]]
        assert bp.metrics.processed.get(WorkType.GOSSIP_ATTESTATION) == 1

    _run(main())


def test_dispatch_thread_wedge_recovers():
    """A batch wedging the dedicated dispatch thread past the deadline:
    the supervisor re-runs it on the synchronous path, replaces the
    thread, and later batches flow through the fresh executor."""

    async def main():
        bp = BeaconProcessor(max_workers=2, batch_flush_ms=1,
                             dispatch_wedge_s=0.15,
                             dispatch_restart_max=3,
                             dispatch_restart_window_s=60.0)
        release = threading.Event()
        runs = []

        def wedge_once(ps):
            runs.append(("wedge_call", len(ps)))
            if len([r for r in runs if r[0] == "wedge_call"]) == 1:
                release.wait(5)  # first execution wedges the thread

        def good(ps):
            runs.append(("good", len(ps)))

        for i in range(3):
            bp.submit(WorkEvent(WorkType.GOSSIP_ATTESTATION, payload=i,
                                process_batch=wedge_once))
        await bp.start()
        await bp.drain()
        assert bp.dispatch_restart_count == 1
        # the recovered batch re-ran synchronously (2 executions total)
        assert len([r for r in runs if r[0] == "wedge_call"]) == 2
        # the REPLACED dispatch thread serves subsequent batches
        for i in range(2):
            bp.submit(WorkEvent(WorkType.GOSSIP_AGGREGATE, payload=i,
                                process_batch=good))
        await bp.drain()
        assert ("good", 2) in runs
        assert bp.dispatch_restart_count == 1  # no further restarts
        release.set()  # unwedge the abandoned thread before teardown
        await bp.stop()
        assert bp.metrics.processed.get(WorkType.GOSSIP_ATTESTATION) == 3
        assert bp.metrics.processed.get(WorkType.GOSSIP_AGGREGATE) == 2

    _run(main())


def test_dispatch_thread_dead_executor_recovers():
    """A DEAD dispatch executor (submit raises): the batch drains
    through the synchronous path and the executor is replaced."""

    async def main():
        bp = BeaconProcessor(max_workers=2, batch_flush_ms=1,
                             dispatch_wedge_s=5.0)
        done = []
        bp._dispatch_executor.shutdown(wait=True)  # kill the thread
        for i in range(2):
            bp.submit(WorkEvent(WorkType.GOSSIP_ATTESTATION, payload=i,
                                process_batch=lambda ps: done.append(
                                    len(ps))))
        await bp.start()
        await bp.drain()
        assert done == [2]
        assert bp.dispatch_restart_count == 1
        await bp.stop()

    _run(main())


def test_dispatch_restart_storm_limiter():
    """Past the restart budget the supervisor stops replacing threads;
    batches still complete via the synchronous path."""

    async def main():
        bp = BeaconProcessor(max_workers=2, batch_flush_ms=1,
                             dispatch_wedge_s=0.1,
                             dispatch_restart_max=1,
                             dispatch_restart_window_s=60.0)
        release = threading.Event()
        sync_done = []

        def wedge(ps):
            # wedges on the dispatch thread; completes on the re-run
            # (the sync path sets no thread name prefix "bp-dispatch")
            if threading.current_thread().name.startswith("bp-dispatch"):
                release.wait(5)
            else:
                sync_done.append(len(ps))

        await bp.start()
        for _ in range(2):
            for i in range(2):
                bp.submit(WorkEvent(WorkType.GOSSIP_ATTESTATION, payload=i,
                                    process_batch=wedge))
            await bp.drain()
        # first wedge restarted; second hit the limiter (max 1/window)
        assert bp.dispatch_restart_count == 1
        assert len(sync_done) == 2
        release.set()
        await bp.stop()

    _run(main())


def test_concurrent_faulted_batches_one_restart(sets):
    """The race: two batches queued behind one wedged thread both time
    out; exactly one restart happens (generation-guarded), both recover
    synchronously."""

    async def main():
        bp = BeaconProcessor(max_workers=4, batch_flush_ms=1, max_batch=1,
                             dispatch_wedge_s=0.2,
                             dispatch_restart_max=5,
                             dispatch_restart_window_s=60.0)
        release = threading.Event()
        done = []

        def wedge(ps):
            if threading.current_thread().name.startswith("bp-dispatch"):
                release.wait(5)
            else:
                done.append(ps[0])

        # two batchable work types -> two batches racing on the one thread
        bp.submit(WorkEvent(WorkType.GOSSIP_ATTESTATION, payload="a",
                            process_batch=wedge))
        bp.submit(WorkEvent(WorkType.GOSSIP_AGGREGATE, payload="b",
                            process_batch=wedge))
        await bp.start()
        await bp.drain()
        assert sorted(done) == ["a", "b"]
        assert bp.dispatch_restart_count >= 1
        release.set()
        await bp.stop()

    _run(main())


# -- ingest storms (IngestPlan) -----------------------------------------------


class TestIngestPlan:
    def teardown_method(self):
        faults.install_ingest_plan(None)

    def test_modes_validated(self):
        with pytest.raises(ValueError):
            faults.IngestPlan(mode="meteor")
        for mode in faults.VALID_INGEST_MODES:
            faults.IngestPlan(mode=mode)

    def test_env_roundtrip(self, monkeypatch):
        monkeypatch.setenv("LHTPU_INGEST_FAULT_MODE", "dup")
        monkeypatch.setenv("LHTPU_INGEST_FAULT_FACTOR", "7")
        monkeypatch.setenv("LHTPU_INGEST_FAULT_S", "3.5")
        plan = faults.ingest_plan_from_env()
        assert plan is not None
        assert (plan.mode, plan.factor, plan.duration_s) == ("dup", 7.0, 3.5)

    def test_env_unset_means_no_storm(self, monkeypatch):
        monkeypatch.delenv("LHTPU_INGEST_FAULT_MODE", raising=False)
        assert faults.ingest_plan_from_env() is None

    def test_malformed_mode_disables_with_warning(self, monkeypatch, capsys):
        monkeypatch.setenv("LHTPU_INGEST_FAULT_MODE", "meteor")
        faults._WARNED_INGEST_ENV = False
        assert faults.ingest_plan_from_env() is None
        assert "ingest storm disabled" in capsys.readouterr().err
        # warns once per process
        assert faults.ingest_plan_from_env() is None
        assert capsys.readouterr().err == ""

    def test_consumer_stall_only_in_stall_mode(self):
        faults.install_ingest_plan(
            faults.IngestPlan("stall", stall_s=0.123))
        assert faults.consumer_stall_s() == 0.123
        faults.install_ingest_plan(faults.IngestPlan("burst"))
        assert faults.consumer_stall_s() == 0.0
        faults.install_ingest_plan(None)
        assert faults.consumer_stall_s() == 0.0

    def test_env_armed_storm_self_expires(self):
        plan = faults.IngestPlan("stall", stall_s=0.2, duration_s=0.05)
        faults.install_ingest_plan(plan, duration_s=plan.duration_s)
        assert faults.consumer_stall_s() == 0.2
        time.sleep(0.06)
        assert faults.active_ingest_plan() is None  # window closed
        assert faults.consumer_stall_s() == 0.0

    def test_programmatic_install_does_not_expire(self):
        plan = faults.IngestPlan("stall", stall_s=0.1, duration_s=0.01)
        faults.install_ingest_plan(plan)  # no duration: driver-bounded
        time.sleep(0.02)
        assert faults.active_ingest_plan() is plan

    def test_phase_restore_preserves_env_storm_expiry(self):
        """A drill phase must not unbound an env-armed storm's window
        when it restores the prior plan."""
        import asyncio

        from lighthouse_tpu.processor import BeaconProcessor
        from lighthouse_tpu.processor.firehose import FirehoseDriver

        armed = faults.IngestPlan("stall", stall_s=0.01, duration_s=0.15)
        faults.install_ingest_plan(armed, duration_s=armed.duration_s)

        async def main():
            bp = BeaconProcessor(max_workers=2, batch_flush_ms=1)
            drv = FirehoseDriver(bp, make_payload=lambda i: i,
                                 process_batch=lambda ps: None)
            await bp.start()
            await drv.run_phase(
                "mid", seconds=0.05, inflight_target=4,
                plan=faults.IngestPlan("burst", factor=2.0))
            await bp.drain()
            await bp.stop()

        asyncio.run(main())
        # restored WITH its remaining window: still armed now...
        assert faults.active_ingest_plan() is armed
        time.sleep(0.15)
        # ...and still self-expires when the original window lapses
        assert faults.active_ingest_plan() is None
