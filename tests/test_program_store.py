"""The persistent AOT program store (ops/program_store + ops/prewarm).

Resilience contract under test (ISSUE 12): corrupted / truncated /
bit-flipped serialized programs are COUNTED misses followed by a
recompile, never a crash; a jax-version or platform-fingerprint change
invalidates the whole program population; a concurrent prewarmer and
foreground dispatch compiling the same entry produce exactly ONE store
commit (single-flight); and ``LHTPU_AOT_STORE=0`` bypasses everything.

Everything here runs zero-XLA through a fake serializer seam
(``_serialize_compiled`` / ``_deserialize_payload`` are monkeypatched,
and the "jit callables" are plain Python stand-ins with the
``lower().compile()`` AOT surface); the one real-executable round-trip
is opt-in via LHTPU_SLOW.
"""

from __future__ import annotations

import os
import pickle
import threading
import time

import pytest

from lighthouse_tpu.common import device_telemetry as dtel
from lighthouse_tpu.ops import program_store as ps

slow = pytest.mark.skipif(
    os.environ.get("LHTPU_SLOW") != "1",
    reason="compiles and serializes a real XLA program; set LHTPU_SLOW=1")


# -- fakes --------------------------------------------------------------------


class Arr:
    """Shape/dtype carrier (enough for signatures + telemetry labels)."""

    def __init__(self, n, dtype="uint32", fill=0):
        self.shape = (n,)
        self.dtype = dtype
        self.fill = fill


class FakeCompiled:
    def __init__(self, tag, fail_call=False):
        self.tag = tag
        self.fail_call = fail_call
        self.calls = []

    def __call__(self, *args, **kwargs):
        if self.fail_call:
            raise TypeError("aval mismatch (injected)")
        self.calls.append((args, kwargs))
        return ("compiled", self.tag)


class FakeLowered:
    def __init__(self, tag, compile_s=0.0, fail=False):
        self.tag = tag
        self.compile_s = compile_s
        self.fail = fail

    def compile(self):
        if self.compile_s:
            time.sleep(self.compile_s)
        if self.fail:
            raise RuntimeError("XLA says no (injected)")
        return FakeCompiled(self.tag)


class FakeJit:
    """Stands in for a jax.jit callable: direct calls are the 'plain
    jit path', .lower().compile() is the AOT path."""

    def __init__(self, tag="p", compile_s=0.0, fail_compile=False):
        self.tag = tag
        self.compile_s = compile_s
        self.fail_compile = fail_compile
        self.direct_calls = 0
        self.lower_calls = 0

    def __call__(self, *args, **kwargs):
        self.direct_calls += 1
        return ("jit", self.tag)

    def lower(self, *args, **kwargs):
        self.lower_calls += 1
        return FakeLowered(self.tag, self.compile_s, self.fail_compile)


@pytest.fixture
def store(tmp_path, monkeypatch):
    """Configured store with the fake serializer seam + fake platform
    fingerprint (no jax import anywhere)."""
    monkeypatch.setattr(ps, "_fingerprint", lambda: {"fake": "fp-1"})
    monkeypatch.setattr(
        ps, "_serialize_compiled",
        lambda compiled: pickle.dumps(("fake-exe", compiled.tag)))

    def fake_deserialize(data, entry="-"):
        kind, tag = pickle.loads(data)
        assert kind == "fake-exe"
        return FakeCompiled(tag)

    monkeypatch.setattr(ps, "_deserialize_payload", fake_deserialize)
    monkeypatch.setattr(ps, "_MANIFEST_INFO", {
        "test::entry@f": {"backend": "test", "static_argnums": (),
                          "static_argnames": ()},
        "test::static@g": {"backend": "test", "static_argnums": (1,),
                           "static_argnames": ("flag",)},
    })
    monkeypatch.delenv("LHTPU_AOT_STORE", raising=False)
    st = ps.configure(tmp_path / "aot")
    assert st is not None
    yield st
    ps.deactivate()
    dtel.reset()


def restart(tmp_path):
    """Drop the in-process memo/telemetry and re-open the same dir —
    the fresh-interpreter simulation."""
    ps.deactivate()
    dtel.reset()
    st = ps.configure(tmp_path / "aot")
    assert st is not None
    return st


def stored_files(store):
    return sorted(store.fpdir().glob("*" + ps.FILE_SUFFIX))


# -- the round trip -----------------------------------------------------------


def test_compile_commit_then_store_hit_after_restart(store, tmp_path):
    fn = FakeJit("p1")
    f = dtel.instrument("test::entry@f", fn)
    out = f(Arr(4))
    assert out == ("compiled", "p1")
    assert fn.lower_calls == 1 and fn.direct_calls == 0
    assert store.commits == 1 and len(stored_files(store)) == 1
    # same signature again: memo hit, no second lower/commit
    assert f(Arr(4)) == ("compiled", "p1")
    assert fn.lower_calls == 1 and store.commits == 1
    snap = dtel.snapshot()["test::entry@f"]
    assert snap["sources"] == {"compiled": 2}

    st2 = restart(tmp_path)
    fn2 = FakeJit("p1b")
    f2 = dtel.instrument("test::entry@f", fn2)
    assert f2(Arr(4)) == ("compiled", "p1")   # the STORED program served
    assert fn2.lower_calls == 0 and fn2.direct_calls == 0
    assert st2.hits == 1 and st2.commits == 0
    assert dtel.snapshot()["test::entry@f"]["sources"] == {"store_hit": 1}


def test_distinct_shapes_are_distinct_programs(store):
    fn = FakeJit()
    f = dtel.instrument("test::entry@f", fn)
    f(Arr(4))
    f(Arr(8))
    f(Arr(4, dtype="int32"))
    assert fn.lower_calls == 3 and store.commits == 3


def test_static_args_stripped_at_call_time(store):
    fn = FakeJit("s")
    f = dtel.instrument("test::static@g", fn)
    a = Arr(4)
    assert f(a, 3, flag=True) == ("compiled", "s")
    st = ps._STATE
    prog = next(iter(st.memo.values()))
    # the Compiled signature drops static argnum 1 and argname "flag"
    (args, kwargs), = prog.compiled.calls
    assert args == (a,) and kwargs == {}
    # a different static VALUE is a different signature → new program
    f(a, 4, flag=True)
    assert fn.lower_calls == 2 and store.commits == 2


def test_exotic_argument_falls_back_to_jit(store):
    fn = FakeJit()
    f = dtel.instrument("test::entry@f", fn)
    assert f(object()) == ("jit", "p")
    assert fn.direct_calls == 1 and fn.lower_calls == 0
    assert store.commits == 0
    assert dtel.snapshot()["test::entry@f"]["sources"] == {"jit": 1}


# -- resilience: corruption is a counted miss + recompile ---------------------


@pytest.mark.parametrize("damage", ["bitflip", "truncate", "garbage",
                                    "empty"])
def test_corrupted_program_is_miss_plus_recompile(store, tmp_path, damage,
                                                  monkeypatch):
    f = dtel.instrument("test::entry@f", FakeJit("v1"))
    f(Arr(4))
    path, = stored_files(store)
    data = path.read_bytes()
    if damage == "bitflip":
        mid = len(data) // 2
        path.write_bytes(data[:mid] + bytes([data[mid] ^ 0x40])
                         + data[mid + 1:])
    elif damage == "truncate":
        path.write_bytes(data[: len(data) // 2])
    elif damage == "garbage":
        path.write_bytes(b"LHE\x01" + os.urandom(32))
    else:
        path.write_bytes(b"")

    reasons = []
    monkeypatch.setattr(ps, "_record_miss", reasons.append)
    st2 = restart(tmp_path)
    fn2 = FakeJit("v2")
    f2 = dtel.instrument("test::entry@f", fn2)
    out = f2(Arr(4))                  # never crashes, recompiles
    assert out == ("compiled", "v2")
    assert fn2.lower_calls == 1
    assert "corrupt" in reasons or "absent" in reasons
    assert st2.commits == 1           # the recompile re-committed
    # the damaged file was quarantined and replaced by a good one
    good, = stored_files(st2)
    rec = st2.get(ps.store_key("test::entry@f", "test",
                               ps.signature((Arr(4),), {})))
    assert rec is not None and rec["entry"] == "test::entry@f"


def test_unpicklable_record_body_is_corruption(store, tmp_path,
                                               monkeypatch):
    from lighthouse_tpu.common import flight_recorder as flight
    from lighthouse_tpu.store import envelope

    f = dtel.instrument("test::entry@f", FakeJit())
    f(Arr(4))
    path, = stored_files(store)
    # a VALID envelope around a non-record body: crc passes, unpickle
    # must not take the node down
    path.write_bytes(envelope.wrap(b"\x80\x04not really a pickle"))
    reasons = []
    monkeypatch.setattr(ps, "_record_miss", reasons.append)
    seq0 = len(flight.RECORDER)
    restart(tmp_path)
    f2 = dtel.instrument("test::entry@f", FakeJit("w"))
    assert f2(Arr(4)) == ("compiled", "w")
    assert reasons.count("corrupt") >= 1
    # the black box carries the corruption event (observatory wiring)
    assert any(e["kind"] == "aot_store_corrupt"
               for e in flight.RECORDER.snapshot()[seq0:])


def test_fingerprint_mismatch_is_full_invalidation(store, tmp_path,
                                                   monkeypatch):
    f = dtel.instrument("test::entry@f", FakeJit("old"))
    f(Arr(4))
    assert store.commits == 1
    # "upgrade jax": the fingerprint changes, the old population is
    # invisible (not even opened), everything recompiles into a new dir
    monkeypatch.setattr(ps, "_fingerprint", lambda: {"fake": "fp-2"})
    st2 = restart(tmp_path)
    fn2 = FakeJit("new")
    f2 = dtel.instrument("test::entry@f", fn2)
    assert f2(Arr(4)) == ("compiled", "new")
    assert fn2.lower_calls == 1 and st2.hits == 0
    assert st2.fpdir() != store.fpdir()
    assert (tmp_path / "aot").exists()
    # ...and the old population still exists untouched for a rollback
    assert len(stored_files(store)) == 1


def test_failed_compile_is_accounted_and_not_retried(store, monkeypatch):
    reasons = []
    monkeypatch.setattr(ps, "_record_miss", reasons.append)
    fn = FakeJit(fail_compile=True)
    f = dtel.instrument("test::entry@f", fn)
    assert f(Arr(4)) == ("jit", "p")      # plain path served the call
    assert reasons.count("compile_failed") == 1
    assert f(Arr(4)) == ("jit", "p")      # bad signature: no re-attempt
    assert fn.lower_calls == 1 and fn.direct_calls == 2


def test_failing_loaded_program_evicted_to_jit_path(store, tmp_path,
                                                    monkeypatch):
    f = dtel.instrument("test::entry@f", FakeJit())
    f(Arr(4))

    def deserialize_broken(data, entry="-"):
        return FakeCompiled("broken", fail_call=True)

    monkeypatch.setattr(ps, "_deserialize_payload", deserialize_broken)
    reasons = []
    monkeypatch.setattr(ps, "_record_miss", reasons.append)
    restart(tmp_path)
    fn2 = FakeJit("fallback")
    f2 = dtel.instrument("test::entry@f", fn2)
    assert f2(Arr(4)) == ("jit", "fallback")   # call failed → fallback
    assert reasons.count("call_failed") == 1
    assert f2(Arr(4)) == ("jit", "fallback")   # evicted, no retry loop
    assert fn2.direct_calls == 2


def test_load_phase_honors_bad_signatures(store):
    """A background load must not resurrect a program the runtime
    already rejected (evicted into the bad set by a call failure)."""
    f = dtel.instrument("test::entry@f", FakeJit())
    f(Arr(4))
    st = ps._STATE
    mkey = next(iter(st.memo))
    st.memo.pop(mkey)
    st.bad.add(mkey)
    rep = ps.load_store_programs()
    assert rep["loaded"] == 0
    assert mkey not in st.memo


def test_unusable_directory_deactivates_store(store, monkeypatch):
    """A structurally broken store dir (read-only fs): ONE failing
    dispatch deactivates the store instead of paying a failing mkdir +
    swallowed exception on every call for process life."""
    def broken_get(self, key):
        raise PermissionError("read-only filesystem (injected)")

    monkeypatch.setattr(ps.ProgramStore, "get", broken_get)
    fn = FakeJit()
    f = dtel.instrument("test::entry@f", fn)
    assert f(Arr(32)) == ("jit", "p")       # served, never crashed
    assert ps._STATE is None                # store self-deactivated
    assert f(Arr(32)) == ("jit", "p")       # hook gone: pure jit path
    assert fn.direct_calls == 2 and fn.lower_calls == 0


# -- single flight ------------------------------------------------------------


def test_concurrent_dispatchers_commit_exactly_once(store):
    """The prewarmer and a foreground dispatch racing on one entry:
    one lower+compile, one store commit, every caller served."""
    fn = FakeJit(compile_s=0.05)
    f = dtel.instrument("test::entry@f", fn)
    results = []
    barrier = threading.Barrier(6)

    def worker():
        barrier.wait()
        results.append(f(Arr(16)))

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == [("compiled", "p")] * 6
    assert fn.lower_calls == 1
    assert store.commits == 1
    assert len(stored_files(store)) == 1


# -- kill switch --------------------------------------------------------------


def test_kill_switch_bypasses_everything(tmp_path, monkeypatch):
    monkeypatch.setenv("LHTPU_AOT_STORE", "0")
    assert ps.configure(tmp_path / "aot") is None
    monkeypatch.setenv("LHTPU_AOT_STORE_DIR", str(tmp_path / "aot"))
    assert ps.configure_from_env() is None
    fn = FakeJit()
    f = dtel.instrument("test::entry@f", fn)
    assert f(Arr(4)) == ("jit", "p")
    assert fn.direct_calls == 1 and fn.lower_calls == 0
    assert not (tmp_path / "aot").exists()
    assert ps.status() == {"configured": False, "enabled": False}
    dtel.reset()


def test_unset_dir_leaves_store_inactive(monkeypatch):
    monkeypatch.delenv("LHTPU_AOT_STORE_DIR", raising=False)
    monkeypatch.delenv("LHTPU_AOT_STORE", raising=False)
    assert ps.configure_from_env() is None


# -- startup load phase (prewarm phase A) -------------------------------------


def test_load_store_programs_fills_memo_in_priority_order(store, tmp_path,
                                                          monkeypatch):
    f = dtel.instrument("test::entry@f", FakeJit())
    g = dtel.instrument("test::static@g", FakeJit("g"))
    f(Arr(4))
    g(Arr(8), 2, flag=False)
    st2 = restart(tmp_path)
    order = {"test::static@g": 0, "test::entry@f": 1}
    rep = ps.load_store_programs(priority=lambda e: order.get(e, 9))
    assert rep["loaded"] == 2 and rep["failed"] == 0
    assert rep["entries"] == {"test::entry@f": 1, "test::static@g": 1}
    # the next dispatch is a pure memo hit — no store read at all
    f2 = dtel.instrument("test::entry@f", FakeJit("x"))
    assert f2(Arr(4)) == ("compiled", "p")
    assert st2.hits == 2  # the two load-phase reads only
    assert dtel.snapshot()["test::entry@f"]["sources"] == {"store_hit": 1}
    assert ps.memo_stats() == {"test::entry@f": {"store_hit": 1},
                               "test::static@g": {"store_hit": 1}}


def test_load_store_programs_skips_damaged_files(store, tmp_path):
    f = dtel.instrument("test::entry@f", FakeJit())
    f(Arr(4))
    f(Arr(8))
    a, b = stored_files(store)
    a.write_bytes(a.read_bytes()[:10])
    restart(tmp_path)
    rep = ps.load_store_programs()
    assert rep["loaded"] == 1
    assert not a.exists()             # quarantined


def test_load_phase_quarantines_undeserializable_payload(store, tmp_path,
                                                         monkeypatch):
    """Valid envelope + record, but a payload the runtime rejects (e.g.
    jaxlib binary drift the fingerprint missed): phase A must count the
    miss AND quarantine, or the file fails every future warm start."""
    f = dtel.instrument("test::entry@f", FakeJit())
    f(Arr(4))
    st2 = restart(tmp_path)

    def always_fails(data, entry="-"):
        raise ValueError("runtime rejects this executable")

    monkeypatch.setattr(ps, "_deserialize_payload", always_fails)
    rep = ps.load_store_programs()
    assert rep == {"loaded": 0, "failed": 1, "entries": {}}
    assert stored_files(st2) == []     # quarantined
    assert st2.misses == 1 and st2.hits == 0
    # next restart's load phase is clean — the walk can report failed=0
    assert ps.load_store_programs() == {"loaded": 0, "failed": 0,
                                        "entries": {}}


# -- calibration persistence --------------------------------------------------


def test_calibration_roundtrip_and_corruption(store, tmp_path):
    data = {"threshold_pairs": 512, "source": "measured",
            "host_pairs_per_s": 1000.0}
    assert ps.save_calibration(data)
    assert ps.load_calibration() == data
    st2 = restart(tmp_path)
    assert ps.load_calibration() == data   # survives restart
    cal = st2._calibration_path()
    cal.write_bytes(cal.read_bytes()[:8])
    assert ps.load_calibration() is None   # corrupt → miss, not crash
    assert not cal.exists()                # quarantined
    assert ps.save_calibration(data)       # re-measure path can re-save


def test_calibration_invalidated_by_fingerprint_change(store, tmp_path,
                                                       monkeypatch):
    assert ps.save_calibration({"threshold_pairs": 256})
    monkeypatch.setattr(ps, "_fingerprint", lambda: {"fake": "fp-9"})
    restart(tmp_path)
    assert ps.load_calibration() is None


def test_apply_calibration_sets_thresholds():
    from lighthouse_tpu.ops import sha256 as sha_ops

    saved = (sha_ops._DEVICE_MIN_PAIRS, sha_ops._DEVICE_FOLD_MIN_LEAVES,
             sha_ops._CALIBRATED)
    try:
        assert sha_ops.apply_calibration({"threshold_pairs": 4096})
        assert sha_ops._DEVICE_MIN_PAIRS == 4096
        assert sha_ops._DEVICE_FOLD_MIN_LEAVES == 8192
        assert sha_ops._CALIBRATED
        # malformed records change nothing and report False (the
        # caller then falls back to measuring)
        assert not sha_ops.apply_calibration({})
        assert not sha_ops.apply_calibration({"threshold_pairs": "no"})
        assert not sha_ops.apply_calibration({"threshold_pairs": 0})
        assert sha_ops._DEVICE_MIN_PAIRS == 4096
    finally:
        (sha_ops._DEVICE_MIN_PAIRS, sha_ops._DEVICE_FOLD_MIN_LEAVES,
         sha_ops._CALIBRATED) = saved


# -- prewarm gating (no drivers run here) -------------------------------------


def test_prewarm_skips_without_store():
    from lighthouse_tpu.ops import prewarm

    ps.deactivate()
    rep = prewarm.run()
    assert rep == {"ran": False, "skipped": "store not configured"}


def test_prewarm_gate_env(store, monkeypatch):
    from lighthouse_tpu.ops import prewarm

    monkeypatch.setenv("LHTPU_AOT_PREWARM", "0")
    rep = prewarm.run()
    assert rep["skipped"] == "LHTPU_AOT_PREWARM gate"
    monkeypatch.setenv("LHTPU_AOT_PREWARM", "1")
    assert prewarm.should_run() is True
    monkeypatch.setenv("LHTPU_AOT_PREWARM", "auto")
    monkeypatch.setenv("LHTPU_AOT_STORE_DIR", "/tmp/somewhere")
    assert prewarm.should_run() is True


def _swallowed(site: str) -> float:
    from lighthouse_tpu.common.metrics import REGISTRY

    return REGISTRY.counter("offload_swallowed_errors_total").labels(
        site=site).value


def test_prewarm_reports_an_owner_that_does_not_import(store, monkeypatch):
    """A device module that no longer imports must not kill the prewarm
    thread in silence: the failure is in the report and counted."""
    from lighthouse_tpu.ops import prewarm

    def boom():
        raise ImportError("cannot import name 'enable_x64'")

    monkeypatch.setattr(prewarm, "_import_owners", boom)
    before = _swallowed("prewarm.import_owners")
    rep = prewarm.run(force=True)
    assert rep["ran"] is False
    assert "enable_x64" in rep["import_error"]
    assert _swallowed("prewarm.import_owners") == before + 1


def test_prewarm_accounts_unknown_driver_tags(store, monkeypatch):
    """A typo'd register_entry driver tag must surface as a missing
    outcome + unknown_drivers report, never a silent skip."""
    from lighthouse_tpu.ops import prewarm

    monkeypatch.setattr(ps, "_REGISTERED", {"test::entry@f": "sha265"})
    monkeypatch.setattr(prewarm, "_import_owners", lambda: None)
    monkeypatch.setattr(prewarm, "_resolve_scale", lambda: "tiny")
    monkeypatch.setattr(prewarm, "calibration_step", lambda: {
        "source": "env"})
    monkeypatch.setattr(prewarm, "msm_calibration_step", lambda: {
        "source": "env"})
    rep = prewarm.run(force=True)
    assert rep["unknown_drivers"] == {"sha265": ["test::entry@f"]}
    assert rep["outcomes"] == {"test::entry@f": "missing"}
    assert rep["counts"]["missing"] == 1


def test_entry_priority_orders_bls_first():
    from lighthouse_tpu.ops import prewarm

    # the real registrations (importing the owner modules is heavier
    # than this test wants) aren't needed: rank through a stub registry
    stub = {"a": "bls", "b": "sha256", "c": "shuffle", "d": "unknown"}
    orig = ps.registered_entries
    ps_registered = lambda: dict(stub)  # noqa: E731
    try:
        ps.registered_entries = ps_registered
        ranks = [prewarm.entry_priority(e) for e in ("a", "b", "c", "d")]
        assert ranks[0] < ranks[1] < ranks[2] < ranks[3]
    finally:
        ps.registered_entries = orig


# -- the real thing (opt-in) --------------------------------------------------


@slow
def test_real_executable_roundtrip(tmp_path, monkeypatch):
    """End to end with a REAL jax program: compile+serialize on the
    first process-life, deserialize+serve on the second, identical
    results, source flips compiled → store_hit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    monkeypatch.setattr(ps, "_MANIFEST_INFO", {
        "test::real@f": {"backend": "test", "static_argnums": (),
                         "static_argnames": ()}})
    monkeypatch.delenv("LHTPU_AOT_STORE", raising=False)
    try:
        st = ps.configure(tmp_path / "aot")
        f = dtel.instrument("test::real@f", jax.jit(lambda x: x * 3 + 1))
        x = jnp.arange(16, dtype=jnp.uint32)
        cold = np.asarray(f(x))
        assert st.commits == 1
        assert dtel.snapshot()["test::real@f"]["sources"] == {
            "compiled": 1}

        ps.deactivate()
        dtel.reset()
        st2 = ps.configure(tmp_path / "aot")
        f2 = dtel.instrument("test::real@f", jax.jit(lambda x: x * 3 + 1))
        warm = np.asarray(f2(x))
        assert np.array_equal(cold, warm)
        assert st2.hits == 1
        assert dtel.snapshot()["test::real@f"]["sources"] == {
            "store_hit": 1}
    finally:
        ps.deactivate()
        dtel.reset()
