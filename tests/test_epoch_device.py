"""Device epoch pass (ops/epoch_kernels via the epoch_processing seam).

Fast tests are zero-XLA: seam routing, breaker/fault recovery (with the
device bridge monkeypatched), gather-table exactness against the spec
formulas in Python bigints, bucket/clamp plumbing.  The tests that
actually compile the fused program (verdict identity on randomized
states across forks, the mesh-sharded rung) sit behind LHTPU_SLOW=1
like every other extra-compile-shape suite.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from lighthouse_tpu import types as T
from lighthouse_tpu.state_transition import epoch_processing as ep
from lighthouse_tpu.testing import (
    randomized_registry_state as randomized_state,
    registry_state_digest as state_digest,
)

slow = pytest.mark.skipif(
    os.environ.get("LHTPU_SLOW") != "1",
    reason="compiles the fused epoch program; set LHTPU_SLOW=1")


@pytest.fixture(autouse=True)
def _clean_seam(monkeypatch):
    monkeypatch.delenv("LHTPU_EPOCH_BACKEND", raising=False)
    monkeypatch.delenv("LHTPU_EPOCH_DEVICE_MIN", raising=False)
    monkeypatch.delenv("LHTPU_EPOCH_BUCKET_FLOOR", raising=False)
    ep.reset_epoch_supervisor()
    yield
    ep.reset_epoch_supervisor()


# randomized_state / state_digest live in lighthouse_tpu.testing
# (randomized_registry_state / registry_state_digest): shared with the
# pinned digests in test_epoch_pins.py and bench.py --child-epoch.


# -- fast: seam routing -------------------------------------------------------


def test_auto_routing_small_registry_stays_reference(monkeypatch):
    # below the device-min threshold no jax import may even happen
    import builtins

    real_import = builtins.__import__

    def guarded(name, *a, **k):
        assert name != "jax", "auto routing touched jax below the threshold"
        return real_import(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", guarded)
    assert ep.resolve_epoch_backend(4096) == "reference"


def test_forced_backend_wins(monkeypatch):
    monkeypatch.setenv("LHTPU_EPOCH_BACKEND", "device")
    assert ep.resolve_epoch_backend(8) == "device"
    monkeypatch.setenv("LHTPU_EPOCH_BACKEND", "sharded")
    assert ep.resolve_epoch_backend(8) == "sharded"
    monkeypatch.setenv("LHTPU_EPOCH_BACKEND", "bogus")
    assert ep.resolve_epoch_backend(8) == "reference"


def test_breaker_opens_and_auto_falls_back(monkeypatch):
    from lighthouse_tpu.state_transition import epoch_device

    st, spec = randomized_state(64, "altair", seed=7)
    ref = st.copy()
    monkeypatch.setenv("LHTPU_EPOCH_BACKEND", "reference")
    ep.process_epoch(ref, spec)

    calls = {"n": 0}

    def boom(*a, **k):
        calls["n"] += 1
        raise RuntimeError("injected epoch device fault")

    monkeypatch.setattr(epoch_device, "prepare_and_run", boom)
    monkeypatch.setenv("LHTPU_EPOCH_BACKEND", "device")
    monkeypatch.setenv("LHTPU_SUPERVISOR_FAILS", "1")
    flt = st.copy()
    ep.process_epoch(flt, spec)  # must not raise: reference recovery
    assert calls["n"] == 1
    assert state_digest(flt) == state_digest(ref)
    assert ep._BREAKER["open_until"] > 0
    # breaker open: auto routing parks on reference without re-probing
    monkeypatch.delenv("LHTPU_EPOCH_BACKEND")
    assert ep.resolve_epoch_backend(10**7) == "reference"
    ep.reset_epoch_supervisor()
    assert ep._BREAKER["open_until"] == 0.0


@pytest.mark.parametrize("exc", [
    ImportError("cannot import name 'enable_x64' from 'jax.experimental'"),
    AttributeError("module 'jax' has no attribute 'moved'"),
    NameError("name 'enable_x64' is not defined"),
    TypeError("fn() got an unexpected keyword argument"),
])
def test_program_fault_is_loud_in_epoch_and_shuffle(monkeypatch, exc):
    """A device module that does not import or trace is re-raised, not
    filed as a device fault: on jax 0.9.0 exactly this ImportError was
    swallowed every epoch, the breaker opened and numpy served — with
    exit code 0."""
    import numpy as np

    from lighthouse_tpu.state_transition import epoch_device, shuffle

    def boom(*a, **k):
        raise exc

    st, spec = randomized_state(64, "altair", seed=7)
    monkeypatch.setattr(epoch_device, "prepare_and_run", boom)
    monkeypatch.setenv("LHTPU_EPOCH_BACKEND", "device")
    with pytest.raises(type(exc)):
        ep.process_epoch(st, spec)
    monkeypatch.setattr(shuffle, "shuffle_list_device", boom)
    with pytest.raises(type(exc)):
        shuffle.shuffle_list(np.arange(512, dtype=np.uint64), b"\x01" * 32,
                             10, device=True)
    assert ep._BREAKER["open_until"] == 0.0


def test_fault_leaves_state_untouched_for_reference_rerun(monkeypatch):
    """A fault AFTER partial prep must not leave a torn state: the
    bridge applies columns only after every fetch completed."""
    from lighthouse_tpu.state_transition import epoch_device

    st, spec = randomized_state(128, "altair", seed=9)
    before = state_digest(st)

    def late_boom(state, *a, **k):
        # emulate a fault between prep and apply: bridge contract says
        # state is untouched at any raise point
        assert state_digest(state) == before
        raise RuntimeError("late fault")

    monkeypatch.setattr(epoch_device, "prepare_and_run", late_boom)
    monkeypatch.setenv("LHTPU_EPOCH_BACKEND", "device")
    ref = st.copy()
    monkeypatch.setenv("LHTPU_EPOCH_BACKEND", "reference")
    ep.process_epoch(ref, spec)
    monkeypatch.setenv("LHTPU_EPOCH_BACKEND", "device")
    ep.process_epoch(st, spec)
    assert state_digest(st) == state_digest(ref)


# -- fast: exact tables -------------------------------------------------------


def test_tables_match_spec_formulas_bigint():
    from lighthouse_tpu.state_transition import epoch_device

    st, spec = randomized_state(300, "altair", seed=11)
    leak = ep.is_in_inactivity_leak(st, spec)
    tables = epoch_device.build_tables(st, spec, "altair", leak=leak)
    assert tables is not None
    v = st.validators
    incr = spec.effective_balance_increment
    from lighthouse_tpu.state_transition import misc

    total = misc.get_total_active_balance(st, spec)
    brpi = ep.base_reward_per_increment(spec, total)
    total_increments = total // incr
    prev = misc.previous_epoch(st, spec)
    unslashed_active = v.is_active(prev) & ~v.slashed
    for f, w in enumerate(ep.PARTICIPATION_FLAG_WEIGHTS):
        part = unslashed_active & ep.has_flag(
            st.previous_epoch_participation, f)
        u_incr = max(int(v.effective_balance[part].sum()), incr) // incr
        for k in (0, 1, 7, 32):
            base_reward = k * brpi
            expect = (0 if leak else
                      base_reward * w * u_incr
                      // (total_increments * ep.WEIGHT_DENOMINATOR))
            assert tables["reward"][f][k] == expect
            if f != ep.TIMELY_HEAD_FLAG_INDEX:
                assert tables["penalty"][f][k] == (
                    base_reward * w // ep.WEIGHT_DENOMINATOR)
    mult = ep._proportional_slashing_multiplier(spec, "altair")
    adjusted = min(int(st.slashings.sum()) * mult, total)
    for k in (0, 5, 32):
        assert tables["slash"][k] == (k * adjusted) // total * incr


def test_table_guards_route_overflow_to_reference():
    from lighthouse_tpu.state_transition import epoch_device

    st, spec = randomized_state(64, "altair", seed=13)
    st.inactivity_scores[3] = np.uint64(2**61)  # eff*score overflows i64
    assert epoch_device.build_tables(st, spec, "altair", leak=False) is None
    st, spec = randomized_state(64, "altair", seed=13)
    st.validators.effective_balance[0] = np.uint64(
        spec.max_effective_balance + spec.effective_balance_increment)
    assert epoch_device.build_tables(st, spec, "altair", leak=False) is None


def test_bucket_and_clamp_plumbing():
    from lighthouse_tpu.ops import epoch_kernels as ek
    from lighthouse_tpu.state_transition import epoch_device

    assert ek.bucket_size(1, 256) == 256
    assert ek.bucket_size(257, 256) == 512
    assert ek.bucket_size(4096, 256) == 4096
    assert ek.bucket_size(4097, 256) == 8192
    clamped = epoch_device._clamp_epochs(
        np.array([0, 5, T.FAR_FUTURE_EPOCH], np.uint64))
    assert clamped.dtype == np.int64
    assert clamped[2] == epoch_device.EPOCH_CLAMP
    assert list(clamped[:2]) == [0, 5]


def test_columns_pad_with_masked_tail():
    from lighthouse_tpu.state_transition import epoch_device

    st, spec = randomized_state(100, "altair", seed=17)
    cols = epoch_device.build_columns(st, spec, 256)
    for name, col in cols.items():
        assert col.shape[0] == 256, name
    # tail lanes: inactive, unslashed, zero balance — every mask False
    assert not cols["slashed"][100:].any()
    assert (cols["activation"][100:] == 0).all()
    assert (cols["exit_epoch"][100:] == 0).all()  # active_prev False
    assert (cols["balances"][100:] == 0).all()


# -- slow: the real fused program ---------------------------------------------


@slow
@pytest.mark.parametrize("fork", ["altair", "bellatrix", "electra"])
@pytest.mark.parametrize("leak", [False, True])
def test_device_verdict_identical_randomized(fork, leak, monkeypatch):
    for n in (200, 777):  # non-pow2: masked tails at buckets 256/1024
        st, spec = randomized_state(n, fork, seed=n + leak, leak=leak)
        ref = st.copy()
        monkeypatch.setenv("LHTPU_EPOCH_BACKEND", "reference")
        ep.process_epoch(ref, spec)
        dev = st.copy()
        monkeypatch.setenv("LHTPU_EPOCH_BACKEND", "device")
        ep.process_epoch(dev, spec)
        assert state_digest(ref) == state_digest(dev), (fork, leak, n)


@slow
def test_sharded_verdict_identical(monkeypatch):
    st, spec = randomized_state(1000, "altair", seed=23)
    ref = st.copy()
    monkeypatch.setenv("LHTPU_EPOCH_BACKEND", "reference")
    ep.process_epoch(ref, spec)
    shd = st.copy()
    monkeypatch.setenv("LHTPU_EPOCH_BACKEND", "sharded")
    ep.process_epoch(shd, spec)
    assert state_digest(ref) == state_digest(shd)


@slow
def test_device_engages_and_records(monkeypatch):
    from lighthouse_tpu.ops import epoch_kernels as ek

    calls = {"n": 0}
    orig = ek.epoch_pass_device

    def spy(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(ek, "epoch_pass_device", spy)
    st, spec = randomized_state(200, "altair", seed=29)
    monkeypatch.setenv("LHTPU_EPOCH_BACKEND", "device")
    ep.process_epoch(st, spec)
    assert calls["n"] == 1


# -- fast: batched exit queue -------------------------------------------------
# process_registry_updates ejects through initiate_validator_exits (one
# O(n) queue scan for the whole sweep) / a hoisted electra churn limit.
# These pin the batch paths to the scalar per-validator semantics.


def _scalar_ejection_sweep(st, spec, fork):
    """The pre-batching ejection loop: scalar initiate per candidate."""
    from lighthouse_tpu.state_transition.electra import (
        initiate_validator_exit_electra,
    )

    v = st.validators
    cur = int(st.slot) // spec.slots_per_epoch
    eject = v.is_active(np.uint64(cur)) & (
        v.effective_balance <= np.uint64(spec.ejection_balance))
    for idx in np.nonzero(eject)[0]:
        if fork == "electra":
            initiate_validator_exit_electra(st, spec, int(idx))
        else:
            ep.initiate_validator_exit(st, spec, int(idx))


@pytest.mark.parametrize("fork", ["altair", "electra"])
def test_batched_ejections_match_scalar_sweep(fork):
    # eff balances drawn 0..max put ~half the active lanes at or below
    # the ejection balance: a mass ejection that walks the queue across
    # many epochs (churn at minimal preset is small), so epoch bumps,
    # pre-existing exits at the tail epoch, and already-exited skips
    # are all exercised
    st, spec = randomized_state(512, fork, seed=97)
    scalar = st.copy()
    _scalar_ejection_sweep(scalar, spec, fork)
    batched = st.copy()
    ep.process_registry_updates(batched, spec, fork)
    assert np.array_equal(scalar.validators.exit_epoch,
                          batched.validators.exit_epoch)
    assert np.array_equal(scalar.validators.withdrawable_epoch,
                          batched.validators.withdrawable_epoch)
    if fork == "electra":
        assert (int(scalar.earliest_exit_epoch)
                == int(batched.earliest_exit_epoch))
        assert (int(scalar.exit_balance_to_consume)
                == int(batched.exit_balance_to_consume))
