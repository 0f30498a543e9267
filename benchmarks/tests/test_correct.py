"""What decides ``correct``, shown to fail.

The control is the plain reference put in the program's place with one
step taken that would tempt a later PR: the batch check without its 64-bit
blinding scalars (BLS cells), the flag rewards on 32-bit integers (state
cell).  The faults break the timed path underneath a whole rehearsal-size
run of ``run.main`` (the look for a chip skipped by ``--rehearse``) and
``correct`` has to come out false.  Sizes are the workloads'
``rehearse_params``; the chip-size readings are in PERF.md.
"""

import json
import os

import numpy as np
import pytest

from benchmarks import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (1, 2_147_483_659, 3_000_000_019)


def _cell(name, seed):
    import importlib

    workload = json.load(open(os.path.join(BENCH, "workloads", f"{name}.json")))
    config = json.load(open(os.path.join(
        BENCH, "configs", f"{workload['config']}.json")))
    params = {**workload["params"], **workload.get("rehearse_params", {})}
    generator = importlib.import_module(
        f"benchmarks.traffic.{workload['generator']}")
    return generator.build(config, params, seed, lambda text: None)


def _wrong(compared):
    return {k for k, (value, limit) in compared.items() if value > limit}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", ["block-131"])
def test_bls_reference_passes_and_unblinded_control_fails(name, seed):
    cell = _cell(name, seed)
    cell.precompile.join()
    entries = range(len(cell.batches))
    sound = [(e, cell.reference_verdict(e)) for e in entries]
    assert [v for _, v in sound] == cell.expect_by_construction
    assert not _wrong(cell.check(sound))
    control = [(e, cell.reference_verdict(e, blind=False)) for e in entries]
    assert "verdict_mismatches" in _wrong(cell.check(control))


@pytest.mark.parametrize("seed", SEEDS)
def test_epoch_reference_passes_and_int32_control_fails(seed):
    cell = _cell("epoch-boundary", seed)
    from benchmarks.traffic.epoch_state import plain

    variants = cell.variants
    sound = [(k, cell.reference_answer(plain(v)))
             for k, v in enumerate(variants)]
    assert not _wrong(cell.check(sound))
    cell.variants = variants
    control = [(k, cell.reference_answer(plain(v), precision="int32"))
               for k, v in enumerate(variants)]
    cell.variants = variants
    assert _wrong(cell.check(control)) == {
        "state_root_mismatches", "post_state_mismatches"}


def _run(capsys, name, seed=7, seconds=1):
    rc = run.main(["--workload", name, "--seed", str(seed),
                   "--seconds", str(seconds),
                   "--trace", "0", "--rehearse"])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return rc, json.loads(line)


@pytest.mark.parametrize("name", ["block-131", "epoch-boundary"])
def test_a_sound_run_is_correct(capsys, name):
    rc, result = _run(capsys, name)
    assert rc == 0 and result["correct"] is True


def _half_left_out(real):
    def verify(sets, **kw):
        return real(sets[:len(sets) // 2], **kw)
    return verify


def _answer_altered(real):
    calls = []

    def verify(sets, **kw):
        calls.append(1)
        ok = real(sets, **kw)
        return (not ok) if len(calls) % 3 == 0 else ok
    return verify


@pytest.mark.parametrize("fault", [_half_left_out, _answer_altered])
@pytest.mark.parametrize("name", ["block-131"])
def test_bls_faults_are_not_correct(capsys, monkeypatch, name, fault):
    from lighthouse_tpu.crypto import bls

    real = bls.verify_signature_sets
    # the warm-up checks each pool entry against its construction and
    # would stop the run: the fault goes in after it
    import benchmarks.traffic.bls_sets as gen

    warm = gen.Cell.warm_up

    def warm_then_break(self):
        warm(self)
        monkeypatch.setattr(bls, "verify_signature_sets", fault(real))

    monkeypatch.setattr(gen.Cell, "warm_up", warm_then_break)
    # long enough for the cycle to come round: a fault shows only in the
    # pool entries it reaches
    rc, result = _run(capsys, name, seconds=5)
    assert rc == 1 and result["correct"] is False


def _state_unchanged(real):
    return lambda state, spec, target: None


def _half_of_registry(real):
    def advance(state, spec, target):
        before = np.array(state.balances)
        real(state, spec, target)
        half = len(before) // 2
        state.balances[half:] = before[half:]
    return advance


def _balance_altered(real):
    def advance(state, spec, target):
        real(state, spec, target)
        state.balances[3] += np.uint64(1)
    return advance


@pytest.mark.parametrize(
    "fault", [_state_unchanged, _half_of_registry, _balance_altered])
def test_epoch_faults_are_not_correct(capsys, monkeypatch, fault):
    from lighthouse_tpu.state_transition import slot_processing

    monkeypatch.setattr(slot_processing, "state_advance",
                        fault(slot_processing.state_advance))
    rc, result = _run(capsys, "epoch-boundary")
    assert rc == 1 and result["correct"] is False
