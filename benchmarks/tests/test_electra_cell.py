"""The electra signature cell: the configuration at full size, what decides
``correct`` shown to fail, and its fold roofline counted by hand.

The control is the plain reference without its 64-bit blinding scalars: it
accepts both swapped variants.  The faults break the timed path underneath
a whole rehearsal-size run of ``run.main``.  Sizes are the workload's
``rehearse_params`` (two sets of 1,100 keys: wider than a segment of the
fold, so the device path splits them when it is the one that serves); the
chip-size control is ``control_at_size_electra.py``.
"""

import importlib
import json
import os

import pytest

from benchmarks import run
from benchmarks.rooflines import blinded_fold, pipeline_fused

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (1, 2_147_483_659, 3_000_000_019)
NAME = "block-8x32k"


def _files():
    workload = json.load(open(os.path.join(BENCH, "workloads", f"{NAME}.json")))
    config = json.load(open(os.path.join(
        BENCH, "configs", f"{workload['config']}.json")))
    return workload, config


def _cell(seed):
    workload, config = _files()
    params = {**workload["params"], **workload["rehearse_params"]}
    generator = importlib.import_module(
        f"benchmarks.traffic.{workload['generator']}")
    return generator.build(config, params, seed, lambda text: None)


def _wrong(compared):
    return {k for k, (value, limit) in compared.items() if value > limit}


def test_the_cell_is_the_configuration_at_full_size():
    from lighthouse_tpu.ops import bls_backend as bb
    from lighthouse_tpu.types.spec import MAINNET_PRESET

    workload, config = _files()
    params, preset = workload["params"], config["preset"]
    assert (MAINNET_PRESET.max_committees_per_slot,
            MAINNET_PRESET.slots_per_epoch,
            MAINNET_PRESET.max_attestations_electra,
            MAINNET_PRESET.max_attester_slashings_electra,
            MAINNET_PRESET.max_validators_per_committee,
            MAINNET_PRESET.target_committee_size,
            MAINNET_PRESET.sync_committee_size) == (
        preset["MAX_COMMITTEES_PER_SLOT"], preset["SLOTS_PER_EPOCH"],
        preset["MAX_ATTESTATIONS_ELECTRA"],
        preset["MAX_ATTESTER_SLASHINGS_ELECTRA"],
        preset["MAX_VALIDATORS_PER_COMMITTEE"],
        preset["TARGET_COMMITTEE_SIZE"], preset["SYNC_COMMITTEE_SIZE"])
    # the published widths, uncut: committees of 512 at 2^20 validators, 64
    # of them an aggregate, 8 aggregates and a 512-member sync set a block
    committee = config["active_validators"] // (
        preset["SLOTS_PER_EPOCH"] * preset["MAX_COMMITTEES_PER_SLOT"])
    assert committee == config["committee_size"] == 512
    assert config["keys_per_aggregate"] == (
        config["committees_per_aggregate"] * committee) == 32768
    assert config["committees_per_aggregate"] == preset["MAX_COMMITTEES_PER_SLOT"]
    wide, sync, single = params["sets"]
    assert wide == {"count": preset["MAX_ATTESTATIONS_ELECTRA"],
                    "keys": config["keys_per_aggregate"], "replace": False}
    assert sync == {"count": 1, "keys": preset["SYNC_COMMITTEE_SIZE"],
                    "replace": True}
    assert single == {"count": 2, "keys": 1, "replace": False}
    keys, sets = blinded_fold.request_shape(params)
    assert (keys, sets) == (config["keys_per_block"],
                            config["sets_per_block"]) == (262658, 11)
    assert config["blinding_bits"] == 64
    assert config["reduced"] == ["key_pool"] and config["architecture"] is None
    assert params["key_pool"] == config["key_pool"] == 262144
    assert (params["good"], params["bad"], params["check_requests"]) == (6, 2, 4)
    # the shapes the hints name are the ones the program gives this block
    assert bb._fold_shape([s["keys"] for s in params["sets"]
                           for _ in range(s["count"])]) == (512, 32)
    hints = {h["entry"].split(":")[1]: h["args"] for h in params["precompile"]}
    assert hints["_blinded_fold"][0]["zeros"] == [2 * 512 * 32, 27]
    assert hints["_blinded_fold"][-1] == 32
    assert hints["_pipeline_fused"][0]["zeros"] == [
        bb._next_pow2(sets, floor=4), 27] == [16, 27]
    assert hints["_g2_subgroup_kernel"][0]["zeros"] == [16, 27]
    # the rehearsal still splits a set over several segments
    assert all(s["keys"] > 2 * 512 or s["keys"] <= 16
               for s in workload["rehearse_params"]["sets"])


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_passes_and_unblinded_control_accepts_the_swaps(seed):
    cell = _cell(seed)
    cell.precompile.join()
    entries = range(len(cell.batches))
    sound = [(e, cell.reference_verdict(e)) for e in entries]
    assert [v for _, v in sound] == cell.expect_by_construction == [
        True, True, False, False]
    assert not _wrong(cell.check(sound))
    control = [(e, cell.reference_verdict(e, blind=False)) for e in entries]
    assert [v for _, v in control] == [True] * 4
    assert _wrong(cell.check(control)) == {"verdict_mismatches"}


def _run(capsys, seed=7, seconds=1):
    rc = run.main(["--workload", NAME, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0", "--rehearse"])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return rc, json.loads(line)


def test_a_sound_run_is_correct(capsys):
    rc, result = _run(capsys)
    assert rc == 0 and result["correct"] is True
    assert set(result["metrics"]) == {"block_verify_p50_ms", "setup_s"}


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
def test_faults_in_the_served_verdicts_are_not_correct(capsys, monkeypatch,
                                                       fault):
    from lighthouse_tpu.crypto import bls

    import benchmarks.traffic.bls_sets as gen

    real = bls.verify_signature_sets
    warm = gen.Cell.warm_up

    # the warm-up holds each pool entry to its construction and would stop
    # the run: the fault goes in after it
    def warm_then_break(self):
        warm(self)
        monkeypatch.setattr(bls, "verify_signature_sets", fault(real))

    monkeypatch.setattr(gen.Cell, "warm_up", warm_then_break)
    # long enough for the cycle to come round
    rc, result = _run(capsys, seconds=8)
    assert rc == 1 and result["correct"] is False


PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def test_fold_roofline_counts_by_hand():
    workload, _ = _files()
    # one set of 2 keys: one mixed addition, 11 products of 13,824 int8 ops;
    # two points in, one point and its flag out
    assert blinded_fold.work(2, 1) == {"ops": 11 * 13824,
                                       "bytes": 2 * 216 + 217}
    assert blinded_fold.INT8_OPS_PER_FP_MUL == pipeline_fused.INT8_OPS_PER_FP_MUL
    # the cell: 8 x 32,768 + 512 + 2 keys in 11 sets
    keys, sets = blinded_fold.request_shape(workload["params"])
    assert (keys, sets) == (8 * 32768 + 512 + 2, 11)
    assert blinded_fold.work(keys, sets) == {
        "ops": 262647 * 11 * 13824, "bytes": 262658 * 216 + 11 * 217}
    ctx = {"requests": 3, "params": workload["params"]}
    least, binds = blinded_fold.least_seconds(ctx, PEAKS, 51)
    assert binds == "compute"
    assert least == pytest.approx(3 * 262647 * 11 * 13824 / 393e12)
    # lanes, slices and dispatches change nothing
    assert blinded_fold.least_seconds(ctx, PEAKS, 1)[0] == least
