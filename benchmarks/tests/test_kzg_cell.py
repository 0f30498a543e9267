"""The KZG blob cell: what decides ``correct``, shown to fail, and its
roofline counts checked by hand.

The control is the plain reference with every power of r set to 1 — the
step that would take the 255-bit scalar multiplications out of half of the
fused program's lanes.  It must accept the forged pair of variant (b) and
nothing else that is bad: that variant is one only the random linear
combination catches.  The faults break the timed path underneath a whole
rehearsal-size run of ``run.main``.  Sizes are the workload's
``rehearse_params``; the chip-size control is ``control_at_size_kzg.py``.
"""

import importlib
import json
import os

import pytest

from benchmarks import run
from benchmarks.rooflines import kzg_batch, kzg_eval, kzg_fused

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (1, 2_147_483_659, 3_000_000_019)
NAME = "kzg-6x128"


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
    workload, config = _files()
    params = workload["params"]
    assert params["blocks"] == config["network"]["MAX_REQUEST_BLOCKS_DENEB"]
    assert params["blobs_per_block"] == config["preset"]["MAX_BLOBS_PER_BLOCK"]
    assert (params["field_elements_per_blob"]
            == config["preset"]["FIELD_ELEMENTS_PER_BLOB"])
    assert (params["blocks"] * params["blobs_per_block"]
            == config["network"]["MAX_REQUEST_BLOB_SIDECARS"])
    assert (params["blocks"], params["blobs_per_block"]) == (
        config["blocks"], config["blobs_per_block"])
    assert config["bytes_per_request"] == (
        768 * 4096 * config["preset"]["BYTES_PER_FIELD_ELEMENT"])
    assert config["reduced"] == []
    # ISSUE 29's traffic: a pool of 2 good batches and 2 bad variants of
    # the first, 3 of 4 requests good
    assert (params["good"], params["bad"], params["good_repeats"]) == (2, 2, 3)
    for seed in SEEDS:
        cycle = _cell(seed).cycle
        assert sorted(cycle) == [0, 0, 0, 1, 1, 1, 2, 3]


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_passes_and_unweighted_control_accepts_the_forged_pair(seed):
    cell = _cell(seed)
    entries = range(len(cell.pool))
    sound = [(e, cell.reference_verdict(e)) for e in entries]
    assert [v for _, v in sound] == cell.expect_by_construction
    assert cell.expect_by_construction == [True, True, False, False]
    assert not _wrong(cell.check(sound))
    control = [(e, cell.reference_verdict(e, blind=False)) for e in entries]
    # r = 1 still rejects the changed field element and accepts the pair
    assert [v for _, v in control] == [True, True, False, True]
    assert _wrong(cell.check(control)) == {
        "verdict_mismatches", "verdicts_off_construction"}


def test_the_system_rejects_what_the_control_accepts():
    cell = _cell(SEEDS[1])
    forged = len(cell.pool) - 1
    i = cell.cycle.index(forged)
    assert cell.reference_verdict(forged, blind=False) is True
    assert cell.serve(cell.prepare(i)) is False


def _run(capsys, seed=7, seconds=1):
    rc = run.main(["--workload", NAME, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0", "--rehearse"])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return rc, json.loads(line)


def test_a_sound_run_is_correct(capsys):
    rc, result = _run(capsys)
    assert rc == 0 and result["correct"] is True
    assert set(result["metrics"]) == {"block_verify_p50_ms", "setup_s"}


def _break_after_warm_up(monkeypatch, planted):
    # the warm-up holds its request to the construction and would stop the
    # run: the fault goes in after it
    import benchmarks.traffic.kzg_blobs as gen

    warm = gen.Cell.warm_up

    def warm_then_break(self):
        warm(self)
        planted()

    monkeypatch.setattr(gen.Cell, "warm_up", warm_then_break)


def test_a_batch_served_on_the_host_path_is_not_correct(capsys, monkeypatch):
    from lighthouse_tpu.crypto import kzg

    _break_after_warm_up(monkeypatch, lambda: monkeypatch.setattr(
        kzg, "_DEVICE_EVAL_MIN", 1 << 20))
    rc, result = _run(capsys)
    assert rc == 1 and result["correct"] is False


def test_an_accepted_bad_batch_is_not_correct(capsys, monkeypatch):
    from lighthouse_tpu.ops import bls_backend

    _break_after_warm_up(monkeypatch, lambda: monkeypatch.setattr(
        bls_backend, "_final_exp_is_one", lambda f: True))
    # long enough for the cycle to reach a bad variant
    rc, result = _run(capsys, seconds=6)
    assert rc == 1 and result["correct"] is False


PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def test_roofline_counts_by_hand():
    # evaluation, one blob of 4,096: 5 products an element, the one
    # inversion (254 squarings, popcount(r - 2) - 1 = 163 products), 12
    # squarings for z^4096, 2 more; 6,144 int8 operations a product
    assert kzg_eval.FR_MUL_INVERSION == 254 + 163
    assert kzg_eval.work(1, 4096) == {
        "ops": (4096 * 5 + 417 + 12 + 2) * 6144, "bytes": 4098 * 32}
    assert kzg_eval.work(1, 4096)["ops"] == 128_477_184
    assert kzg_eval.work(768, 4096) == {
        "ops": 768 * 128_477_184, "bytes": 768 * 4098 * 32}
    # fused check, one blob: 4 points of 3,056, two Miller lanes of 5,311
    # and 2,268 for the shared accumulator; 13,824 a product
    assert kzg_fused.FP_MUL_PER_POINT == 3056
    assert kzg_fused.work(1, 1) == {
        "ops": (4 * 3056 + 2 * 5311 + 2268) * 13824,
        "bytes": 4 * 128 + 960}
    assert kzg_fused.work(1, 1)["ops"] == 347_175_936
    # 768 blobs in one batch: 3 * 768 + 1 = 2,305 points
    assert kzg_fused.work(768, 1) == {
        "ops": (2305 * 3056 + 2 * 5311 + 2268) * 13824,
        "bytes": 2305 * 128 + 960}
    assert kzg_fused.work(768, 1)["ops"] == 97_555_553_280


def test_roofline_seconds_of_one_full_request():
    ctx = {"requests": 1, "units_per_request": 768,
           "params": {"field_elements_per_blob": 4096}}
    ev, binds_ev = kzg_eval.least_seconds(ctx, PEAKS, 12)
    fu, binds_fu = kzg_fused.least_seconds(ctx, PEAKS, 1)
    both, _ = kzg_batch.least_seconds(ctx, PEAKS, 1)
    assert binds_ev == binds_fu == "compute"
    assert ev == pytest.approx(768 * 128_477_184 / 393e12)
    assert fu == pytest.approx(97_555_553_280 / 393e12)
    assert both == pytest.approx(ev + fu)
    # padded lanes and the number of dispatches change nothing
    assert kzg_eval.least_seconds(ctx, PEAKS, 1)[0] == ev
