"""The data-column cell: what decides ``correct``, shown to fail, and its
roofline counts checked by hand.

The control is the plain reference with every power of r set to 1.  It
must accept the forged pair of variant (b) and nothing else that is bad:
that variant is one only the random linear combination catches.  The faults
break the timed path underneath a whole rehearsal-size run of ``run.main``.
Sizes are the workload's ``rehearse_params``; the chip-size control is
``control_at_size_columns.py``.
"""

import importlib
import json
import os

import pytest

from benchmarks import run
from benchmarks.rooflines import kzg_cell_fused, kzg_cell_interp, kzg_fused

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (1, 2_147_483_659, 3_000_000_019)
NAME = "columns-21x128"


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
    from lighthouse_tpu.types.spec import MAINNET_PRESET, ChainSpec

    workload, config = _files()
    params, preset, network = (workload["params"], config["preset"],
                               config["network"])
    last = config["blob_schedule"][-1]
    assert params["blobs_per_block"] == last["MAX_BLOBS_PER_BLOCK"] == 21
    assert params["columns"] == network["NUMBER_OF_COLUMNS"] == 128
    assert params["field_elements_per_blob"] == preset["FIELD_ELEMENTS_PER_BLOB"]
    assert preset["FIELD_ELEMENTS_PER_EXT_BLOB"] == 2 * 4096
    assert (preset["FIELD_ELEMENTS_PER_EXT_BLOB"]
            == preset["CELLS_PER_EXT_BLOB"] * preset["FIELD_ELEMENTS_PER_CELL"])
    assert params["blocks"] == 1
    assert (params["columns"], params["blobs_per_block"]) == (
        config["columns"], config["blobs_per_block"])
    assert config["cells_per_request"] == 128 * 21 == 2688
    assert config["bytes_per_request"] == {
        "cells": 2688 * 64 * 32, "proofs": 2688 * 48}
    assert config["reduced"] == [] and config["architecture"] is None
    # the configuration's constants are the program's
    spec = ChainSpec.mainnet()
    assert [(e["EPOCH"], e["MAX_BLOBS_PER_BLOCK"])
            for e in config["blob_schedule"]] == list(spec.blob_schedule)
    assert (spec.number_of_columns, spec.number_of_custody_groups,
            spec.max_request_data_column_sidecars) == (
        network["NUMBER_OF_COLUMNS"], network["NUMBER_OF_CUSTODY_GROUPS"],
        network["MAX_REQUEST_DATA_COLUMN_SIDECARS"])
    assert (MAINNET_PRESET.field_elements_per_cell,
            MAINNET_PRESET.field_elements_per_ext_blob,
            MAINNET_PRESET.cells_per_ext_blob,
            MAINNET_PRESET.kzg_commitments_inclusion_proof_depth,
            MAINNET_PRESET.slots_per_epoch) == (
        preset["FIELD_ELEMENTS_PER_CELL"],
        preset["FIELD_ELEMENTS_PER_EXT_BLOB"], preset["CELLS_PER_EXT_BLOB"],
        preset["KZG_COMMITMENTS_INCLUSION_PROOF_DEPTH"],
        preset["SLOTS_PER_EPOCH"])
    # ISSUE 33's traffic: a pool of 2 good blocks and 2 bad variants of the
    # first, 3 of 4 requests good, the window starting at a verified entry
    assert (params["good"], params["bad"], params["good_repeats"]) == (2, 2, 3)
    for seed in SEEDS:
        cycle = _cell(seed).cycle
        assert sorted(cycle) == [0, 0, 0, 1, 1, 1, 2, 3]
        assert cycle[0] != 1


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
    # a bad variant differs from the first good block in one sidecar, which
    # is what the reference verifies of it beside the good block
    for entry, (b, c) in cell.changed.items():
        assert [x for x in range(cell.columns)
                if cell.pool[entry][b][x] != cell.pool[0][b][x]] == [c]


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
    import benchmarks.traffic.das_columns as gen

    warm = gen.Cell.warm_up

    def warm_then_break(self):
        warm(self)
        planted()

    monkeypatch.setattr(gen.Cell, "warm_up", warm_then_break)


def test_a_batch_served_by_the_host_loop_is_not_correct(capsys, monkeypatch):
    from lighthouse_tpu.crypto import das

    _break_after_warm_up(monkeypatch, lambda: monkeypatch.setattr(
        das, "_CELL_BATCH_FUSED_MIN", 1 << 20))
    rc, result = _run(capsys)
    assert rc == 1 and result["correct"] is False


def test_an_accepted_bad_segment_is_not_correct(capsys, monkeypatch):
    from lighthouse_tpu.ops import bls_backend

    _break_after_warm_up(monkeypatch, lambda: monkeypatch.setattr(
        bls_backend, "_final_exp_is_one", lambda f: True))
    # long enough for the cycle to reach a bad variant
    rc, result = _run(capsys, seconds=8)
    assert rc == 1 and result["correct"] is False


PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
FULL = {"requests": 1, "units_per_request": 2688,
        "params": {"field_elements_per_blob": 4096, "blocks": 1,
                   "columns": 128, "blobs_per_block": 21}}


def test_roofline_counts_by_hand():
    # interpolation, one cell of 64 in one column: 64 weights, one inverse
    # FFT of 32 * 6 products and 64 for the shift; 6,144 a product
    assert kzg_cell_interp.work(1, 1, 64) == {
        "ops": (64 + 32 * 6 + 64) * 6144, "bytes": (65 + 64) * 32}
    # one full block: 2,688 cells in 128 columns
    assert kzg_cell_interp.work(2688, 128, 64) == {
        "ops": (172_032 + 128 * 256) * 6144,
        "bytes": (2688 * 65 + 64) * 32}
    assert kzg_cell_interp.cell_size({"field_elements_per_blob": 4096}) == 64
    assert kzg_cell_interp.cell_size({"field_elements_per_blob": 128}) == 2
    # the fused check of one full block: 21 commitments, 64 monomial points
    # and 2,688 proofs twice, one pairing, at kzg_fused.py's prices
    assert kzg_cell_fused.work(2688, 21, 64, 1) == {
        "ops": (5461 * 3056 + 2 * 5311 + 2268) * 13824,
        "bytes": 5461 * 128 + 960}
    assert kzg_fused.FP_MUL_PER_POINT == 3056


def test_roofline_seconds_of_one_full_request():
    interp, binds = kzg_cell_interp.least_seconds(FULL, PEAKS, 1)
    assert binds == "memory"       # 5.6 MB of cells against 1.26 G int8 ops
    assert interp == pytest.approx((2688 * 65 + 64) * 32 / 819e9)
    fused, binds = kzg_cell_fused.least_seconds(FULL, PEAKS, 2)
    assert binds == "compute"
    assert fused == pytest.approx(
        (5461 * 3056 + 2 * 5311 + 2268) * 13824 / 393e12)
    # groups, padded lanes and the number of dispatches change nothing
    assert kzg_cell_fused.least_seconds(FULL, PEAKS, 1)[0] == fused
    three = dict(FULL, requests=3)
    assert kzg_cell_interp.least_seconds(three, PEAKS, 3)[0] == (
        pytest.approx(3 * interp))
