"""The reconstruction cell: what decides ``correct``, shown to fail, and its
roofline counts checked by hand.

A whole rehearsal-size run of ``run.main`` (the workload's
``rehearse_params``: 2 blobs of 256, so four field elements a cell, 64 of
128 columns held) reads correct; with one served proof or one recovered
cell altered underneath it, or a request served on the host rung, it does
not.  The chip-size control is ``control_at_size_recover.py``.  On XLA:CPU
the FK20 program compiles in about a minute and a request takes ~15 s.
"""

import importlib
import json
import os

import pytest

from benchmarks import run
from benchmarks.rooflines import das_ntt, fk20

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (1, 2_147_483_659, 3_000_000_019)
NAME = "recover-21x64"


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


def test_the_cell_is_the_configuration_at_full_size():
    workload, config = _files()
    params, preset = workload["params"], config["preset"]
    columns = json.load(open(os.path.join(BENCH, "configs",
                                          "fulu-columns.json")))
    # fulu-columns' preset, network, blob schedule and setup
    for key in ("preset", "network", "blob_schedule", "trusted_setup"):
        assert config[key] == columns[key]
    assert params["blobs_per_block"] == config["blobs_per_block"] == 21
    assert params["blobs_per_block"] == config["blob_schedule"][-1][
        "MAX_BLOBS_PER_BLOCK"]
    assert params["field_elements_per_blob"] == preset[
        "FIELD_ELEMENTS_PER_BLOB"] == 4096
    assert params["columns_in"] == config["columns_in"] == 64
    assert config["columns_out"] == config["columns"] - 64 == 64
    assert config["cells_recovered_per_request"] == 21 * 64 == 1344
    assert config["proofs_per_request"] == 21 * 128 == 2688
    assert config["bytes_in"] == 1344 * preset["FIELD_ELEMENTS_PER_CELL"] * 32
    assert config["reduced"] == [] and config["architecture"] is None
    # ISSUE 41's traffic: 2 blocks x 2 subsets, each twice in a cycle of 8
    assert (params["blocks"], params["subsets"], params["repeats"],
            params["reference"]) == (2, 2, 2, 2)
    for seed in SEEDS:
        cell = _cell(seed)
        assert sorted(cell.cycle) == [0, 0, 1, 1, 2, 2, 3, 3]
        assert cell.referenced[0] == cell.cycle[0]
        assert len(set(cell.referenced)) == 2
        for block, held in cell.pool:
            assert len(held) == 64 == len(set(held))


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
    import benchmarks.traffic.das_recovery as gen

    warm = gen.Cell.warm_up

    def warm_then_break(self):
        warm(self)
        planted()

    monkeypatch.setattr(gen.Cell, "warm_up", warm_then_break)


def _alter(monkeypatch, field):
    """Every served answer with its first rebuilt sidecar's first proof or
    cell changed by one bit."""
    from lighthouse_tpu.chain import data_column_verification as dcv

    real = dcv.reconstruct_data_columns

    def altered(*args, **kwargs):
        out = real(*args, **kwargs)
        s = out[0]
        items = list(getattr(s, field))
        b = bytearray(items[0])
        b[-1] ^= 1
        items[0] = bytes(b)
        out[0] = s._replace(**{field: items})
        return out

    import benchmarks.traffic.das_recovery as gen

    _break_after_warm_up(monkeypatch, lambda: monkeypatch.setattr(
        gen.Cell, "serve", lambda self, request: altered(
            self.settings, request[1], self.spec)))


@pytest.mark.parametrize("field", ["kzg_proofs", "column"])
def test_an_altered_answer_is_not_correct(capsys, monkeypatch, field):
    _alter(monkeypatch, field)
    rc, result = _run(capsys)
    assert rc == 1 and result["correct"] is False


def test_a_request_on_the_host_rung_is_not_correct(capsys, monkeypatch):
    from lighthouse_tpu.crypto import das

    _break_after_warm_up(monkeypatch, lambda: monkeypatch.setattr(
        das, "_DEVICE_MIN_WIDTH", 1 << 30))
    rc, result = _run(capsys)
    assert rc == 1 and result["correct"] is False


PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
FULL = {"requests": 1, "units_per_request": 1344,
        "params": {"field_elements_per_blob": 4096, "blobs_per_block": 21}}


def test_roofline_counts_by_hand():
    # a transform of 8,192: 4,096 x 13 products
    assert das_ntt.transform(8192) == 53248
    assert das_ntt.work(21, 4096) == {
        "ops": (21 * (4 * 53248 + 4 * 8192) + 2 * 53248 + 4 * 8192) * 6144,
        "bytes": 21 * (8192 + 4096) * 32}
    # FK20 a blob: 8,192 points at 3,056 and 2 x 448 butterflies at 3,072
    assert fk20.FP_MUL_PER_BUTTERFLY == 3072
    assert fk20.work(1, 4096) == {
        "ops": (8192 * 3056 + 896 * 3072) * 13824,
        "bytes": 4096 * 32 + 128 * 48 + 8192 * 96}
    assert fk20.cell_size(4096) == 64 and fk20.cell_size(256) == 4


def test_roofline_seconds_of_one_full_request():
    ntt, binds = das_ntt.least_seconds(FULL, PEAKS, 1)
    assert binds == "compute"
    assert ntt == pytest.approx(
        (21 * (4 * 53248 + 4 * 8192) + 2 * 53248 + 4 * 8192) * 6144 / 393e12)
    proofs, binds = fk20.least_seconds(FULL, PEAKS, 1)
    assert binds == "compute"
    assert proofs == pytest.approx(
        21 * (8192 * 3056 + 896 * 3072) * 13824 / 393e12)
    three = dict(FULL, requests=3)
    assert fk20.least_seconds(three, PEAKS, 3)[0] == pytest.approx(
        3 * proofs)
