"""The operation and byte counts the rooflines stand on."""

import json
import os

from benchmarks.rooflines import epoch_pass, pipeline_fused

PEAKS = json.load(open(os.path.join(
    os.path.dirname(os.path.dirname(__file__)), "peaks.json")))["TPU v5 lite"]


def test_pipeline_fused_counts():
    assert pipeline_fused.FP_MUL_PER_LANE == 8596
    assert pipeline_fused.FP_MUL_PER_BATCH == 2268
    assert pipeline_fused.INT8_OPS_PER_FP_MUL == 13824
    w = pipeline_fused.work(lanes=131, batches=1)
    assert w["ops"] == (131 * 8596 + 2268) * 13824
    assert w["bytes"] == 131 * 1145 + 1296


def test_pipeline_fused_is_compute_bound():
    ctx = {"requests": 10, "units_per_request": 131}
    least, binds = pipeline_fused.least_seconds(ctx, PEAKS, events=10)
    assert binds == "compute"
    assert least == (1310 * 8596 + 10 * 2268) * 13824 / 393e12


def test_epoch_pass_bytes():
    ctx = {"params": {"validators": 1 << 20}}
    least, binds = epoch_pass.least_seconds(ctx, PEAKS, events=2)
    assert binds == "memory"
    assert least == 2 * (1 << 20) * 74 / 819e9
