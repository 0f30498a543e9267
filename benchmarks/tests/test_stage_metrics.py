"""The per-layer metrics PR 26 added, and ``host_gaps.py``'s attribution.

Every new ``layer_metrics/*.json`` names a reader that imports and reads a
synthetic window; a program without the family (the parent commit, under
the driver's overlay) reads nothing and does not raise.  Tier-1 runs these
too: ``tests/test_stage_spans.py`` imports them.
"""

import importlib
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NEW_METRICS = {
    "bls_aggregate_layout_ms.block": (
        "bls_verify_stage_seconds",
        {"backend": "tpu", "stage": "aggregate_layout"}, 1000.0 * 0.5 / 2),
    "bls_fold_wait_ms.block": (
        "bls_verify_stage_seconds",
        {"backend": "tpu", "stage": "aggregate_fetch"}, 1000.0 * 0.5 / 2),
    "bls_pipeline_wait_ms.block": (
        "bls_verify_stage_seconds",
        {"backend": "tpu", "stage": "pipeline_wait"}, 1000.0 * 0.5 / 2),
    "state_root_ms": ("state_root_seconds", {}, 1000.0 * 0.5 / 2),
    "tree_host_ms": ("merkle_stage_seconds", {"stage": "gather"},
                     1000.0 * 0.5 / 2),
    "tree_transfer_ms": ("merkle_stage_seconds", {"stage": "d2h"},
                         1000.0 * 0.5 / 2),
    "epoch_registry_ms": ("epoch_stage_seconds",
                          {"stage": "registry_updates"}, 1000.0 * 0.5 / 2),
    "store_load_s.block": (
        "aot_store_load_seconds",
        {"entry": "ops/x.py::f@f", "stage": "deserialize"}, 1.5),
}


def _read(metric, ctx):
    spec = json.load(open(os.path.join(
        ROOT, "benchmarks", "layer_metrics", f"{metric}.json")))
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    return reader.read(ctx, spec["args"])


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_histogram_metric_reads_a_synthetic_window(metric):
    """Before the window the family stood at 1.0 s over 3 observations,
    after it at 1.5 s over 5, 2 requests: the per-request readers give the
    growth per request, ``histogram_sum_total`` what stands at the close;
    a label set the metric does not name is left out; a program without
    the family (the parent commit) reads nothing, and does not raise."""
    family, labels, expected = NEW_METRICS[metric]
    key = frozenset(labels.items())
    stray = frozenset({"stage": "no_such_stage", "backend": "reference"}.items())
    before = {(family + "_sum", key): 1.0, (family + "_count", key): 3.0}
    after = {(family + "_sum", key): 1.5, (family + "_count", key): 5.0}
    if labels and metric != "store_load_s.block":
        after.update({(family + "_sum", stray): 7.0,
                      (family + "_count", stray): 1.0})
    ctx = {"before": before, "after": after, "requests": 2}
    assert _read(metric, ctx) == pytest.approx(expected)
    assert _read(metric, {"before": {}, "after": {}, "requests": 2}) is None


def test_merkle_pad_waste_reads_a_synthetic_window():
    family = "sha256_device_lanes_total"
    live, padding = (frozenset({"kind": k}.items()) for k in ("live", "padding"))
    ctx = {"before": {(family, live): 10.0, (family, padding): 6.0},
           "after": {(family, live): 40.0, (family, padding): 16.0},
           "requests": 1}
    assert _read("merkle_pad_waste_pct", ctx) == pytest.approx(25.0)
    assert _read("merkle_pad_waste_pct",
                 {"before": {}, "after": {}, "requests": 1}) is None


def test_every_per_layer_metric_of_benchmark_json_resolves():
    """test_harness.py's check, with the readers imported and the cells named."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in bench["per_layer"]]
    assert set(NEW_METRICS) | {"merkle_pad_waste_pct"} <= set(names)
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        spec = json.load(open(os.path.join(
            ROOT, "benchmarks", "layer_metrics", f"{m['name']}.json")))
        importlib.import_module(f"benchmarks.readers.{spec['reader']}")
        assert set(m["workloads"]) <= cells


# -- host_gaps.py's attribution ------------------------------------------------

def test_idle_gaps_go_to_the_innermost_span_open():
    from benchmarks.tests import host_gaps

    ms = 1_000_000
    requests = [(0, 100 * ms), (200 * ms, 300 * ms)]
    ops = [(10 * ms, 20 * ms, "a"), (60 * ms, 70 * ms, "b"),
           (210 * ms, 300 * ms, "c")]
    spans = [(0, 100 * ms, "state.slot"), (0, 50 * ms, "state.root"),
             (20 * ms, 45 * ms, "tree.level.gather"),
             (200 * ms, 205 * ms, "state.slot")]
    out = host_gaps.attribute(requests, [("/device:TPU:0", ops, [])], spans)
    # idle: 0-10, 20-60, 70-100 in the first request, 200-210 in the second
    assert out["idle_s"] == pytest.approx(0.090)
    by_span = dict(out["by_span"])
    assert by_span["tree.level.gather"] == pytest.approx(0.025)
    assert by_span["state.root"] == pytest.approx(0.015)      # 0-10, 45-50
    assert by_span["state.slot"] == pytest.approx(0.045)      # 50-60, 70-100, 200-205
    assert by_span[host_gaps.NO_SPAN] == pytest.approx(0.005)
    assert out["named_s"] == pytest.approx(0.085)
    assert out["longest"][0] == ["tree.level.gather", pytest.approx(0.040)]
    # a request the trace buffer did not reach is left out
    assert host_gaps.attribute([(400 * ms, 500 * ms)],
                               [("/device:TPU:0", ops, [])], spans)["idle_s"] == 0
    assert host_gaps.PROGRAM_SPAN.match("bls.aggregate.layout")
    assert not host_gaps.PROGRAM_SPAN.match("copy.13")
    assert not host_gaps.PROGRAM_SPAN.match("PjitFunction(f)")
