"""The ten `host runtime` metrics PR 37 added and their new reader.

Every new ``layer_metrics/*.json`` names a reader that imports and reads a
synthetic window: the growth a request, a stray label set left out, and a
program without the family (the parent commit, under the driver's overlay)
reads nothing and does not raise; ``counter_per_request`` reads 0.0, not
nothing, for a family that is there and did not move.  Tier-1 runs these
too: ``tests/test_stage_spans.py`` imports them.
"""

import importlib
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SUFFIXES = {"block": "block-131", "electra": "block-8x32k",
            "blobs": "kzg-6x128", "columns": "columns-21x128",
            "epoch": "epoch-boundary"}
#: a host-code stage span each cell's off-CPU metric must admit, and a wait
#: or dispatch it must leave out
OFFCPU_SPANS = {"block": ("bls.aggregate.layout", "bls.pipeline.wait"),
                "electra": ("bls.aggregate.combine", "bls.aggregate.fetch"),
                "blobs": ("kzg.limbs", "kzg.eval.fetch"),
                "columns": ("kzg.decode", "kzg.decode.verdict"),
                "epoch": ("tree.level.gather", "sha.d2h")}
KINDS = ("host_offcpu_ms", "host_gc_pause_ms")
NEW_METRICS = [f"{kind}.{suffix}" for kind in KINDS for suffix in SUFFIXES]


def _spec(metric):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           f"{metric}.json")) as f:
        return json.load(f)


def _read(metric, ctx):
    spec = _spec(metric)
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    return reader.read(ctx, spec["args"])


def _window(metric):
    """(before, after, the reading two requests should give): the family
    moves on a label set the metric names and on one it does not."""
    kind, suffix = metric.split(".")
    if kind == "host_offcpu_ms":
        named, stray = OFFCPU_SPANS[suffix]
        fam = "span_offcpu_seconds"
        a, b = (frozenset({"span": n}.items()) for n in (named, stray))
        before = {(fam + "_sum", a): 1.0, (fam + "_count", a): 3.0}
        after = {(fam + "_sum", a): 1.5, (fam + "_count", a): 5.0,
                 (fam + "_sum", b): 7.0, (fam + "_count", b): 1.0}
        return before, after, 1000.0 * 0.5 / 2
    fam = "host_gc_pause_seconds_total"
    gens = [frozenset({"generation": str(g)}.items()) for g in range(3)]
    before = {(fam, g): 0.25 for g in gens}
    after = {(fam, gens[0]): 0.26, (fam, gens[1]): 0.25,
             (fam, gens[2]): 0.29}
    return before, after, 1000.0 * 0.05 / 2


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_host_stall_metric_reads_a_synthetic_window(metric):
    before, after, expected = _window(metric)
    ctx = {"before": before, "after": after, "requests": 2}
    assert _read(metric, ctx) == pytest.approx(expected)
    # the parent commit under this PR's benchmark files
    assert _read(metric, {"before": {}, "after": {}, "requests": 2}) is None


@pytest.mark.parametrize("metric", [m for m in NEW_METRICS
                                    if not m.startswith("host_offcpu_ms")])
def test_a_still_family_reads_zero_not_nothing(metric):
    """A calm window leaves no null in the ledger: the family is there
    (the program made its label children when it installed the hook) and
    did not move."""
    before, _, _ = _window(metric)
    assert _read(metric, {"before": before, "after": dict(before),
                          "requests": 3}) == 0.0


def test_counter_per_request_sums_scales_and_tells_absent_from_still():
    from benchmarks.readers import counter_per_request

    a, b = (frozenset({"kind": k}.items()) for k in ("a", "b"))
    ctx = {"before": {("f_total", a): 1.0, ("f_total", b): 1.0},
           "after": {("f_total", a): 4.0, ("f_total", b): 11.0},
           "requests": 2}
    assert counter_per_request.read(ctx, {"family": "f_total"}) == 6.5
    assert counter_per_request.read(
        ctx, {"family": "f_total", "scale": 10.0}) == 65.0
    still = {"before": ctx["after"], "after": ctx["after"], "requests": 2}
    assert counter_per_request.read(still, {"family": "f_total"}) == 0.0
    assert counter_per_request.read(ctx, {"family": "g_total"}) is None
    assert counter_per_request.read(
        dict(ctx, requests=0), {"family": "f_total"}) is None


def test_the_new_entries_of_benchmark_json():
    """One layer, the cell's own end-to-end metric, one cell each; the
    off-CPU metrics name host-code stages only, never a wait or dispatch."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    reports = {e["name"]: set(e.get("workloads", ()))
               for e in bench["end_to_end"]}
    for metric in NEW_METRICS:
        entry = entries[metric]
        cell = SUFFIXES[metric.split(".")[1]]
        assert entry["layer"] == "host runtime" and entry["better"] == "lower"
        assert entry["workloads"] == [cell]
        assert cell in reports[entry["moves"]]
        importlib.import_module(
            f"benchmarks.readers.{_spec(metric)['reader']}")
    for suffix in SUFFIXES:
        spans = _spec(f"host_offcpu_ms.{suffix}")["args"]["any_of"]["span"]
        assert not [s for s in spans if s.endswith(
            (".wait", ".fetch", ".dispatch", ".verdict", ".h2d", ".d2h",
             ".execute"))]
