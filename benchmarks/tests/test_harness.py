"""The harness's own arithmetic and its refusals."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_percentile_is_nearest_rank_over_all_requests():
    spans = [0.7, 0.5, 0.9, 0.6, 0.8]
    assert run.percentile(spans, 50) == 0.7
    assert run.percentile(spans, 95) == 0.9
    assert run.percentile(spans, 0) == 0.5
    assert run.percentile(list(range(1, 101)), 95) == 95


def test_end_to_end_rules_use_the_whole_window():
    spans = [1.0, 3.0]
    rules = {"p50": {"stat": "percentile", "q": 50, "scale": 1000},
             "step": {"stat": "mean", "scale": 1000}}
    assert run.end_to_end(rules, spans) == {"p50": 1000.0, "step": 2000.0}


def test_served_by_rules_count_what_another_rung_answered():
    from benchmarks import counters

    span = {"name": "s", "span": "bls.verify", "attr": "served",
            "must_be": "tpu"}
    family = {"name": "c", "counter": "batches_total", "label": "backend",
              "must_be": "device"}

    def spans(*served):
        return [{"name": "bls.verify", "attrs": {"served": r}} for r in served]

    def counts(**by_backend):
        return {("batches_total", frozenset({("backend", k)})): float(v)
                for k, v in by_backend.items()}

    def read(rule, sp=(), before=None, after=None):
        return run.served_elsewhere([rule], sp, before or {}, after or {},
                                    counters)[rule["name"]]

    assert read(span, spans("tpu", "tpu")) == (0, 0)
    assert read(span, spans("tpu", "reference", "tpu")) == (1, 0)
    assert read(span, []) == (1, 0)          # nothing seen is not a pass
    assert read(family, after=counts(device=5)) == (0, 0)
    assert read(family, before=counts(device=2, reference=1),
                after=counts(device=5, reference=3)) == (2, 0)
    assert read(family, after=counts(reference=4)) == (5, 0)


def test_every_metric_and_cell_of_benchmark_json_has_its_files():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b = os.path.join(ROOT, "benchmarks")
    for cell in bench["workloads"]:
        w = json.load(open(os.path.join(b, "workloads", f"{cell['name']}.json")))
        assert w["config"] == cell["config"] and w["why"] == cell["why"]
        assert os.path.exists(os.path.join(b, "traffic", f"{w['generator']}.py"))
        named = {m["name"] for m in bench["end_to_end"]
                 if cell["name"] in m.get("workloads", [cell["name"]])}
        assert set(w["end_to_end"]) | {"setup_s"} == named
    for c in bench["configs"]:
        assert json.load(open(os.path.join(ROOT, c["file"])))["reduced"] == c["reduced"]
    for m in bench["per_layer"]:
        spec = json.load(open(os.path.join(b, "layer_metrics", f"{m['name']}.json")))
        assert os.path.exists(os.path.join(b, "readers", f"{spec['reader']}.py"))


@pytest.mark.parametrize("argv, code", [
    (["--workload", "no-such-cell"], 2),
    (["--workload", "block-131", "--seed", "1", "--seconds", "1",
      "--trace", "0"], 3),   # no TPU here: no result, another code than 0
])
def test_refusals_print_no_result(argv, code):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
                        *argv], capture_output=True, text=True, env=env,
                       timeout=300)
    assert p.returncode == code and p.stdout.strip() == ""
