#!/usr/bin/env python3
"""Why was this request slow (not a pytest file; on the chip, set-up plus
``n`` untraced requests):

    python3 benchmarks/tests/request_stalls.py --workload <cell> --seed <n> --requests <n>

Builds the cell's driver as ``run.py`` does and serves ``n`` requests in
the harness's own loop (prepare, serve on the clock, answer), the profiler
off.  One row a request from the program's own roots: its milliseconds on
the harness's clock and, per span name summed over the roots the request
closed, ``[ms, offcpu_ms, gc_ms]`` (the evidence PR 37 put on the spans:
wall less the thread's CPU time, and the collector's pauses).  All
rows go to ``--out`` (default ``chiprun_out/request_stalls.<cell>.<seed>.json``);
the one JSON line on stdout is what fits the end of a call's output: the
series of request ms and its median by pool entry, per stage the spread of
each column, over the cell's
host-code stages (those its ``host_offcpu_ms.*`` metric names) how far the
request's ms moves with CPU time, off-CPU time and collector pauses, the
slowest requests with what moved in them, the ``slow_request`` /
``slow_span`` events the flight recorder filed, and the growth of the
collector's families.  A program without the
span fields (the parent commit) gives the ms columns alone.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import statistics
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402

COLUMNS = ("ms", "offcpu_ms", "gc_ms")
FAMILIES = ("host_gc_pause_seconds_total", "host_gc_collections_total",
            "slow_requests_total")


def stage_rows(roots) -> tuple:
    """({span name: [ms, offcpu_ms, gc_ms]}, spans, those of them that
    print an ``offcpu_ms``: a name that runs brief reads no CPU clock)
    summed over the trees of ``roots`` (``Span.to_dict()`` forms; a key
    the program does not print is 0)."""
    rows, spans, clocked = {}, 0, 0

    def walk(d):
        nonlocal spans, clocked
        spans += 1
        clocked += "offcpu_ms" in d
        row = rows.setdefault(d["name"], [0.0, 0.0, 0.0])
        row[0] += d["duration_ms"]
        for i, key in enumerate(COLUMNS[1:], 1):
            row[i] += d.get(key, 0)
        for child in d.get("children", ()):
            walk(child)

    for root in roots:
        walk(root)
    return ({name: [round(v, 3) for v in row] for name, row in rows.items()},
            spans, clocked)


def spread(values) -> dict:
    """Nearest-rank quantiles, as ``run.py`` takes a window's."""
    return {name: run.percentile(values, q) for name, q in (
        ("min", 0), ("p05", 5), ("p50", 50), ("p95", 95), ("max", 100))}


def correlation(xs, ys):
    if len(xs) < 3 or len(set(xs)) < 2 or len(set(ys)) < 2:
        return None
    return round(statistics.correlation(xs, ys), 3)


def summarize(rows, host_stages) -> dict:
    """What of ``rows`` fits one line (see the module's docstring)."""
    ms = [r["ms"] for r in rows]
    median = statistics.median(ms)
    names = sorted({n for r in rows for n in r["stages"]})
    stages = {}
    for name in names:
        cols = list(zip(*(r["stages"].get(name, [0.0, 0.0, 0.0])
                          for r in rows)))
        stages[name] = {c: spread(col) for c, col in zip(COLUMNS, cols)
                        if any(col)}
    # over the cell's host-code stages: what the request's ms moves with
    host = {c: [sum(r["stages"].get(n, [0] * 3)[i] for n in host_stages)
                for r in rows] for i, c in enumerate(COLUMNS)}
    host["cpu_ms"] = [a - b for a, b in zip(host["ms"], host["offcpu_ms"])]
    moves_with = {c: {"r": correlation(col, ms), **spread(col)}
                  for c, col in host.items()}
    slowest = []
    for r in sorted(rows, key=lambda r: -r["ms"])[:6]:
        moved = {}
        for name, row in r["stages"].items():
            base = stages[name].get("ms", {}).get("p50", 0.0)
            if abs(row[0] - base) > 0.02 * median:
                moved[name] = {"ms": row[0], "p50": base,
                               **{c: v for c, v in zip(COLUMNS[1:], row[1:])
                                  if v}}
        slowest.append({"i": r["i"], "key": r["key"], "ms": r["ms"],
                        "moved": moved})
    by_key = {}
    for r in rows:
        by_key.setdefault(str(r["key"]), []).append(r["ms"])
    return {"requests": len(rows), "ms": spread(ms),
            "spans_a_request": spread([r["spans"] for r in rows]),
            "clocked_a_request": spread([r["clocked"] for r in rows]),
            # the pool entry a request carries: a level that follows the
            # entry is the traffic's, not the moment's
            "p50_ms_by_key": {k: round(statistics.median(v), 1)
                              for k, v in sorted(by_key.items())},
            "over_1.5x_median": [r["i"] for r in rows
                                 if r["ms"] > 1.5 * median],
            "series_ms": [round(v, 1) for v in ms],
            "host_stages": sorted(host_stages), "moves_with": moves_with,
            "stages": stages, "slowest": slowest}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=150)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any platform")
    args = ap.parse_args(argv)

    from benchmarks import counters

    # as run.py: a node's background prewarm is the one setting made
    os.environ["LHTPU_AOT_PREWARM"] = "0"
    bench = run.load(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config_entry = run.find_cell(bench, args.workload)
    workload = run.load(os.path.join(BENCH, "workloads", f"{cell['name']}.json"))
    config = run.load(os.path.join(ROOT, config_entry["file"]))
    params = dict(workload["params"])
    if args.rehearse:
        params.update(workload.get("rehearse_params", {}))
    host_stages = set()
    for m in bench["per_layer"]:
        if (m["name"].startswith("host_offcpu_ms.")
                and m.get("workloads") == [cell["name"]]):
            spec = run.load(os.path.join(BENCH, "layer_metrics",
                                         f"{m['name']}.json"))
            host_stages |= set(spec["args"]["any_of"]["span"])
    import jax

    from lighthouse_tpu.common import compile_cache, tracing
    from lighthouse_tpu.common import flight_recorder as flight
    from lighthouse_tpu.ops import program_store

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        run.fail("need a TPU chip", code=3)
    compile_cache.configure()
    program_store.configure(os.path.join(run.CACHE, "aot_programs"))
    generator = importlib.import_module(
        f"benchmarks.traffic.{workload['generator']}")
    driver = generator.build(config, params, args.seed, run.log)
    driver.warm_up()
    gc.collect()
    gc.freeze()

    roots, rows = [], []
    sink = lambda root, _slot: roots.append(root.to_dict())  # noqa: E731
    tracing.TRACER.add_sink(sink)
    seq = flight.RECORDER.seq
    before = counters.samples()
    t_window = time.perf_counter()
    for i in range(args.requests):
        request = driver.prepare(i)
        del roots[:]
        t0 = time.perf_counter()
        out = driver.serve(request)
        ms = (time.perf_counter() - t0) * 1000
        stages, spans, clocked = stage_rows(roots)
        rows.append({"i": i, "key": request[0], "ms": round(ms, 3),
                     "spans": spans, "clocked": clocked, "stages": stages})
        # reduced outside the clock, as in run.py: what it allocates and
        # frees between requests is part of what the next request meets
        if hasattr(driver, "answer"):
            driver.answer(request, out)
    wall_s = time.perf_counter() - t_window
    tracing.TRACER.remove_sink(sink)
    after = counters.samples()
    events = [e for e in flight.RECORDER.events_since(seq)
              if e["kind"] in ("slow_request", "slow_span")]
    growth = {}
    for (name, labels), value in sorted(after.items(), key=str):
        if name in FAMILIES:
            moved = value - before.get((name, labels), 0.0)
            growth[f"{name}{sorted(dict(labels).values())}"] = round(moved, 6)
    driver.release()

    result = {"workload": cell["name"], "seed": args.seed,
              "device": jax.devices()[0].device_kind, "wall_s": round(wall_s, 3),
              **summarize(rows, host_stages),
              "flight_events": [{k: v for k, v in e.items() if k != "attrs"}
                                for e in events],
              "families_growth": growth}
    out = args.out or os.path.join(
        ROOT, "chiprun_out", f"request_stalls.{cell['name']}.{args.seed}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"rows": rows, **result}, f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 1
    except BaseException:
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
