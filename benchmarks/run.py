#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process on the chips of the machine it is started on, the package at
its defaults.  The cell's name leads to everything else through data:
``BENCHMARK.json`` -> ``workloads/<cell>.json`` (its traffic generator under
``traffic/``, the parameters, how its end-to-end metrics are taken from
the window) and ``configs/<config>.json``; with ``--trace 1`` each
per-layer metric of ``BENCHMARK.json`` that lists the cell is read by
``layer_metrics/<metric>.json`` -> ``readers/<reader>.py``.  This file holds
no cell's, metric's or kernel's name (see README.md).

Order of a run: set-up (imports, traffic from the seed, warm-up of every
shape) -> the window, on the clock only while a request is being served
-> memory peak read -> the program's state freed -> the plain reference
judges what the window produced -> one JSON line, last on stdout.
``--rehearse`` runs the same at the workload's tiny sizes without asking
for a TPU and never prints that line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")


def log(text):
    print(f"[{time.perf_counter() - T_START:8.1f}s] {text}", file=sys.stderr,
          flush=True)


def fail(text, code=2):
    print(f"benchmarks/run.py: {text}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def load(path):
    with open(path) as f:
        return json.load(f)


def percentile(values, q):
    """Nearest-rank percentile of all requests of the window."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def end_to_end(rules, spans):
    """The window's end-to-end metrics by the workload file's rules; the
    window is the time on the clock, which runs only inside requests."""
    out = {}
    for name, rule in rules.items():
        scale = rule.get("scale", 1.0)
        if rule["stat"] == "percentile":
            out[name] = percentile(spans, rule["q"]) * scale
        elif rule["stat"] == "mean":
            out[name] = sum(spans) / len(spans) * scale
        else:
            fail(f"unknown stat {rule['stat']!r} for {name}")
    return out


def served_elsewhere(rules, spans, before, after, counters):
    """The workload file's ``served_by`` rules, for every run: how much of
    the window another rung of the program's ladders answered than the one
    the cell is about (a fallen-back run is correct and is not this cell's
    timing).  A rule reads a span's attribute or a counter family's label;
    finding nothing at all on the named rung counts as one."""
    out = {}
    for rule in rules:
        if "span" in rule:
            got = [s["attrs"].get(rule["attr"]) for s in spans
                   if s["name"] == rule["span"]]
            on = sum(g == rule["must_be"] for g in got)
            off = len(got) - on
        else:
            on = counters.delta(
                before, after, rule["counter"],
                lambda labels: labels.get(rule["label"]) == rule["must_be"])
            off = counters.delta(before, after, rule["counter"]) - on
        out[rule["name"]] = (int(off) if on else int(off) + 1, 0)
    return out


def find_cell(bench, name):
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        fail(f"no workload {name!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, config


def device_facts(jax):
    dev = jax.devices()
    peak = 0
    for d in dev:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev), "memory_peak_bytes": peak}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any platform, no result line")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "lighthouse_tpu")):
        fail("the program (lighthouse_tpu/) is not in this checkout")
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config_entry = find_cell(bench, args.workload)
    workload = load(os.path.join(BENCH, "workloads", f"{cell['name']}.json"))
    config = load(os.path.join(ROOT, config_entry["file"]))
    params = dict(workload["params"])
    if args.rehearse:
        params.update(workload.get("rehearse_params", {}))

    # the one knob: a node's background prewarm would compile every
    # manifest entry at production scale while the window runs
    os.environ["LHTPU_AOT_PREWARM"] = "0"
    log("settings made: LHTPU_AOT_PREWARM=0; everything else at package "
        "defaults")
    sys.path.insert(0, ROOT)
    import jax

    from lighthouse_tpu.common import compile_cache
    from lighthouse_tpu.ops import program_store

    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu"
                              or len(devices) < cell["chips"]):
        fail(f"need {cell['chips']} TPU chip(s); jax found "
             f"{len(devices)} x {devices[0].platform}", code=3)
    cache_dir = compile_cache.configure()
    program_store.configure(os.path.join(CACHE, "aot_programs"))
    log(f"device: {len(devices)} x {devices[0].device_kind}; compile cache "
        f"{cache_dir}; program store {os.path.join(CACHE, 'aot_programs')}")

    from benchmarks import counters

    generator = importlib.import_module(
        f"benchmarks.traffic.{workload['generator']}")
    driver = generator.build(config, params, args.seed, log)
    driver.warm_up()
    sources = {dict(labels).get("source"): value
               for (name, labels), value in counters.samples().items()
               if name == "jit_dispatch_source_total"}
    log(f"warm-up done; device programs by source so far: {sources}")
    # the traffic pools are millions of long-lived Python objects of the
    # harness's own; frozen, the collector's full passes during the window
    # walk what the program allocates, not the benchmark's inputs
    gc.collect()
    gc.freeze()

    tracing = bool(args.trace)
    # how much of the window a --trace 1 run traces is the cell's own
    # (what stopping a trace costs depends on its device programs)
    seconds = (min(args.seconds, params["trace_seconds"]) if tracing
               else args.seconds)
    trace_dir = os.path.join(CACHE, "trace", cell["name"])
    if tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # the Python tracer hooks every call of the host's Python and the
        # HLO protos of these programs are hundreds of MB: neither is read
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    spans, served, clocked = [], [], 0.0
    before = counters.samples()
    setup_s = time.perf_counter() - T_START
    with counters.SpanSink() as sink:
        while clocked < seconds:
            i = len(spans)
            request = driver.prepare(i)
            with (jax.profiler.TraceAnnotation("bench.request", i=i)
                  if tracing else contextlib.nullcontext()):
                t0 = time.perf_counter()
                out = driver.serve(request)
                spans.append(time.perf_counter() - t0)
            clocked += spans[-1]
            answer = driver.answer(request, out) if hasattr(
                driver, "answer") else out
            served.append((request[0], answer))
    after = counters.samples()
    log(f"window closed: {len(spans)} requests in {clocked:.3f} s on the "
        f"clock, {time.perf_counter() - T_START - setup_s:.3f} s of wall; "
        "request ms min/p25/p50/p75/max "
        + "/".join(f"{percentile(spans, q) * 1000:.1f}"
                   for q in (0, 25, 50, 75, 100)))
    # the series itself: a level that moves between or inside runs, or with
    # the pool entry, is seen here and in no quartile
    log("request key:ms in order: " + " ".join(
        f"{request_key}:{span * 1000:.1f}"
        for (request_key, _), span in zip(served, spans)))
    timers = sorted(((value - before.get(key, 0.0), key)
                     for key, value in after.items()
                     if key[0].endswith("_seconds_sum")), reverse=True,
                    key=lambda t: t[0])[:8]
    log("program timers, ms a request: " + ", ".join(
        f"{name[:-4]}{sorted(dict(labels).values())} "
        f"{total / len(spans) * 1000:.1f}"
        for total, (name, labels) in timers if total > 0))
    if tracing:
        jax.profiler.stop_trace()
        log("trace stopped")

    compiles = counters.delta(before, after, "jit_compiles_total")
    device = device_facts(jax)
    metrics = {}
    breakdown = None
    if tracing:
        from benchmarks import trace_reduce

        path = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        trace = trace_reduce.reduce(path)
        shutil.rmtree(trace_dir, ignore_errors=True)
        peaks = load(os.path.join(BENCH, "peaks.json")).get(device["kind"])
        if peaks is None and not args.rehearse:
            fail(f"no peaks for device kind {device['kind']!r} in peaks.json")
        ctx = {"spans": sink.spans, "before": before, "after": after,
               "trace": trace, "requests": len(spans),
               "units_per_request": driver.units_per_request,
               "params": params, "peaks": peaks, "log": log}
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            spec = load(os.path.join(BENCH, "layer_metrics", f"{m['name']}.json"))
            reader = importlib.import_module(
                f"benchmarks.readers.{spec['reader']}")
            value = reader.read(ctx, spec["args"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        by_module = sorted(((k, v["seconds"]) for k, v in
                            trace["modules"].items()), key=lambda kv: -kv[1])
        breakdown = {
            "device_ops": [[k[:120], s] for k, s in
                           (by_module[:5] + trace["ops"][:5])],
            "idle_gaps": [[k, s] for k, s in trace["gaps"][:10]]}
    else:
        values = end_to_end(workload["end_to_end"], spans)
        values["setup_s"] = setup_s
        for m in bench["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    # the plain reference, once the peak is read and the program's state
    # is let go
    t_ref = time.perf_counter()
    compared = dict(driver.check(served))
    driver.release()
    compared.update(served_elsewhere(params.get("served_by", ()), sink.spans,
                                     before, after, counters))
    compared["compiles_in_window"] = (compiles, 0)
    log(f"reference done in {time.perf_counter() - t_ref:.1f} s")
    correct = all(value <= limit for value, limit in compared.values())
    failed = 0 if correct else len(served)
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "correct": correct,
                          "requests": len(spans), "metrics": metrics,
                          "device": device, "breakdown": breakdown}))
        return 0 if correct else 1
    result = {"correct": correct, "attempted": len(served), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
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
    # the program's watchdog and dispatch threads are daemons; leave
    # without waiting on interpreter teardown under a live device client
    os._exit(rc)
