#!/usr/bin/env python3
"""What the host was doing while the device sat idle (not a pytest file;
on the chip, set-up plus a few requests):

    python3 benchmarks/tests/host_gaps.py --workload <cell> --seed <n> --requests <k> [--profile 0]

Builds the cell's driver as ``run.py`` does, traces ``k`` requests with the
same ``ProfileOptions``, loads the ``.xplane.pb`` and, for every stretch
inside a request in which no op ran on the device, names the innermost
program span open on the host (every ``/host:CPU`` line; the program's
spans get there through ``common.tracing``'s annotator, on the device
trace's clock).  Prints one JSON line: idle seconds per span name (a gap
is split among the spans open during it), the ten longest gaps with the
span open at their midpoint, and the per-request series of the program's
stage spans from its own tracer — the series shows which stage moves when
identical requests land on two levels — and what the program's timers
gathered during set-up.  ``--profile 0`` leaves the profiler off and
prints the series alone.

``attribute`` is the function a later benchmark PR would lift into
``trace_reduce.py`` to label ``breakdown.idle_gaps``.
"""

from __future__ import annotations

import argparse
import bisect
import contextlib
import gc
import glob
import importlib
import json
import os
import re
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import trace_reduce  # noqa: E402

#: the program's span names are dotted lower-case words (``bls.aggregate``,
#: ``tree.level.gather``); the runtime's own host events are not (an op of
#: a host-side XLA program reads ``copy.13``: a number is no word)
PROGRAM_SPAN = re.compile(r"^[a-z_]+(\.[a-z_][a-z_0-9]*)+$")
NO_SPAN = "(no program span open)"


def host_spans(path: str) -> list:
    """[(start, end, name)] in ns of the program's spans on ``/host:CPU``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events
                    if PROGRAM_SPAN.match(e.name)
                    and e.name != trace_reduce.REQUEST]
    return out


def idle_gaps(requests, devices) -> list:
    """[(start, end)] in ns: inside each request that the device's trace
    buffer covers, the stretches in which no op ran on any device."""
    ops = trace_reduce._union(
        (lo, hi) for _, dev_ops, _ in devices for lo, hi, _ in dev_ops)
    starts = [lo for lo, _ in ops]
    gaps = []
    for lo, hi in sorted(requests):
        i = bisect.bisect_left(starts, lo - trace_reduce.SKEW_NS)
        if i == len(ops) or ops[i][0] >= hi + trace_reduce.SKEW_NS:
            continue  # nothing of this request reached the trace
        cursor = lo
        j = max(i - 1, 0)
        while j < len(ops) and ops[j][0] < hi:
            if ops[j][1] > cursor:
                if ops[j][0] > cursor:
                    gaps.append((cursor, ops[j][0]))
                cursor = ops[j][1]
            j += 1
        if cursor < hi:
            gaps.append((cursor, hi))
    return gaps


def innermost_timeline(spans) -> list:
    """Disjoint sorted [(start, end, name)]: at each moment the span that
    started last among those open (spans of one thread nest, so that is the
    innermost)."""
    edges = sorted({t for lo, hi, _ in spans for t in (lo, hi)})
    by_start = sorted(spans)
    out, stack, k = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(by_start) and by_start[k][0] <= a:
            stack.append(by_start[k])
            k += 1
        stack = [s for s in stack if s[1] > a]
        if stack:
            name = max(stack, key=lambda s: (s[0], -s[1]))[2]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def attribute(requests, devices, spans) -> dict:
    """Idle seconds of the device inside requests, by the innermost program
    span open on the host: {"idle_s", "named_s", "by_span": [[name, s]],
    "longest": [[name at the midpoint, s]]}."""
    gaps = idle_gaps(requests, devices)
    timeline = innermost_timeline(spans)
    starts = [lo for lo, _, _ in timeline]
    by_span = {}

    def open_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return timeline[i][2] if i >= 0 and timeline[i][1] > t else NO_SPAN

    for lo, hi in gaps:
        named = 0.0
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(timeline) and timeline[i][0] < hi:
            part = min(hi, timeline[i][1]) - max(lo, timeline[i][0])
            if part > 0:
                by_span[timeline[i][2]] = by_span.get(timeline[i][2], 0.0) + part
                named += part
            i += 1
        if hi - lo > named:
            by_span[NO_SPAN] = by_span.get(NO_SPAN, 0.0) + (hi - lo - named)
    idle = sum(hi - lo for lo, hi in gaps)
    return {
        "idle_s": idle / 1e9,
        "named_s": (idle - by_span.get(NO_SPAN, 0.0)) / 1e9,
        "by_span": sorted(([k, v / 1e9] for k, v in by_span.items()),
                          key=lambda kv: -kv[1]),
        "longest": [[open_at((lo + hi) / 2), (hi - lo) / 1e9] for lo, hi in
                    sorted(gaps, key=lambda g: g[0] - g[1])[:10]],
    }


def stage_series(roots) -> list:
    """Per finished root span, in order, milliseconds by span name summed
    over the tree (the root's own under its name) and how many spans it
    holds.  A request is one root or several: a slot each in the state
    plane, ``bls.verify`` and the supervised thread's ``bls.verify_pipeline``
    in the seam."""
    def walk(d, row):
        row["(spans)"] = row.get("(spans)", 0) + 1
        row[d["name"]] = row.get(d["name"], 0.0) + d["duration_ms"]
        for child in d.get("children", ()):
            walk(child, row)
        return row

    return [{k: round(v, 3) for k, v in walk(root, {}).items()}
            for root in roots]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any platform")
    args = ap.parse_args(argv)

    from benchmarks import run

    # as run.py: a node's background prewarm is the one setting made
    os.environ["LHTPU_AOT_PREWARM"] = "0"
    bench = run.load(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config_entry = run.find_cell(bench, args.workload)
    workload = run.load(os.path.join(BENCH, "workloads", f"{cell['name']}.json"))
    config = run.load(os.path.join(ROOT, config_entry["file"]))
    params = dict(workload["params"])
    if args.rehearse:
        params.update(workload.get("rehearse_params", {}))
    import jax

    from lighthouse_tpu.common import compile_cache, tracing
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
    # what the program's own timers gathered during set-up, largest first
    # (the store's load stages by entry are here)
    from benchmarks import counters

    setup_timers = sorted(
        ([f"{name[:-4]}{sorted(dict(labels).values())}", value]
         for (name, labels), value in counters.samples().items()
         if name.endswith("_seconds_sum") and value > 0),
        key=lambda kv: -kv[1])[:12]

    trace_dir = os.path.join(run.CACHE, "trace", f"{cell['name']}.host_gaps")
    if args.profile:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    roots, request_ms = [], []
    sink = lambda root, _slot: roots.append(root.to_dict())  # noqa: E731
    tracing.TRACER.add_sink(sink)
    for i in range(args.requests):
        request = driver.prepare(i)
        with (jax.profiler.TraceAnnotation(trace_reduce.REQUEST, i=i)
              if args.profile else contextlib.nullcontext()):
            t0 = time.perf_counter()
            out = driver.serve(request)
            request_ms.append((time.perf_counter() - t0) * 1000)
        # reduced outside the clock, as in run.py: what it allocates and
        # frees between requests is part of what the next request meets
        if hasattr(driver, "answer"):
            driver.answer(request, out)
    tracing.TRACER.remove_sink(sink)
    result = {"workload": cell["name"], "seed": args.seed,
              "profiled": bool(args.profile),
              "setup_timers_s": setup_timers,
              "request_ms": [round(ms, 3) for ms in request_ms],
              "series": stage_series(roots)}
    if args.profile:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        result["stop_trace_s"] = time.perf_counter() - t0
        path = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        requests, devices = trace_reduce.load(path)
        spans = host_spans(path)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["host_span_names"] = sorted({name for _, _, name in spans})
        result.update(attribute(requests, devices, spans))
        result["named_share"] = (result["named_s"] / result["idle_s"]
                                 if result["idle_s"] else None)
    driver.release()
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
