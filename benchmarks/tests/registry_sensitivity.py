#!/usr/bin/env python3
"""How the state cell's step moves with the registry's shares (not a pytest
file; a few minutes on the chip):

    python3 benchmarks/tests/registry_sensitivity.py <cell> <seed> [steps [validators]]

One process, the program set up as ``run.py`` sets it up.  For each mix
below — the generator's own, then the same with one group of shares
calmed at a time — it builds the cell's start state at full size with one
variant, warms it up, and times ``steps`` steps as the window does (the
deep copy outside the clock).  One JSON line a mix: the registry's counts
and the mean, least and greatest step.
"""

import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
os.environ["LHTPU_AOT_PREWARM"] = "0"

NO_EJECTIONS = {"effective_balance_increments": (17, 32)}
NO_QUEUE = {"not_eligible_share": 0.0, "not_activated_share": 0.0}
CALM_BALANCES = {"balance_offset_gwei": (-3 * 10**8, 6 * 10**8)}
MIXES = [
    ("generator", {}),
    ("no_ejections", NO_EJECTIONS),
    ("no_ejections_no_queue", {**NO_EJECTIONS, **NO_QUEUE}),
    ("no_ejections_no_queue_calm_balances",
     {**NO_EJECTIONS, **NO_QUEUE, **CALM_BALANCES}),
]


def main(name, seed, steps=6, validators=None):
    from lighthouse_tpu.common import compile_cache
    from lighthouse_tpu.ops import program_store

    compile_cache.configure()
    program_store.configure(os.path.join(BENCH, ".cache", "aot_programs"))
    from benchmarks.traffic import epoch_state

    workload = json.load(open(os.path.join(BENCH, "workloads", f"{name}.json")))
    config = json.load(open(os.path.join(
        BENCH, "configs", f"{workload['config']}.json")))
    params = dict(workload["params"], variants=1)
    if validators:  # a try-out of this script off the chip
        params["validators"] = validators
    for label, mix in MIXES:
        notes = []
        cell = epoch_state.Cell(config, params, seed, notes.append, mix=mix)
        cell.warm_up()
        spans = []
        for i in range(steps):
            request = cell.prepare(i)
            t0 = time.perf_counter()
            cell.serve(request)
            spans.append((time.perf_counter() - t0) * 1000)
        print(json.dumps({
            "mix": label, "changed": mix, "seed": seed,
            "registry": notes[0].split("; ", 1)[-1],
            "step_ms_mean": sum(spans) / len(spans),
            "step_ms_min": min(spans), "step_ms_max": max(spans)}), flush=True)
        cell.release()
        del cell, request
        gc.collect()


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), *[int(a) for a in sys.argv[3:]])
