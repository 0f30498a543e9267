#!/usr/bin/env python3
"""The gossip flood cell's control at its own size (not a pytest file;
minutes a seed; ``control_at_size_electra.py``'s counterpart for
``flood-2048``):

    python3 benchmarks/tests/control_at_size_flood.py <cell> <seed> [<seed> ...]

Builds the cell's traffic from the seed at full size (262,144 keys, 8 pool
entries of 2,048 attestations over two messages), puts the plain reference
in the program's place, once sound, once as the control (the batch check
and its bisection without the 64-bit blinding scalars), and prints what
``check`` compares for each.  The control has to accept the swapped pair
of bad variant (a), so ``verdict_mismatches`` reads 2 for each time that
entry was served.  Both are host code: the chip plays no part in a
control.  The lines also say what the key pool and the signing cost,
which is the cell's set-up floor.
"""

import importlib
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))


def main(name, seeds):
    workload = json.load(open(os.path.join(BENCH, "workloads", f"{name}.json")))
    config = json.load(open(os.path.join(
        BENCH, "configs", f"{workload['config']}.json")))
    params = dict(workload["params"], precompile=[])
    generator = importlib.import_module(
        f"benchmarks.traffic.{workload['generator']}")
    for seed in seeds:
        t0 = time.perf_counter()
        stamps = {}
        cell = generator.build(
            config, params, seed, lambda text: stamps.setdefault(
                text.split(":")[0], round(time.perf_counter() - t0, 1)))
        # the bad variants and their sources, each served once
        bad = list(range(params["good"], len(cell.batches)))
        entries = bad + list(range(len(bad)))
        for label, blind in (("sound", True), ("control", False)):
            served = [(e, tuple(cell.reference_verdicts(e, blind=blind)))
                      for e in entries]
            print(json.dumps({"cell": name, "seed": seed, "run": label,
                              "rejected": {e: len(v) - sum(v)
                                           for e, v in served},
                              "compared": cell.check(served),
                              "built_at_s": stamps,
                              "s": round(time.perf_counter() - t0, 1)}),
                  flush=True)


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:]])
