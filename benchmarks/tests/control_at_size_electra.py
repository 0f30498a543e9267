#!/usr/bin/env python3
"""The electra signature cell's control at its own size (not a pytest file;
minutes a seed; ``control_at_size.py``'s counterpart for ``block-8x32k``):

    python3 benchmarks/tests/control_at_size_electra.py <cell> <seed> [<seed> ...]

Builds the cell's traffic from the seed at full size (262,144 keys, 8 pool
entries of 262,658 member keys), puts the plain reference in the program's
place, once sound, once as the control (the batch check without its 64-bit
blinding scalars), and prints what ``check`` compares for each.  The control
has to accept both swapped variants, so ``verdict_mismatches`` reads their
count.  Both are host code: the chip plays no part in a control.  The lines
also say what the key pool cost to make, which is the cell's set-up floor.
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
        # every bad variant and as many good entries: check alternates them
        bad = list(range(params["good"], len(cell.batches)))
        entries = bad + list(range(len(bad)))
        for label, blind in (("sound", True), ("control", False)):
            served = [(e, cell.reference_verdict(e, blind=blind))
                      for e in entries]
            print(json.dumps({"cell": name, "seed": seed, "run": label,
                              "verdicts": dict(served),
                              "compared": cell.check(served),
                              "built_at_s": stamps,
                              "s": round(time.perf_counter() - t0, 1)}),
                  flush=True)


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:]])
