#!/usr/bin/env python3
"""The data-column cell's control at its own size (not a pytest file; about
two minutes a seed; ``control_at_size_kzg.py``'s counterpart for the
``das_columns`` generator):

    python3 benchmarks/tests/control_at_size_columns.py <cell> <seed> [<seed> ...]

Builds the cell's traffic from the seed at full size, puts the plain
reference in the program's place, once sound, once as the control (every
power of r set to 1), and prints what ``check`` compares for each.  Both
are host code: the chip plays no part in a control.  The generator imports
the program for its sidecar checks and touches no device.
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
        cell = generator.build(config, params, seed, lambda text: None)
        # the first good segment and its bad variants, as check samples them
        entries = [0] + list(range(params["good"], len(cell.pool)))
        for label, blind in (("sound", True), ("control", False)):
            served = [(e, cell.reference_verdict(e, blind=blind))
                      for e in entries]
            print(json.dumps({"cell": name, "seed": seed, "run": label,
                              "verdicts": dict(served),
                              "compared": cell.check(served),
                              "s": round(time.perf_counter() - t0, 1)}),
                  flush=True)


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:]])
