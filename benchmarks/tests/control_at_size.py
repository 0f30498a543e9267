#!/usr/bin/env python3
"""The controls at the cells' own sizes (not a pytest file; minutes a seed):

    python3 benchmarks/tests/control_at_size.py <cell> <seed> [<seed> ...]

Builds the cell's traffic from the seed at full size, puts the plain
reference in the program's place — once sound, once as the control (BLS:
the batch check without blinding; state: flag rewards on 32-bit integers)
— and prints what ``check`` compares for each.  Both are host code: the
chip plays no part in a control, so this runs wherever Python does.
"""

import importlib
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["LHTPU_AOT_PREWARM"] = "0"


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
        if workload["generator"] == "bls_sets":
            entries = range(len(cell.batches))
            runs = {"sound": [(e, cell.reference_verdict(e)) for e in entries],
                    "control": [(e, cell.reference_verdict(e, blind=False))
                                for e in entries]}
        else:
            from benchmarks.traffic.epoch_state import plain

            starts = cell.variants
            runs = {}
            for label, precision in (("sound", "exact"), ("control", "int32")):
                runs[label] = [(k, cell.reference_answer(plain(v), precision=precision))
                               for k, v in enumerate(starts[:1])]
        for label, served in runs.items():
            if hasattr(cell, "variants"):
                cell.variants = starts
            compared = cell.check(served)
            print(json.dumps({"cell": name, "seed": seed, "run": label,
                              "compared": compared,
                              "s": round(time.perf_counter() - t0, 1)}),
                  flush=True)


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:]])
