#!/usr/bin/env python3
"""The reconstruction cell's control at its own size (not a pytest file;
host code only, the chip plays no part):

    python3 benchmarks/tests/control_at_size_recover.py <cell> <seed> [<seed> ...]

Builds the cell's traffic from the seed at full size and puts the plain
reference in the program's place for the entries ``check`` compares: once
sound, once as the control, which places the received cells' evaluations
in natural rather than bit-reversed order.  At exactly half of the columns
every input is a codeword, so the control rebuilds without complaint, and
``check`` must read ``reconstruction_mismatches`` 2 (both entries) and
cells and proofs off the block's.  Prints one JSON line a run.
"""

import hashlib
import importlib
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))


def served_by_reference(cell, entry, natural_cosets):
    """What ``answer`` would hold of the reference's rebuilt sidecars."""
    from benchmarks.reference import recovery_plain as ref

    block, held = cell.pool[entry]
    rows = [ref.recover_cells_and_kzg_proofs(
        held, [cell.blocks[block][c][0][b] for c in held], cell.setup,
        natural_cosets=natural_cosets) for b in range(cell.blobs)]
    cells_off = proofs_off = 0
    digest = hashlib.sha256()
    for c in range(cell.columns):
        if c in set(held):
            continue
        want_cells, _, want_proofs = cell.blocks[block][c]
        got_cells = [cells[c] for cells, _ in rows]
        got_proofs = [proofs[c] for _, proofs in rows]
        cells_off += sum(a != b for a, b in zip(got_cells, want_cells))
        proofs_off += sum(a != b for a, b in zip(got_proofs, want_proofs))
        digest.update(c.to_bytes(8, "big"))
        for x in got_cells + got_proofs:
            digest.update(x)
    return (cells_off, proofs_off, 0), digest.hexdigest()


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
        for label, natural in (("sound", False), ("control", True)):
            served = [(e, served_by_reference(cell, e, natural))
                      for e in cell.referenced]
            print(json.dumps({"cell": name, "seed": seed, "run": label,
                              "compared": cell.check(served),
                              "s": round(time.perf_counter() - t0, 1)}),
                  flush=True)


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:]])
