"""Traffic generator ``das_recovery``: a supernode's reconstruction, from
the seed alone.

A request is one ``reconstruct_data_columns(settings, sidecars, spec)``
call: the ``columns_in`` data-column sidecars a node holds of one block of
``blobs_per_block`` blobs (cells, proofs and the block's commitments in
each, as fresh ``bytes``), and the answer is every missing sidecar, its
cells recovered and its proofs recomputed: what a PeerDAS supernode does
when half of a block's columns have arrived and its reconstruction delay
runs out (consensus-specs fulu das-core.md, "Reconstruction and
cross-seeding").

Parameters (a workload file's ``params``):

  blocks, blobs_per_block, field_elements_per_blob
                  the blocks of the pool and their shape; none may pass
                  the configuration's constant (the blob schedule's
                  maximum, FIELD_ELEMENTS_PER_BLOB)
  columns_in      the columns held of a block, at least half of the cells
  subsets         seeded uniform subsets of ``columns_in`` columns a block:
                  the pool is blocks x subsets entries, all good (a node
                  only rebuilds from verified columns)
  repeats         how often each pool entry stands in the cycle of requests
  reference       pool entries the plain reference rebuilds at the end
  precompile      as ``kzg_blobs``: hints, never requirements

The blocks are ``das_columns``' (imported, not copied): uniform canonical
blobs, the spec's ``compute_cells``, commitments and cell proofs in the
scalar field from the configuration's known tau, every sidecar with its
block's header and depth-4 inclusion proof.  The program's settings hold
what reconstruction reads of a setup: ``g1_monomial[:width]`` (made from
the same tau), ``[tau^n]G2`` and the roots of unity.

The cycle's order is drawn from the seed; its first place holds an entry
the reference rebuilds.  ``answer`` holds a served request to the block's
full matrix as soon as the clock stops (cell for cell, proof for proof)
and keeps a digest; ``check`` compares the digests of the reference's
entries with its own ``recover_cells_and_kzg_proofs``
(``reference/recovery_plain.py``), a blob at a time.
"""

from __future__ import annotations

import hashlib
import random
import time

from benchmarks.reference import recovery_plain as ref
from benchmarks.reference.bls_py import curve as cv
from benchmarks.traffic import das_columns
from benchmarks.traffic.kzg_blobs import Precompile


class Cell:
    def __init__(self, config, params, seed, log):
        self.params, self.seed, self.log = params, seed, log
        # the entry the cell is about, before any work: a program that
        # lacks it fails here, at once
        from lighthouse_tpu.chain.data_column_verification import (
            reconstruct_data_columns,
        )
        from lighthouse_tpu.types.spec import ChainSpec

        self.reconstruct = reconstruct_data_columns
        self.spec = ChainSpec.mainnet()
        self.precompile = Precompile(params.get("precompile", ()), log)
        self.blobs = params["blobs_per_block"]
        width = params["field_elements_per_blob"]
        full = das_columns.build(config, {
            "blocks": 1, "blobs_per_block": self.blobs,
            "field_elements_per_blob": width,
            "columns": min(config["network"]["NUMBER_OF_COLUMNS"], 2 * width),
            "good": params["blocks"], "bad": 0}, seed, log)
        self.setup = full.setup
        self.columns = full.columns
        if not self.columns // 2 <= params["columns_in"] < self.columns:
            raise SystemExit("das_recovery: columns_in is at least half of "
                             "the columns and misses one")
        # [block] -> [column] -> (cells over blobs, commitments, proofs)
        self.blocks = [segment[0] for segment in full.pool]
        self.headers = [h[0] for h in full.headers]
        rng = random.Random(seed ^ 0x5EC0)
        self.pool = [(b, sorted(rng.sample(range(self.columns),
                                           params["columns_in"])))
                     for b in range(params["blocks"])
                     for _ in range(params["subsets"])]
        cycle = list(range(len(self.pool))) * params["repeats"]
        rng.shuffle(cycle)
        self.cycle = cycle
        # the reference's entries: the first distinct ones of the cycle
        self.referenced = list(dict.fromkeys(cycle))[:params["reference"]]
        log(f"pool: {params['blocks']} block(s) x {self.blobs} blobs, "
            f"{params['subsets']} subsets of {params['columns_in']} columns "
            f"each; cycle {cycle}")
        self.units_per_request = self.blobs * (self.columns
                                               - params["columns_in"])
        self.monomial = self.setup.g1_times(
            [pow(self.setup.tau, i, ref.BLS_MODULUS) for i in range(width)])
        self.settings = None

    # -- the program's side ---------------------------------------------------

    def _program_settings(self):
        """What a node holds of its trusted setup, as far as reconstruction
        reads it: the Lagrange points and [tau]G2 are placeholders."""
        if self.settings is None:
            from lighthouse_tpu.crypto import kzg
            from lighthouse_tpu.crypto.bls import curve as program_cv

            n = self.setup.cell_size
            settings = kzg.KzgSettings.from_setup_points(
                [None] * self.setup.width, None)
            settings.g1_monomial = [program_cv.g1_from_bytes(
                b, subgroup_check=False) for b in self.monomial]
            settings.g2_monomial = [None] * n + [
                program_cv.g2_from_bytes(cv.g2_to_bytes(self.setup.g2_tau_n))]
            self.settings = settings
        return self.settings

    def prepare(self, i):
        """Request ``i``: the held sidecars as they come off the wire,
        fresh ``bytes`` in fresh lists (outside the clock)."""
        self._program_settings()
        entry = self.cycle[i % len(self.cycle)]
        block, held = self.pool[entry]

        def fresh(parts):
            return [bytes(memoryview(p)) for p in parts]

        return entry, [
            das_columns.Sidecar(c, *(fresh(part) for part in
                                     self.blocks[block][c]),
                                *self.headers[block])
            for c in held]

    def serve(self, request):
        return self.reconstruct(self.settings, request[1], self.spec)

    def answer(self, request, out):
        """Outside the clock: how far the answer is off the block's full
        matrix ((cells off, proofs off, sidecars off)) and a digest of its
        bytes for the reference."""
        entry, given = request
        block, held = self.pool[entry]
        want = [c for c in range(self.columns) if c not in set(held)]
        cells_off = proofs_off = 0
        sidecars_off = abs(len(out) - len(want))
        digest = hashlib.sha256()
        header, proof = self.headers[block]
        for s, c in zip(out, want):
            cells, commitments, proofs = self.blocks[block][c]
            if (int(s.index) != c
                    or [bytes(x) for x in s.kzg_commitments] != commitments
                    or s.signed_block_header != header
                    or [bytes(x) for x in s.kzg_commitments_inclusion_proof]
                    != [bytes(x) for x in proof]):
                sidecars_off += 1
            got_cells = [bytes(x) for x in s.column]
            got_proofs = [bytes(x) for x in s.kzg_proofs]
            cells_off += sum(a != b for a, b in zip(got_cells, cells)) + abs(
                len(got_cells) - len(cells))
            proofs_off += sum(a != b for a, b in zip(got_proofs, proofs)) + abs(
                len(got_proofs) - len(proofs))
            digest.update(int(s.index).to_bytes(8, "big"))
            for x in got_cells + got_proofs:
                digest.update(x)
        return (cells_off, proofs_off, sidecars_off), digest.hexdigest()

    def warm_up(self):
        """One request: every request of the cell dispatches the same
        shapes."""
        self.precompile.join()
        request = self.prepare(0)
        (cells_off, proofs_off, sidecars_off), _ = self.answer(
            request, self.serve(request))
        if cells_off or proofs_off or sidecars_off:
            raise SystemExit(f"warm-up: pool entry {request[0]} rebuilt "
                             f"{cells_off} cells and {proofs_off} proofs "
                             "off the block's")

    def release(self):
        self.settings = None

    # -- the reference's side -------------------------------------------------

    def reference_digest(self, entry, *, natural_cosets=False):
        """The digest of the sidecars the reference rebuilds for a pool
        entry: ``recover_cells_and_kzg_proofs`` a blob at a time."""
        block, held = self.pool[entry]
        rows = [ref.recover_cells_and_kzg_proofs(
            held, [self.blocks[block][c][0][b] for c in held], self.setup,
            natural_cosets=natural_cosets) for b in range(self.blobs)]
        digest = hashlib.sha256()
        for c in range(self.columns):
            if c in set(held):
                continue
            digest.update(c.to_bytes(8, "big"))
            for x in [cells[c] for cells, _ in rows] + [
                    proofs[c] for _, proofs in rows]:
                digest.update(x)
        return digest.hexdigest()

    def check(self, served, *, natural_cosets=False):
        """``served``: [(pool entry, answer)] of the whole window.  Every
        answer is held to the block's full matrix; the reference rebuilds
        the entries in ``referenced`` and every answer the window gave for
        them must equal its digest."""
        off = [0, 0, 0]
        by_entry = {}
        for entry, (counts, digest) in served:
            for k, v in enumerate(counts):
                off[k] += v
            by_entry.setdefault(entry, []).append(digest)
        wrong = checked = 0
        for entry in self.referenced:
            if entry not in by_entry:
                continue
            t0 = time.perf_counter()
            want = self.reference_digest(entry, natural_cosets=natural_cosets)
            checked += len(by_entry[entry])
            wrong += sum(d != want for d in by_entry[entry])
            self.log(f"reference: pool entry {entry} rebuilt in "
                     f"{time.perf_counter() - t0:.1f} s; served "
                     f"{len(by_entry[entry])}x")
        self.log("pool entries served: " + " ".join(
            f"{e}:{len(by_entry.get(e, ()))}x" for e in range(len(self.pool))))
        return {"cells_off_construction": (off[0], 0),
                "proofs_off_construction": (off[1], 0),
                "sidecars_off_construction": (off[2], 0),
                "reconstruction_mismatches": (wrong, 0),
                "answers_left_uncompared": (0 if checked else 1, 0)}


def build(config, params, seed, log):
    return Cell(config, params, seed, log)
