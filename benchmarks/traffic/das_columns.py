"""Traffic generator ``das_columns``: pools of data-column segments for the
data-availability checker's RPC entry, from the seed alone.

A request is one ``verify_kzg_for_rpc_blocks(settings, blocks)`` call:
``blocks`` blocks of a chain segment, each with the ``columns`` data-column
sidecars a supernode holds of it (``blobs_per_block`` cells, as many cell
proofs and the block's commitments in each, as fresh ``bytes``), all of
them in ONE ``validate_data_columns`` batch: what a block's
``data_column_sidecars_by_root`` response, or a slot's gossip, is to a node
that custodies every column.

Parameters (a workload file's ``params``):

  blocks, blobs_per_block, field_elements_per_blob, columns
                  the shape of a request; none may pass the configuration's
                  constant (the blob schedule's maximum, FIELD_ELEMENTS_PER_BLOB,
                  NUMBER_OF_COLUMNS), nor their cells
                  MAX_REQUEST_DATA_COLUMN_SIDECARS; the sidecars are those
                  of columns 0 .. columns - 1
  good            segments of distinct valid blocks
  good_repeats    how often each good segment stands in the cycle of
                  requests (a bad variant stands once; default 1)
  bad             0 or 2 variants of the FIRST good segment, each differing
                  from it in ONE sidecar:
                  (a) one field element of one cell changed (still
                      canonical): its interpolation polynomial moves and
                      its proof no longer fits;
                  (b) two proofs of one sidecar forged with the known tau,
                      d and -d added to the quotients: the column, so the
                      coset and tau^n - h^n, is the same, and the errors
                      cancel in the unweighted sum; every proof is a
                      subgroup point and only the powers of r reject
  precompile      as ``kzg_blobs``: hints, never requirements

The configuration's setup is an insecure one with tau known, so commitments
and cell proofs are made in the scalar field (``reference.das_plain.Setup``);
the cells are the spec's ``compute_cells`` of uniform canonical blobs.  The
program gets what cell verification reads of a setup and no more: the
width, the roots of unity, ``g1_monomial[:n]`` and ``[tau^n]G2``.  Every
sidecar carries its block's header and the depth-4 inclusion proof of the
commitments, made and checked with the program's own
``data_column_verification`` at build time; the timed call is the KZG
check, as the reference client's RPC entry is.

The cycle's order is drawn from the seed and turned until its first place
holds an entry the reference verifies (the window, traced or not, starts
there); every seed gives the same multiset of requests, and every request,
good or bad, is the same work: both bad variants fail at a final
exponentiation.
"""

from __future__ import annotations

import random
import time
from typing import NamedTuple

import numpy as np

from benchmarks.reference import das_plain as ref
from benchmarks.reference.bls_py import curve as cv
from benchmarks.traffic.kzg_blobs import Precompile, _canonical_blobs


class Sidecar(NamedTuple):
    """What the checker reads of a DataColumnSidecar."""

    index: int
    column: list
    kzg_commitments: list
    kzg_proofs: list
    signed_block_header: object
    kzg_commitments_inclusion_proof: list


class Cell:
    def __init__(self, config, params, seed, log):
        self.params, self.seed, self.log = params, seed, log
        # the entry and the sidecar checks the cell is about, before any
        # work: a program that lacks them fails here, at once
        from lighthouse_tpu.chain import data_column_verification as dcv
        from lighthouse_tpu.chain.data_availability import (
            verify_kzg_for_rpc_blocks,
        )

        self.verify = verify_kzg_for_rpc_blocks
        self.precompile = Precompile(params.get("precompile", ()), log)
        preset, network = config["preset"], config["network"]
        self.blocks = params["blocks"]
        self.blobs = params["blobs_per_block"]
        self.columns = params["columns"]
        width = params["field_elements_per_blob"]
        schedule_max = max(entry["MAX_BLOBS_PER_BLOCK"]
                           for entry in config["blob_schedule"])
        if (self.blobs > schedule_max
                or self.columns > network["NUMBER_OF_COLUMNS"]
                or self.blocks * self.columns
                > network["MAX_REQUEST_DATA_COLUMN_SIDECARS"]
                or width > preset["FIELD_ELEMENTS_PER_BLOB"]):
            raise SystemExit("das_columns: a request larger than the "
                             "configuration's protocol constants allow")
        self.setup = ref.Setup.from_config(config, width)
        if self.columns > self.setup.cells_per_ext_blob:
            raise SystemExit("das_columns: more columns than the width has")
        self.slot = (config["blob_schedule"][-1]["EPOCH"]
                     * preset["SLOTS_PER_EPOCH"])
        rng = random.Random(seed)
        np_rng = np.random.default_rng(seed)
        # a pool entry: [block][column] -> (cells, commitments, proofs),
        # cells and proofs as lists of bytes over the block's blobs
        self.pool, self.headers = [], []
        first = None    # the first segment's quotients q[block][blob][column]
        for g in range(params["good"]):
            segment, quotients = [], []
            for b in range(self.blocks):
                raw = _canonical_blobs(np_rng, self.blobs, width)
                cells, commitments, proofs, q_taus = [], [], [], []
                for i in range(self.blobs):
                    blob = raw[i].tobytes()
                    evals = ref.compute_cells(blob, self.setup)
                    p_tau, c = self.setup.commit(
                        ref.blob_to_polynomial(blob, self.setup))
                    q = self.setup.quotients_at_tau(p_tau, evals)
                    cells.append([ref.cell_to_bytes(e)
                                  for e in evals[:self.columns]])
                    commitments.append(c)
                    q_taus.append(q[:self.columns])
                    proofs.append(self.setup.g1_times(q_taus[-1]))
                segment.append([
                    ([cells[i][c] for i in range(self.blobs)], commitments,
                     [proofs[i][c] for i in range(self.blobs)])
                    for c in range(self.columns)])
                quotients.append(q_taus)
            self.pool.append(segment)
            self.headers.append([
                self._header(dcv, block[0][1], g, b)
                for b, block in enumerate(segment)])
            if first is None:
                first = quotients
            log(f"pool: segment {g} of {self.blocks} block(s) x {self.blobs} "
                f"blobs x {self.columns} columns extended, committed and "
                "proved")
        self.expect_by_construction = [True] * params["good"]
        self.changed = {}   # bad pool entry -> (block, column) it changed
        if params["bad"] not in (0, 2):
            raise SystemExit("das_columns: bad is 0 or 2")
        if params["bad"]:
            # (a) one field element of one cell, plus one, still canonical
            b, c, i = (rng.randrange(self.blocks), rng.randrange(self.columns),
                       rng.randrange(self.blobs))
            e = rng.randrange(self.setup.cell_size)
            cells, commitments, proofs = self.pool[0][b][c]
            old = int.from_bytes(cells[i][32 * e:32 * e + 32], "big")
            cell = bytearray(cells[i])
            cell[32 * e:32 * e + 32] = (
                (old + 1) % ref.BLS_MODULUS).to_bytes(32, "big")
            self._variant(b, c, (cells[:i] + [bytes(cell)] + cells[i + 1:],
                                 commitments, proofs))
            note = f"block {b} column {c} blob {i} element {e} changed"
            # (b) two proofs of one sidecar whose errors cancel when every
            # r^k is 1: one column, so one tau^n - h^n
            b, c = rng.randrange(self.blocks), rng.randrange(self.columns)
            i, j = rng.sample(range(self.blobs), 2)
            d = rng.randrange(1, ref.BLS_MODULUS)
            cells, commitments, proofs = self.pool[0][b][c]
            forged = list(proofs)
            forged[i], forged[j] = self.setup.g1_times(
                [first[b][i][c] + d, first[b][j][c] - d])
            self._variant(b, c, (cells, commitments, forged))
            self.expect_by_construction += [False, False]
            log(f"pool: bad variants of segment 0: {note}; block {b} column "
                f"{c} proofs {i} and {j} forged to cancel")
        good = params["good"]
        cycle = (list(range(good)) * params.get("good_repeats", 1)
                 + list(range(good, len(self.pool))))
        rng.shuffle(cycle)
        # the window starts at a place whose entry the reference verifies
        start = next(k for k, entry in enumerate(cycle)
                     if entry == 0 or entry >= good)
        self.cycle = cycle[start:] + cycle[:start]
        self.units_per_request = self.blocks * self.columns * self.blobs
        self.settings = None
        self._memo = {}

    def _variant(self, block, column, sidecar):
        segment = [list(b) for b in self.pool[0]]
        segment[block][column] = sidecar
        self.changed[len(self.pool)] = (block, column)
        self.pool.append(segment)
        self.headers.append(self.headers[0])

    def _header(self, dcv, commitments, g, b):
        """(signed header, inclusion proof) of a block whose body holds
        these commitments, at a slot of the schedule's last entry; every
        sidecar of the block is checked against them with the program's
        own structure and inclusion-proof checks."""
        from lighthouse_tpu.types.containers import (
            BeaconBlockHeader,
            SignedBeaconBlockHeader,
            make_types,
        )
        from lighthouse_tpu.types.spec import MAINNET_PRESET, ChainSpec

        body = make_types(MAINNET_PRESET).beacon_block_body_class("electra")(
            blob_kzg_commitments=commitments)
        header = SignedBeaconBlockHeader(message=BeaconBlockHeader(
            slot=self.slot + self.blocks * g + b,
            body_root=body.hash_tree_root()))
        proof = dcv.compute_kzg_commitments_inclusion_proof(body)
        spec = ChainSpec.mainnet()
        probe = Sidecar(0, [b""] * len(commitments), commitments,
                        [b""] * len(commitments), header, proof)
        dcv.verify_data_column_sidecar(probe, spec)
        if not dcv.verify_data_column_sidecar_inclusion_proof(probe, spec):
            raise SystemExit("das_columns: a built sidecar's inclusion "
                             "proof does not verify")
        return header, proof

    # -- the program's side ---------------------------------------------------

    def _program_settings(self):
        """What a node holds of its trusted setup, as far as cell
        verification reads it: the Lagrange points and [tau]G2 are
        placeholders, of the monomial points those up to the cell width."""
        if self.settings is None:
            from lighthouse_tpu.crypto import kzg
            from lighthouse_tpu.crypto.bls import curve as program_cv

            n = self.setup.cell_size
            self.settings = kzg.KzgSettings.from_setup_points(
                [None] * self.setup.width, None)
            self.settings.g1_monomial = [
                program_cv.g1_from_bytes(cv.g1_to_bytes(p))
                for p in self.setup.g1_monomial]
            self.settings.g2_monomial = [None] * n + [
                program_cv.g2_from_bytes(cv.g2_to_bytes(self.setup.g2_tau_n))]
            assert self.settings.roots_brp == self.setup.roots_brp
        return self.settings

    def prepare(self, i):
        """Request ``i``: the segment as it comes off the wire, fresh
        ``bytes`` in fresh lists (outside the clock, as SSZ decoding is
        outside the checker)."""
        self._program_settings()
        entry = self.cycle[i % len(self.cycle)]

        def fresh(parts):
            return [bytes(memoryview(p)) for p in parts]

        return entry, [
            [Sidecar(c, fresh(cells), fresh(commitments), fresh(proofs),
                     *self.headers[entry][b])
             for c, (cells, commitments, proofs) in enumerate(block)]
            for b, block in enumerate(self.pool[entry])]

    def serve(self, request):
        return self.verify(self.settings, request[1])

    def warm_up(self):
        """One request: every request of the cell dispatches the same
        shapes.  The rest of the pool meets the program in the window."""
        self.precompile.join()
        entry, blocks = self.prepare(0)
        got = self.serve((entry, blocks))
        if got is not self.expect_by_construction[entry]:
            raise SystemExit(f"warm-up: pool entry {entry} verdict {got}")

    def release(self):
        self.settings = None

    # -- the reference's side -------------------------------------------------

    def _sidecars_verdict(self, entry, places, blind):
        """The spec's batch over the sidecars at ``places`` ((block,
        column) pairs) of a pool entry, a sidecar after the other."""
        commitments, cell_ids, cells, proofs = [], [], [], []
        for b, c in places:
            s_cells, s_commitments, s_proofs = self.pool[entry][b][c]
            commitments += s_commitments
            cell_ids += [c] * len(s_cells)
            cells += s_cells
            proofs += s_proofs
        return ref.verify_cell_kzg_proof_batch(
            commitments, cell_ids, cells, proofs, self.setup, blind=blind)

    def reference_verdict(self, entry, *, blind=True):
        """A good segment is verified whole, in one batch.  A bad variant
        differs from segment 0 in one sidecar, and a segment is valid
        exactly when each of its sidecars is (the spec's own
        verify_data_column_sidecar_kzg_proofs is a sidecar's): its verdict
        is segment 0's and its changed sidecar's, verified alone."""
        key = (entry, blind)
        if key not in self._memo:
            if entry in self.changed:
                self._memo[key] = (
                    self.reference_verdict(0, blind=blind)
                    and self._sidecars_verdict(
                        entry, [self.changed[entry]], blind))
            else:
                self._memo[key] = self._sidecars_verdict(
                    entry, [(b, c) for b in range(self.blocks)
                            for c in range(self.columns)], blind)
        return self._memo[key]

    def check(self, served, *, blind=True):
        """``served``: [(pool entry, answer)] of the whole window.  The
        reference verifies the first good segment and both of its bad
        variants; every answer the window gave for them must equal its
        verdict.  Every answer is also held to the construction."""
        by_entry = {}
        for entry, answer in served:
            by_entry.setdefault(entry, []).append(answer)
        good = self.params["good"]
        sample = [0] + list(range(good, len(self.pool)))
        wrong = checked = off = 0
        for entry in sample:
            if entry not in by_entry:
                continue
            t0 = time.perf_counter()
            want = self.reference_verdict(entry, blind=blind)
            checked += len(by_entry[entry])
            wrong += sum(a is not want for a in by_entry[entry])
            self.log(f"reference: pool entry {entry} -> {want} in "
                     f"{time.perf_counter() - t0:.1f} s; served "
                     f"{len(by_entry[entry])}x {set(by_entry[entry])}")
        for entry, answers in by_entry.items():
            off += sum(a is not self.expect_by_construction[entry]
                       for a in answers)
        self._memo.clear()
        ref.forget()
        self.log("pool entries served (good first, then the bad variants): "
                 + " ".join(f"{e}:{len(by_entry.get(e, ()))}x"
                            for e in range(len(self.pool))))
        return {"verdict_mismatches": (wrong, 0),
                "verdicts_off_construction": (off, 0),
                "answers_left_uncompared": (0 if checked else 1, 0)}


def build(config, params, seed, log):
    return Cell(config, params, seed, log)
