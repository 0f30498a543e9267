"""Traffic generator ``kzg_blobs``: pools of blob-sidecar segments for the
data-availability checker's RPC entry, from the seed alone.

A request is one ``verify_kzg_for_rpc_blocks(settings, blocks)`` call:
``blocks`` blocks of a chain segment, each with ``blobs_per_block``
sidecars (blob, commitment, proof as fresh ``bytes``), all of them in ONE
``validate_blobs`` batch — what a ``blob_sidecars_by_range`` response is to
a node catching up.

Parameters (a workload file's ``params``):

  blocks, blobs_per_block, field_elements_per_blob
                  the shape of a request; each may not pass the
                  configuration's constant (MAX_REQUEST_BLOCKS_DENEB,
                  MAX_BLOBS_PER_BLOCK, FIELD_ELEMENTS_PER_BLOB), and their
                  product of the first two not MAX_REQUEST_BLOB_SIDECARS
  good            batches of distinct valid blobs
  good_repeats    how often each good batch stands in the cycle of
                  requests (a bad variant stands once; default 1): 2 good
                  x 3 beside 2 bad makes 3 of 4 requests good
  bad             0 or 2 variants of the FIRST good batch:
                  (a) one field element of one blob changed (still
                      canonical): its challenge and evaluation move, its
                      proof no longer fits;
                  (b) two proofs forged with the known tau so that their
                      errors cancel in the unweighted sum,
                      d_1 (tau - z_1) + d_2 (tau - z_2) = 0: every proof
                      is a subgroup point and only the powers of r in the
                      random linear combination reject the batch
  precompile      [{"entry": "module:attribute", "call": bool, "args":
                  [...]}]: hints, never requirements.  The cell's device
                  programs as the program names them today, dispatched
                  once on zero operands on threads of their own while the
                  host makes blobs and proofs.  ``call`` marks an
                  attribute that BUILDS the program (it is called first,
                  without arguments).  A hint that no longer fits is
                  logged and skipped: the warm-up compiles what the
                  request needs.  An argument is an integer or {"zeros":
                  shape, "dtype": name}

The configuration's setup is an insecure one with tau known, so commitments
and proofs are made in the scalar field (``reference.kzg_plain.Setup``):
C = [p(tau)]G1, pi = [(p(tau) - y) / (tau - z)]G1.  The program gets what
verification reads of a setup: the width, the roots of unity and [tau]G2.

The cycle's order is drawn from the seed; every seed gives the same
multiset of requests, and every request (good or bad) is the same work:
both bad variants fail at the final exponentiation.
"""

from __future__ import annotations

import importlib
import random
import threading
import time
from typing import NamedTuple

import numpy as np

from benchmarks.reference import kzg_plain as ref
from benchmarks.reference.bls_py import curve as cv


class Sidecar(NamedTuple):
    """What the checker reads of a BlobSidecar."""

    blob: bytes
    kzg_commitment: bytes
    kzg_proof: bytes


class Precompile:
    """The cell's device programs compiling side by side, through the same
    instrumented entries (and program store) a real dispatch takes."""

    def __init__(self, jobs, log):
        self.log = log
        self.threads = [threading.Thread(target=self._one, args=(job,),
                                         daemon=True) for job in jobs]
        for t in self.threads:
            t.start()

    def _one(self, job):
        import jax
        import jax.numpy as jnp

        try:
            module, name = job["entry"].split(":")
            fn = getattr(importlib.import_module(module), name)
            if job.get("call"):
                fn = fn()
            args = [a if isinstance(a, int)
                    else jnp.zeros(tuple(a["zeros"]), a["dtype"])
                    for a in job["args"]]
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            self.log(f"precompile: {job['entry']} ready in "
                     f"{time.perf_counter() - t0:.1f} s")
        except Exception as e:
            self.log(f"precompile: WARNING, hint {job['entry']} skipped "
                     f"({type(e).__name__}: {str(e)[:200]}); the warm-up "
                     "compiles what the request needs")

    def join(self):
        for t in self.threads:
            t.join()


def _canonical_blobs(rng, count, width):
    """uint8[count, width, 32]: big-endian field elements uniform below the
    modulus, by rejection from 255-bit draws."""
    modulus = np.frombuffer(ref.BLS_MODULUS.to_bytes(32, "big"), ">u8")
    raw = rng.integers(0, 256, size=(count * width, 32), dtype=np.uint8)
    while True:
        raw[:, 0] &= 0x7F
        words = raw.view(">u8")
        lt, eq = words < modulus, words == modulus
        ok = lt[:, 0] | (eq[:, 0] & (lt[:, 1] | (eq[:, 1] & (
            lt[:, 2] | (eq[:, 2] & lt[:, 3])))))
        again = np.flatnonzero(~ok)
        if not again.size:
            return raw.reshape(count, width, 32)
        raw[again] = rng.integers(0, 256, size=(again.size, 32),
                                  dtype=np.uint8)


class Cell:
    def __init__(self, config, params, seed, log):
        self.params, self.seed, self.log = params, seed, log
        # the entry the cell is about, before any work: a program that
        # lacks it fails here, at once
        from lighthouse_tpu.chain.data_availability import (
            verify_kzg_for_rpc_blocks,
        )

        self.verify = verify_kzg_for_rpc_blocks
        self.precompile = Precompile(params.get("precompile", ()), log)
        preset, network = config["preset"], config["network"]
        self.blocks = params["blocks"]
        self.per_block = params["blobs_per_block"]
        width = params["field_elements_per_blob"]
        n = self.blocks * self.per_block
        if (self.blocks > network["MAX_REQUEST_BLOCKS_DENEB"]
                or self.per_block > preset["MAX_BLOBS_PER_BLOCK"]
                or n > network["MAX_REQUEST_BLOB_SIDECARS"]
                or width > preset["FIELD_ELEMENTS_PER_BLOB"]):
            raise SystemExit("kzg_blobs: a request larger than the "
                             "configuration's protocol constants allow")
        self.setup = ref.Setup.from_config(config, width)
        rng = random.Random(seed)
        np_rng = np.random.default_rng(seed)
        self.pool = []  # [(blobs, commitments, proofs)], lists of bytes
        first = None    # the first batch's (zs, q_taus)
        for b in range(params["good"]):
            raw = _canonical_blobs(np_rng, n, width)
            blobs = [raw[i].tobytes() for i in range(n)]
            del raw
            commitments, zs, q_taus = [], [], []
            for blob in blobs:
                poly = [int.from_bytes(blob[k:k + 32], "big")
                        for k in range(0, len(blob), 32)]
                p_tau, c = self.setup.commit(poly)
                z = ref.compute_challenge(blob, c, self.setup)
                y = ref.evaluate_polynomial_in_evaluation_form(
                    poly, z, self.setup)
                commitments.append(c)
                zs.append(z)
                q_taus.append(self.setup.quotient_at_tau(p_tau, z, y))
            proofs = self.setup.g1_times(q_taus)
            self.pool.append((blobs, commitments, proofs))
            if first is None:
                first = (zs, q_taus)
            log(f"pool: batch {b} of {n} blobs x {width} field elements "
                "committed and proved")
        self.expect_by_construction = [True] * params["good"]
        if params["bad"] not in (0, 2):
            raise SystemExit("kzg_blobs: bad is 0 or 2")
        if params["bad"]:
            blobs, commitments, proofs = self.pool[0]
            zs, q_taus = first
            # (a) one field element of one blob, plus one, still canonical
            j, e = rng.randrange(n), rng.randrange(width)
            old = int.from_bytes(blobs[j][32 * e:32 * e + 32], "big")
            changed = bytearray(blobs[j])
            changed[32 * e:32 * e + 32] = (
                (old + 1) % ref.BLS_MODULUS).to_bytes(32, "big")
            self.pool.append((blobs[:j] + [bytes(changed)] + blobs[j + 1:],
                              commitments, proofs))
            # (b) two proofs whose errors cancel when every r^i is 1
            a, b = rng.sample(range(n), 2)
            tau = self.setup.tau
            d_a = rng.randrange(1, ref.BLS_MODULUS)
            d_b = (-d_a * (tau - zs[a]) * pow(
                (tau - zs[b]) % ref.BLS_MODULUS, -1, ref.BLS_MODULUS)
            ) % ref.BLS_MODULUS
            forged = list(proofs)
            forged[a], forged[b] = self.setup.g1_times(
                [q_taus[a] + d_a, q_taus[b] + d_b])
            self.pool.append((blobs, commitments, forged))
            self.expect_by_construction += [False, False]
            log(f"pool: bad variants of batch 0: blob {j} element {e} "
                f"changed; proofs {a} and {b} forged to cancel")
        good = params["good"]
        cycle = (list(range(good)) * params.get("good_repeats", 1)
                 + list(range(good, len(self.pool))))
        rng.shuffle(cycle)
        self.cycle = cycle
        self.units_per_request = n
        self.settings = None

    # -- the program's side ---------------------------------------------------

    def _program_settings(self):
        """What a node holds of its trusted setup, as far as verification
        reads it: the Lagrange points are placeholders (only commitment
        and proof computation read them)."""
        if self.settings is None:
            from lighthouse_tpu.crypto import kzg
            from lighthouse_tpu.crypto.bls import curve as program_cv

            self.settings = kzg.KzgSettings.from_setup_points(
                [None] * self.setup.width,
                program_cv.g2_from_bytes(cv.g2_to_bytes(self.setup.g2_tau)))
            assert self.settings.roots_brp == self.setup.roots_brp
        return self.settings

    def prepare(self, i):
        """Request ``i``: the segment as it comes off the wire, fresh
        ``bytes`` in fresh lists (outside the clock, as SSZ decoding is
        outside the checker)."""
        self._program_settings()
        entry = self.cycle[i % len(self.cycle)]
        blobs, commitments, proofs = self.pool[entry]
        k = self.per_block
        return entry, [
            [Sidecar(*(bytes(memoryview(part[s]))
                       for part in (blobs, commitments, proofs)))
             for s in range(b * k, b * k + k)]
            for b in range(self.blocks)]

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

    def reference_verdict(self, entry, *, blind=True):
        blobs, commitments, proofs = self.pool[entry]
        return ref.verify_blob_kzg_proof_batch(
            blobs, commitments, proofs, self.setup, blind=blind)

    def check(self, served, *, blind=True):
        """``served``: [(pool entry, answer)] of the whole window.  The
        reference verifies the first good batch and both of its bad
        variants (they share all but one of its blobs, so the evaluations
        are made once); every answer the window gave for them must equal
        its verdict.  Every answer is also held to the construction.  The
        cycle serves every pool entry once the window holds as many
        requests as the cycle has places."""
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
        ref.forget()
        self.log("pool entries served (good first, then the bad variants): "
                 + " ".join(f"{e}:{len(by_entry.get(e, ()))}x"
                            for e in range(len(self.pool))))
        return {"verdict_mismatches": (wrong, 0),
                "verdicts_off_construction": (off, 0),
                "answers_left_uncompared": (0 if checked else 1, 0)}


def build(config, params, seed, log):
    return Cell(config, params, seed, log)
