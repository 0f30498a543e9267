"""Traffic generator ``gossip_attestations``: beacon processor batches of
unaggregated gossip attestations for the chain's batch entry
(``chain/attestation_verification.batch_verify``), from the seed alone.

Parameters (a workload file's ``params``):

  key_pool        interop keys the attesters are drawn from
  votes           attestations a batch carries for each head vote of the
                  slot: [1984, 64] is a batch of 2,048 (the processor's
                  ``max_batch``) over two messages; attesters are distinct
                  within a batch
  good            batches signed
  bad             0, 1 or 2 variants: (a) a copy of the first good batch
                  with two signatures of the first message SWAPPED (both
                  stay valid and in the subgroup: only the blinding
                  rejects them), (b) a copy of the second with one
                  attestation of the first message signed over the second
  check_requests  pool entries the reference judges after the window (the
                  bad ones first, then by the seed)
  precompile      the cell's device programs as ``bls_sets`` gives them,
                  compiled side by side on threads of their own; here a
                  hint the program does not have fails the set-up at once:
                  without the program that merges on the device a bad
                  batch would bisect through shapes nothing compiled
  served_by       as every cell's

The messages are the slot's head votes (32-byte signing roots from the
seed); signatures are made from a fixed-base table on each message's
H(m).  The request is one ``batch_verify`` call on 2,048 fresh
``VerifiedAttestation``s, their signatures fresh compressed bytes; the
answer is its verdict for each attestation.
"""

from __future__ import annotations

import hashlib
import importlib
import random

from benchmarks.reference import attestation_plain as att_ref
from benchmarks.reference import bls_plain as ref
from benchmarks.reference.bls_py import curve as cv
from benchmarks.traffic.bls_sets import Precompile


def _vote(seed, k):
    return hashlib.sha256(f"gossip_attestations/{seed}/vote/{k}".encode()
                          ).digest()


def _require(hints):
    for job in hints:
        module, name = job["entry"].split(":")
        if not hasattr(importlib.import_module(module), name):
            raise SystemExit(f"set-up: the program has no {job['entry']}")


class Cell:
    def __init__(self, config, params, seed, log):
        _require(params.get("precompile", ()))
        self.params, self.seed, self.log = params, seed, log
        self.precompile = Precompile(params.get("precompile", ()), log)
        rng = random.Random(seed)
        n_keys = params["key_pool"]
        self.points = ref.public_keys(n_keys)
        log(f"keys: {n_keys} interop keys derived")
        self.messages = [_vote(seed, k) for k in range(len(params["votes"]))]
        tables = [att_ref.sign_table(ref.hash_to_g2(m))
                  for m in self.messages]
        votes = [k for k, count in enumerate(params["votes"])
                 for _ in range(count)]
        # [(attester, vote, signature bytes)] a batch
        self.batches = []
        for _ in range(params["good"]):
            attesters = rng.sample(range(n_keys), len(votes))
            order = list(zip(attesters, votes))
            rng.shuffle(order)
            sigs = [None] * len(order)
            for k, table in enumerate(tables):
                at = [i for i, (_, v) in enumerate(order) if v == k]
                signed = att_ref.sign_many(
                    table, [ref.interop_secret(order[i][0]) for i in at])
                for i, sig in zip(at, signed):
                    sigs[i] = sig
            self.batches.append([(a, v, s)
                                 for (a, v), s in zip(order, sigs)])
        log(f"signed: {params['good']} batches of {len(votes)} attestations "
            f"over {len(tables)} messages")
        self.expect = [[True] * len(votes) for _ in self.batches]
        for variant in range(params["bad"]):
            src = self.batches[variant % params["good"]]
            bad = list(src)
            first = [i for i, (_, v, _) in enumerate(src) if v == 0]
            if variant == 0:
                a, b = rng.sample(first, 2)
                bad[a] = (src[a][0], 0, src[b][2])
                bad[b] = (src[b][0], 0, src[a][2])
                wrong = (a, b)
            else:
                a = rng.choice(first)
                bad[a] = (src[a][0], 0, att_ref.sign_many(
                    tables[1], [ref.interop_secret(src[a][0])])[0])
                wrong = (a,)
            self.batches.append(bad)
            self.expect.append([i not in wrong for i in range(len(bad))])
        cycle = list(range(len(self.batches)))
        rng.shuffle(cycle)
        self.cycle = cycle
        self.units_per_request = len(votes)
        self._keys = None

    # -- the program's side ---------------------------------------------------

    def _program_keys(self):
        if self._keys is None:
            from lighthouse_tpu.crypto import bls

            # a node pins the backend "auto" resolves to when it starts
            bls.set_backend(bls.resolve_auto_backend())
            self._keys = [bls.PublicKey(cv.g1_to_bytes(p), p)
                          for p in self.points]
        return self._keys

    def prepare(self, i):
        """Request ``i``: the processor's batch as the gossip checks hand it
        over (outside the clock, as `_prepare_batch` is)."""
        import numpy as np

        from lighthouse_tpu.chain.attestation_verification import (
            VerifiedAttestation,
        )
        from lighthouse_tpu.crypto import bls

        keys = self._program_keys()
        entry = self.cycle[i % len(self.cycle)]
        return entry, [
            VerifiedAttestation(
                j, None, np.array([attester]),
                [bls.SignatureSet(bls.Signature(sig), [keys[attester]],
                                  self.messages[vote])])
            for j, (attester, vote, sig) in enumerate(self.batches[entry])]

    def serve(self, request):
        from lighthouse_tpu.chain.attestation_verification import batch_verify

        return tuple(c.ok for c in batch_verify(None, request[1]))

    def warm_up(self):
        """Every pool entry once: compiles or reloads the cell's shapes, the
        bad ones' bisection halves among them."""
        self.precompile.join()
        for entry in range(len(self.batches)):
            got = self.serve(self.prepare(self.cycle.index(entry)))
            if list(got) != self.expect[entry]:
                raise SystemExit(f"warm-up: pool entry {entry} verdicts "
                                 f"{sum(got)} of {len(got)} true")

    def release(self):
        self._keys = None

    # -- the reference's side -------------------------------------------------

    def reference_verdicts(self, entry, *, blind=True, decompressed=None):
        rng = random.Random(self.seed * 1000003 + entry)
        return att_ref.verdicts(
            [(self.points[a], self.messages[v], sig)
             for a, v, sig in self.batches[entry]],
            rng, blind=blind, decompressed=decompressed)

    def check(self, served, *, blind=True):
        """``served``: [(pool entry, verdicts)] of the whole window.  Every
        answer is held to the construction; the reference judges
        ``check_requests`` served pool entries (the bad ones first, then by
        the seed) and every answer the window gave for them must equal its
        verdicts, attestation by attestation."""
        by_entry = {}
        for entry, answer in served:
            by_entry.setdefault(entry, []).append(answer)
        off = sum(a != e for entry, answer in served
                  for a, e in zip(answer, self.expect[entry]))
        rng = random.Random(self.seed ^ 0x5EED)
        good = [e for e in by_entry if e < self.params["good"]]
        rng.shuffle(good)
        # the bad variants' sources first: they share their signatures,
        # so the reference decompresses them once
        good.sort(key=lambda e: e >= self.params["bad"])
        bad = sorted(e for e in by_entry if e >= self.params["good"])
        sample = (bad + good)[:self.params["check_requests"]]
        decompressed = {}
        wrong = checked = 0
        for entry in sample:
            want = self.reference_verdicts(entry, blind=blind,
                                           decompressed=decompressed)
            for answer in by_entry[entry]:
                checked += 1
                wrong += sum(a != w for a, w in zip(answer, want))
            self.log(f"reference: pool entry {entry} -> "
                     f"{len(want) - sum(want)} of {len(want)} rejected; "
                     f"served {len(by_entry[entry])}x")
        return {"verdict_mismatches": (wrong, 0),
                "verdicts_off_construction": (off, 0),
                "answers_left_uncompared": (0 if checked else 1, 0)}


def build(config, params, seed, log):
    return Cell(config, params, seed, log)
