"""Traffic generator ``bls_sets``: pools of signature-set batches for the
BLS seam, from the seed alone.

Parameters (a workload file's ``params``):

  key_pool        interop keys the members are drawn from
  sets            [{"count", "keys", "replace"}]: the shape of one batch
  good            batches signed (one signature a set, with the sum of its
                  members' secret keys: byte-identical to aggregating)
  bad             0 or 2: variants of good batches with two signatures
                  SWAPPED, one pair inside the first half of the batch and
                  one inside the second; every signature stays valid and in
                  the subgroup, so only the blinded pairing product rejects
  check_requests  pool entries the reference re-verifies after the window
                  (bad and good alternating, drawn from the seed)
  precompile      [{"entry": "module:function", "args": [...]}]: hints,
                  never requirements.  The cell's device programs as the
                  program names them today, dispatched once on zero
                  operands on threads of their own while the host makes
                  keys and signatures (XLA compiles one program on one
                  core; in a row, inside one supervised batch, today's
                  cold compiles pass the seam's 900 s watchdog).  A hint
                  that no longer fits the program (renamed, re-signed,
                  fused away) is logged and skipped: the warm-up through
                  the seam compiles whatever the seam needs, and the
                  workload's ``served_by`` rule fails the run if that
                  left the device rung benched.  An argument is an integer
                  or {"zeros": shape, "dtype": name}

The cycle's order is drawn from the seed; every seed gives the same
multiset of batches, so the seed never changes the amount of work.
Keys, messages and signatures are made by ``reference.bls_plain``; the
program gets ``PublicKey(bytes, point)`` as a node's pubkey cache holds
them, and signatures as fresh compressed bytes on every request.
"""

from __future__ import annotations

import hashlib
import importlib
import random
import threading
import time

from benchmarks.reference import bls_plain as ref
from benchmarks.reference.bls_py import curve as cv


def _msg(seed, batch, i):
    return hashlib.sha256(f"bls_sets/{seed}/{batch}/{i}".encode()).digest()


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
                     "through the seam compiles what it needs")

    def join(self):
        for t in self.threads:
            t.join()


class Cell:
    def __init__(self, config, params, seed, log):
        self.params, self.seed, self.log = params, seed, log
        self.precompile = Precompile(params.get("precompile", ()), log)
        rng = random.Random(seed)
        n_keys = params["key_pool"]
        self.points = ref.public_keys(n_keys)
        self.secrets = [ref.interop_secret(i) for i in range(n_keys)]
        log(f"keys: {n_keys} interop keys derived")
        shape = [(s["keys"], s["replace"])
                 for s in params["sets"] for _ in range(s["count"])]
        self.batches = []  # [(member indices, message, signature bytes)]
        for b in range(params["good"]):
            batch = []
            for i, (k, replace) in enumerate(shape):
                members = ([rng.randrange(n_keys) for _ in range(k)] if replace
                           else rng.sample(range(n_keys), k))
                msg = _msg(seed, b, i)
                sig = ref.sign(sum(self.secrets[j] for j in members), msg)
                batch.append((members, msg, sig))
            self.batches.append(batch)
        log(f"signed: {params['good']} batches of {len(shape)} sets")
        self.expect_by_construction = [True] * params["good"]
        n = len(shape)
        for v in range(params["bad"]):
            src = self.batches[v % params["good"]]
            lo = v % 2 * (n // 2)
            a, b = rng.sample(range(lo, lo + n // 2), 2)
            bad = list(src)
            bad[a] = (src[a][0], src[a][1], src[b][2])
            bad[b] = (src[b][0], src[b][1], src[a][2])
            self.batches.append(bad)
            self.expect_by_construction.append(False)
        cycle = list(range(len(self.batches)))
        rng.shuffle(cycle)
        self.cycle = cycle
        self.units_per_request = n
        self._keys = None

    # -- the program's side ---------------------------------------------------

    def _program_keys(self):
        if self._keys is None:
            from lighthouse_tpu.crypto import bls

            self._keys = [bls.PublicKey(cv.g1_to_bytes(p), p)
                          for p in self.points]
        return self._keys

    def prepare(self, i):
        """Request ``i``: the batch as it comes off the wire (outside the
        clock, as SSZ decoding is outside the seam)."""
        from lighthouse_tpu.crypto import bls

        keys = self._program_keys()
        entry = self.cycle[i % len(self.cycle)]
        return entry, [bls.SignatureSet(bls.Signature(sig),
                                        [keys[j] for j in members], msg)
                       for members, msg, sig in self.batches[entry]]

    def serve(self, request):
        from lighthouse_tpu.crypto import bls

        return bls.verify_signature_sets(request[1], backend="auto")

    def warm_up(self):
        """Every pool entry once: compiles or reloads the cell's shapes and
        leaves the messages where a node that saw them on gossip has them."""
        self.precompile.join()
        for entry in range(len(self.batches)):
            i = self.cycle.index(entry)
            got = self.serve(self.prepare(i))
            if got is not self.expect_by_construction[entry]:
                raise SystemExit(
                    f"warm-up: pool entry {entry} verdict {got}")

    def release(self):
        self._keys = None

    # -- the reference's side -------------------------------------------------

    def reference_verdict(self, entry, *, blind=True):
        rng = random.Random(self.seed * 1000003 + entry)
        sets = [([self.points[j] for j in members], msg, sig)
                for members, msg, sig in self.batches[entry]]
        return ref.verify_batch(sets, rng, blind=blind)

    def check(self, served, *, blind=True):
        """``served``: [(pool entry, answer)] of the whole window.  The
        reference re-verifies ``check_requests`` pool entries that were
        served (bad variants first, then by the seed) and every answer the
        window gave for them must equal its verdict."""
        by_entry = {}
        for entry, answer in served:
            by_entry.setdefault(entry, []).append(answer)
        rng = random.Random(self.seed ^ 0x5EED)
        good = [e for e in by_entry if e < self.params["good"]]
        bad = [e for e in by_entry if e >= self.params["good"]]
        rng.shuffle(good)
        rng.shuffle(bad)
        # alternate bad, good so that a sample of two holds one of each
        order = [e for pair in zip(bad, good) for e in pair]
        order += [e for e in bad + good if e not in order]
        sample = order[:self.params["check_requests"]]
        wrong = checked = 0
        for entry in sample:
            want = self.reference_verdict(entry, blind=blind)
            checked += len(by_entry[entry])
            wrong += sum(a is not want for a in by_entry[entry])
            self.log(f"reference: pool entry {entry} -> {want}; served "
                     f"{len(by_entry[entry])}x {set(by_entry[entry])}")
        return {"verdict_mismatches": (wrong, 0),
                "answers_left_uncompared": (0 if checked else 1, 0)}


def build(config, params, seed, log):
    return Cell(config, params, seed, log)
