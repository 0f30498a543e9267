"""Traffic generator ``epoch_state``: a capella ``BeaconState`` at the last
slot of epoch 1, advanced two slots across the epoch boundary.

Parameters (a workload file's ``params``):

  validators   registry size
  variants     start states cycled through the window: one base registry,
               and per variant its own balances, participation flags and
               inactivity scores (the leaves an epoch dirties), drawn from
               (seed, variant) — the same sizes for every seed
  slots        slots to advance (2: the pre-state root, the epoch pass, the
               post-epoch root)

The registry is the one ISSUE 25 named,
``lighthouse_tpu.testing.randomized_registry_state(n, "capella", seed,
eject_frac=0.0)``, draw for draw (``MIX`` below holds its shares), on the
mainnet preset: the original builds its state from a minimal-preset
harness, which the configuration does not state.  It is a stage-engaging
registry, not a mainnet day: effective balances uniform from 0 to 32 ETH
put half of the active set at or under the ejection balance, and balances
a whole ETH off their effective balance move half of the effective
balances.  No public mainnet state could be read in a sealed sandbox to
take calmer shares from; PERF.md gives the reading of how the step moves
with them (``tests/registry_sensitivity.py``).

Every variant's incremental tree cache is built and warmed in set-up, as a
node's is at start-up; a step runs on a deep copy (outside the clock).
"""

from __future__ import annotations

import hashlib
import random

import numpy as np

FAR = np.uint64(2**64 - 1)
#: the draws of ``testing.randomized_registry_state(..., eject_frac=0.0)``.
#: Constants of the generator: a workload file does not set them
MIX = {
    "effective_balance_increments": (0, 32),  # uniform, both ends in
    "not_eligible_share": 0.2,      # activation_eligibility_epoch far
    "not_activated_share": 0.1,     # activation_epoch far
    "activation_epochs": (0, 3),    # the others: uniform, end out
    "exiting_share": 0.15,          # exit_epoch set, uniform in exit_epochs
    "exit_epochs": (3, 50),
    "slashed_share": 0.08,          # exit_epoch 5, half on the slashings target
    "balance_offset_gwei": (-10**9, 2 * 10**9),
    "participation_flags": (0, 8),  # every flag byte uniform
    "inactivity_scores": (0, 200),
}
_COLUMNS = ("pubkeys", "withdrawal_credentials", "effective_balance",
            "slashed", "activation_eligibility_epoch", "activation_epoch",
            "exit_epoch", "withdrawable_epoch")


def plain(value):
    """A program container, read into plain values for the reference."""
    fields = getattr(type(value), "fields", None)
    if fields:
        return {k: plain(getattr(value, k)) for k in fields}
    if hasattr(value, "effective_balance") and hasattr(value, "pubkeys"):
        return {c: np.array(getattr(value, c)) for c in _COLUMNS}
    if isinstance(value, np.ndarray):
        return np.array(value)
    if isinstance(value, (list, tuple)):
        return [plain(v) for v in value]
    if isinstance(value, (bytes, bytearray)):
        return bytes(value)
    if isinstance(value, (bool, int, np.integer)):
        return int(value)
    raise TypeError(f"no plain reading of {type(value).__name__}")


def digest(s: dict) -> str:
    """One hash over everything of a plain state but the two heavy trees'
    roots themselves: columns, vectors, checkpoints, header, slot."""
    h = hashlib.sha256()

    def walk(v):
        if isinstance(v, dict):
            for k in sorted(v):
                h.update(k.encode())
                walk(v[k])
        elif isinstance(v, np.ndarray):
            h.update(str(v.dtype).encode() + str(v.shape).encode())
            h.update(np.ascontiguousarray(v).tobytes())
        elif isinstance(v, list):
            h.update(len(v).to_bytes(8, "little"))
            for x in v:
                walk(x)
        elif isinstance(v, bytes):
            h.update(v)
        else:
            h.update(int(v).to_bytes(32, "little"))

    walk(s)
    return h.hexdigest()


def _check_config(spec, config):
    """The configuration file is what the reference computes with; the
    program's own spec has to say the same, or the cell compares two
    different deployments."""
    names = {**config["preset"], **config["config"]}
    for name, want in names.items():
        for holder in (spec, spec.preset):
            if hasattr(holder, name.lower()):
                got = getattr(holder, name.lower())
                if int(got) != int(want):
                    raise SystemExit(f"config {name}={want}, program has {got}")
                break
        else:
            raise SystemExit(f"the program's spec has no {name}")


class Cell:
    units_per_request = 1

    def __init__(self, config, params, seed, log, mix=None):
        from lighthouse_tpu import types as T
        from lighthouse_tpu.ssz.tree_cache import enable_tree_cache
        from lighthouse_tpu.state_transition.genesis import genesis_state
        from lighthouse_tpu.types.registry import Validators

        self.config, self.params, self.seed, self.log = config, params, seed, log
        spec = T.ChainSpec.mainnet().with_forks_at(0, through="capella")
        _check_config(spec, config)
        self.spec = spec
        n = params["validators"]
        spe = spec.preset.slots_per_epoch
        incr = spec.effective_balance_increment
        rng = np.random.default_rng(seed)
        st = genesis_state(8, spec, "capella", genesis_time=0)
        v = Validators(n)
        v.pubkeys[...] = rng.integers(0, 256, (n, 48), dtype=np.uint8)
        v.withdrawal_credentials[...] = rng.integers(0, 256, (n, 32), np.uint8)
        r = self.mix = dict(MIX, **(mix or {}))
        lo, hi = r["effective_balance_increments"]
        v.effective_balance[...] = rng.integers(
            lo, hi + 1, n).astype(np.uint64) * np.uint64(incr)
        v.activation_eligibility_epoch[...] = np.where(
            rng.random(n) < r["not_eligible_share"], FAR, np.uint64(0))
        v.activation_epoch[...] = np.where(
            rng.random(n) < r["not_activated_share"], FAR,
            rng.integers(*r["activation_epochs"], n).astype(np.uint64))
        v.exit_epoch[...] = np.where(
            rng.random(n) >= r["exiting_share"], FAR,
            rng.integers(*r["exit_epochs"], n).astype(np.uint64))
        v.withdrawable_epoch[...] = np.where(
            v.exit_epoch == FAR, FAR, v.exit_epoch + np.uint64(
                spec.min_validator_withdrawability_delay))
        slashed = rng.random(n) < r["slashed_share"]
        v.slashed[...] = slashed
        v.exit_epoch[slashed] = np.uint64(5)
        target = 1 + spec.preset.epochs_per_slashings_vector // 2
        idx = np.nonzero(slashed)[0]
        # half the slashed land exactly on the slashings target epoch
        v.withdrawable_epoch[idx] = rng.choice(
            [target, target + 3], idx.size).astype(np.uint64)
        st.validators = v
        st.slashings[0] = np.uint64(int(rng.integers(0, 64)) * incr)
        st.slot = 2 * spe - 1
        self.slots = params["slots"]
        self._columns(st, np.random.default_rng([seed, 0]), r)
        enable_tree_cache(st)
        st.hash_tree_root()
        active = (v.activation_epoch <= 1) & (v.exit_epoch > 1)
        log(f"state: {n} validators at slot {int(st.slot)}, tree cache built; "
            f"{int(active.sum())} active, "
            f"{int((active & (v.exit_epoch == FAR) & (v.effective_balance <= spec.ejection_balance)).sum())}"
            " of them due for ejection, "
            f"{int(((v.activation_eligibility_epoch == 0) & (v.activation_epoch == FAR)).sum())}"
            " in the activation queue")
        self.variants = [st]
        for k in range(1, params["variants"]):
            other = st.copy()
            self._columns(other, np.random.default_rng([seed, k]), r)
            other.hash_tree_root()
            self.variants.append(other)
        log(f"state: {len(self.variants)} variants warm")

    @staticmethod
    def _columns(st, rng, r):
        n = len(st.validators)
        st.balances = (st.validators.effective_balance.astype(np.int64)
                       + rng.integers(*r["balance_offset_gwei"], n)
                       ).clip(0).astype(np.uint64)
        st.previous_epoch_participation = rng.integers(
            *r["participation_flags"], n, dtype=np.uint8)
        st.current_epoch_participation = rng.integers(
            *r["participation_flags"], n, dtype=np.uint8)
        st.inactivity_scores = rng.integers(
            *r["inactivity_scores"], n).astype(np.uint64)

    # -- the program's side ---------------------------------------------------

    def prepare(self, i):
        k = i % len(self.variants)
        return k, self.variants[k].copy()

    def serve(self, request):
        from lighthouse_tpu.state_transition.slot_processing import (
            state_advance,
        )

        _, state = request
        state_advance(state, self.spec, int(state.slot) + self.slots)
        return state

    def answer(self, request, state):
        """What a step hands back, reduced outside the clock: the state
        roots it cached and a digest of the whole post-state."""
        sphr = self.spec.preset.slots_per_historical_root
        end = int(state.slot)
        roots = [state.state_roots[s % sphr].tobytes().hex()
                 for s in range(end - self.slots, end)]
        return {"roots": roots, "digest": digest(plain(state))}

    def warm_up(self):
        for i in range(len(self.variants)):
            req = self.prepare(i)
            self.answer(req, self.serve(req))

    def release(self):
        self.variants = None

    # -- the reference's side -------------------------------------------------

    def reference_answer(self, start: dict, *, precision="exact"):
        from benchmarks.reference import epoch_plain

        s = start
        roots = epoch_plain.process_slots(
            s, self.config["preset"], self.config["config"],
            int(s["slot"]) + self.slots, precision=precision)
        return {"roots": [r.hex() for r in roots], "digest": digest(s)}

    def check(self, served, *, precision="exact"):
        """``served``: [(request key, answer)].  One variant, drawn from the
        seed among those served, is advanced by the reference; every step
        the window ran on it has to give the same roots and digest."""
        by_variant = {}
        for key, answer in served:
            by_variant.setdefault(key, []).append(answer)
        k = random.Random(self.seed ^ 0x5EED).choice(sorted(by_variant))
        start = plain(self.variants[k])
        self.release()
        want = self.reference_answer(start, precision=precision)
        got = by_variant[k]
        self.log(f"reference: variant {k}, {len(got)} steps compared; "
                 f"roots {[r[:16] for r in want['roots']]}")
        return {
            "state_root_mismatches": (
                sum(a["roots"] != want["roots"] for a in got), 0),
            "post_state_mismatches": (
                sum(a["digest"] != want["digest"] for a in got), 0),
        }


def build(config, params, seed, log):
    return Cell(config, params, seed, log)
