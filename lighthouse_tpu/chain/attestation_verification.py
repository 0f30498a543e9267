"""Gossip attestation/aggregate verification with batched BLS.

Rebuild of /root/reference/beacon_node/beacon_chain/src/
attestation_verification.rs and attestation_verification/batch.rs: gossip
checks (slot window, committee membership, dup detection) per item, then
ONE batched `verify_signature_sets` call for the whole batch.

Two deliberate deltas from the reference:
- Poisoned-batch fallback is recursive bisection (log-depth) instead of
  linear per-item re-verification (batch.rs:104-127) — a 64k-lane device
  batch with k bad items costs O(k·log n) re-verifies (SURVEY.md §7 #6).
- Dup caches are only READ before signature verification and written
  after it succeeds, so unauthenticated garbage cannot suppress honest
  validators' later messages.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.state_transition import signature_sets as sigs
from lighthouse_tpu.state_transition.block_processing import (
    get_attesting_indices,
)
from lighthouse_tpu.state_transition.misc import get_beacon_committee


def is_aggregator(spec, committee_len: int, selection_proof: bytes) -> bool:
    """Spec is_aggregator: the selection proof elects ~TARGET_AGGREGATORS
    members per committee (reference attestation_verification.rs
    InvalidSelectionProof rejection)."""
    import hashlib

    modulo = max(1, committee_len // spec.target_aggregators_per_committee)
    digest = hashlib.sha256(selection_proof).digest()
    return int.from_bytes(digest[:8], "little") % modulo == 0


class AttestationError(ValueError):
    def __init__(self, reason: str):
        self.reason = reason
        super().__init__(reason)


@dataclass
class VerifiedAttestation:
    item: object            # what the caller submitted
    attestation: object     # the (inner) Attestation
    indexed_indices: np.ndarray
    sets: list
    observations: list = field(default_factory=list)  # deferred cache marks
    ok: bool = False


def verify_signature_sets_with_bisection(
    sets: Sequence[bls.SignatureSet], *, backend: str | None = None
) -> np.ndarray:
    """Per-set validity mask via batch verify + bisection fallback."""
    n = len(sets)
    out = np.zeros(n, bool)

    def rec(lo: int, hi: int, known_failed: bool):
        if lo >= hi:
            return
        if not known_failed and bls.verify_signature_sets(
                sets[lo:hi], backend=backend):
            out[lo:hi] = True
            return
        if hi - lo == 1:
            out[lo] = False
            return
        mid = (lo + hi) // 2
        rec(lo, mid, False)
        rec(mid, hi, False)

    # callers reach this after a failed whole-batch verify: skip re-checking
    # the root span
    rec(0, n, True)
    return out


def _gossip_checks(chain, attestation, state) -> np.ndarray:
    """Structure/timing checks; returns attesting validator indices."""
    spec = chain.spec
    data = attestation.data
    att_slot = int(data.slot)
    current_slot = chain.current_slot()
    # propagation window: [att_slot, att_slot + ATTESTATION_PROPAGATION_SLOT_RANGE]
    if att_slot > current_slot:
        raise AttestationError("future_slot")
    if att_slot + spec.slots_per_epoch < current_slot:
        raise AttestationError("past_slot")
    target_epoch = int(data.target.epoch)
    if target_epoch != spec.compute_epoch_at_slot(att_slot):
        raise AttestationError("target_epoch_mismatch")
    head_root = bytes(data.beacon_block_root)
    if head_root not in chain.fork_choice.proto:
        raise AttestationError("unknown_head_block")
    # target consistency (reference verify_attestation_target_root): the
    # target must be a known block AND the epoch-boundary ancestor of the
    # LMD vote, else validly-signed attestations with inconsistent targets
    # would be counted in fork choice
    target_root = bytes(data.target.root)
    if target_root not in chain.fork_choice.proto:
        raise AttestationError("unknown_target_root")
    expected_target = chain.fork_choice.proto.get_ancestor(
        head_root, spec.compute_start_slot_at_epoch(target_epoch))
    if expected_target != target_root:
        raise AttestationError("invalid_target_root")
    shuffle = chain.committee_shuffle(state, target_epoch)
    indices = get_attesting_indices(state, spec, attestation, shuffle)
    if indices.size == 0:
        raise AttestationError("empty_aggregation_bits")
    return indices


def verify_unaggregated_for_gossip(chain, attestation, state) -> VerifiedAttestation:
    """Checks for a single-bit gossip attestation (reference
    IndexedUnaggregatedAttestation::verify).  Dup checks are read-only;
    marking is deferred to post-signature commit."""
    indices = _gossip_checks(chain, attestation, state)
    if indices.size != 1:
        raise AttestationError("not_unaggregated")
    epoch = int(attestation.data.target.epoch)
    if chain.observed_attesters.seen_mask(epoch, indices).any():
        raise AttestationError("prior_attestation_known")
    sset = sigs.indexed_attestation_set(state, chain.spec, _as_indexed(
        chain, attestation, indices))
    return VerifiedAttestation(
        attestation, attestation, indices, [sset],
        observations=[("attesters", epoch, indices)])


def verify_aggregated_for_gossip(chain, signed_aggregate, state) -> VerifiedAttestation:
    """Checks for a SignedAggregateAndProof (reference
    IndexedAggregatedAttestation::verify): 3 signature sets — selection
    proof, aggregator signature, aggregate (batch.rs:62-102)."""
    msg = signed_aggregate.message
    aggregate = msg.aggregate
    indices = _gossip_checks(chain, aggregate, state)
    epoch = int(aggregate.data.target.epoch)
    aggregator = int(msg.aggregator_index)
    if chain.observed_aggregators.is_seen(epoch, aggregator):
        raise AttestationError("aggregator_already_known")
    agg_digest = (aggregate.data.hash_tree_root()
                  + bytes(np.packbits(np.asarray(aggregate.aggregation_bits))))
    if chain.observed_aggregates.is_seen(epoch, agg_digest):
        raise AttestationError("aggregate_already_known")
    if aggregator not in set(int(i) for i in indices):
        raise AttestationError("aggregator_not_in_committee")
    from lighthouse_tpu.state_transition.misc import (
        attestation_committee_index,
    )

    slot = int(aggregate.data.slot)
    committee = get_beacon_committee(
        state, chain.spec, slot, attestation_committee_index(aggregate),
        chain.committee_shuffle(state, epoch))
    if not is_aggregator(
            chain.spec, committee.shape[0], bytes(msg.selection_proof)):
        raise AttestationError("invalid_selection_proof_not_aggregator")
    sets = [
        sigs.selection_proof_set(
            state, chain.spec, slot, aggregator, bytes(msg.selection_proof)),
        sigs.aggregate_and_proof_set(state, chain.spec, signed_aggregate),
        sigs.indexed_attestation_set(
            state, chain.spec, _as_indexed(chain, aggregate, indices)),
    ]
    return VerifiedAttestation(
        signed_aggregate, aggregate, indices, sets,
        observations=[
            ("aggregators", epoch, np.array([aggregator])),
            ("aggregates", epoch, agg_digest),
        ])


def _as_indexed(chain, attestation, indices: np.ndarray):
    t = chain.t
    cls = (t.IndexedAttestationElectra
           if hasattr(attestation, "committee_bits")
           else t.IndexedAttestation)
    return cls(
        attesting_indices=[int(i) for i in np.sort(indices)],
        data=attestation.data,
        signature=attestation.signature,
    )


def commit_observations(chain, verified: VerifiedAttestation) -> bool:
    """Mark dup caches for a signature-verified item.  Returns False if a
    concurrent in-batch duplicate already claimed a mark (item rejected)."""
    ok = True
    for kind, epoch, payload in verified.observations:
        if kind == "attesters":
            if chain.observed_attesters.observe_batch(epoch, payload).any():
                ok = False
        elif kind == "aggregators":
            if chain.observed_aggregators.observe_batch(epoch, payload).any():
                ok = False
        elif kind == "aggregates":
            if chain.observed_aggregates.observe(epoch, payload):
                ok = False
    return ok


def batch_verify(
    chain, candidates: list[VerifiedAttestation]
) -> list[VerifiedAttestation]:
    """One device-sized batch verification over all candidates' sets, with
    bisection fallback attributing failures to items
    (reference batch_verify_unaggregated_attestations, batch.rs:133).

    The batch first passes through the pre-BLS stage
    (pool/pre_aggregation): exact duplicates verify once.  Sets that
    share a message (since EIP-7549 the signed data is the same for
    every committee, so a slot's attestations share one message a head
    vote) are then merged by the rung that serves the batch, so a
    mainnet-width attestation sweep pays one pairing lane per head vote
    instead of one per validator: the "tpu" backend merges them on the
    device (ops/bls_backend -> ops/msm._blinded_merge), the reference
    rung in Python (pool/pre_aggregation.merge_same_message).  The fast
    path verifies the deduplicated batch; on failure, bisection runs
    over the ORIGINAL per-candidate sets so attribution is unchanged."""
    from lighthouse_tpu.pool.pre_aggregation import coalesce_sets

    all_sets: list[bls.SignatureSet] = []
    spans: list[tuple[int, int]] = []
    for c in candidates:
        spans.append((len(all_sets), len(all_sets) + len(c.sets)))
        all_sets.extend(c.sets)
    if not all_sets:
        return candidates
    coalesced, _stats = coalesce_sets(all_sets)
    if bls.verify_signature_sets(coalesced):
        for c in candidates:
            c.ok = True
        return candidates
    mask = verify_signature_sets_with_bisection(all_sets)
    for c, (lo, hi) in zip(candidates, spans):
        c.ok = bool(mask[lo:hi].all())
    return candidates
