"""In-process chain harness: produce and sign valid blocks on interop keys.

Rebuild of the reference's `BeaconChainHarness`
(/root/reference/beacon_node/beacon_chain/src/test_utils.rs:611): extend a
chain block-by-block with correctly signed randao/proposals/sync
aggregates/attestations, entirely in-process, no network.

Also home of the fault-injection test seams (:func:`inject_fault`,
:func:`supervised_bls`) over ops/faults and the offload supervisor —
the deterministic stand-ins for device faults that real hardware won't
produce on demand.
"""

from __future__ import annotations

import contextlib
import hashlib
import os

import numpy as np

from lighthouse_tpu import ssz
from lighthouse_tpu import types as T
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls.fields import R as CURVE_ORDER
from lighthouse_tpu.state_transition import (
    SignatureStrategy,
    genesis_state,
    interop_pubkey,
    interop_secret_key,
    misc,
    process_block,
    state_advance,
)
from lighthouse_tpu.state_transition.block_processing import (
    get_expected_withdrawals,
)


# --- fault-injection seams ---------------------------------------------------


@contextlib.contextmanager
def inject_fault(mode: str, sites=("tpu",), indices=None, hang_s: float = 0.05,
                 max_fires: int | None = None, corrupt_value: bool = True):
    """Install a deterministic device-fault plan for the `with` body.

        with inject_fault("raise", sites={"chunk"}, indices={1}):
            bls.verify_signature_sets(sets, backend="tpu")

    See ops/faults for the list of modes.  The previous plan (usually
    none) is restored on exit, so tests cannot leak faults."""
    from lighthouse_tpu.ops import faults

    prev = faults.active_plan()
    faults.install_plan(faults.FaultPlan(
        mode=mode, sites=frozenset(sites), indices=indices, hang_s=hang_s,
        max_fires=max_fires, corrupt_value=corrupt_value))
    try:
        yield
    finally:
        faults.install_plan(prev)


@contextlib.contextmanager
def supervised_bls(**env):
    """Pin the offload supervisor's knobs for the `with` body and rebuild
    it (LHTPU_WATCHDOG_S, LHTPU_SUPERVISOR_LADDER, ...); restores the
    previous environment and resets the supervisor again on exit."""
    from lighthouse_tpu.crypto.bls import api

    saved = {k: os.environ.get(k) for k in env}
    os.environ.update({k: str(v) for k, v in env.items()})
    api.reset_supervisor()
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        api.reset_supervisor()


class Harness:
    """`real_crypto=False` mirrors the reference's fake_crypto test builds:
    deterministic dummy signatures + the "fake" verification backend, so
    transition-logic tests don't pay pairing costs (the crypto itself is
    covered by the real-crypto tests and tests/test_bls.py)."""

    def __init__(self, n_validators: int = 64, spec: T.ChainSpec | None = None,
                 fork: str = "capella", real_crypto: bool = True,
                 genesis_time: int = 0):
        self.spec = spec or T.ChainSpec.minimal().with_forks_at(0, through=fork)
        self.fork = fork
        self.real_crypto = real_crypto
        self.t = T.make_types(self.spec.preset)
        self.state = genesis_state(n_validators, self.spec, fork,
                                   genesis_time=genesis_time)
        from lighthouse_tpu.ssz.tree_cache import enable_tree_cache

        enable_tree_cache(self.state)
        self.genesis_root = self.state.latest_block_header.hash_tree_root()
        self._sk_by_pubkey = {}
        for i in range(n_validators):
            self._sk_by_pubkey[interop_pubkey(i)] = interop_secret_key(i)

    # --- signing helpers ---------------------------------------------------

    def sk(self, validator_index: int) -> bls.SecretKey:
        pk = self.state.validators.pubkeys[validator_index].tobytes()
        return self._sk_by_pubkey[pk]

    @staticmethod
    def _aggregate_sign(sks, signing_root: bytes) -> bls.Signature:
        """The aggregate of every key's signature over one message, as
        ONE signature by the summed secret key: sum(sk_i)*H(m) is the
        same point as sum(sk_i*H(m)), so the bytes are identical to
        aggregating len(sks) signatures at one scalar multiplication
        (~20 ms) instead of len(sks) of them."""
        return bls.SecretKey(
            sum(sk.k for sk in sks) % CURVE_ORDER).sign(signing_root)

    def _sign(self, sk, obj_root: bytes, domain_type: int, epoch: int) -> bytes:
        if not self.real_crypto:
            return b"\xab" * 96
        domain = misc.get_domain(self.state, self.spec, domain_type, epoch)
        return sk.sign(misc.compute_signing_root(obj_root, domain)).to_bytes()

    def _verify_strategy(self) -> SignatureStrategy:
        return (SignatureStrategy.VERIFY_BULK if self.real_crypto
                else SignatureStrategy.NO_VERIFICATION)

    # --- block production --------------------------------------------------

    def produce_block(self, slot: int | None = None, attestations=(),
                      blob_commitments=()):
        """Produce a fully valid signed block at `slot` (default: next slot).

        Advances self.state to the block's slot as a side effect of
        production (on a copy), then applies the block to self.state.
        `blob_commitments` populates body.blob_kzg_commitments (deneb+).
        """
        spec, t = self.spec, self.t
        target_slot = int(self.state.slot) + 1 if slot is None else slot

        # work on a copy advanced to the target slot
        pre = self.state.copy()
        state_advance(pre, spec, target_slot)

        proposer = misc.get_beacon_proposer_index(pre, spec)
        sk = self.sk(proposer)
        epoch = spec.compute_epoch_at_slot(target_slot)

        randao_reveal = self._sign(
            sk, ssz.uint64.hash_tree_root(epoch), spec.domain_randao, epoch)

        body_kw = dict(
            randao_reveal=randao_reveal,
            eth1_data=pre.eth1_data,
            graffiti=b"lighthouse-tpu".ljust(32, b"\x00"),
            attestations=list(attestations),
        )
        if self.fork != "phase0":
            body_kw["sync_aggregate"] = self._sync_aggregate(pre, target_slot)
        if self.fork in ("bellatrix", "capella", "deneb", "electra"):
            body_kw["execution_payload"] = self._execution_payload(pre, target_slot)
        if blob_commitments:
            body_kw["blob_kzg_commitments"] = [bytes(c) for c in blob_commitments]

        body = t.beacon_block_body_class(self.fork)(**body_kw)
        parent_root = self._parent_root(pre)
        block = t.beacon_block_class(self.fork)(
            slot=target_slot,
            proposer_index=proposer,
            parent_root=parent_root,
            state_root=b"\x00" * 32,
            body=body,
        )

        # trial-apply to compute the post-state root
        trial = pre.copy()
        trial_signed = t.signed_beacon_block_class(self.fork)(
            message=block, signature=b"\x00" * 95 + b"\x01")
        process_block(
            trial, spec, trial_signed, SignatureStrategy.NO_VERIFICATION)
        block.state_root = trial.hash_tree_root()

        sig = self._sign(
            sk, block.hash_tree_root(), spec.domain_beacon_proposer, epoch)
        return t.signed_beacon_block_class(self.fork)(
            message=block, signature=sig)

    def _parent_root(self, advanced_state) -> bytes:
        header = advanced_state.latest_block_header
        if header.state_root == b"\x00" * 32:
            # root as it will appear after process_slot fills state_root —
            # but advance already ran process_slot for past slots, so the
            # header here always has its state root filled unless genesis
            hdr = T.BeaconBlockHeader(
                slot=header.slot, proposer_index=header.proposer_index,
                parent_root=header.parent_root,
                state_root=advanced_state.hash_tree_root(),
                body_root=header.body_root)
            return hdr.hash_tree_root()
        return header.hash_tree_root()

    def _sync_aggregate(self, pre, slot: int):
        spec = self.spec
        prev_slot = max(slot, 1) - 1
        domain = misc.get_domain(
            pre, spec, spec.domain_sync_committee,
            spec.compute_epoch_at_slot(prev_slot))
        root = misc.get_block_root_at_slot(pre, spec, prev_slot)
        signing_root = misc.compute_signing_root(root, domain)
        signers, bits = [], []
        for pk in pre.current_sync_committee.pubkeys:
            sk = self._sk_by_pubkey.get(pk)
            bits.append(sk is not None)
            if sk is not None:
                signers.append(sk)
        if not signers:
            agg = b"\xc0" + b"\x00" * 95
        elif not self.real_crypto:
            agg = b"\xab" * 96
        else:
            agg = self._aggregate_sign(signers, signing_root).to_bytes()
        return self.t.SyncAggregate(
            sync_committee_bits=bits, sync_committee_signature=agg)

    def _execution_payload(self, pre, slot: int):
        spec = self.spec
        parent_hash = pre.latest_execution_payload_header.block_hash
        block_hash = hashlib.sha256(parent_hash + slot.to_bytes(8, "little")).digest()
        cls = {
            "bellatrix": self.t.ExecutionPayloadBellatrix,
            "capella": self.t.ExecutionPayloadCapella,
            "deneb": self.t.ExecutionPayloadDeneb,
            "electra": self.t.ExecutionPayloadElectra,
        }[self.fork]
        kw = dict(
            parent_hash=parent_hash,
            prev_randao=misc.get_randao_mix(
                pre, spec, spec.compute_epoch_at_slot(slot)),
            block_number=slot,
            timestamp=int(pre.genesis_time) + slot * spec.seconds_per_slot,
            block_hash=block_hash,
        )
        if self.fork in ("capella", "deneb", "electra"):
            kw["withdrawals"] = get_expected_withdrawals(pre, spec)
        return cls(**kw)

    # --- attestations -------------------------------------------------------

    def make_blob_sidecars(self, signed_block, blobs, proofs):
        """BlobSidecars for a produced block (header reuses the block
        signature: header root == block root by construction)."""
        from lighthouse_tpu.chain.blob_verification import (
            compute_kzg_inclusion_proof,
        )
        from lighthouse_tpu.types.containers import (
            BeaconBlockHeader,
            SignedBeaconBlockHeader,
        )

        block = signed_block.message
        body = block.body
        header = SignedBeaconBlockHeader(
            message=BeaconBlockHeader(
                slot=int(block.slot),
                proposer_index=int(block.proposer_index),
                parent_root=bytes(block.parent_root),
                state_root=bytes(block.state_root),
                body_root=body.hash_tree_root()),
            signature=bytes(signed_block.signature))
        out = []
        for i, (blob, proof) in enumerate(zip(blobs, proofs)):
            out.append(self.t.BlobSidecar(
                index=i,
                blob=blob,
                kzg_commitment=bytes(body.blob_kzg_commitments[i]),
                kzg_proof=proof,
                signed_block_header=header,
                kzg_commitment_inclusion_proof=compute_kzg_inclusion_proof(
                    body, i, self.spec),
            ))
        return out

    def attest(self, slot: int | None = None, committee_index: int = 0):
        """All committee members attest to the current head at `slot`."""
        spec, state = self.spec, self.state
        s = int(state.slot) if slot is None else slot
        epoch = spec.compute_epoch_at_slot(s)
        committee = misc.get_beacon_committee(state, spec, s, committee_index)
        head_root = self._parent_root(state)
        target_root = (
            head_root if spec.compute_start_slot_at_epoch(epoch) >= int(state.slot)
            else misc.get_block_root(state, spec, epoch))
        source = (
            state.current_justified_checkpoint
            if epoch == misc.current_epoch(state, spec)
            else state.previous_justified_checkpoint)
        data = T.AttestationData(
            slot=s, index=committee_index,
            beacon_block_root=head_root,
            source=source,
            target=T.Checkpoint(epoch=epoch, root=target_root),
        )
        if self.real_crypto:
            domain = misc.get_domain(state, spec, spec.domain_beacon_attester, epoch)
            signing_root = misc.compute_signing_root(data.hash_tree_root(), domain)
            sig = self._aggregate_sign(
                [self.sk(int(v)) for v in committee], signing_root).to_bytes()
        else:
            sig = b"\xab" * 96
        if self.fork == "electra":
            # EIP-7549: data.index moves into committee_bits
            data = T.AttestationData(
                slot=s, index=0,
                beacon_block_root=bytes(data.beacon_block_root),
                source=data.source, target=data.target)
            if self.real_crypto:
                domain = misc.get_domain(
                    state, spec, spec.domain_beacon_attester, epoch)
                signing_root = misc.compute_signing_root(
                    data.hash_tree_root(), domain)
                sig = self._aggregate_sign(
                    [self.sk(int(v)) for v in committee],
                    signing_root).to_bytes()
            committee_bits = [i == committee_index
                              for i in range(spec.preset.max_committees_per_slot)]
            return self.t.AttestationElectra(
                aggregation_bits=[True] * committee.shape[0],
                data=data,
                committee_bits=committee_bits,
                signature=sig,
            )
        return self.t.Attestation(
            aggregation_bits=[True] * committee.shape[0],
            data=data,
            signature=sig,
        )

    # --- driving ------------------------------------------------------------

    def extend_chain(self, n_blocks: int, with_attestations: bool = True):
        """Apply n blocks to self.state, optionally packing attestations from
        the previous slot."""
        from lighthouse_tpu.state_transition import state_transition

        blocks = []
        for _ in range(n_blocks):
            atts = []
            if with_attestations and int(self.state.slot) > 0:
                atts = [self.attest()]
            signed = self.produce_block(attestations=atts)
            state_transition(self.state, self.spec, signed,
                             self._verify_strategy())
            blocks.append(signed)
        return blocks


# --- randomized epoch-transition registries ----------------------------------


def randomized_registry_state(n: int, fork: str, seed: int, *,
                              leak: bool = False,
                              eject_frac: float = 0.02):
    """A coherent randomized registry: balances, flags, slashings and
    churn boundaries — respecting the invariants real states carry
    (slashed ⇒ exit epoch set; withdrawable tracks exit; effective
    balances are increment multiples at or below the fork's max).

    The single source for epoch-backend verdict tests, the pinned
    digests in tests/test_epoch_pins.py (bodies here are digest-load-
    bearing: any change to the RNG draw sequence moves the pins) and
    bench.py --child-epoch, so the device rung always faces the same
    stage-engaging workload the reference was pinned against.

    ``eject_frac`` sets the fraction of lanes parked at the ejection
    balance.  Every ejection pays an O(n) host exit-queue scan in
    process_registry_updates, so the bench child passes 0.0 to keep the
    host registry stage (excluded from backend comparisons) from
    drowning the device-covered core at n = 2^16+.  The draw is
    consumed either way — changing the fraction never shifts the RNG
    stream the pins were frozen against."""
    from lighthouse_tpu.types.registry import Validators

    far = np.uint64(T.FAR_FUTURE_EPOCH)
    h = Harness(n_validators=8, fork=fork, real_crypto=False)
    spec, st = h.spec, h.state
    rng = np.random.default_rng(seed)
    v = Validators(n)
    v.pubkeys[...] = rng.integers(0, 256, (n, 48), dtype=np.uint8)
    v.withdrawal_credentials[...] = rng.integers(0, 256, (n, 32), np.uint8)
    if fork == "electra":
        v.withdrawal_credentials[:, 0] = rng.choice(
            [0, 1, 2], n).astype(np.uint8)
        max_eb = spec.max_effective_balance_electra
    else:
        max_eb = spec.max_effective_balance
    incr = spec.effective_balance_increment
    v.effective_balance[...] = rng.integers(
        0, max_eb // incr + 1, n).astype(np.uint64) * np.uint64(incr)
    v.activation_eligibility_epoch[...] = np.where(
        rng.random(n) < 0.2, far, np.uint64(0))
    v.activation_epoch[...] = np.where(
        rng.random(n) < 0.1, far, rng.integers(0, 3, n).astype(np.uint64))
    exit_far = rng.random(n) < 0.85
    v.exit_epoch[...] = np.where(
        exit_far, far, rng.integers(3, 50, n).astype(np.uint64))
    v.withdrawable_epoch[...] = np.where(
        v.exit_epoch == far, far,
        v.exit_epoch + np.uint64(spec.min_validator_withdrawability_delay))
    slashed = rng.random(n) < 0.08
    v.slashed[...] = slashed
    v.exit_epoch[slashed] = np.uint64(5)
    # derive the slashings-target epoch from the epoch the state will
    # actually transition at (leak states sit at epoch 9, not 1) so the
    # proportional-slashings stage engages in BOTH leak variants
    cur = (10 if leak else 2) - 1
    target = cur + spec.preset.epochs_per_slashings_vector // 2
    idx = np.nonzero(slashed)[0]
    # half the slashed land exactly on the slashings target epoch
    v.withdrawable_epoch[idx] = rng.choice(
        [target, target + 3], idx.size).astype(np.uint64)
    # churn boundaries: some active lanes sit at the ejection balance
    eject = rng.random(n) < eject_frac
    v.effective_balance[eject] = np.uint64(spec.ejection_balance)
    st.validators = v
    st.balances = (v.effective_balance.astype(np.int64)
                   + rng.integers(-10**9, 2 * 10**9, n)
                   ).clip(0).astype(np.uint64)
    st.previous_epoch_participation = rng.integers(0, 8, n, dtype=np.uint8)
    st.current_epoch_participation = rng.integers(0, 8, n, dtype=np.uint8)
    st.inactivity_scores = rng.integers(0, 200, n).astype(np.uint64)
    st.slashings[0] = np.uint64(int(rng.integers(0, 64)) * incr)
    st.slot = spec.slots_per_epoch * (10 if leak else 2) - 1
    return st, spec


def registry_state_digest(st) -> str:
    """Hex digest of every column an epoch transition mutates."""
    h = hashlib.sha256()
    v = st.validators
    for arr in (st.balances, v.effective_balance, st.inactivity_scores,
                v.activation_eligibility_epoch, v.activation_epoch,
                v.exit_epoch, v.withdrawable_epoch, v.slashed,
                st.previous_epoch_participation,
                st.current_epoch_participation, st.slashings):
        h.update(np.ascontiguousarray(arr).tobytes())
    h.update(int(st.finalized_checkpoint.epoch).to_bytes(8, "little"))
    h.update(int(st.current_justified_checkpoint.epoch).to_bytes(8, "little"))
    return h.hexdigest()
