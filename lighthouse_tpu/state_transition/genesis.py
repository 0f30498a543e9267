"""Genesis state construction (interop/testing path).

Reference: /root/reference/beacon_node/genesis/src/interop.rs +
consensus/state_processing/src/genesis.rs.  Builds a state directly at a
chosen fork (the reference upgrades progressively; for testing we construct
at-fork like its `interop_genesis_state` with fork overrides).
"""

from __future__ import annotations

import hashlib
from functools import lru_cache

import numpy as np

from lighthouse_tpu import types as T
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls.fields import R as CURVE_ORDER
from lighthouse_tpu.state_transition import misc

ETH1_GENESIS_HASH = b"\x42" * 32


@lru_cache(maxsize=None)
def interop_secret_key(index: int) -> bls.SecretKey:
    """sk_i = int_le(sha256(le32(i))) mod r (eth2 interop spec; reference
    common/eth2_interop_keypairs/src/lib.rs)."""
    pre = index.to_bytes(32, "little")
    k = int.from_bytes(hashlib.sha256(pre).digest(), "little") % CURVE_ORDER
    return bls.SecretKey(k)


@lru_cache(maxsize=None)
def interop_public_key(index: int) -> bls.PublicKey:
    """One ~4 ms pure-Python scalar multiplication per index, paid once
    per process whoever asks (genesis, a harness, chip_smoke.py)."""
    return interop_secret_key(index).public_key()


def interop_pubkey(index: int) -> bytes:
    return interop_public_key(index).to_bytes()


def interop_validators(n: int, spec: T.ChainSpec) -> T.Validators:
    v = T.Validators(n)
    for i in range(n):
        pk = interop_pubkey(i)
        v.pubkeys[i] = np.frombuffer(pk, np.uint8)
        creds = b"\x00" + hashlib.sha256(pk).digest()[1:]
        v.withdrawal_credentials[i] = np.frombuffer(creds, np.uint8)
    v.effective_balance[:] = spec.max_effective_balance
    v.activation_eligibility_epoch[:] = T.GENESIS_EPOCH
    v.activation_epoch[:] = T.GENESIS_EPOCH
    v.exit_epoch[:] = T.FAR_FUTURE_EPOCH
    v.withdrawable_epoch[:] = T.FAR_FUTURE_EPOCH
    return v


def genesis_state(
    n_validators: int,
    spec: T.ChainSpec,
    fork: str = "capella",
    genesis_time: int = 0,
) -> object:
    """Build a genesis BeaconState directly at `fork` with interop keys."""
    t = T.make_types(spec.preset)
    cls = t.beacon_state_class(fork)
    state = cls()

    state.genesis_time = genesis_time
    state.slot = T.GENESIS_SLOT
    version = spec.fork_version(fork)
    state.fork = T.Fork(
        previous_version=version, current_version=version, epoch=T.GENESIS_EPOCH)

    body = t.beacon_block_body_class(fork)()
    state.latest_block_header = T.BeaconBlockHeader(
        body_root=body.hash_tree_root())

    state.validators = interop_validators(n_validators, spec)
    state.balances = np.full(
        n_validators, spec.max_effective_balance, dtype=np.uint64)

    mixes = np.tile(np.frombuffer(ETH1_GENESIS_HASH, np.uint8),
                    (spec.preset.epochs_per_historical_vector, 1))
    state.randao_mixes = mixes

    state.eth1_data = T.Eth1Data(
        deposit_root=b"\x00" * 32,
        deposit_count=n_validators,
        block_hash=ETH1_GENESIS_HASH,
    )
    state.eth1_deposit_index = n_validators

    if fork != "phase0":
        state.previous_epoch_participation = np.zeros(n_validators, np.uint8)
        state.current_epoch_participation = np.zeros(n_validators, np.uint8)
        state.inactivity_scores = np.zeros(n_validators, np.uint64)

    # genesis_validators_root over the filled registry
    state.genesis_validators_root = T.ValidatorRegistryType(
        spec.preset.validator_registry_limit).hash_tree_root(state.validators)

    if fork != "phase0":
        # both committees are derived from the identical genesis state, so
        # one computation serves both (spec initialize_beacon_state semantics)
        committee = misc.get_next_sync_committee(state, spec, t)
        state.current_sync_committee = committee
        state.next_sync_committee = committee

    if fork in ("bellatrix", "capella", "deneb", "electra"):
        # a synthetic pre-existing execution head so payload checks chain
        header_cls = {
            "bellatrix": t.ExecutionPayloadHeaderBellatrix,
            "capella": t.ExecutionPayloadHeaderCapella,
            "deneb": t.ExecutionPayloadHeaderDeneb,
            "electra": t.ExecutionPayloadHeaderElectra,
        }[fork]
        state.latest_execution_payload_header = header_cls(
            block_hash=ETH1_GENESIS_HASH,
            timestamp=genesis_time,
        )
    if fork == "electra":
        from lighthouse_tpu.state_transition.electra import (
            UNSET_DEPOSIT_REQUESTS_START_INDEX,
        )

        state.deposit_requests_start_index = \
            UNSET_DEPOSIT_REQUESTS_START_INDEX
        state.earliest_exit_epoch = spec.compute_activation_exit_epoch(0)
        state.earliest_consolidation_epoch = \
            spec.compute_activation_exit_epoch(0)
    return state
