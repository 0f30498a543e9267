"""Consensus containers, multi-fork, parameterized by preset.

Reference equivalent: /root/reference/consensus/types/src/*.rs, where the
`superstruct` macro generates Base/Altair/Bellatrix/Capella/Deneb variants
(beacon_state.rs:225, beacon_block_body, execution_payload).  Here fork
variants are explicit classes produced by `make_types(preset)`; big state
columns use the columnar numpy-backed SSZ types from
lighthouse_tpu.types.registry so epoch processing and merkleization stay
vectorized (TPU-first).

Field orders follow the consensus spec exactly — they are consensus-critical
(hash_tree_root depends on them).
"""

from functools import lru_cache
from types import SimpleNamespace

from lighthouse_tpu import ssz
from lighthouse_tpu.types.registry import (
    RootsList,
    RootsVector,
    U8List,
    U64List,
    U64Vector,
    ValidatorRegistryType,
    Validators,
)
from lighthouse_tpu.types.spec import Preset

DEPOSIT_CONTRACT_TREE_DEPTH = 32
JUSTIFICATION_BITS_LENGTH = 4
KZG_COMMITMENT_INCLUSION_PROOF_DEPTH = 17
NUMBER_OF_COLUMNS = 128    # PeerDAS (fulu), configs/mainnet.yaml


# --- preset-independent containers -----------------------------------------

class Fork(ssz.Container):
    previous_version: ssz.Bytes4
    current_version: ssz.Bytes4
    epoch: ssz.uint64


class ForkData(ssz.Container):
    current_version: ssz.Bytes4
    genesis_validators_root: ssz.Bytes32


class Checkpoint(ssz.Container):
    epoch: ssz.uint64
    root: ssz.Bytes32


class DataColumnsByRootIdentifier(ssz.Container):
    """One block's columns in a data_column_sidecars_by_root request."""

    block_root: ssz.Bytes32
    columns: ssz.List(ssz.uint64, NUMBER_OF_COLUMNS)


class Validator(ssz.Container):
    """Object view of one registry row (columnar store: registry.Validators)."""

    pubkey: ssz.Bytes48
    withdrawal_credentials: ssz.Bytes32
    effective_balance: ssz.uint64
    slashed: ssz.boolean
    activation_eligibility_epoch: ssz.uint64
    activation_epoch: ssz.uint64
    exit_epoch: ssz.uint64
    withdrawable_epoch: ssz.uint64


class AttestationData(ssz.Container):
    slot: ssz.uint64
    index: ssz.uint64
    beacon_block_root: ssz.Bytes32
    source: Checkpoint
    target: Checkpoint


class SigningData(ssz.Container):
    object_root: ssz.Bytes32
    domain: ssz.Bytes32


class BeaconBlockHeader(ssz.Container):
    slot: ssz.uint64
    proposer_index: ssz.uint64
    parent_root: ssz.Bytes32
    state_root: ssz.Bytes32
    body_root: ssz.Bytes32


class SignedBeaconBlockHeader(ssz.Container):
    message: BeaconBlockHeader
    signature: ssz.Bytes96


class Eth1Data(ssz.Container):
    deposit_root: ssz.Bytes32
    deposit_count: ssz.uint64
    block_hash: ssz.Bytes32


class DepositMessage(ssz.Container):
    pubkey: ssz.Bytes48
    withdrawal_credentials: ssz.Bytes32
    amount: ssz.uint64


class DepositData(ssz.Container):
    pubkey: ssz.Bytes48
    withdrawal_credentials: ssz.Bytes32
    amount: ssz.uint64
    signature: ssz.Bytes96


class Deposit(ssz.Container):
    proof: ssz.Vector(ssz.Bytes32, DEPOSIT_CONTRACT_TREE_DEPTH + 1)
    data: DepositData


class VoluntaryExit(ssz.Container):
    epoch: ssz.uint64
    validator_index: ssz.uint64


class SignedVoluntaryExit(ssz.Container):
    message: VoluntaryExit
    signature: ssz.Bytes96


class ProposerSlashing(ssz.Container):
    signed_header_1: SignedBeaconBlockHeader
    signed_header_2: SignedBeaconBlockHeader


class Withdrawal(ssz.Container):
    index: ssz.uint64
    validator_index: ssz.uint64
    address: ssz.Bytes20
    amount: ssz.uint64


class BLSToExecutionChange(ssz.Container):
    validator_index: ssz.uint64
    from_bls_pubkey: ssz.Bytes48
    to_execution_address: ssz.Bytes20


class SignedBLSToExecutionChange(ssz.Container):
    message: BLSToExecutionChange
    signature: ssz.Bytes96


class HistoricalSummary(ssz.Container):
    block_summary_root: ssz.Bytes32
    state_summary_root: ssz.Bytes32


class SyncCommitteeMessage(ssz.Container):
    slot: ssz.uint64
    beacon_block_root: ssz.Bytes32
    validator_index: ssz.uint64
    signature: ssz.Bytes96


class SyncAggregatorSelectionData(ssz.Container):
    slot: ssz.uint64
    subcommittee_index: ssz.uint64


class Eth1Block(ssz.Container):
    timestamp: ssz.uint64
    deposit_root: ssz.Bytes32
    deposit_count: ssz.uint64


# --- electra containers (reference consensus/types/src/{pending_balance_
# deposit,pending_partial_withdrawal,pending_consolidation,consolidation,
# deposit_request,execution_layer_withdrawal_request}.rs) -------------------

class PendingBalanceDeposit(ssz.Container):
    index: ssz.uint64
    amount: ssz.uint64


class PendingPartialWithdrawal(ssz.Container):
    index: ssz.uint64
    amount: ssz.uint64
    withdrawable_epoch: ssz.uint64


class PendingConsolidation(ssz.Container):
    source_index: ssz.uint64
    target_index: ssz.uint64


class Consolidation(ssz.Container):
    source_index: ssz.uint64
    target_index: ssz.uint64
    epoch: ssz.uint64


class SignedConsolidation(ssz.Container):
    message: Consolidation
    signature: ssz.Bytes96


class DepositRequest(ssz.Container):
    pubkey: ssz.Bytes48
    withdrawal_credentials: ssz.Bytes32
    amount: ssz.uint64
    signature: ssz.Bytes96
    index: ssz.uint64


class ExecutionLayerWithdrawalRequest(ssz.Container):
    source_address: ssz.Bytes20
    validator_pubkey: ssz.Bytes48
    amount: ssz.uint64


def _container(name: str, field_specs: list[tuple[str, object]], doc: str = ""):
    """Build an ssz.Container subclass with exact field order."""
    ns = {"__annotations__": {f: t for f, t in field_specs}}
    if doc:
        ns["__doc__"] = doc
    return type(name, (ssz.Container,), ns)


@lru_cache(maxsize=4)
def make_types(preset: Preset) -> SimpleNamespace:
    """All preset-dependent containers for every fork, as a namespace.

    Access pattern: ``t = make_types(spec.preset); t.AttestationPhase0`` …
    Fork-variant lookup helpers: ``t.beacon_state_class('capella')``.
    """
    P = preset
    validators_per_slot = P.max_validators_per_committee * P.max_committees_per_slot

    IndexedAttestation = _container("IndexedAttestation", [
        ("attesting_indices", U64List(P.max_validators_per_committee)),
        ("data", AttestationData),
        ("signature", ssz.Bytes96),
    ])

    PendingAttestation = _container("PendingAttestation", [
        ("aggregation_bits", ssz.Bitlist(P.max_validators_per_committee)),
        ("data", AttestationData),
        ("inclusion_delay", ssz.uint64),
        ("proposer_index", ssz.uint64),
    ])

    Attestation = _container("Attestation", [
        ("aggregation_bits", ssz.Bitlist(P.max_validators_per_committee)),
        ("data", AttestationData),
        ("signature", ssz.Bytes96),
    ])

    AttesterSlashing = _container("AttesterSlashing", [
        ("attestation_1", IndexedAttestation),
        ("attestation_2", IndexedAttestation),
    ])

    # electra (EIP-7549): attestations span every committee of the slot;
    # committee membership moves from data.index to committee_bits
    # (reference attestation.rs superstruct Electra variant — note this
    # snapshot's field order places committee_bits BEFORE signature)
    AttestationElectra = _container("AttestationElectra", [
        ("aggregation_bits", ssz.Bitlist(validators_per_slot)),
        ("data", AttestationData),
        ("committee_bits", ssz.Bitvector(P.max_committees_per_slot)),
        ("signature", ssz.Bytes96),
    ])

    IndexedAttestationElectra = _container("IndexedAttestationElectra", [
        ("attesting_indices", U64List(validators_per_slot)),
        ("data", AttestationData),
        ("signature", ssz.Bytes96),
    ])

    AttesterSlashingElectra = _container("AttesterSlashingElectra", [
        ("attestation_1", IndexedAttestationElectra),
        ("attestation_2", IndexedAttestationElectra),
    ])

    AggregateAndProof = _container("AggregateAndProof", [
        ("aggregator_index", ssz.uint64),
        ("aggregate", Attestation),
        ("selection_proof", ssz.Bytes96),
    ])

    SignedAggregateAndProof = _container("SignedAggregateAndProof", [
        ("message", AggregateAndProof),
        ("signature", ssz.Bytes96),
    ])

    AggregateAndProofElectra = _container("AggregateAndProofElectra", [
        ("aggregator_index", ssz.uint64),
        ("aggregate", AttestationElectra),
        ("selection_proof", ssz.Bytes96),
    ])

    SignedAggregateAndProofElectra = _container(
        "SignedAggregateAndProofElectra", [
            ("message", AggregateAndProofElectra),
            ("signature", ssz.Bytes96),
        ])

    SyncAggregate = _container("SyncAggregate", [
        ("sync_committee_bits", ssz.Bitvector(P.sync_committee_size)),
        ("sync_committee_signature", ssz.Bytes96),
    ])

    SyncCommittee = _container("SyncCommittee", [
        ("pubkeys", ssz.Vector(ssz.Bytes48, P.sync_committee_size)),
        ("aggregate_pubkey", ssz.Bytes48),
    ])

    SyncCommitteeContribution = _container("SyncCommitteeContribution", [
        ("slot", ssz.uint64),
        ("beacon_block_root", ssz.Bytes32),
        ("subcommittee_index", ssz.uint64),
        ("aggregation_bits", ssz.Bitvector(P.sync_committee_size // 4)),
        ("signature", ssz.Bytes96),
    ])

    ContributionAndProof = _container("ContributionAndProof", [
        ("aggregator_index", ssz.uint64),
        ("contribution", SyncCommitteeContribution),
        ("selection_proof", ssz.Bytes96),
    ])

    SignedContributionAndProof = _container("SignedContributionAndProof", [
        ("message", ContributionAndProof),
        ("signature", ssz.Bytes96),
    ])

    Transactions = ssz.List(
        ssz.ByteList(P.max_bytes_per_transaction), P.max_transactions_per_payload
    )

    _payload_base = [
        ("parent_hash", ssz.Bytes32),
        ("fee_recipient", ssz.Bytes20),
        ("state_root", ssz.Bytes32),
        ("receipts_root", ssz.Bytes32),
        ("logs_bloom", ssz.ByteVector(P.bytes_per_logs_bloom)),
        ("prev_randao", ssz.Bytes32),
        ("block_number", ssz.uint64),
        ("gas_limit", ssz.uint64),
        ("gas_used", ssz.uint64),
        ("timestamp", ssz.uint64),
        ("extra_data", ssz.ByteList(P.max_extra_data_bytes)),
        ("base_fee_per_gas", ssz.uint256),
        ("block_hash", ssz.Bytes32),
    ]
    _withdrawals = ("withdrawals", ssz.List(Withdrawal, P.max_withdrawals_per_payload))
    _blob_gas = [("blob_gas_used", ssz.uint64), ("excess_blob_gas", ssz.uint64)]

    ExecutionPayloadBellatrix = _container(
        "ExecutionPayloadBellatrix", _payload_base + [("transactions", Transactions)]
    )
    ExecutionPayloadCapella = _container(
        "ExecutionPayloadCapella",
        _payload_base + [("transactions", Transactions), _withdrawals],
    )
    ExecutionPayloadDeneb = _container(
        "ExecutionPayloadDeneb",
        _payload_base + [("transactions", Transactions), _withdrawals] + _blob_gas,
    )
    _el_requests = [
        ("deposit_requests", ssz.List(
            DepositRequest, P.max_deposit_requests_per_payload)),
        ("withdrawal_requests", ssz.List(
            ExecutionLayerWithdrawalRequest,
            P.max_withdrawal_requests_per_payload)),
    ]
    ExecutionPayloadElectra = _container(
        "ExecutionPayloadElectra",
        _payload_base + [("transactions", Transactions), _withdrawals]
        + _blob_gas + _el_requests,
    )

    _header_mid = [("transactions_root", ssz.Bytes32)]
    ExecutionPayloadHeaderBellatrix = _container(
        "ExecutionPayloadHeaderBellatrix", _payload_base + _header_mid
    )
    ExecutionPayloadHeaderCapella = _container(
        "ExecutionPayloadHeaderCapella",
        _payload_base + _header_mid + [("withdrawals_root", ssz.Bytes32)],
    )
    ExecutionPayloadHeaderDeneb = _container(
        "ExecutionPayloadHeaderDeneb",
        _payload_base + _header_mid + [("withdrawals_root", ssz.Bytes32)] + _blob_gas,
    )
    ExecutionPayloadHeaderElectra = _container(
        "ExecutionPayloadHeaderElectra",
        _payload_base + _header_mid + [("withdrawals_root", ssz.Bytes32)]
        + _blob_gas + [("deposit_requests_root", ssz.Bytes32),
                       ("withdrawal_requests_root", ssz.Bytes32)],
    )

    KzgCommitments = ssz.List(ssz.Bytes48, P.max_blob_commitments_per_block)

    # --- block bodies per fork ------------------------------------------

    _body_base = [
        ("randao_reveal", ssz.Bytes96),
        ("eth1_data", Eth1Data),
        ("graffiti", ssz.Bytes32),
        ("proposer_slashings", ssz.List(ProposerSlashing, P.max_proposer_slashings)),
        ("attester_slashings", ssz.List(AttesterSlashing, P.max_attester_slashings)),
        ("attestations", ssz.List(Attestation, P.max_attestations)),
        ("deposits", ssz.List(Deposit, P.max_deposits)),
        ("voluntary_exits", ssz.List(SignedVoluntaryExit, P.max_voluntary_exits)),
    ]
    _sync = ("sync_aggregate", SyncAggregate)
    _blschanges = (
        "bls_to_execution_changes",
        ssz.List(SignedBLSToExecutionChange, P.max_bls_to_execution_changes),
    )

    BeaconBlockBodyPhase0 = _container("BeaconBlockBodyPhase0", list(_body_base))
    BeaconBlockBodyAltair = _container("BeaconBlockBodyAltair", _body_base + [_sync])
    BeaconBlockBodyBellatrix = _container(
        "BeaconBlockBodyBellatrix",
        _body_base + [_sync, ("execution_payload", ExecutionPayloadBellatrix)],
    )
    BeaconBlockBodyCapella = _container(
        "BeaconBlockBodyCapella",
        _body_base
        + [_sync, ("execution_payload", ExecutionPayloadCapella), _blschanges],
    )
    BeaconBlockBodyDeneb = _container(
        "BeaconBlockBodyDeneb",
        _body_base
        + [
            _sync,
            ("execution_payload", ExecutionPayloadDeneb),
            _blschanges,
            ("blob_kzg_commitments", KzgCommitments),
        ],
    )
    # electra body: base ops swap to the electra attestation containers
    # with their own (smaller) per-block limits; consolidations appended
    # (reference beacon_block_body.rs Electra variant)
    _body_base_electra = [
        spec if spec[0] not in ("attester_slashings", "attestations") else (
            ("attester_slashings", ssz.List(
                AttesterSlashingElectra, P.max_attester_slashings_electra))
            if spec[0] == "attester_slashings"
            else ("attestations", ssz.List(
                AttestationElectra, P.max_attestations_electra)))
        for spec in _body_base
    ]
    BeaconBlockBodyElectra = _container(
        "BeaconBlockBodyElectra",
        _body_base_electra
        + [
            _sync,
            ("execution_payload", ExecutionPayloadElectra),
            _blschanges,
            ("blob_kzg_commitments", KzgCommitments),
            ("consolidations", ssz.List(
                SignedConsolidation, P.max_consolidations)),
        ],
    )

    def _block(name, body_cls):
        return _container(name, [
            ("slot", ssz.uint64),
            ("proposer_index", ssz.uint64),
            ("parent_root", ssz.Bytes32),
            ("state_root", ssz.Bytes32),
            ("body", body_cls),
        ])

    BeaconBlockPhase0 = _block("BeaconBlockPhase0", BeaconBlockBodyPhase0)
    BeaconBlockAltair = _block("BeaconBlockAltair", BeaconBlockBodyAltair)
    BeaconBlockBellatrix = _block("BeaconBlockBellatrix", BeaconBlockBodyBellatrix)
    BeaconBlockCapella = _block("BeaconBlockCapella", BeaconBlockBodyCapella)
    BeaconBlockDeneb = _block("BeaconBlockDeneb", BeaconBlockBodyDeneb)
    BeaconBlockElectra = _block("BeaconBlockElectra", BeaconBlockBodyElectra)

    def _signed(name, block_cls):
        return _container(name, [
            ("message", block_cls),
            ("signature", ssz.Bytes96),
        ])

    SignedBeaconBlockPhase0 = _signed("SignedBeaconBlockPhase0", BeaconBlockPhase0)
    SignedBeaconBlockAltair = _signed("SignedBeaconBlockAltair", BeaconBlockAltair)
    SignedBeaconBlockBellatrix = _signed("SignedBeaconBlockBellatrix", BeaconBlockBellatrix)
    SignedBeaconBlockCapella = _signed("SignedBeaconBlockCapella", BeaconBlockCapella)
    SignedBeaconBlockDeneb = _signed("SignedBeaconBlockDeneb", BeaconBlockDeneb)
    SignedBeaconBlockElectra = _signed("SignedBeaconBlockElectra", BeaconBlockElectra)

    # --- blinded blocks (builder/MEV path) --------------------------------
    # The body swaps execution_payload for its HEADER; since an
    # ExecutionPayloadHeader's hash_tree_root equals the payload's (the
    # header IS the payload's field-root vector), a blinded block's
    # hash_tree_root — hence its signing root — equals the full block's
    # (reference consensus/types/src/beacon_block_body.rs blinded variants)

    def _blinded_body(name, full_body_cls, header_cls):
        # derive from the BUILT full body so the field lists can never
        # drift (the root-equality invariant depends on identical order)
        return _container(name, [
            ("execution_payload_header", header_cls)
            if fname == "execution_payload" else (fname, ftype)
            for fname, ftype in full_body_cls.fields.items()])

    BlindedBeaconBlockBodyBellatrix = _blinded_body(
        "BlindedBeaconBlockBodyBellatrix", BeaconBlockBodyBellatrix,
        ExecutionPayloadHeaderBellatrix)
    BlindedBeaconBlockBodyCapella = _blinded_body(
        "BlindedBeaconBlockBodyCapella", BeaconBlockBodyCapella,
        ExecutionPayloadHeaderCapella)
    BlindedBeaconBlockBodyDeneb = _blinded_body(
        "BlindedBeaconBlockBodyDeneb", BeaconBlockBodyDeneb,
        ExecutionPayloadHeaderDeneb)
    BlindedBeaconBlockBodyElectra = _blinded_body(
        "BlindedBeaconBlockBodyElectra", BeaconBlockBodyElectra,
        ExecutionPayloadHeaderElectra)

    BlindedBeaconBlockBellatrix = _block(
        "BlindedBeaconBlockBellatrix", BlindedBeaconBlockBodyBellatrix)
    BlindedBeaconBlockCapella = _block(
        "BlindedBeaconBlockCapella", BlindedBeaconBlockBodyCapella)
    BlindedBeaconBlockDeneb = _block(
        "BlindedBeaconBlockDeneb", BlindedBeaconBlockBodyDeneb)
    BlindedBeaconBlockElectra = _block(
        "BlindedBeaconBlockElectra", BlindedBeaconBlockBodyElectra)

    SignedBlindedBeaconBlockBellatrix = _signed(
        "SignedBlindedBeaconBlockBellatrix", BlindedBeaconBlockBellatrix)
    SignedBlindedBeaconBlockCapella = _signed(
        "SignedBlindedBeaconBlockCapella", BlindedBeaconBlockCapella)
    SignedBlindedBeaconBlockDeneb = _signed(
        "SignedBlindedBeaconBlockDeneb", BlindedBeaconBlockDeneb)
    SignedBlindedBeaconBlockElectra = _signed(
        "SignedBlindedBeaconBlockElectra", BlindedBeaconBlockElectra)

    HistoricalBatch = _container("HistoricalBatch", [
        ("block_roots", RootsVector(P.slots_per_historical_root)),
        ("state_roots", RootsVector(P.slots_per_historical_root)),
    ])

    # --- states per fork -------------------------------------------------

    _state_pre = [
        ("genesis_time", ssz.uint64),
        ("genesis_validators_root", ssz.Bytes32),
        ("slot", ssz.uint64),
        ("fork", Fork),
        ("latest_block_header", BeaconBlockHeader),
        ("block_roots", RootsVector(P.slots_per_historical_root)),
        ("state_roots", RootsVector(P.slots_per_historical_root)),
        ("historical_roots", RootsList(P.historical_roots_limit)),
        ("eth1_data", Eth1Data),
        ("eth1_data_votes", ssz.List(
            Eth1Data, P.epochs_per_eth1_voting_period * P.slots_per_epoch)),
        ("eth1_deposit_index", ssz.uint64),
        ("validators", ValidatorRegistryType(P.validator_registry_limit, Validator)),
        ("balances", U64List(P.validator_registry_limit)),
        ("randao_mixes", RootsVector(P.epochs_per_historical_vector)),
        ("slashings", U64Vector(P.epochs_per_slashings_vector)),
    ]
    _state_post = [
        ("justification_bits", ssz.Bitvector(JUSTIFICATION_BITS_LENGTH)),
        ("previous_justified_checkpoint", Checkpoint),
        ("current_justified_checkpoint", Checkpoint),
        ("finalized_checkpoint", Checkpoint),
    ]
    _participation = [
        ("previous_epoch_participation", U8List(P.validator_registry_limit)),
        ("current_epoch_participation", U8List(P.validator_registry_limit)),
    ]
    _altair_tail = [
        ("inactivity_scores", U64List(P.validator_registry_limit)),
        ("current_sync_committee", SyncCommittee),
        ("next_sync_committee", SyncCommittee),
    ]
    _capella_tail = [
        ("next_withdrawal_index", ssz.uint64),
        ("next_withdrawal_validator_index", ssz.uint64),
        ("historical_summaries", ssz.List(HistoricalSummary, P.historical_roots_limit)),
    ]

    BeaconStatePhase0 = _container("BeaconStatePhase0", _state_pre + [
        ("previous_epoch_attestations", ssz.List(
            PendingAttestation, P.max_attestations * P.slots_per_epoch)),
        ("current_epoch_attestations", ssz.List(
            PendingAttestation, P.max_attestations * P.slots_per_epoch)),
    ] + _state_post)

    BeaconStateAltair = _container(
        "BeaconStateAltair",
        _state_pre + _participation + _state_post + _altair_tail,
    )
    BeaconStateBellatrix = _container(
        "BeaconStateBellatrix",
        _state_pre + _participation + _state_post + _altair_tail
        + [("latest_execution_payload_header", ExecutionPayloadHeaderBellatrix)],
    )
    BeaconStateCapella = _container(
        "BeaconStateCapella",
        _state_pre + _participation + _state_post + _altair_tail
        + [("latest_execution_payload_header", ExecutionPayloadHeaderCapella)]
        + _capella_tail,
    )
    BeaconStateDeneb = _container(
        "BeaconStateDeneb",
        _state_pre + _participation + _state_post + _altair_tail
        + [("latest_execution_payload_header", ExecutionPayloadHeaderDeneb)]
        + _capella_tail,
    )
    _electra_tail = [
        ("deposit_requests_start_index", ssz.uint64),
        ("deposit_balance_to_consume", ssz.uint64),
        ("exit_balance_to_consume", ssz.uint64),
        ("earliest_exit_epoch", ssz.uint64),
        ("consolidation_balance_to_consume", ssz.uint64),
        ("earliest_consolidation_epoch", ssz.uint64),
        ("pending_balance_deposits", ssz.List(
            PendingBalanceDeposit, P.pending_deposits_limit)),
        ("pending_partial_withdrawals", ssz.List(
            PendingPartialWithdrawal, P.pending_partial_withdrawals_limit)),
        ("pending_consolidations", ssz.List(
            PendingConsolidation, P.pending_consolidations_limit)),
    ]
    BeaconStateElectra = _container(
        "BeaconStateElectra",
        _state_pre + _participation + _state_post + _altair_tail
        + [("latest_execution_payload_header", ExecutionPayloadHeaderElectra)]
        + _capella_tail + _electra_tail,
    )

    BlobSidecar = _container("BlobSidecar", [
        ("index", ssz.uint64),
        ("blob", ssz.ByteVector(P.field_elements_per_blob * 32)),
        ("kzg_commitment", ssz.Bytes48),
        ("kzg_proof", ssz.Bytes48),
        ("signed_block_header", SignedBeaconBlockHeader),
        ("kzg_commitment_inclusion_proof", ssz.Vector(
            ssz.Bytes32, KZG_COMMITMENT_INCLUSION_PROOF_DEPTH)),
    ])

    # PeerDAS (fulu): one column of cells across a block's blobs, a cell
    # proof a cell, the block's commitments and their depth-4 proof under
    # the body root
    Cell = ssz.ByteVector(P.field_elements_per_cell * 32)
    DataColumnSidecar = _container("DataColumnSidecar", [
        ("index", ssz.uint64),
        ("column", ssz.List(Cell, P.max_blob_commitments_per_block)),
        ("kzg_commitments", KzgCommitments),
        ("kzg_proofs", ssz.List(ssz.Bytes48,
                                P.max_blob_commitments_per_block)),
        ("signed_block_header", SignedBeaconBlockHeader),
        ("kzg_commitments_inclusion_proof", ssz.Vector(
            ssz.Bytes32, P.kzg_commitments_inclusion_proof_depth)),
    ])

    ns = SimpleNamespace(**{
        k: v for k, v in locals().items()
        if isinstance(v, (type, ssz.SSZType)) and not k.startswith("_")
    })
    ns.preset = P

    _by_fork = {
        "phase0": (BeaconStatePhase0, BeaconBlockPhase0, SignedBeaconBlockPhase0,
                   BeaconBlockBodyPhase0),
        "altair": (BeaconStateAltair, BeaconBlockAltair, SignedBeaconBlockAltair,
                   BeaconBlockBodyAltair),
        "bellatrix": (BeaconStateBellatrix, BeaconBlockBellatrix,
                      SignedBeaconBlockBellatrix, BeaconBlockBodyBellatrix),
        "capella": (BeaconStateCapella, BeaconBlockCapella,
                    SignedBeaconBlockCapella, BeaconBlockBodyCapella),
        "deneb": (BeaconStateDeneb, BeaconBlockDeneb, SignedBeaconBlockDeneb,
                  BeaconBlockBodyDeneb),
        "electra": (BeaconStateElectra, BeaconBlockElectra,
                    SignedBeaconBlockElectra, BeaconBlockBodyElectra),
    }
    ns.beacon_state_class = lambda fork: _by_fork[fork][0]
    ns.beacon_block_class = lambda fork: _by_fork[fork][1]
    ns.signed_beacon_block_class = lambda fork: _by_fork[fork][2]
    ns.beacon_block_body_class = lambda fork: _by_fork[fork][3]
    ns.forks = tuple(_by_fork)

    _blinded_by_fork = {
        "bellatrix": (BlindedBeaconBlockBellatrix,
                      SignedBlindedBeaconBlockBellatrix,
                      ExecutionPayloadHeaderBellatrix),
        "capella": (BlindedBeaconBlockCapella,
                    SignedBlindedBeaconBlockCapella,
                    ExecutionPayloadHeaderCapella),
        "deneb": (BlindedBeaconBlockDeneb, SignedBlindedBeaconBlockDeneb,
                  ExecutionPayloadHeaderDeneb),
        "electra": (BlindedBeaconBlockElectra,
                    SignedBlindedBeaconBlockElectra,
                    ExecutionPayloadHeaderElectra),
    }
    ns.blinded_beacon_block_class = lambda fork: _blinded_by_fork[fork][0]
    ns.signed_blinded_beacon_block_class = \
        lambda fork: _blinded_by_fork[fork][1]
    ns.execution_payload_header_class = \
        lambda fork: _blinded_by_fork[fork][2]

    def decode_signed_block(raw: bytes):
        """Decode a SignedBeaconBlock of unknown fork (newest first —
        later forks are supersets, so they must be tried first).
        Returns None if no fork's layout fits."""
        for f in reversed(ns.forks):
            try:
                return ns.signed_beacon_block_class(f).deserialize(raw)
            except Exception:
                continue
        return None

    ns.decode_signed_block = decode_signed_block
    return ns
