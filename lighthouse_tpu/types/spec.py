"""Chain specification: runtime constants, presets, forks, domains.

Reference equivalents: `ChainSpec` (/root/reference/consensus/types/src/
chain_spec.rs) for runtime constants and the `EthSpec` preset trait
(/root/reference/consensus/types/src/eth_spec.rs) for compile-time sizes.
Here both are plain data: a `Preset` (sizes that shape SSZ types) and a
`ChainSpec` (tunables + fork schedule), with `mainnet` and `minimal`
constructors.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

FAR_FUTURE_EPOCH = 2**64 - 1
GENESIS_SLOT = 0
GENESIS_EPOCH = 0

# Fork names in activation order.
FORKS = ("phase0", "altair", "bellatrix", "capella", "deneb", "electra")


@dataclass(frozen=True)
class Preset:
    """Compile-time sizes (shape SSZ types and committee math)."""

    name: str
    # time
    slots_per_epoch: int
    # committees
    max_committees_per_slot: int
    target_committee_size: int
    max_validators_per_committee: int
    shuffle_round_count: int
    # state list sizes
    slots_per_historical_root: int
    epochs_per_historical_vector: int
    epochs_per_slashings_vector: int
    historical_roots_limit: int
    validator_registry_limit: int
    epochs_per_eth1_voting_period: int
    # block operation caps
    max_proposer_slashings: int
    max_attester_slashings: int
    max_attestations: int
    max_deposits: int
    max_voluntary_exits: int
    max_bls_to_execution_changes: int
    # sync committee (altair)
    sync_committee_size: int
    epochs_per_sync_committee_period: int
    # execution (bellatrix)
    max_bytes_per_transaction: int
    max_transactions_per_payload: int
    bytes_per_logs_bloom: int
    max_extra_data_bytes: int
    # withdrawals (capella)
    max_withdrawals_per_payload: int
    max_validators_per_withdrawals_sweep: int
    # blobs (deneb)
    max_blob_commitments_per_block: int
    field_elements_per_blob: int
    max_blobs_per_block: int = 6
    # PeerDAS (fulu): consensus-specs presets/mainnet/fulu.yaml
    field_elements_per_cell: int = 64
    field_elements_per_ext_blob: int = 8192
    cells_per_ext_blob: int = 128
    kzg_commitments_inclusion_proof_depth: int = 4
    # electra
    max_attester_slashings_electra: int = 1
    max_attestations_electra: int = 8
    pending_deposits_limit: int = 2**27
    pending_partial_withdrawals_limit: int = 2**27
    pending_consolidations_limit: int = 2**18
    max_deposit_requests_per_payload: int = 8192
    max_withdrawal_requests_per_payload: int = 16
    max_consolidation_requests_per_payload: int = 2
    max_consolidations: int = 1
    max_pending_partials_per_withdrawals_sweep: int = 8
    max_pending_deposits_per_epoch: int = 16


MAINNET_PRESET = Preset(
    name="mainnet",
    slots_per_epoch=32,
    max_committees_per_slot=64,
    target_committee_size=128,
    max_validators_per_committee=2048,
    shuffle_round_count=90,
    slots_per_historical_root=8192,
    epochs_per_historical_vector=65536,
    epochs_per_slashings_vector=8192,
    historical_roots_limit=2**24,
    validator_registry_limit=2**40,
    epochs_per_eth1_voting_period=64,
    max_proposer_slashings=16,
    max_attester_slashings=2,
    max_attestations=128,
    max_deposits=16,
    max_voluntary_exits=16,
    max_bls_to_execution_changes=16,
    sync_committee_size=512,
    epochs_per_sync_committee_period=256,
    max_bytes_per_transaction=2**30,
    max_transactions_per_payload=2**20,
    bytes_per_logs_bloom=256,
    max_extra_data_bytes=32,
    max_withdrawals_per_payload=16,
    max_validators_per_withdrawals_sweep=16384,
    max_blob_commitments_per_block=4096,
    field_elements_per_blob=4096,
)

MINIMAL_PRESET = Preset(
    name="minimal",
    slots_per_epoch=8,
    max_committees_per_slot=4,
    target_committee_size=4,
    max_validators_per_committee=2048,
    shuffle_round_count=10,
    slots_per_historical_root=64,
    epochs_per_historical_vector=64,
    epochs_per_slashings_vector=64,
    historical_roots_limit=2**24,
    validator_registry_limit=2**40,
    epochs_per_eth1_voting_period=4,
    max_proposer_slashings=16,
    max_attester_slashings=2,
    max_attestations=128,
    max_deposits=16,
    max_voluntary_exits=16,
    max_bls_to_execution_changes=16,
    sync_committee_size=32,
    epochs_per_sync_committee_period=8,
    max_bytes_per_transaction=2**30,
    max_transactions_per_payload=2**20,
    bytes_per_logs_bloom=256,
    max_extra_data_bytes=32,
    max_withdrawals_per_payload=4,
    max_validators_per_withdrawals_sweep=16,
    max_blob_commitments_per_block=4096,
    field_elements_per_blob=4096,
    # electra (minimal preset overrides)
    pending_partial_withdrawals_limit=64,
    pending_consolidations_limit=64,
    max_deposit_requests_per_payload=4,
    max_withdrawal_requests_per_payload=2,
    max_pending_partials_per_withdrawals_sweep=1,
)


@dataclass(frozen=True)
class ChainSpec:
    """Runtime tunables + fork schedule (reference chain_spec.rs)."""

    preset: Preset = MAINNET_PRESET
    config_name: str = "mainnet"

    seconds_per_slot: int = 12
    genesis_delay: int = 604800
    min_genesis_time: int = 1606824000
    min_genesis_active_validator_count: int = 16384

    # deposits / balances (Gwei)
    min_deposit_amount: int = 10**9
    max_effective_balance: int = 32 * 10**9
    effective_balance_increment: int = 10**9
    ejection_balance: int = 16 * 10**9
    hysteresis_quotient: int = 4
    hysteresis_downward_multiplier: int = 1
    hysteresis_upward_multiplier: int = 5

    # time parameters
    min_attestation_inclusion_delay: int = 1
    min_seed_lookahead: int = 1
    max_seed_lookahead: int = 4
    min_validator_withdrawability_delay: int = 256
    shard_committee_period: int = 256
    min_epochs_to_inactivity_penalty: int = 4
    eth1_follow_distance: int = 2048

    # rewards & penalties
    base_reward_factor: int = 64
    whistleblower_reward_quotient: int = 512
    proposer_reward_quotient: int = 8
    inactivity_penalty_quotient: int = 2**26
    min_slashing_penalty_quotient: int = 128
    proportional_slashing_multiplier: int = 1
    # altair overrides
    inactivity_penalty_quotient_altair: int = 3 * 2**24
    min_slashing_penalty_quotient_altair: int = 64
    proportional_slashing_multiplier_altair: int = 2
    # bellatrix overrides
    inactivity_penalty_quotient_bellatrix: int = 2**24
    min_slashing_penalty_quotient_bellatrix: int = 32
    proportional_slashing_multiplier_bellatrix: int = 3
    # altair participation
    inactivity_score_bias: int = 4
    inactivity_score_recovery_rate: int = 16

    # validator cycle
    min_per_epoch_churn_limit: int = 4
    churn_limit_quotient: int = 2**16
    max_per_epoch_activation_churn_limit: int = 8
    # electra
    min_activation_balance: int = 32 * 10**9
    max_effective_balance_electra: int = 2048 * 10**9
    min_per_epoch_churn_limit_electra: int = 128 * 10**9
    max_per_epoch_activation_exit_churn_limit: int = 256 * 10**9
    whistleblower_reward_quotient_electra: int = 4096
    min_slashing_penalty_quotient_electra: int = 4096

    # fork choice
    proposer_score_boost: int = 40
    reorg_head_weight_threshold: int = 20
    reorg_parent_weight_threshold: int = 160
    reorg_max_epochs_since_finalization: int = 2

    # fork schedule: version (4 bytes) and activation epoch per fork
    genesis_fork_version: bytes = b"\x00\x00\x00\x00"
    altair_fork_version: bytes = b"\x01\x00\x00\x00"
    bellatrix_fork_version: bytes = b"\x02\x00\x00\x00"
    capella_fork_version: bytes = b"\x03\x00\x00\x00"
    deneb_fork_version: bytes = b"\x04\x00\x00\x00"
    electra_fork_version: bytes = b"\x05\x00\x00\x00"
    altair_fork_epoch: int = 74240
    bellatrix_fork_epoch: int = 144896
    capella_fork_epoch: int = 194048
    deneb_fork_epoch: int = 269568
    electra_fork_epoch: int = FAR_FUTURE_EPOCH

    # PeerDAS (fulu): consensus-specs configs/mainnet.yaml.  The blob
    # maximum is a schedule since the blob-parameter-only forks:
    # (epoch, MAX_BLOBS_PER_BLOCK), ascending; before its first entry
    # electra's maximum holds, before electra the preset's
    number_of_columns: int = 128
    number_of_custody_groups: int = 128
    max_request_data_column_sidecars: int = 16384
    max_blobs_per_block_electra: int = 9
    blob_schedule: tuple = ((412672, 15), (419072, 21))

    # domains (4-byte little-endian tags)
    domain_beacon_proposer: int = 0
    domain_beacon_attester: int = 1
    domain_randao: int = 2
    domain_deposit: int = 3
    domain_voluntary_exit: int = 4
    domain_selection_proof: int = 5
    domain_aggregate_and_proof: int = 6
    domain_sync_committee: int = 7
    domain_sync_committee_selection_proof: int = 8
    domain_contribution_and_proof: int = 9
    domain_bls_to_execution_change: int = 10
    domain_consolidation: int = 11
    domain_application_mask: int = 0x00000001

    # networking-ish constants used by subnet scheduling
    attestation_subnet_count: int = 64
    sync_committee_subnet_count: int = 4
    target_aggregators_per_committee: int = 16

    # deposit contract
    deposit_contract_address: bytes = bytes.fromhex(
        "00000000219ab540356cbb839cbe05303d7705fa"
    )
    deposit_chain_id: int = 1
    deposit_network_id: int = 1

    # -- derived helpers -------------------------------------------------

    @property
    def slots_per_epoch(self) -> int:
        return self.preset.slots_per_epoch

    def fork_version(self, fork: str) -> bytes:
        return {
            "phase0": self.genesis_fork_version,
            "altair": self.altair_fork_version,
            "bellatrix": self.bellatrix_fork_version,
            "capella": self.capella_fork_version,
            "deneb": self.deneb_fork_version,
            "electra": self.electra_fork_version,
        }[fork]

    def fork_epoch(self, fork: str) -> int:
        return {
            "phase0": GENESIS_EPOCH,
            "altair": self.altair_fork_epoch,
            "bellatrix": self.bellatrix_fork_epoch,
            "capella": self.capella_fork_epoch,
            "deneb": self.deneb_fork_epoch,
            "electra": self.electra_fork_epoch,
        }[fork]

    def fork_at_epoch(self, epoch: int) -> str:
        current = "phase0"
        for f in FORKS[1:]:
            if self.fork_epoch(f) <= epoch:
                current = f
        return current

    @staticmethod
    def fork_at_least(fork: str, base: str) -> bool:
        """fork >= base in activation order.  Use this instead of
        hardcoded suffix tuples like `fork in ("deneb", "electra")` —
        those silently exclude every later fork added to FORKS."""
        return FORKS.index(fork) >= FORKS.index(base)

    def max_blobs_per_block_at(self, epoch: int) -> int:
        """get_blob_parameters(epoch).max_blobs_per_block: the newest
        entry of the schedule at or before ``epoch``."""
        current = (self.max_blobs_per_block_electra
                   if epoch >= self.electra_fork_epoch
                   else self.preset.max_blobs_per_block)
        for at, maximum in self.blob_schedule:
            if at <= epoch:
                current = maximum
        return current

    def compute_epoch_at_slot(self, slot: int) -> int:
        return slot // self.slots_per_epoch

    def compute_start_slot_at_epoch(self, epoch: int) -> int:
        return epoch * self.slots_per_epoch

    def compute_activation_exit_epoch(self, epoch: int) -> int:
        return epoch + 1 + self.max_seed_lookahead

    def sync_committee_period_at_slot(self, slot: int) -> int:
        """compute_sync_committee_period_at_slot (altair validator.md)."""
        return (self.compute_epoch_at_slot(int(slot))
                // self.preset.epochs_per_sync_committee_period)

    def balance_churn_limit(self, active_validator_count: int) -> int:
        return max(
            self.min_per_epoch_churn_limit,
            active_validator_count // self.churn_limit_quotient,
        )

    @staticmethod
    def mainnet() -> "ChainSpec":
        return ChainSpec()

    @staticmethod
    def minimal() -> "ChainSpec":
        return ChainSpec(
            preset=MINIMAL_PRESET,
            config_name="minimal",
            seconds_per_slot=6,
            min_genesis_active_validator_count=64,
            shard_committee_period=64,
            eth1_follow_distance=16,
            # minimal config activates all forks at genesis-adjacent epochs
            # only when a test overrides them; defaults stay far-future so
            # fork logic is exercised explicitly.
            altair_fork_epoch=FAR_FUTURE_EPOCH,
            bellatrix_fork_epoch=FAR_FUTURE_EPOCH,
            capella_fork_epoch=FAR_FUTURE_EPOCH,
            deneb_fork_epoch=FAR_FUTURE_EPOCH,
        )

    def with_forks_at(self, epoch: int, through: str = "capella") -> "ChainSpec":
        """Testing helper: activate forks up to `through` at `epoch`."""
        kw = {}
        for f in FORKS[1:]:
            idx_f, idx_t = FORKS.index(f), FORKS.index(through)
            kw[f"{f}_fork_epoch"] = epoch if idx_f <= idx_t else FAR_FUTURE_EPOCH
        return replace(self, **kw)
