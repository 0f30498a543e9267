"""Data-availability checker (Deneb; the column branch of its segment
entry is fulu's).

Rebuild of /root/reference/beacon_node/beacon_chain/src/
data_availability_checker.rs (:32,:61) + its overflow LRU cache: pending
block/blob components are held per block root until every commitment the
block carries has a verified sidecar — only then does import proceed.
Capacity-bounded; finalization prunes.  `verify_kzg_for_rpc_blocks` is the
checker's segment entry (:verify_kzg_for_rpc_blocks -> kzg_utils.rs
validate_blobs): the sidecars of a whole chain segment in ONE batch.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field


def verify_kzg_for_rpc_blocks(settings, blocks_sidecars) -> bool:
    """KZG verification for a chain segment that arrived over RPC (range
    sync, backfill, a lookup): ``blocks_sidecars`` holds, block by block,
    what came with it.

    Blob sidecars (anything with ``blob``, ``kzg_commitment`` and
    ``kzg_proof``): every sidecar of the segment goes through
    `validate_blobs` in one call, up to MAX_REQUEST_BLOB_SIDECARS (768) of
    them, one `verify_blob_kzg_proof_batch`.  Data-column sidecars
    (anything with ``index``, ``column``, ``kzg_commitments`` and
    ``kzg_proofs``; PeerDAS, fulu): every sidecar of the segment through
    `validate_data_columns`, one `verify_cell_kzg_proof_batch`.  One
    invalid proof anywhere fails the segment, as in the reference."""
    sidecars = [s for block in blocks_sidecars for s in block]
    if sidecars and hasattr(sidecars[0], "column"):
        from lighthouse_tpu.chain.data_column_verification import (
            validate_data_columns,
        )

        return validate_data_columns(settings, sidecars)
    from lighthouse_tpu.chain.blob_verification import validate_blobs

    return validate_blobs(
        settings,
        [s.kzg_commitment for s in sidecars],
        [s.blob for s in sidecars],
        [s.kzg_proof for s in sidecars])


@dataclass
class PendingComponents:
    block: object | None = None
    blobs: dict[int, object] = field(default_factory=dict)  # index -> sidecar

    def num_expected(self) -> int | None:
        if self.block is None:
            return None
        body = self.block.message.body
        commitments = getattr(body, "blob_kzg_commitments", None)
        return 0 if commitments is None else len(commitments)


@dataclass
class Availability:
    """Either available (block + ordered blobs) or missing components."""

    block_root: bytes
    block: object | None = None
    blobs: list | None = None

    @property
    def is_available(self) -> bool:
        return self.block is not None


class DataAvailabilityChecker:
    def __init__(self, spec, capacity: int = 64):
        self.spec = spec
        self._pending: OrderedDict[bytes, PendingComponents] = OrderedDict()
        self.capacity = capacity

    def _entry(self, block_root: bytes) -> PendingComponents:
        entry = self._pending.get(block_root)
        if entry is None:
            entry = self._pending[block_root] = PendingComponents()
            while len(self._pending) > self.capacity:
                self._pending.popitem(last=False)  # LRU overflow
        else:
            self._pending.move_to_end(block_root)
        return entry

    def _check(self, block_root: bytes) -> Availability:
        entry = self._pending.get(block_root)
        if entry is None:
            return Availability(block_root)
        expected = entry.num_expected()
        if expected is None or len(entry.blobs) < expected:
            return Availability(block_root)
        blobs = [entry.blobs[i] for i in sorted(entry.blobs)][:expected]
        self._pending.pop(block_root, None)
        return Availability(block_root, entry.block, blobs)

    def put_verified_blobs(self, block_root: bytes, verified_blobs) -> Availability:
        """Record gossip/RPC-verified sidecars; returns availability."""
        entry = self._entry(block_root)
        for vb in verified_blobs:
            sidecar = getattr(vb, "sidecar", vb)
            entry.blobs[int(sidecar.index)] = sidecar
        return self._check(block_root)

    def put_pending_executed_block(self, block_root: bytes, block) -> Availability:
        """Record a fully-verified block awaiting its blobs."""
        entry = self._entry(block_root)
        entry.block = block
        return self._check(block_root)

    def has_block(self, block_root: bytes) -> bool:
        e = self._pending.get(block_root)
        return e is not None and e.block is not None

    def missing_blob_indices(self, block_root: bytes) -> list[int] | None:
        e = self._pending.get(block_root)
        if e is None or e.block is None:
            return None
        expected = e.num_expected() or 0
        return [i for i in range(expected) if i not in e.blobs]

    def prune_finalized(self, finalized_slot: int):
        for root in list(self._pending):
            e = self._pending[root]
            if e.block is not None and int(e.block.message.slot) < finalized_slot:
                del self._pending[root]

    def __len__(self) -> int:
        return len(self._pending)
