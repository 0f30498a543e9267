"""Data-column sidecar verification (PeerDAS, fulu).

Beside blob_verification.py: what consensus-specs
specs/fulu/p2p-interface.md asks of a DataColumnSidecar
(verify_data_column_sidecar, verify_data_column_sidecar_inclusion_proof,
verify_data_column_sidecar_kzg_proofs) and the segment form of the last,
`validate_data_columns`: structure sidecar by sidecar, then ONE
`das.verify_cell_kzg_proof_batch` for every cell of every sidecar, as the
reference's verify_kzg_for_data_column_list -> validate_data_columns does.
And a supernode's reconstruction, `reconstruct_data_columns` (the
reference's kzg_utils::reconstruct_data_columns).  Gossip topics, the
by-root and by-range protocols, custody and the reconstruction's place in
the availability checker are not here (ROADMAP.md Queue 2 A7).
"""

from __future__ import annotations

import hashlib

from lighthouse_tpu.common import tracing
from lighthouse_tpu.crypto import das, kzg
from lighthouse_tpu.state_transition.misc import is_valid_merkle_branch
from lighthouse_tpu.types.containers import make_types
from lighthouse_tpu.types.spec import ChainSpec

# BeaconBlockBody (deneb, electra, fulu): blob_kzg_commitments is field
# 11 of at most 16, so its generalized index is 16 + 11 and the proof has
# floorlog2(27) = KZG_COMMITMENTS_INCLUSION_PROOF_DEPTH = 4 siblings
_BODY_FIELDS = 16
_COMMITMENTS_FIELD_INDEX = 11


class DataColumnError(ValueError):
    def __init__(self, reason: str):
        self.reason = reason
        super().__init__(reason)


def verify_data_column_sidecar(sidecar, spec: ChainSpec) -> None:
    """The spec's verify_data_column_sidecar; raises DataColumnError with
    the first fault: the index is a column, the block has blobs and no
    more than the schedule allows at its slot, and the column, the
    commitments and the proofs are as many."""
    if int(sidecar.index) >= spec.number_of_columns:
        raise DataColumnError("invalid_column_index")
    blobs = len(sidecar.kzg_commitments)
    if blobs == 0:
        raise DataColumnError("no_commitments")
    epoch = spec.compute_epoch_at_slot(
        int(sidecar.signed_block_header.message.slot))
    if blobs > spec.max_blobs_per_block_at(epoch):
        raise DataColumnError("too_many_commitments")
    if len(sidecar.column) != blobs or len(sidecar.kzg_proofs) != blobs:
        raise DataColumnError("length_mismatch")


def compute_kzg_commitments_inclusion_proof(body) -> list[bytes]:
    """The four siblings of ``body.blob_kzg_commitments`` under the body
    root (what a proposer puts in every DataColumnSidecar of its block)."""
    nodes = [ftype.hash_tree_root(getattr(body, name))
             for name, ftype in type(body).fields.items()]
    nodes += [b"\x00" * 32] * (_BODY_FIELDS - len(nodes))
    branch, idx = [], _COMMITMENTS_FIELD_INDEX
    while len(nodes) > 1:
        branch.append(nodes[idx ^ 1])
        nodes = [hashlib.sha256(nodes[i] + nodes[i + 1]).digest()
                 for i in range(0, len(nodes), 2)]
        idx >>= 1
    return branch


def verify_data_column_sidecar_inclusion_proof(sidecar, spec: ChainSpec
                                               ) -> bool:
    """The sidecar's commitments are the ``blob_kzg_commitments`` of the
    body its header commits to."""
    depth = spec.preset.kzg_commitments_inclusion_proof_depth
    leaf = make_types(spec.preset).KzgCommitments.hash_tree_root(
        [bytes(c) for c in sidecar.kzg_commitments])
    return is_valid_merkle_branch(
        leaf,
        [bytes(b) for b in sidecar.kzg_commitments_inclusion_proof],
        depth, _COMMITMENTS_FIELD_INDEX,
        bytes(sidecar.signed_block_header.message.body_root))


def validate_data_columns(settings: kzg.KzgSettings, sidecars,
                          spec: ChainSpec | None = None) -> bool:
    """Batched KZG verification of data-column sidecars: each one's
    structure (a fault rejects before anything is dispatched), then every
    cell of every sidecar in one `verify_cell_kzg_proof_batch`, a sidecar
    after the other, so that a sidecar is a stretch of one cell index.
    One invalid cell anywhere fails them all."""
    spec = spec or ChainSpec.mainnet()
    commitments, cell_ids, cells, proofs = [], [], [], []
    with kzg.stage_span("das.validate", "validate", sidecars=len(sidecars)):
        for sidecar in sidecars:
            try:
                verify_data_column_sidecar(sidecar, spec)
            except DataColumnError:
                return False
            commitments += [bytes(c) for c in sidecar.kzg_commitments]
            cell_ids += [int(sidecar.index)] * len(sidecar.column)
            cells += [bytes(c) for c in sidecar.column]
            proofs += [bytes(p) for p in sidecar.kzg_proofs]
    return das.verify_cell_kzg_proof_batch(
        commitments, cell_ids, cells, proofs, settings)


def reconstruct_data_columns(settings: kzg.KzgSettings, sidecars,
                             spec: ChainSpec | None = None) -> list:
    """A supernode's reconstruction (consensus-specs fulu das-core.md,
    "Reconstruction and cross-seeding"; the reference's
    kzg_utils::reconstruct_data_columns): from at least half of one
    block's verified column sidecars, every missing one, in column order,
    each with its rebuilt cells and proofs and the header, commitments and
    inclusion proof of the sidecars given (as a sidecar of the type given).

    One `das.recover_cells_and_kzg_proofs_batch` over all of the block's
    blobs.  Raises KzgError as that does (fewer than half the columns, a
    repeated column, cells that are no codeword) and DataColumnError when
    the sidecars are not of one block.  Span ``das.reconstruct`` with
    ``blobs``, ``columns_in``, ``columns_out`` and ``path``."""
    spec = spec or ChainSpec.mainnet()
    n_cells, _ = das._cell_geometry(settings.width)
    held = sorted(int(s.index) for s in sidecars)
    missing = [c for c in range(min(n_cells, spec.number_of_columns))
               if c not in set(held)]
    template = sidecars[0]
    blobs = len(template.column)
    with tracing.span("das.reconstruct", blobs=blobs,
                      columns_in=len(sidecars), columns_out=len(missing),
                      path=das.recovery_path(settings)):
        header = template.signed_block_header
        root = header.message.hash_tree_root()
        commitments = [bytes(c) for c in template.kzg_commitments]
        for s in sidecars:
            if ((s.signed_block_header is not header
                 and s.signed_block_header.message.hash_tree_root() != root)
                    or [bytes(c) for c in s.kzg_commitments] != commitments
                    or len(s.column) != blobs):
                raise DataColumnError("sidecars_of_several_blocks")
        if not missing:
            return []
        ids = [int(s.index) for s in sidecars]
        cells, proofs = das.recover_cells_and_kzg_proofs_batch(
            ids, [[bytes(s.column[b]) for s in sidecars]
                  for b in range(blobs)], settings)
        with kzg.das_stage_span("das.sidecars", "sidecars",
                                sidecars=len(missing)):
            return [type(template)(
                index=c, column=[cells[b][c] for b in range(blobs)],
                kzg_commitments=list(template.kzg_commitments),
                kzg_proofs=[proofs[b][c] for b in range(blobs)],
                signed_block_header=template.signed_block_header,
                kzg_commitments_inclusion_proof=list(
                    template.kzg_commitments_inclusion_proof))
                for c in missing]
