"""Plain SSZ merkleization of a capella ``BeaconState`` with hashlib.

The state is a dict of plain values (ints, bytes, numpy columns, dicts,
lists), as ``traffic.epoch_state.plain_state`` reads them from the
pre-state before the window.  Every hash is one ``hashlib.sha256`` call
on 64 bytes; nothing is cached between roots and nothing of the program
is imported.  Sizes come from the configuration file (``preset`` group).
"""

from __future__ import annotations

import hashlib

import numpy as np

_sha = hashlib.sha256
ZERO = [b"\x00" * 32]
for _ in range(64):
    ZERO.append(_sha(ZERO[-1] * 2).digest())


def _level(data) -> bytes:
    mv = memoryview(data)
    return b"".join([_sha(mv[i:i + 64]).digest()
                     for i in range(0, len(mv), 64)])


def merkleize(chunks: bytes, limit: int | None = None) -> bytes:
    n = len(chunks) // 32
    limit = n if limit is None else limit
    if n > limit:
        raise ValueError("more chunks than the limit")
    depth = max(limit - 1, 0).bit_length()
    if n == 0:
        return ZERO[depth]
    level = bytes(chunks)
    for d in range(depth):
        if (len(level) // 32) % 2:
            level += ZERO[d]
        level = _level(level)
    return level


def mix_in_length(root: bytes, n: int) -> bytes:
    return _sha(root + n.to_bytes(32, "little")).digest()


def _pad(b: bytes) -> bytes:
    return b + b"\x00" * (-len(b) % 32)


def u64(v) -> bytes:
    return int(v).to_bytes(8, "little") + b"\x00" * 24


def bytes_n(b: bytes) -> bytes:
    return merkleize(_pad(bytes(b))) if len(b) > 32 else _pad(bytes(b))


def container(field_roots) -> bytes:
    return merkleize(b"".join(field_roots))


def roots_vector(arr: np.ndarray, length: int) -> bytes:
    if arr.shape[0] != length:
        raise ValueError("vector length")
    return merkleize(np.ascontiguousarray(arr, np.uint8).tobytes(), length)


def roots_list(arr: np.ndarray, limit: int) -> bytes:
    n = arr.shape[0]
    return mix_in_length(
        merkleize(np.ascontiguousarray(arr, np.uint8).tobytes(), limit), n)


def basic_list(arr: np.ndarray, dtype: str, limit: int) -> bytes:
    """List[uintN, limit] of a numpy column."""
    a = np.ascontiguousarray(arr).astype(dtype)
    chunks = (limit * a.dtype.itemsize + 31) // 32
    return mix_in_length(merkleize(_pad(a.tobytes()), chunks), a.shape[0])


def u64_vector(arr: np.ndarray, length: int) -> bytes:
    if arr.shape[0] != length:
        raise ValueError("vector length")
    return merkleize(_pad(np.ascontiguousarray(arr).astype("<u8").tobytes()),
                     (length * 8 + 31) // 32)


def validators(v: dict, limit: int) -> bytes:
    """List[Validator, limit] over the registry's columns: eight leaves a
    validator (the pubkey's is the hash of its two chunks), three levels
    to the element root, then the list tree."""
    n = v["effective_balance"].shape[0]
    pk = np.zeros((n, 64), np.uint8)
    pk[:, :48] = v["pubkeys"]
    leaves = np.zeros((n, 8, 32), np.uint8)
    leaves[:, 0] = np.frombuffer(_level(pk.tobytes()), np.uint8).reshape(n, 32)
    leaves[:, 1] = v["withdrawal_credentials"]
    for k, col in ((2, "effective_balance"), (4, "activation_eligibility_epoch"),
                   (5, "activation_epoch"), (6, "exit_epoch"),
                   (7, "withdrawable_epoch")):
        leaves[:, k, :8] = np.ascontiguousarray(
            v[col]).astype("<u8").view(np.uint8).reshape(n, 8)
    leaves[:, 3, 0] = v["slashed"].astype(np.uint8)
    level = leaves.tobytes()
    for _ in range(3):
        level = _level(level)
    return mix_in_length(merkleize(level, limit), n)


def _fork(f):
    return container([bytes_n(f["previous_version"]),
                      bytes_n(f["current_version"]), u64(f["epoch"])])


def header(h):
    return container([u64(h["slot"]), u64(h["proposer_index"]),
                      bytes_n(h["parent_root"]), bytes_n(h["state_root"]),
                      bytes_n(h["body_root"])])


def _eth1(e):
    return container([bytes_n(e["deposit_root"]), u64(e["deposit_count"]),
                      bytes_n(e["block_hash"])])


def _checkpoint(c):
    return container([u64(c["epoch"]), bytes_n(c["root"])])


def _sync_committee(c, size):
    if len(c["pubkeys"]) != size:
        raise ValueError("sync committee size")
    return container([merkleize(b"".join(bytes_n(p) for p in c["pubkeys"]), size),
                      bytes_n(c["aggregate_pubkey"])])


def _payload_header(h, max_extra):
    extra = bytes(h["extra_data"])
    return container([
        bytes_n(h["parent_hash"]), bytes_n(h["fee_recipient"]),
        bytes_n(h["state_root"]), bytes_n(h["receipts_root"]),
        bytes_n(h["logs_bloom"]), bytes_n(h["prev_randao"]),
        u64(h["block_number"]), u64(h["gas_limit"]), u64(h["gas_used"]),
        u64(h["timestamp"]),
        mix_in_length(merkleize(_pad(extra), (max_extra + 31) // 32), len(extra)),
        int(h["base_fee_per_gas"]).to_bytes(32, "little"),
        bytes_n(h["block_hash"]), bytes_n(h["transactions_root"]),
        bytes_n(h["withdrawals_root"])])


def _list_of(items, root_of, limit):
    return mix_in_length(
        merkleize(b"".join(root_of(i) for i in items), limit), len(items))


def state_root(s: dict, preset: dict) -> bytes:
    """hash_tree_root(BeaconState) at capella, field by field."""
    p = preset
    reg = p["VALIDATOR_REGISTRY_LIMIT"]
    bits = sum(1 << i for i, b in enumerate(s["justification_bits"]) if b)
    roots = [
        u64(s["genesis_time"]), bytes_n(s["genesis_validators_root"]),
        u64(s["slot"]), _fork(s["fork"]), header(s["latest_block_header"]),
        roots_vector(s["block_roots"], p["SLOTS_PER_HISTORICAL_ROOT"]),
        roots_vector(s["state_roots"], p["SLOTS_PER_HISTORICAL_ROOT"]),
        roots_list(s["historical_roots"], p["HISTORICAL_ROOTS_LIMIT"]),
        _eth1(s["eth1_data"]),
        _list_of(s["eth1_data_votes"], _eth1,
                 p["EPOCHS_PER_ETH1_VOTING_PERIOD"] * p["SLOTS_PER_EPOCH"]),
        u64(s["eth1_deposit_index"]),
        validators(s["validators"], reg),
        basic_list(s["balances"], "<u8", reg),
        roots_vector(s["randao_mixes"], p["EPOCHS_PER_HISTORICAL_VECTOR"]),
        u64_vector(s["slashings"], p["EPOCHS_PER_SLASHINGS_VECTOR"]),
        basic_list(s["previous_epoch_participation"], "u1", reg),
        basic_list(s["current_epoch_participation"], "u1", reg),
        _pad(bytes([bits])),
        _checkpoint(s["previous_justified_checkpoint"]),
        _checkpoint(s["current_justified_checkpoint"]),
        _checkpoint(s["finalized_checkpoint"]),
        basic_list(s["inactivity_scores"], "<u8", reg),
        _sync_committee(s["current_sync_committee"], p["SYNC_COMMITTEE_SIZE"]),
        _sync_committee(s["next_sync_committee"], p["SYNC_COMMITTEE_SIZE"]),
        _payload_header(s["latest_execution_payload_header"],
                        p["MAX_EXTRA_DATA_BYTES"]),
        u64(s["next_withdrawal_index"]),
        u64(s["next_withdrawal_validator_index"]),
        _list_of(s["historical_summaries"],
                 lambda h: container([bytes_n(h["block_summary_root"]),
                                      bytes_n(h["state_summary_root"])]),
                 p["HISTORICAL_ROOTS_LIMIT"]),
    ]
    return merkleize(b"".join(roots))
