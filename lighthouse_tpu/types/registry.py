"""Columnar (struct-of-arrays) state collections.

The reference reaches ~1M validators by wrapping every list in persistent
tree structures with interior hash caches (milhouse "tree-states",
/root/reference/consensus/types/src/beacon_state.rs:216-224).  A TPU-native
design inverts that: the validator registry, balances, participation flags
and inactivity scores live as flat numpy columns, so

- epoch processing is vectorized arithmetic over whole columns (one fused
  XLA program instead of a per-validator walk, reference single_pass.rs);
- merkleization builds all leaf chunks with numpy reshapes and runs the
  whole forest through the batched SHA-256 device kernel.

Object views (`Validator` containers) are materialized only at the API
boundary.
"""

from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np

from lighthouse_tpu.common.metrics import REGISTRY
from lighthouse_tpu.ops import sha256 as sha_ops
from lighthouse_tpu.ssz import core as ssz_core
from lighthouse_tpu.ssz.core import SSZType, _batch_merkleize_subtrees


def _u64_chunks(arr: np.ndarray) -> np.ndarray:
    """uint64[N] -> uint32[N, 8] SSZ chunk words (LE value, BE word order)."""
    n = arr.shape[0]
    chunk = np.zeros((n, 32), dtype=np.uint8)
    chunk[:, :8] = arr.astype("<u8").view(np.uint8).reshape(n, 8)
    return np.frombuffer(chunk.tobytes(), dtype=">u4").astype(np.uint32).reshape(n, 8)


def _bytes_col_chunks(col: np.ndarray, width: int) -> np.ndarray:
    """uint8[N, width<=32] -> uint32[N, 8] chunk words."""
    n = col.shape[0]
    chunk = np.zeros((n, 32), dtype=np.uint8)
    chunk[:, :width] = col
    return np.frombuffer(chunk.tobytes(), dtype=">u4").astype(np.uint32).reshape(n, 8)


def _pack_bytes_to_chunk_words(data: bytes, n_chunks: int) -> np.ndarray:
    buf = np.zeros(n_chunks * 32, dtype=np.uint8)
    raw = np.frombuffer(data, dtype=np.uint8)
    buf[: raw.shape[0]] = raw
    return np.frombuffer(buf.tobytes(), dtype=">u4").astype(np.uint32).reshape(n_chunks, 8)


class U64List(SSZType):
    """SSZ List[uint64, limit] stored as a numpy uint64 column."""

    def __init__(self, limit: int):
        self.limit = limit
        self.fixed_size = None

    def _as_array(self, value) -> np.ndarray:
        arr = np.asarray(value, dtype=np.uint64)
        if arr.ndim != 1:
            raise ValueError("U64List expects a 1-D sequence")
        if arr.shape[0] > self.limit:
            raise ValueError(f"U64List over limit {self.limit}")
        return arr

    def serialize(self, value) -> bytes:
        return self._as_array(value).astype("<u8").tobytes()

    def deserialize(self, data: bytes) -> np.ndarray:
        if len(data) % 8:
            raise ValueError("u64 list misalignment")
        arr = np.frombuffer(data, dtype="<u8").astype(np.uint64)
        if arr.shape[0] > self.limit:
            raise ValueError("U64List over limit")
        return arr

    def chunk_count(self) -> int:
        return (self.limit * 8 + 31) // 32

    def hash_tree_root(self, value) -> bytes:
        arr = self._as_array(value)
        n = arr.shape[0]
        n_chunks = (n + 3) // 4
        padded = np.zeros(n_chunks * 4, dtype=np.uint64)
        padded[:n] = arr
        raw = padded.astype("<u8").tobytes()
        words = np.frombuffer(raw, dtype=">u4").astype(np.uint32).reshape(n_chunks, 8)
        root = sha_ops.merkleize_words(words, self.chunk_count())
        return sha_ops.mix_in_length(sha_ops.words_to_bytes(root), n)

    def default(self) -> np.ndarray:
        return np.zeros(0, dtype=np.uint64)

    def __repr__(self):
        return f"U64List[{self.limit}]"


class U64Vector(SSZType):
    """SSZ Vector[uint64, length] as a numpy column (e.g. slashings)."""

    def __init__(self, length: int):
        self.length = length
        self.fixed_size = 8 * length

    def serialize(self, value) -> bytes:
        arr = np.asarray(value, dtype=np.uint64)
        if arr.shape != (self.length,):
            raise ValueError(f"U64Vector length {self.length} mismatch")
        return arr.astype("<u8").tobytes()

    def deserialize(self, data: bytes) -> np.ndarray:
        if len(data) != self.fixed_size:
            raise ValueError("U64Vector size mismatch")
        return np.frombuffer(data, dtype="<u8").astype(np.uint64)

    def chunk_count(self) -> int:
        return (self.length * 8 + 31) // 32

    def hash_tree_root(self, value) -> bytes:
        arr = np.asarray(value, dtype=np.uint64)
        n_chunks = self.chunk_count()
        padded = np.zeros(n_chunks * 4, dtype=np.uint64)
        padded[: arr.shape[0]] = arr
        raw = padded.astype("<u8").tobytes()
        words = np.frombuffer(raw, dtype=">u4").astype(np.uint32).reshape(n_chunks, 8)
        return sha_ops.words_to_bytes(sha_ops.merkleize_words(words, n_chunks))

    def default(self) -> np.ndarray:
        return np.zeros(self.length, dtype=np.uint64)

    def __repr__(self):
        return f"U64Vector[{self.length}]"


class U8List(SSZType):
    """SSZ List[uint8, limit] as a numpy column (participation flags)."""

    def __init__(self, limit: int):
        self.limit = limit
        self.fixed_size = None

    def serialize(self, value) -> bytes:
        arr = np.asarray(value, dtype=np.uint8)
        if arr.shape[0] > self.limit:
            raise ValueError("U8List over limit")
        return arr.tobytes()

    def deserialize(self, data: bytes) -> np.ndarray:
        if len(data) > self.limit:
            raise ValueError("U8List over limit")
        return np.frombuffer(data, dtype=np.uint8).copy()

    def chunk_count(self) -> int:
        return (self.limit + 31) // 32

    def hash_tree_root(self, value) -> bytes:
        arr = np.asarray(value, dtype=np.uint8)
        n = arr.shape[0]
        n_chunks = max((n + 31) // 32, 1) if n else 0
        words = _pack_bytes_to_chunk_words(arr.tobytes(), n_chunks) if n else np.zeros((0, 8), np.uint32)
        root = sha_ops.merkleize_words(words, self.chunk_count())
        return sha_ops.mix_in_length(sha_ops.words_to_bytes(root), n)

    def default(self) -> np.ndarray:
        return np.zeros(0, dtype=np.uint8)

    def __repr__(self):
        return f"U8List[{self.limit}]"


class RootsVector(SSZType):
    """SSZ Vector[Bytes32, length] as uint8[length, 32] (block/state roots,
    randao mixes)."""

    def __init__(self, length: int):
        self.length = length
        self.fixed_size = 32 * length

    def serialize(self, value) -> bytes:
        arr = self._as_array(value)
        return arr.tobytes()

    def _as_array(self, value) -> np.ndarray:
        if isinstance(value, np.ndarray):
            arr = value
        else:
            arr = np.frombuffer(b"".join(value), dtype=np.uint8).reshape(-1, 32)
        if arr.shape != (self.length, 32):
            raise ValueError(f"RootsVector shape {arr.shape} != ({self.length}, 32)")
        return np.ascontiguousarray(arr, dtype=np.uint8)

    def deserialize(self, data: bytes) -> np.ndarray:
        if len(data) != self.fixed_size:
            raise ValueError("RootsVector size mismatch")
        return np.frombuffer(data, dtype=np.uint8).reshape(self.length, 32).copy()

    def chunk_count(self) -> int:
        return self.length

    def hash_tree_root(self, value) -> bytes:
        arr = self._as_array(value)
        words = np.frombuffer(arr.tobytes(), dtype=">u4").astype(np.uint32).reshape(self.length, 8)
        return sha_ops.words_to_bytes(sha_ops.merkleize_words(words, self.length))

    def default(self) -> np.ndarray:
        return np.zeros((self.length, 32), dtype=np.uint8)

    def __repr__(self):
        return f"RootsVector[{self.length}]"


class RootsList(SSZType):
    """SSZ List[Bytes32, limit] as uint8[n, 32] (historical roots, etc.)."""

    def __init__(self, limit: int):
        self.limit = limit
        self.fixed_size = None

    def _as_array(self, value) -> np.ndarray:
        if isinstance(value, np.ndarray):
            arr = value.reshape(-1, 32)
        elif len(value) == 0:
            arr = np.zeros((0, 32), dtype=np.uint8)
        else:
            arr = np.frombuffer(b"".join(value), dtype=np.uint8).reshape(-1, 32)
        if arr.shape[0] > self.limit:
            raise ValueError("RootsList over limit")
        return np.ascontiguousarray(arr, dtype=np.uint8)

    def serialize(self, value) -> bytes:
        return self._as_array(value).tobytes()

    def deserialize(self, data: bytes) -> np.ndarray:
        if len(data) % 32:
            raise ValueError("RootsList misalignment")
        return np.frombuffer(data, dtype=np.uint8).reshape(-1, 32).copy()

    def chunk_count(self) -> int:
        return self.limit

    def hash_tree_root(self, value) -> bytes:
        arr = self._as_array(value)
        n = arr.shape[0]
        words = (
            np.frombuffer(arr.tobytes(), dtype=">u4").astype(np.uint32).reshape(n, 8)
            if n
            else np.zeros((0, 8), np.uint32)
        )
        root = sha_ops.merkleize_words(words, self.limit)
        return sha_ops.mix_in_length(sha_ops.words_to_bytes(root), n)

    def default(self) -> np.ndarray:
        return np.zeros((0, 32), dtype=np.uint8)

    def __repr__(self):
        return f"RootsList[{self.limit}]"


# ---------------------------------------------------------------------------
# Validator registry
# ---------------------------------------------------------------------------

_VALIDATOR_RECORD_SIZE = 48 + 32 + 8 + 1 + 8 * 4  # = 121 bytes, SSZ field order


class Validators:
    """Columnar validator registry (mutable, numpy-backed).

    Columns are views into capacity-doubled backing arrays so `append`
    (one per deposit) is amortized O(1) — a deposit flood grows the
    registry linearly, not quadratically.  Element and mask writes go
    through the views; whole-column replacement uses the setters.
    """

    _COLUMNS = (
        "pubkeys",
        "withdrawal_credentials",
        "effective_balance",
        "slashed",
        "activation_eligibility_epoch",
        "activation_epoch",
        "exit_epoch",
        "withdrawable_epoch",
    )

    __slots__ = tuple("_" + c for c in _COLUMNS) + ("_n",)

    def __init__(self, n: int = 0):
        self._n = n
        self._pubkeys = np.zeros((n, 48), dtype=np.uint8)
        self._withdrawal_credentials = np.zeros((n, 32), dtype=np.uint8)
        self._effective_balance = np.zeros(n, dtype=np.uint64)
        self._slashed = np.zeros(n, dtype=bool)
        self._activation_eligibility_epoch = np.zeros(n, dtype=np.uint64)
        self._activation_epoch = np.zeros(n, dtype=np.uint64)
        self._exit_epoch = np.zeros(n, dtype=np.uint64)
        self._withdrawable_epoch = np.zeros(n, dtype=np.uint64)

    def __len__(self) -> int:
        return self._n

    def columns(self) -> list[np.ndarray]:
        """The column views in ``_COLUMNS`` order — SSZ field order, the
        argument order of ``sha256.validator_roots``."""
        return [getattr(self, c) for c in self._COLUMNS]

    def _grow_to(self, cap: int) -> None:
        for c in self._COLUMNS:
            backing = getattr(self, "_" + c)
            shape = (cap,) + backing.shape[1:]
            new = np.zeros(shape, dtype=backing.dtype)
            new[: self._n] = backing[: self._n]
            setattr(self, "_" + c, new)

    def append(
        self,
        *,
        pubkey: bytes,
        withdrawal_credentials: bytes,
        effective_balance: int,
        slashed: bool = False,
        activation_eligibility_epoch: int,
        activation_epoch: int,
        exit_epoch: int,
        withdrawable_epoch: int,
    ) -> None:
        if self._n == self._effective_balance.shape[0]:
            self._grow_to(max(64, 2 * self._n))
        i = self._n
        self._pubkeys[i] = np.frombuffer(pubkey, dtype=np.uint8)
        self._withdrawal_credentials[i] = np.frombuffer(
            withdrawal_credentials, dtype=np.uint8)
        self._effective_balance[i] = effective_balance
        self._slashed[i] = bool(slashed)
        self._activation_eligibility_epoch[i] = activation_eligibility_epoch
        self._activation_epoch[i] = activation_epoch
        self._exit_epoch[i] = exit_epoch
        self._withdrawable_epoch[i] = withdrawable_epoch
        self._n = i + 1

    def copy(self) -> "Validators":
        out = Validators(0)
        out._n = self._n
        for c in self._COLUMNS:
            setattr(out, "_" + c, getattr(self, c).copy())
        return out

    def __eq__(self, other) -> bool:
        return isinstance(other, Validators) and all(
            np.array_equal(getattr(self, f), getattr(other, f))
            for f in self._COLUMNS
        )

    def is_active(self, epoch: int) -> np.ndarray:
        e = np.uint64(epoch)
        return (self.activation_epoch <= e) & (e < self.exit_epoch)

    # Column views (length-n windows over the capacity arrays) are added
    # below the class body via _install_column_views().

    def is_eligible_for_activation_queue(self, max_effective_balance: int) -> np.ndarray:
        from lighthouse_tpu.types.spec import FAR_FUTURE_EPOCH

        return (self.activation_eligibility_epoch == np.uint64(FAR_FUTURE_EPOCH)) & (
            self.effective_balance == np.uint64(max_effective_balance)
        )

    def is_slashable(self, epoch: int) -> np.ndarray:
        e = np.uint64(epoch)
        return (
            ~self.slashed
            & (self.activation_epoch <= e)
            & (e < self.withdrawable_epoch)
        )


def _install_column_views() -> None:
    def make(col: str) -> property:
        backing = "_" + col

        def get(self):
            return getattr(self, backing)[: self._n]

        def set_(self, value):
            view = getattr(self, backing)[: self._n]
            arr = np.asarray(value, dtype=view.dtype)
            if arr.shape != view.shape:
                raise ValueError(
                    f"{col}: column assignment must keep shape {view.shape}, "
                    f"got {arr.shape}")
            view[...] = arr

        return property(get, set_)

    for c in Validators._COLUMNS:
        setattr(Validators, c, make(c))


_install_column_views()


class ValidatorRegistryType(SSZType):
    """SSZ List[Validator, limit] over the columnar `Validators` store."""

    def __init__(self, limit: int, validator_container=None):
        self.limit = limit
        self.fixed_size = None
        self.validator_container = validator_container  # object-view class

    def serialize(self, value: Validators) -> bytes:
        n = len(value)
        rec = np.zeros((n, _VALIDATOR_RECORD_SIZE), dtype=np.uint8)
        rec[:, 0:48] = value.pubkeys
        rec[:, 48:80] = value.withdrawal_credentials
        rec[:, 80:88] = value.effective_balance.astype("<u8").view(np.uint8).reshape(n, 8)
        rec[:, 88] = value.slashed.astype(np.uint8)
        off = 89
        for col in (
            value.activation_eligibility_epoch,
            value.activation_epoch,
            value.exit_epoch,
            value.withdrawable_epoch,
        ):
            rec[:, off: off + 8] = col.astype("<u8").view(np.uint8).reshape(n, 8)
            off += 8
        return rec.tobytes()

    def deserialize(self, data: bytes) -> Validators:
        if len(data) % _VALIDATOR_RECORD_SIZE:
            raise ValueError("validator record misalignment")
        n = len(data) // _VALIDATOR_RECORD_SIZE
        if n > self.limit:
            raise ValueError("registry over limit")
        rec = np.frombuffer(data, dtype=np.uint8).reshape(n, _VALIDATOR_RECORD_SIZE)
        out = Validators(n)
        out.pubkeys = rec[:, 0:48].copy()
        out.withdrawal_credentials = rec[:, 48:80].copy()
        out.effective_balance = rec[:, 80:88].copy().view("<u8").reshape(n).astype(np.uint64)
        bad = rec[:, 88] > 1
        if bad.any():
            raise ValueError("invalid slashed boolean")
        out.slashed = rec[:, 88] == 1
        off = 89
        for name in (
            "activation_eligibility_epoch",
            "activation_epoch",
            "exit_epoch",
            "withdrawable_epoch",
        ):
            setattr(out, name, rec[:, off: off + 8].copy().view("<u8").reshape(n).astype(np.uint64))
            off += 8
        return out

    def chunk_count(self) -> int:
        return self.limit

    def batch_roots(self, value: Validators) -> np.ndarray:
        """All validator roots as one lockstep merkleization: from the
        device-routing row count up, one device program fed the raw columns
        (``sha256.validator_roots``); under it, chunk words built on the
        host and hashed level by level."""
        n = len(value)
        if n == 0:
            return np.zeros((0, 8), dtype=np.uint32)
        fused = n >= sha_ops._DEVICE_MIN_PAIRS
        REGISTRY.counter(
            "validator_roots_total",
            "validator element roots computed, by path: fused = one device "
            "program from the columns, host = chunk words built on the host",
        ).labels(path="fused" if fused else "host").inc(n)
        if fused:
            return sha_ops.validator_roots(value.columns())
        # pubkey (48B) root needs one pre-hash of its 2 chunks
        with sha_ops.merkle_stage_span("tree.leaves", "leaves", rows=n):
            pk = np.zeros((n, 64), dtype=np.uint8)
            pk[:, :48] = value.pubkeys
            pk_pairs = np.frombuffer(pk.tobytes(), dtype=">u4").astype(np.uint32).reshape(n, 16)
        pk_roots = sha_ops.batch_hash_pairs(pk_pairs)
        with sha_ops.merkle_stage_span("tree.leaves", "leaves", rows=n):
            leaves = np.zeros((n, 8, 8), dtype=np.uint32)
            leaves[:, 0] = pk_roots
            leaves[:, 1] = _bytes_col_chunks(value.withdrawal_credentials, 32)
            leaves[:, 2] = _u64_chunks(value.effective_balance)
            leaves[:, 3] = _bytes_col_chunks(
                value.slashed.astype(np.uint8).reshape(n, 1), 1
            )
            leaves[:, 4] = _u64_chunks(value.activation_eligibility_epoch)
            leaves[:, 5] = _u64_chunks(value.activation_epoch)
            leaves[:, 6] = _u64_chunks(value.exit_epoch)
            leaves[:, 7] = _u64_chunks(value.withdrawable_epoch)
        return _batch_merkleize_subtrees(leaves)

    def hash_tree_root(self, value: Validators) -> bytes:
        roots = self.batch_roots(value)
        root = sha_ops.merkleize_words(roots, self.limit)
        return sha_ops.mix_in_length(sha_ops.words_to_bytes(root), len(value))

    def default(self) -> Validators:
        return Validators(0)

    def __repr__(self):
        return f"ValidatorRegistry[{self.limit}]"
