"""Incremental tree-hash cache (milhouse-equivalent, TPU-first).

The reference keeps per-field merkle caches inside persistent tree
structures with structural sharing, so a state root after a block hashes
only the dirty subtrees (/root/reference/consensus/types/src/
beacon_state.rs:216-224,2031-2032 via the milhouse crate).

This rebuild reaches the same asymptotics a different way, chosen for the
columnar numpy state representation: every heavy field keeps a *snapshot*
of its leaf chunks plus the full interior tree, and an update

1. rebuilds the leaf chunks from the live columns (vectorized numpy,
   memory-bandwidth-bound),
2. vector-diffs them against the snapshot to recover the dirty-leaf
   worklist (the milhouse dirty-set, without interposing on mutation),
3. rehashes only the dirty paths, level by level, as ONE batched call per
   level (device-routed when the batch is large).

SHA-256 work per block therefore scales with the diff, not the state:
a 1M-validator state whose block touched k validators costs O(k·log n)
hashes plus an O(n) compare instead of O(n) hashes.  Full builds run as a
single fused device program (ops/sha256.fold_levels).
"""

from __future__ import annotations

import numpy as np

from lighthouse_tpu.common import tracing
from lighthouse_tpu.ops import sha256 as sha_ops
from lighthouse_tpu.ssz.core import _next_pow2

# a span whose duration feeds merkle_stage_seconds{stage}
_stage = sha_ops.merkle_stage_span

_ZERO = sha_ops.ZERO_HASH_WORDS  # uint32[depth+1, 8] ladder


def _COLS():
    """Registry column set — sourced from Validators so a new fork column
    is automatically snapshotted and diffed here."""
    from lighthouse_tpu.types.registry import Validators

    return Validators._COLUMNS


class IncrementalTree:
    """Merkle tree over uint32[n, 8] leaf chunks with dirty-path updates.

    Levels are stored padded to the power of two above the live leaf
    count; padded nodes hold the zero-subtree ladder constants, so every
    sibling lookup is in-array.  The virtual depth up to ``limit`` is
    climbed with ladder constants at root() time (log2(limit) host hashes).
    """

    __slots__ = ("limit", "n", "leaves", "levels")

    def __init__(self, leaves: np.ndarray, limit: int):
        self.limit = max(int(limit), 1)
        self._build(leaves)

    # -- construction ----------------------------------------------------

    def _build(self, leaves: np.ndarray) -> None:
        n = leaves.shape[0]
        if n > self.limit:
            raise ValueError(f"{n} leaves exceed limit {self.limit}")
        self.n = n
        pow2 = _next_pow2(max(n, 1))
        padded = np.zeros((pow2, 8), dtype=np.uint32)
        padded[:n] = leaves
        self.leaves = padded
        self.levels = sha_ops.fold_levels(padded)

    # -- updates ---------------------------------------------------------

    def update(self, new_leaves: np.ndarray,
               dirty: np.ndarray | None = None) -> None:
        """Re-root after mutation.  ``new_leaves`` is the full current leaf
        array; ``dirty`` optionally names the changed rows (skips the
        diff).  Shrinks trigger a full rebuild (rare: list truncation)."""
        n_new = new_leaves.shape[0]
        if n_new > self.limit:
            raise ValueError(f"{n_new} leaves exceed limit {self.limit}")
        if n_new < self.n:
            self._build(new_leaves)
            return
        pow2 = _next_pow2(max(n_new, 1))
        if pow2 != self.leaves.shape[0]:
            self._grow(pow2)

        with _stage("tree.diff", "diff"):
            if dirty is None:
                same = (self.leaves[: self.n]
                        == new_leaves[: self.n]).all(axis=1)
                dirty = np.nonzero(~same)[0]
            else:
                dirty = np.asarray(dirty, dtype=np.int64)
                dirty = dirty[dirty < self.n]
            if n_new > self.n:
                appended = np.arange(self.n, n_new, dtype=np.int64)
                dirty = np.concatenate([dirty, appended])
        if dirty.size == 0:
            self.n = n_new
            return

        with tracing.span("tree.update", dirty=int(dirty.size),
                          levels=len(self.levels)):
            with _stage("tree.level.scatter", "scatter", level=0):
                self.leaves[: n_new][dirty] = new_leaves[dirty]
            self.n = n_new

            level = self.leaves
            idx = dirty
            for k, nxt in enumerate(self.levels, start=1):
                with _stage("tree.level.gather", "gather", level=k):
                    idx = np.unique(idx >> 1)
                    pairs = np.empty((idx.shape[0], 16), dtype=np.uint32)
                    pairs[:, :8] = level[2 * idx]
                    pairs[:, 8:] = level[2 * idx + 1]
                hashed = sha_ops.batch_hash_pairs(pairs)
                with _stage("tree.level.scatter", "scatter", level=k):
                    nxt[idx] = hashed
                level = nxt

    def _grow(self, pow2: int) -> None:
        """Extend padded storage to a larger power of two; new regions are
        zero-subtree constants (real values arrive via dirty paths)."""
        old = self.leaves
        self.leaves = np.zeros((pow2, 8), dtype=np.uint32)
        self.leaves[: old.shape[0]] = old
        new_levels = []
        size = pow2 // 2
        k = 1
        for lv in self.levels:
            ext = np.broadcast_to(_ZERO[k], (size, 8)).copy()
            ext[: lv.shape[0]] = lv
            new_levels.append(ext)
            size //= 2
            k += 1
        while size >= 1:
            ext = np.broadcast_to(_ZERO[k], (size, 8)).copy()
            new_levels.append(ext)
            size //= 2
            k += 1
        self.levels = new_levels

    # -- roots -----------------------------------------------------------

    def root_words(self) -> np.ndarray:
        """uint32[8] root at the virtual ``limit`` depth."""
        depth = max(self.limit - 1, 0).bit_length()
        top = self.levels[-1][0] if self.levels else self.leaves[0]
        k = len(self.levels)
        node = top
        while k < depth:
            pair = np.concatenate([node, _ZERO[k]])[None, :]
            node = sha_ops.hash_pairs_np(pair)[0]
            k += 1
        return node

    def root(self) -> bytes:
        return sha_ops.words_to_bytes(self.root_words())


# ---------------------------------------------------------------------------
# Leaf-chunk builders (one per columnar SSZ type)
# ---------------------------------------------------------------------------

def _u64_leaves(arr: np.ndarray) -> np.ndarray:
    arr = np.asarray(arr, dtype=np.uint64)
    n = arr.shape[0]
    n_chunks = (n + 3) // 4
    padded = np.zeros(n_chunks * 4, dtype=np.uint64)
    padded[:n] = arr
    return (np.frombuffer(padded.astype("<u8").tobytes(), dtype=">u4")
            .astype(np.uint32).reshape(n_chunks, 8))


def _u8_leaves(arr: np.ndarray) -> np.ndarray:
    arr = np.asarray(arr, dtype=np.uint8)
    n = arr.shape[0]
    n_chunks = (n + 31) // 32
    padded = np.zeros(n_chunks * 32, dtype=np.uint8)
    padded[:n] = arr
    return (np.frombuffer(padded.tobytes(), dtype=">u4")
            .astype(np.uint32).reshape(n_chunks, 8))


def _roots_leaves(arr: np.ndarray) -> np.ndarray:
    arr = np.ascontiguousarray(arr, dtype=np.uint8)
    n = arr.shape[0]
    return (np.frombuffer(arr.tobytes(), dtype=">u4")
            .astype(np.uint32).reshape(n, 8))


class _FieldCache:
    """Incremental root for one flat columnar field."""

    __slots__ = ("tree", "mixin_len")

    def __init__(self, leaves, limit_chunks, mixin_len):
        self.tree = IncrementalTree(leaves, limit_chunks)
        self.mixin_len = mixin_len

    def root(self, leaves: np.ndarray, length: int | None) -> bytes:
        self.tree.update(leaves)
        r = self.tree.root()
        if self.mixin_len:
            r = sha_ops.mix_in_length(r, length)
        return r


class ValidatorsCache:
    """Incremental registry root: column-diff -> per-validator re-root.

    The expensive step for the registry is the 9 hashes per validator
    *element* root; the column snapshots find exactly which rows changed
    so only those rows re-root (batched), then the element-root tree
    updates along the dirty paths.
    """

    __slots__ = ("snap", "element_roots", "tree")

    # single source of truth for the column set: Validators._COLUMNS
    # (a new fork column added there is automatically diffed here)

    def __init__(self, typ, validators):
        self.snap = {c: getattr(validators, c).copy() for c in _COLS()}
        # np.array: batch_roots may hand back a read-only device transfer
        self.element_roots = np.array(typ.batch_roots(validators))
        self.tree = IncrementalTree(self.element_roots, typ.limit)

    def _dirty_rows(self, v) -> np.ndarray:
        n_old = self.snap["effective_balance"].shape[0]
        n_new = len(v)
        m = min(n_old, n_new)
        changed = np.zeros(m, dtype=bool)
        for c in _COLS():
            new, old = getattr(v, c), self.snap[c]
            d = new[:m] != old[:m]
            changed |= d.any(axis=1) if d.ndim == 2 else d
        return np.nonzero(changed)[0]

    def root(self, typ, validators) -> bytes:
        n_old = self.snap["effective_balance"].shape[0]
        n_new = len(validators)
        if n_new < n_old:
            self.__init__(typ, validators)  # shrink: rebuild (never in spec)
        else:
            with _stage("tree.validators.diff", "diff"):
                dirty = self._dirty_rows(validators)
                appended = np.arange(n_old, n_new, dtype=np.int64)
                rows = np.concatenate([dirty, appended])
            if rows.size:
                with _stage("tree.validators.slice", "slice",
                            rows=int(rows.size)):
                    sub = _slice_validators(validators, rows)
                # a parent only: its time is its children's (the sha.*
                # stages of the one fused call; under the device-routing
                # row count tree.leaves and the four levels' sha.* stages)
                with tracing.span("tree.validators.element_roots",
                                  rows=int(rows.size)):
                    new_roots = typ.batch_roots(sub)
                with _stage("tree.validators.snapshot", "snapshot"):
                    if n_new > n_old:
                        grown = np.zeros((n_new, 8), dtype=np.uint32)
                        grown[:n_old] = self.element_roots
                        self.element_roots = grown
                        for c in _COLS():
                            col = getattr(validators, c)
                            self.snap[c] = np.concatenate(
                                [self.snap[c], col[n_old:n_new].copy()])
                    self.element_roots[rows] = new_roots
                    for c in _COLS():
                        self.snap[c][dirty] = getattr(validators, c)[dirty]
                self.tree.update(self.element_roots, dirty=rows)
        r = self.tree.root()
        return sha_ops.mix_in_length(r, n_new)


def _slice_validators(v, rows: np.ndarray):
    """Row-subset view with the Validators column interface."""
    from lighthouse_tpu.types.registry import Validators

    out = Validators(0)
    out._n = int(rows.shape[0])
    for c in _COLS():
        setattr(out, "_" + c, getattr(v, c)[rows])
    return out


# ---------------------------------------------------------------------------
# Whole-state cache
# ---------------------------------------------------------------------------

class StateTreeCache:
    """Per-state field-root cache: heavy columnar fields update
    incrementally, small fields recompute (they are O(1))."""

    def __init__(self):
        self.fields: dict[str, object] = {}

    def field_root(self, fname: str, ftype, value) -> bytes:
        with tracing.span("tree.field", field=fname):
            return self._field_root(fname, ftype, value)

    def _field_root(self, fname: str, ftype, value) -> bytes:
        from lighthouse_tpu.types import registry as reg

        if isinstance(ftype, reg.ValidatorRegistryType):
            c = self.fields.get(fname)
            if c is None:
                c = self.fields[fname] = ValidatorsCache(ftype, value)
            return c.root(ftype, value)

        build = None
        length = None
        mixin = False
        if isinstance(ftype, reg.U64List):
            build, length, mixin = _u64_leaves, len(value), True
            limit = (ftype.limit * 8 + 31) // 32
        elif isinstance(ftype, reg.U64Vector):
            build, limit = _u64_leaves, (ftype.length * 8 + 31) // 32
        elif isinstance(ftype, reg.U8List):
            build, length, mixin = _u8_leaves, len(value), True
            limit = (ftype.limit + 31) // 32
        elif isinstance(ftype, reg.RootsVector):
            build, limit = _roots_leaves, ftype.length
            value = ftype._as_array(value)
        elif isinstance(ftype, reg.RootsList):
            arr = ftype._as_array(value)
            build, length, mixin = _roots_leaves, arr.shape[0], True
            limit = ftype.limit
            value = arr
        else:
            return ftype.hash_tree_root(value)

        with _stage("tree.leaves", "leaves"):
            leaves = build(value)
        c = self.fields.get(fname)
        if c is None:
            c = self.fields[fname] = _FieldCache(leaves, limit, mixin)
            r = c.tree.root()
            return sha_ops.mix_in_length(r, length) if mixin else r
        return c.root(leaves, length)

    def state_root(self, state) -> bytes:
        cls = type(state)
        roots = b"".join(
            self.field_root(fname, ftype, getattr(state, fname))
            for fname, ftype in cls.fields.items()
        )
        return sha_ops.merkleize(roots, len(cls.fields))


def enable_tree_cache(state) -> None:
    """Attach an incremental cache; copies of the state deep-copy it, so
    child states keep the parent's tree as their diff baseline."""
    if getattr(state, "_tree_cache", None) is None:
        state._tree_cache = StateTreeCache()


__all__ = [
    "IncrementalTree",
    "StateTreeCache",
    "ValidatorsCache",
    "enable_tree_cache",
]
