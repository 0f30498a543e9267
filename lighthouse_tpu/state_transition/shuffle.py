"""Swap-or-not shuffle (spec `compute_shuffled_index` / full-list shuffle).

Reference: /root/reference/consensus/swap_or_not_shuffle (scalar Rust).
TPU-first design: the full-list shuffle is vectorized — each of the 90
rounds operates on ALL indices at once (and the per-round "source"
bytes are produced by one batched hash sweep), instead of the
reference's per-index loop.  This is the committee-shuffling hot path
for ~1M validators.

Two vectorized rungs behind the same seam as the epoch pass
(LHTPU_EPOCH_BACKEND / LHTPU_EPOCH_DEVICE_MIN):

- **host** (numpy + hashlib): the default below the device threshold;
- **device** (:func:`shuffle_list_device`): ALL ``rounds × chunks``
  source hashes ride ops/sha256's batched single-block kernel in ONE
  sweep instead of 90 hashlib loops, and the 90 swap-or-not rounds run
  as one jitted ``lax.fori_loop`` over every position at once
  (ops/epoch_kernels.shuffle_rounds_device, pow2 position buckets with
  discarded tail lanes).  Faults fall back to the host path through the
  epoch supervisor's fault counter — callers always get the spec
  permutation.
"""

from __future__ import annotations

import hashlib

import numpy as np

from lighthouse_tpu.ops.faults import PROGRAM_FAULTS


def compute_shuffled_index(index: int, count: int, seed: bytes, rounds: int) -> int:
    """Single-index forward shuffle (spec semantics, scalar)."""
    assert index < count
    for r in range(rounds):
        pivot = int.from_bytes(
            hashlib.sha256(seed + bytes([r])).digest()[:8], "little"
        ) % count
        flip = (pivot + count - index) % count
        position = max(index, flip)
        source = hashlib.sha256(
            seed + bytes([r]) + (position // 256).to_bytes(4, "little")
        ).digest()
        byte = source[(position % 256) // 8]
        if (byte >> (position % 8)) & 1:
            index = flip
    return index


def _shuffle_hash_sweep(seed: bytes, rounds: int, count: int,
                        device: bool | None = None):
    """All per-round pivots and source bytes in one batched sweep.

    Returns (pivots int64[rounds], src uint8[rounds, n_chunks * 32])
    where ``src[r][p >> 3]`` holds position p's decision byte for round
    r — the layout both the numpy and the device round loops consume.
    """
    from lighthouse_tpu.ops import sha256 as sha_ops

    n_chunks = (count - 1) // 256 + 1
    prefix = np.frombuffer(seed, np.uint8)
    pivot_msgs = np.zeros((rounds, 33), np.uint8)
    pivot_msgs[:, :32] = prefix
    pivot_msgs[:, 32] = np.arange(rounds, dtype=np.uint8)
    pivot_digests = sha_ops.sha256_msgs(pivot_msgs, device=False)
    # mod in uint64 BEFORE the int64 cast: the raw 8-byte LE value can
    # exceed 2**63 and a premature signed cast would corrupt the pivot
    pivots = (pivot_digests[:, :8].copy().view("<u8").reshape(rounds)
              % np.uint64(count)).astype(np.int64)

    src_msgs = np.zeros((rounds * n_chunks, 37), np.uint8)
    src_msgs[:, :32] = prefix
    src_msgs[:, 32] = np.repeat(
        np.arange(rounds, dtype=np.uint8), n_chunks)
    chunk_ids = np.tile(np.arange(n_chunks, dtype="<u4"), rounds)
    src_msgs[:, 33:37] = chunk_ids.view(np.uint8).reshape(-1, 4)
    digests = sha_ops.sha256_msgs(src_msgs, device=device)
    return pivots, digests.reshape(rounds, n_chunks * 32)


def shuffle_list_device(indices: np.ndarray, seed: bytes,
                        rounds: int) -> np.ndarray:
    """Device rung of the full-list shuffle (see module doc)."""
    from lighthouse_tpu.ops import epoch_kernels as ek
    from lighthouse_tpu.state_transition import epoch_device

    count = indices.shape[0]
    if count <= 1:
        return indices.copy()
    bucket = ek.bucket_size(count, epoch_device.bucket_floor())
    pivots, src = _shuffle_hash_sweep(seed, rounds, count)
    fwd = ek.shuffle_rounds_device(count, pivots, src, bucket)
    return indices[fwd]


def _auto_device(count: int) -> bool:
    """Shuffle rides the epoch backend seam's routing: forced backend
    first, else the device threshold on a real TPU only (the numpy path
    wins on the XLA-CPU fallback).  Even a forced backend keeps
    sub-bucket-floor shuffles on the host rung — a padded 256-lane jit
    dispatch per 2-element conformance shuffle is strictly slower than
    the numpy loop, and the force exists to speed up the big
    committee-scale sweeps, not to tax every tiny call site."""
    from lighthouse_tpu.state_transition import epoch_device
    from lighthouse_tpu.state_transition.epoch_processing import (
        resolve_epoch_backend,
    )

    if count < epoch_device.bucket_floor():
        return False
    return resolve_epoch_backend(count) != "reference"


def shuffle_list(indices: np.ndarray, seed: bytes, rounds: int, *,
                 device: bool | None = None) -> np.ndarray:
    """Vectorized full-list shuffle: permutation of `indices`.

    Equivalent to applying compute_shuffled_index to every position
    (``out[i] = indices[compute_shuffled_index(i, ...)]``), with the 90
    swap-or-not rounds as column operations.  ``device`` forces the
    rung; None auto-routes through the epoch backend seam.
    """
    count = indices.shape[0]
    if count <= 1:
        return indices.copy()
    if device is None:
        device = _auto_device(count)
    if device:
        from lighthouse_tpu.state_transition import epoch_processing as _ep

        try:
            out = shuffle_list_device(indices, seed, rounds)
        except PROGRAM_FAULTS:
            raise  # the device module does not import/trace: loud
        except Exception as exc:  # recover on the host rung
            _ep.record_epoch_fault("shuffle", type(exc).__name__)
            # shuffle shares the epoch circuit breaker: a flapping
            # device shuffle parks auto routing on the host rung too,
            # instead of paying the doomed dispatch every epoch
            _ep._breaker_fault()
        else:
            # …and a success closes the consecutive-fault count, so
            # isolated faults spread over thousands of shuffles never
            # accumulate to the breaker threshold
            _ep._breaker_ok()
            return out
    pos = np.arange(count, dtype=np.int64)
    # forward shuffle of positions: apply the rounds to the position
    # array exactly as the scalar loop does to a single index, with the
    # per-round hashes batched through ops/sha256
    cur = pos
    pivots, src = _shuffle_hash_sweep(seed, rounds, count, device=False)
    for r in range(rounds):
        flip = (pivots[r] - cur) % count
        position = np.maximum(cur, flip)
        bytes_ = src[r][position >> 3]
        bits = (bytes_ >> (position % 8).astype(np.uint8)) & 1
        cur = np.where(bits.astype(bool), flip, cur)
    out = np.empty(count, dtype=indices.dtype)
    out[:] = indices[cur]
    return out
