"""Batched SHA-256 for SSZ merkleization, as a JAX/XLA program.

The reference client's #2 CPU cost is SHA-256 merkleization of the beacon
state forest (reference: tree_hash `MerkleHasher` + ethereum_hashing's
CPU-vectorized SHA-256; consumed at
/root/reference/consensus/types/src/beacon_state.rs:2031
``update_tree_hash_cache``).  Here the hasher is a data-parallel device
program: every (left, right) node pair in a tree level is one lane of a
batched 64-round compression, so a level with N pairs is two fused
compression sweeps over a ``uint32[N, 16]`` tensor — int32 VPU work that
vectorizes across the whole level at once.

Design notes (TPU-first):
- All arithmetic is uint32 (wrapping adds, shifts, xors) — no 64-bit needed,
  so the same program runs identically on TPU and the CPU test platform.
- The 64-byte merkle node message is exactly one message block; the second
  (padding) block is a compile-time constant, so its message schedule is
  precomputed host-side once (``_PAD_W``) and only the 64 round updates run
  for it on device.
- Message-schedule extension and the round function are `lax.scan`s: traced
  once, compiled once, batch-vectorized by XLA.
"""

from __future__ import annotations

import hashlib
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from lighthouse_tpu.common import device_telemetry as _dtel
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import REGISTRY
from lighthouse_tpu.ops import program_store as _pstore

# AOT program-store coverage (lhlint LH606): the merkle hashers are
# prewarmed by the "sha256" driver in ops/prewarm
_pstore.register_entry("ops/sha256.py::sha256_block@sha256_block",
                       driver="sha256")
_pstore.register_entry("ops/sha256.py::hash_pairs_device@hash_pairs_device",
                       driver="sha256")
_pstore.register_entry(
    "ops/sha256.py::_fold_levels_device@_fold_levels_device",
    driver="sha256")
_pstore.register_entry(
    "ops/sha256.py::validator_roots_device@validator_roots_device",
    driver="sha256")
_pstore.register_entry("ops/sha256.py::<module>@<lambda>", driver="sha256")

# shapes whose whole-fold device program has already been dispatched in
# this process: the first call at a shape pays tracing + XLA compile (or
# a persistent-cache load), later calls are pure execution — the metric
# splits the two so "compile storms" are visible per-process
_FOLD_SHAPES_SEEN: set = set()


def _record_fold_dispatch(shape_key, seconds: float) -> None:
    phase = "execute" if shape_key in _FOLD_SHAPES_SEEN else "compile"
    _FOLD_SHAPES_SEEN.add(shape_key)
    try:
        REGISTRY.histogram(
            "sha256_fold_dispatch_seconds",
            "whole-fold device program wall time; compile = first call "
            "at this shape (includes XLA compile / cache load)",
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0,
                     120.0),
        ).labels(phase=phase).observe(seconds)
    except Exception as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("sha256.record_fold", e)


def record_merkle_stage(stage: str, seconds: float) -> None:
    """One stage of an incremental merkle update (sole registration site
    of the merkle_stage_* family — lhlint LH501 FAMILY_OWNERS).  The tree
    cache's stages (leaves, diff, slice, snapshot, gather, scatter) and
    this module's (pad, h2d, execute, d2h, hash_host) share the family so
    a state root's time adds up in one place."""
    try:
        REGISTRY.histogram(
            "merkle_stage_seconds",
            "incremental merkle update wall time by stage (device stages: "
            "h2d is the host side of the copy-in, execute the enqueue, "
            "d2h the blocked fetch, which waits for both)",
            buckets=(0.00001, 0.0001, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5,
                     1.0, 5.0),
        ).labels(stage=stage).observe(seconds)
    except Exception as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("sha256.record_merkle_stage", e)


def merkle_stage_span(name: str, stage: str, **attrs):
    """A span whose duration also feeds ``merkle_stage_seconds{stage}``."""
    return tracing.span(name, observe=partial(record_merkle_stage, stage),
                        **attrs)

# FIPS 180-4 round constants.
_K = np.array(
    [
        0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B, 0x59F111F1,
        0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
        0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174, 0xE49B69C1, 0xEFBE4786,
        0x0FC19DC6, 0x240CA1CC, 0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
        0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
        0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
        0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85, 0xA2BFE8A1, 0xA81A664B,
        0xC24B8B70, 0xC76C51A3, 0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
        0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A,
        0x5B9CCA4F, 0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
        0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
    ],
    dtype=np.uint32,
)

_H0 = np.array(
    [0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
     0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19],
    dtype=np.uint32,
)


def _py_rotr(x: int, n: int) -> int:
    return ((x >> n) | (x << (32 - n))) & 0xFFFFFFFF


def _np_schedule(block: np.ndarray) -> np.ndarray:
    """Host-side message-schedule expansion (for the constant padding block)."""
    w = [int(v) for v in block]
    for t in range(16, 64):
        s0 = _py_rotr(w[t - 15], 7) ^ _py_rotr(w[t - 15], 18) ^ (w[t - 15] >> 3)
        s1 = _py_rotr(w[t - 2], 17) ^ _py_rotr(w[t - 2], 19) ^ (w[t - 2] >> 10)
        w.append((w[t - 16] + s0 + w[t - 7] + s1) & 0xFFFFFFFF)
    return np.array(w, dtype=np.uint32)


# Padding block for a message of exactly 64 bytes: 0x80 then zeros, bit length
# 512 in the final 64-bit field.  Its schedule is message-independent.
_PAD_BLOCK = np.zeros(16, dtype=np.uint32)
_PAD_BLOCK[0] = 0x80000000
_PAD_BLOCK[15] = 512
_PAD_W = _np_schedule(_PAD_BLOCK)  # uint32[64]


def _rotr(x: jax.Array, n: int) -> jax.Array:
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _expand_schedule(block: jax.Array) -> jax.Array:
    """block: uint32[..., 16] -> W: uint32[64, ...] (round axis leading)."""
    window = jnp.moveaxis(block, -1, 0)  # [16, ...]

    def step(win, _):
        w15, w2 = win[1], win[14]
        s0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> np.uint32(3))
        s1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> np.uint32(10))
        new = win[0] + s0 + win[9] + s1
        return jnp.concatenate([win[1:], new[None]], axis=0), new

    _, extra = jax.lax.scan(step, window, None, length=48)
    return jnp.concatenate([window, extra], axis=0)


def _rounds(state: jax.Array, w: jax.Array) -> jax.Array:
    """Run 64 rounds.  state: uint32[..., 8]; w: uint32[64, ...]."""
    kw = w + jnp.asarray(_K, dtype=jnp.uint32).reshape((64,) + (1,) * (w.ndim - 1))

    def round_fn(carry, kw_t):
        a, b, c, d, e, f, g, h = carry
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + kw_t
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        return (t1 + t2, a, b, c, d + t1, e, f, g), None

    init = tuple(state[..., i] for i in range(8))
    out, _ = jax.lax.scan(round_fn, init, kw)
    return state + jnp.stack(out, axis=-1)


@jax.jit
def sha256_block(state: jax.Array, block: jax.Array) -> jax.Array:
    """One compression: state uint32[...,8], block uint32[...,16] -> uint32[...,8]."""
    return _rounds(state, _expand_schedule(block))


sha256_block = _dtel.instrument(
    "ops/sha256.py::sha256_block@sha256_block", sha256_block)


@jax.jit
def hash_pairs_device(pairs: jax.Array) -> jax.Array:
    """SHA-256 of N 64-byte messages given as big-endian words.

    pairs: uint32[N, 16] (each row = left||right node) -> uint32[N, 8].
    This is the merkle work-horse: compress the data block, then apply the
    constant-schedule padding block.
    """
    h0 = jnp.broadcast_to(jnp.asarray(_H0, jnp.uint32), pairs.shape[:-1] + (8,))
    mid = _rounds(h0, _expand_schedule(pairs))
    pad_w = jnp.asarray(_PAD_W, jnp.uint32).reshape((64,) + (1,) * (pairs.ndim - 1))
    pad_w = jnp.broadcast_to(pad_w, (64,) + pairs.shape[:-1])
    return _rounds(mid, pad_w)


hash_pairs_device = _dtel.instrument(
    "ops/sha256.py::hash_pairs_device@hash_pairs_device", hash_pairs_device)


def fold_to_root_device(leaves: jax.Array) -> jax.Array:
    """Whole-tree fold inside one traced program: uint32[n, 8] (n a power
    of two) -> uint32[1, 8].  Shared by bench.py, the driver compile check
    and the multichip dryrun — one definition, one jit shape per n."""
    x = leaves
    while x.shape[0] > 1:
        x = hash_pairs_device(x.reshape(x.shape[0] // 2, 16))
    return x


@jax.jit
def _fold_levels_device(leaves: jax.Array):
    """All interior tree levels in ONE device program.

    leaves: uint32[n, 8] with n a power of two -> tuple of levels
    (uint32[n/2, 8], ..., uint32[1, 8]).  One dispatch and one transfer
    per level instead of a host round-trip per level — the production
    full-build path for the incremental tree cache (fixes the
    per-level ping-pong called out for merkleize_words).
    """
    out = []
    x = leaves
    while x.shape[0] > 1:
        x = hash_pairs_device(x.reshape(x.shape[0] // 2, 16))
        out.append(x)
    return tuple(out)


_fold_levels_device = _dtel.instrument(
    "ops/sha256.py::_fold_levels_device@_fold_levels_device",
    _fold_levels_device)


def _be_words(x: jax.Array) -> jax.Array:
    """Little-endian uint32 views of a byte column -> SHA-256's
    big-endian words (a byte swap)."""
    return ((x << np.uint32(24)) | ((x & np.uint32(0xFF00)) << np.uint32(8))
            | ((x >> np.uint32(8)) & np.uint32(0xFF00)) | (x >> np.uint32(24)))


@jax.jit
def validator_roots_device(pubkeys, credentials, effective_balance, slashed,
                           eligibility_epoch, activation_epoch, exit_epoch,
                           withdrawable_epoch) -> jax.Array:
    """``hash_tree_root`` of N ``Validator`` records from the registry's
    columns, in ONE device program: the SSZ chunk packing, the pubkey
    pre-hash and the three subtree levels 8 -> 4 -> 2 -> 1.

    The columns arrive as the host's bytes, viewed as little-endian
    uint32: pubkeys uint32[N, 12], credentials uint32[N, 8], each uint64
    column uint32[N, 2] (low word first), slashed uint8[N].  -> uint32[N, 8].
    Every level is ``hash_pairs_device``'s compression: the hashes are the
    ones ``_batch_merkleize_subtrees`` computes from ``leaves[N, 8, 8]``,
    which here never exists on the host.  A level's lanes run pair by pair
    over all records, not record by record (rows [k N, (k+1) N) hold pair k
    of every record), so the next level is made of whole slices of the last
    and no level shuffles lanes.
    """
    n = pubkeys.shape[0]
    zeros = jnp.zeros((n, 8), jnp.uint32)

    def uint64_chunk(col):
        return jnp.concatenate([_be_words(col), zeros[:, :6]], axis=1)

    pubkey_root = hash_pairs_device(jnp.concatenate(
        [_be_words(pubkeys), zeros[:, :4]], axis=1))
    slashed_chunk = jnp.concatenate(
        [slashed.astype(jnp.uint32)[:, None] << np.uint32(24), zeros[:, :7]],
        axis=1)
    level = [pubkey_root, _be_words(credentials),
             uint64_chunk(effective_balance), slashed_chunk,
             uint64_chunk(eligibility_epoch), uint64_chunk(activation_epoch),
             uint64_chunk(exit_epoch), uint64_chunk(withdrawable_epoch)]
    while len(level) > 1:
        hashed = hash_pairs_device(jnp.concatenate(
            [jnp.concatenate(level[k: k + 2], axis=1)
             for k in range(0, len(level), 2)], axis=0))
        level = jnp.split(hashed, len(level) // 2, axis=0)
    return level[0]


validator_roots_device = _dtel.instrument(
    "ops/sha256.py::validator_roots_device@validator_roots_device",
    validator_roots_device)


def fold_levels(leaves: np.ndarray, *, device: bool | None = None) -> list[np.ndarray]:
    """Build every interior level of a power-of-two-leaf merkle tree.

    leaves: uint32[n, 8], n a power of two (zero-chunk padded by caller).
    Returns [level1, ..., levelL] where level k has n/2^k rows.  Routes to
    a single fused device program for large trees, hashlib below the
    dispatch-overhead threshold.
    """
    n = leaves.shape[0]
    assert n & (n - 1) == 0 and n >= 1
    if n == 1:
        return []
    use_device = device if device is not None else n // 2 >= _DEVICE_MIN_PAIRS
    REGISTRY.counter(
        "sha256_merkle_chunks_total",
        "leaf chunks merkleized, by fold path").labels(
        path="levels_device" if use_device else "levels_host").inc(n)
    if use_device:
        t0 = time.perf_counter()
        levels = _fold_levels_device(jnp.asarray(leaves))
        _record_fold_dispatch(("levels", n), time.perf_counter() - t0)
        # np.array (not asarray): device transfers are read-only views and
        # the incremental cache scatters into these levels
        return [np.array(lv) for lv in levels]
    out = []
    x = leaves
    while x.shape[0] > 1:
        x = hash_pairs_np(x.reshape(x.shape[0] // 2, 16))
        out.append(x)
    return out


# native SHA-NI batch hasher (native/sha256.cc): ~8x a hashlib loop on
# x86 with the sha extension; loaded lazily, any failure leaves the
# hashlib path in place
_NATIVE_SHA = None
_NATIVE_SHA_TRIED = False


def _native_sha():
    global _NATIVE_SHA, _NATIVE_SHA_TRIED
    if _NATIVE_SHA_TRIED:
        return _NATIVE_SHA
    _NATIVE_SHA_TRIED = True
    try:
        import ctypes

        from lighthouse_tpu.native import build_shared_lib

        lib = ctypes.CDLL(str(build_shared_lib("sha256.cc")))
        lib.sha256_pairs.restype = ctypes.c_int
        lib.sha256_pairs.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p]
        _NATIVE_SHA = lib
    except Exception as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("sha256.native_load", e)
        _NATIVE_SHA = None
    return _NATIVE_SHA


def hash_pairs_np(pairs: np.ndarray) -> np.ndarray:
    """Host pair hashing (uint32[N,16] -> uint32[N,8]): one FFI crossing
    into the SHA-NI batch kernel, hashlib loop as the fallback."""
    n = pairs.shape[0]
    data = pairs.astype(">u4").tobytes()
    lib = _native_sha()
    if lib is not None and n:
        import ctypes

        out_buf = ctypes.create_string_buffer(n * 32)
        if lib.sha256_pairs(data, n, out_buf) == 0:
            return np.frombuffer(
                out_buf.raw, dtype=">u4").astype(np.uint32).reshape(n, 8)
    out = np.empty((n, 8), dtype=np.uint32)
    for i in range(n):
        out[i] = np.frombuffer(
            hashlib.sha256(data[64 * i: 64 * (i + 1)]).digest(), dtype=">u4"
        )
    return out


def sha256_msgs(msgs: np.ndarray, *, device: bool | None = None) -> np.ndarray:
    """Batched SHA-256 of N equal-length short messages: uint8[N, L] ->
    uint8[N, 32], L <= 55 (one padded compression block per message).

    The shuffle's per-round source sweeps (hash(seed ‖ round ‖ chunk)
    for every round × chunk at once) ride this instead of a host
    hashlib loop: each message is padded into a single 64-byte block
    host-side and the whole batch is ONE ``sha256_block`` dispatch.
    Lane counts are padded to a power of two so the jit cache stays
    bounded exactly like the pair-hash path.
    """
    n, length = msgs.shape
    if length > 55:
        raise ValueError("sha256_msgs handles single-block messages only")
    use_device = device if device is not None else n >= _DEVICE_MIN_PAIRS
    if not use_device or n == 0:
        out = np.empty((n, 32), dtype=np.uint8)
        data = np.ascontiguousarray(msgs, dtype=np.uint8)
        for i in range(n):
            out[i] = np.frombuffer(
                hashlib.sha256(data[i].tobytes()).digest(), np.uint8)
        return out
    blocks = np.zeros((n, 64), dtype=np.uint8)
    blocks[:, :length] = msgs
    blocks[:, length] = 0x80
    blocks[:, 56:64] = np.frombuffer(
        (length * 8).to_bytes(8, "big"), np.uint8)
    words = np.frombuffer(blocks.tobytes(), dtype=">u4").astype(
        np.uint32).reshape(n, 16)
    padded = 1 << max(n - 1, 0).bit_length()
    if padded != n:
        words = np.concatenate(
            [words, np.zeros((padded - n, 16), np.uint32)], axis=0)
    state = np.broadcast_to(_H0, (padded, 8))
    out_words = np.asarray(sha256_block(
        jnp.asarray(state), jnp.asarray(words)))[:n]
    return np.frombuffer(
        out_words.astype(">u4").tobytes(), np.uint8).reshape(n, 32).copy()


# --------------------------------------------------------------------------
# Byte <-> word helpers (SSZ chunks are 32-byte little-endian-agnostic blobs;
# SHA-256 words are big-endian).
# --------------------------------------------------------------------------

def chunks_to_words(data: bytes) -> np.ndarray:
    """bytes (len % 32 == 0) -> uint32[n_chunks, 8] in SHA-256 word order."""
    if len(data) % 32:
        raise ValueError("chunk data must be a multiple of 32 bytes")
    return np.frombuffer(data, dtype=">u4").astype(np.uint32).reshape(-1, 8)


def words_to_bytes(words: np.ndarray) -> bytes:
    return np.asarray(words, dtype=np.uint32).astype(">u4").tobytes()


def _zero_hash_ladder(depth: int = 64) -> list[bytes]:
    zh = [b"\x00" * 32]
    for _ in range(depth):
        zh.append(hashlib.sha256(zh[-1] + zh[-1]).digest())
    return zh


ZERO_HASHES: list[bytes] = _zero_hash_ladder()
ZERO_HASH_WORDS: np.ndarray = np.stack(
    [np.frombuffer(h, dtype=">u4").astype(np.uint32) for h in ZERO_HASHES]
)


# --------------------------------------------------------------------------
# Merkleization
# --------------------------------------------------------------------------

# Below this many pairs a device dispatch costs more than hashlib (measured:
# XLA-CPU ≈ hashlib ≈ 0.55 Mhash/s, but per-call dispatch ~100µs; small tree
# levels are pure overhead).  Also bounds the jit compile cache to the few
# large power-of-two shapes.  These STATIC defaults assume a real TPU;
# calibrate_device_thresholds (run once at node startup / bench setup)
# replaces them with measured values — on an XLA-CPU fallback host the
# device path is SLOWER than hashlib+SHA-NI (BENCH merkle_vs_host ≈ 0.29),
# so the static numbers mis-route mid-sized trees to the slow path.
_DEVICE_MIN_PAIRS = 2048


def batch_hash_pairs(pairs: np.ndarray, *, device: bool | None = None) -> np.ndarray:
    """Public batched pair-hash: uint32[N,16] -> uint32[N,8], device-routed.

    The routing point of the tree cache's dirty levels and of the registry's
    element roots: counts its chunks under ``levels_device``/``levels_host``
    from the same boolean that routes the call."""
    use_device = (device if device is not None
                  else pairs.shape[0] >= _DEVICE_MIN_PAIRS)
    REGISTRY.counter(
        "sha256_merkle_chunks_total",
        "leaf chunks merkleized, by fold path").labels(
        path="levels_device" if use_device else "levels_host").inc(
        2 * pairs.shape[0])
    return _hash_level(pairs, device=use_device)


def _count_device_lanes(live: int, padding: int) -> None:
    lanes = REGISTRY.counter(
        "sha256_device_lanes_total",
        "lanes of hash_pairs_device's compression dispatched: live pairs, "
        "and the padding up to the power-of-two program shape")
    lanes.labels(kind="live").inc(live)
    lanes.labels(kind="padding").inc(padding)


def _hash_level(pairs: np.ndarray, *, device: bool | None = None) -> np.ndarray:
    use_device = device if device is not None else pairs.shape[0] >= _DEVICE_MIN_PAIRS
    n = pairs.shape[0]
    if not use_device:
        with merkle_stage_span("sha.host", "hash_host", pairs=n):
            return hash_pairs_np(pairs)
    # Pad the lane count to a power of two so the jit compile cache is
    # bounded at ~log2(max_pairs) programs shared by every tree size
    # (padded lanes hash garbage and are discarded).
    padded = 1 << max(n - 1, 0).bit_length()
    _count_device_lanes(n, padded - n)
    with merkle_stage_span("sha.pad", "pad", pairs=n, lanes=padded):
        if padded != n:
            pairs = np.concatenate(
                [pairs, np.zeros((padded - n, 16), np.uint32)], axis=0
            )
    # three pieces of host code, no sync added: h2d is the host side of
    # the copy-in, execute the enqueue, and d2h — the one call that
    # blocks — waits for the copy, the program and the fetch back
    with merkle_stage_span("sha.h2d", "h2d", pairs=n, lanes=padded):
        operand = jnp.asarray(pairs)
    with merkle_stage_span("sha.execute", "execute", pairs=n, lanes=padded):
        hashed = hash_pairs_device(operand)
    with merkle_stage_span("sha.d2h", "d2h", pairs=n, lanes=padded):
        return np.asarray(hashed)[:n]


def _stage_column(col: np.ndarray, rows: int) -> np.ndarray:
    """One registry column at the program's bucket size, as the view of its
    bytes ``validator_roots_device`` takes.  The rows past the column's own
    are left as allocated: they hash garbage that the caller slices off."""
    dtype = col.dtype.newbyteorder("<")
    if col.shape[0] == rows:
        staged = np.ascontiguousarray(col, dtype=dtype)
    else:
        staged = np.empty((rows,) + col.shape[1:], dtype)
        staged[: col.shape[0]] = col
    if staged.dtype.itemsize == 1 and staged.ndim == 1:
        return staged.view(np.uint8)
    return staged.view("<u4").reshape(rows, -1)


def validator_roots(columns) -> np.ndarray:
    """Element roots of n validator records, uint32[n, 8], through ONE
    dispatch of ``validator_roots_device``: only the raw columns
    (``validator_roots_device``'s arguments, n rows each) go up and only the
    roots come back.  The rows are padded to a power of two, as
    ``_hash_level`` pads its lanes (one program per bucket); the counters
    move by what the four levels of the per-level path would count — 16
    chunks and 8 lanes a record."""
    n = columns[0].shape[0]
    padded = 1 << max(n - 1, 0).bit_length()
    REGISTRY.counter(
        "sha256_merkle_chunks_total",
        "leaf chunks merkleized, by fold path").labels(
        path="levels_device").inc(16 * n)
    _count_device_lanes(8 * n, 8 * (padded - n))
    with merkle_stage_span("sha.pad", "pad", rows=n, lanes=8 * padded):
        staged = [_stage_column(col, padded) for col in columns]
    # the same three pieces of host code as _hash_level's device branch
    with merkle_stage_span("sha.h2d", "h2d", rows=n, lanes=8 * padded):
        operands = [jnp.asarray(col) for col in staged]
    with merkle_stage_span("sha.execute", "execute", rows=n,
                           lanes=8 * padded):
        roots = validator_roots_device(*operands)
    with merkle_stage_span("sha.d2h", "d2h", rows=n, lanes=8 * padded):
        return np.asarray(roots)[:n]


# whole-fold one-dispatch threshold: pow2 leaf counts keep the jit
# cache at ~log2(max tree) programs
_DEVICE_FOLD_MIN_LEAVES = 1 << 12
_fold_to_root_jit = jax.jit(
    lambda leaves: fold_to_root_device(leaves))
_fold_to_root_jit = _dtel.instrument(
    "ops/sha256.py::<module>@<lambda>", _fold_to_root_jit)

# --- startup micro-calibration ---------------------------------------------

_CALIBRATED = False
_THRESHOLD_CEIL = 1 << 22     # "device never wins here": route all to host


def _measure_rate(fn, pairs, min_s: float = 0.02) -> float:
    """pairs hashed per second, repeating until min_s of wall time."""
    n = pairs.shape[0]
    done = 0
    t0 = time.perf_counter()
    while True:
        fn(pairs)
        done += n
        dt = time.perf_counter() - t0
        if dt >= min_s:
            return done / max(dt, 1e-9)


def calibrate_device_thresholds(sample_pairs: int = 2048,
                                force: bool = False) -> dict:
    """One-shot startup micro-calibration of the device-vs-host routing.

    Measures the host pair-hash rate (SHA-NI/hashlib) and the device
    rate + per-dispatch overhead on a small power-of-two sample, then
    solves the break-even pair count  n* = overhead / (1/host - 1/device)
    — below n* a device dispatch loses even if its asymptotic rate wins.
    Sets _DEVICE_MIN_PAIRS (rounded up to a power of two, floored at the
    static default's scale) and _DEVICE_FOLD_MIN_LEAVES (= 2·pairs
    threshold), publishes the choice as the
    ``sha256_device_threshold_pairs`` gauge, and returns the measurements.

    ``LHTPU_SHA_DEVICE_MIN`` overrides measurement entirely (operator
    pin, also the escape hatch when calibration itself is unwanted).
    Runs once per process unless ``force``; callers that monkeypatch
    _DEVICE_MIN_PAIRS directly (tests) are unaffected because nothing
    here runs implicitly on the hash path."""
    global _DEVICE_MIN_PAIRS, _DEVICE_FOLD_MIN_LEAVES, _CALIBRATED
    from lighthouse_tpu.common import env as envreg

    if _CALIBRATED and not force:
        return {"threshold_pairs": _DEVICE_MIN_PAIRS, "cached": True}
    _CALIBRATED = True
    env = envreg.get_int("LHTPU_SHA_DEVICE_MIN")
    if env is not None:
        _DEVICE_MIN_PAIRS = max(1, env)
        _DEVICE_FOLD_MIN_LEAVES = 2 * _DEVICE_MIN_PAIRS
        _publish_threshold()
        return {"threshold_pairs": _DEVICE_MIN_PAIRS, "source": "env"}
    n = 1 << max(sample_pairs - 1, 1).bit_length()
    rng = np.random.default_rng(7)
    pairs = rng.integers(0, 2**32, size=(n, 16), dtype=np.uint64).astype(
        np.uint32)
    dev_pairs = jnp.asarray(pairs)
    # compile outside the timing (persistent cache makes this a load)
    jax.block_until_ready(hash_pairs_device(dev_pairs))
    host_rate = _measure_rate(hash_pairs_np, pairs)
    dev_rate = _measure_rate(
        lambda p: jax.block_until_ready(hash_pairs_device(p)), dev_pairs)
    # per-dispatch overhead: a tiny (already-compiled small shape) call
    tiny = jnp.asarray(pairs[:4])
    jax.block_until_ready(hash_pairs_device(tiny))
    t0 = time.perf_counter()
    reps = 10
    for _ in range(reps):
        jax.block_until_ready(hash_pairs_device(tiny))
    overhead_s = (time.perf_counter() - t0) / reps
    if dev_rate <= host_rate:
        # the device asymptote loses outright (XLA-CPU fallback):
        # route everything realistic to the host path
        threshold = _THRESHOLD_CEIL
    else:
        n_star = overhead_s / (1.0 / host_rate - 1.0 / dev_rate)
        threshold = 1 << max(int(n_star) - 1, 1).bit_length()
        threshold = min(max(threshold, 256), _THRESHOLD_CEIL)
    _DEVICE_MIN_PAIRS = threshold
    _DEVICE_FOLD_MIN_LEAVES = min(2 * threshold, _THRESHOLD_CEIL)
    _publish_threshold()
    return {
        "threshold_pairs": threshold,
        "host_pairs_per_s": round(host_rate, 1),
        "device_pairs_per_s": round(dev_rate, 1),
        "dispatch_overhead_ms": round(overhead_s * 1000, 3),
        "source": "measured",
    }


def apply_calibration(data: dict) -> bool:
    """Adopt a persisted calibration measurement (ops/program_store's
    sidecar for this platform fingerprint) instead of re-measuring:
    restart skips the micro-benchmark entirely.  Returns False — and
    changes nothing — when the record does not carry a usable
    threshold, so a damaged sidecar falls back to measurement."""
    global _DEVICE_MIN_PAIRS, _DEVICE_FOLD_MIN_LEAVES, _CALIBRATED
    try:
        threshold = int(data["threshold_pairs"])
    except (KeyError, TypeError, ValueError):
        return False
    if threshold < 1:
        return False
    _DEVICE_MIN_PAIRS = min(threshold, _THRESHOLD_CEIL)
    _DEVICE_FOLD_MIN_LEAVES = min(2 * _DEVICE_MIN_PAIRS, _THRESHOLD_CEIL)
    _CALIBRATED = True
    _publish_threshold()
    return True


def _publish_threshold() -> None:
    try:
        REGISTRY.gauge(
            "sha256_device_threshold_pairs",
            "pair count above which merkle levels route to the device "
            "(static default or startup calibration)",
        ).set(_DEVICE_MIN_PAIRS)
    except Exception as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("sha256.publish_threshold", e)


def merkleize_words(
    leaves: np.ndarray, limit: int | None = None, *, device: bool | None = None
) -> np.ndarray:
    """SSZ merkleize: uint32[n, 8] leaf chunks -> uint32[8] root.

    Pads the leaf count to the next power of two (or to ``limit``) with the
    precomputed zero-subtree ladder, then folds level by level; each level is
    one batched device sweep.  Mirrors tree_hash's ``merkleize_padded``
    semantics (reference consumer: consensus/types tree-hash caches).
    """
    n = leaves.shape[0]
    n_pow2 = 1 << max(n - 1, 0).bit_length()
    # THE device-vs-host fold decision; the impl takes it as a flag so
    # the metric's "path" label can never desynchronize from the branch
    # actually executed
    fold_device = (device is not False and n > 0
                   and n_pow2 >= _DEVICE_FOLD_MIN_LEAVES)
    path = "fold_device" if fold_device else "level_loop"
    t0 = time.perf_counter()
    out = _merkleize_words_impl(leaves, limit, device=device,
                                fold_device=fold_device)
    REGISTRY.counter(
        "sha256_merkle_chunks_total",
        "leaf chunks merkleized, by fold path").labels(path=path).inc(n)
    REGISTRY.histogram(
        "sha256_merkleize_seconds",
        "one merkleize_words call, by fold path",
    ).labels(path=path).observe(time.perf_counter() - t0)
    return out


def _merkleize_words_impl(
    leaves: np.ndarray, limit: int | None = None, *,
    device: bool | None = None, fold_device: bool = False,
) -> np.ndarray:
    n = leaves.shape[0]
    size = max(limit if limit is not None else n, 1)
    depth = max(size - 1, 0).bit_length()
    if limit is not None and n > limit:
        raise ValueError(f"{n} leaves exceed limit {limit}")
    if n == 0:
        return ZERO_HASH_WORDS[depth].copy()

    level = np.ascontiguousarray(leaves, dtype=np.uint32)
    n_pow2 = 1 << max(n - 1, 0).bit_length()
    if fold_device:
        # big trees: ONE whole-fold dispatch (padding the leaf level
        # with zero chunks is ladder-equivalent), then the remaining
        # zero-subtree ladder on host.  The per-level loop below costs
        # a host<->device round trip and a full level transfer PER
        # LEVEL — 20 ping-pongs for a 1M-validator column was the
        # round-4 "full-pass state root is CPU-speed" finding.
        if n_pow2 != n:
            level = np.concatenate(
                [level, np.zeros((n_pow2 - n, 8), np.uint32)])
        t0 = time.perf_counter()
        node = np.asarray(_fold_to_root_jit(jnp.asarray(level)))[0]
        _record_fold_dispatch(("root", n_pow2), time.perf_counter() - t0)
        for dd in range(n_pow2.bit_length() - 1, depth):
            pair = np.concatenate([node, ZERO_HASH_WORDS[dd]])[None, :]
            node = hash_pairs_np(pair)[0]
        return node
    for d in range(depth):
        if level.shape[0] % 2:
            level = np.concatenate([level, ZERO_HASH_WORDS[d][None]], axis=0)
        pairs = level.reshape(level.shape[0] // 2, 16)
        level = _hash_level(pairs, device=device)
        # Entirely-zero right subtrees above current data are folded lazily:
        # once a single node remains we can combine with ladder constants.
        if level.shape[0] == 1 and d + 1 < depth:
            node = level[0]
            for dd in range(d + 1, depth):
                pair = np.concatenate([node, ZERO_HASH_WORDS[dd]])[None, :]
                node = hash_pairs_np(pair)[0]
            return node
    return level[0]


def _merkleize_small(data: bytes, limit: int | None) -> bytes:
    """Scalar hashlib fold for tiny trees.  The word-plane path below
    costs ~30 µs of numpy plumbing per call; control-plane containers
    (AttestationData & co, <= 8 chunks) hash thousands of times per
    gossip batch, so this fast path matters for slot-time budgets."""
    n_chunks = max(len(data) // 32, 1)
    if limit is not None and len(data) // 32 > limit:
        # same contract as merkleize_words: overfull input is an error,
        # never a plausible-looking root
        raise ValueError(f"{len(data) // 32} leaves exceed limit {limit}")
    n_leaves = max(limit if limit is not None else n_chunks, 1)
    depth = max(n_leaves - 1, 0).bit_length()
    nodes = [data[i:i + 32] for i in range(0, len(data), 32)] or [
        b"\x00" * 32]
    for d in range(depth):
        nxt = []
        for i in range(0, len(nodes), 2):
            left = nodes[i]
            right = (nodes[i + 1] if i + 1 < len(nodes)
                     else ZERO_HASHES[d])
            nxt.append(hashlib.sha256(left + right).digest())
        nodes = nxt
    return nodes[0]


def merkleize(data: bytes, limit: int | None = None, *, device: bool | None = None) -> bytes:
    """SSZ merkleize over packed 32-byte chunks -> 32-byte root."""
    if len(data) % 32:
        data = data + b"\x00" * (32 - len(data) % 32)
    if device is not True and len(data) <= 512 and (
            limit is None or limit <= 16):
        return _merkleize_small(data, limit)
    leaves = chunks_to_words(data) if data else np.zeros((0, 8), np.uint32)
    return words_to_bytes(merkleize_words(leaves, limit, device=device))


def mix_in_length(root: bytes, length: int) -> bytes:
    return hashlib.sha256(root + length.to_bytes(32, "little")).digest()


def sha256(data: bytes) -> bytes:
    """Host one-shot SHA-256 (control-plane use)."""
    return hashlib.sha256(data).digest()
