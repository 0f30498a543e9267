"""Device-resident epoch processing: fused lane-parallel epoch pass + shuffle.

PAPER.md §L2 names pure state transition as a dominant CPU cost next to
BLS; ROADMAP item 2 calls per-epoch processing the biggest unopened
workload.  The registry math is column arithmetic already
(state_transition/epoch_processing.py) — this module is its device form:
ONE fused ``jax.jit`` program per fork that takes the validator-registry
columns as fixed shape-bucketed arrays and runs inactivity updates,
rewards/penalties, slashings and effective-balance hysteresis as a
single lane-parallel pass, plus the swap-or-not shuffle's 90 rounds as
one ``lax.fori_loop`` program over all positions at once.

Design notes (TPU-first, see README "Epoch processing"):

- **Exact integer semantics via gather tables.**  Every spec quantity
  that is a pure function of a validator's effective-balance increment
  (per-flag rewards and penalties, the proportional slashing penalty)
  is precomputed host-side with arbitrary-precision Python ints into a
  small table (``max_effective_balance // increment + 1`` entries, 33
  pre-electra / 2049 electra) and gathered by lane on device.  The
  kernel itself never divides by a runtime total — so the device path
  is bit-identical to the numpy/bigint reference and TPUs never run
  the slow integer-division path.
- **int64 lanes under a scoped x64 context.**  Balances/scores/epochs
  need 64 bits; the kernels trace and run inside
  ``jax.enable_x64`` so the rest of the process keeps the
  default 32-bit world (the BLS limb kernels are explicit-dtype and
  unaffected).  ``FAR_FUTURE_EPOCH`` (2**64-1) is clamped host-side to
  ``state_transition.epoch_device.EPOCH_CLAMP`` (1<<62 — large enough
  that every "far future" comparison stays true, small enough that
  epoch+1 cannot overflow), preserving every comparison the pass makes.
- **pow2 shape buckets, masked tails.**  Registry length is padded to
  the next power of two (floored at ``LHTPU_EPOCH_BUCKET_FLOOR``) so
  the jit cache holds ~log2(n) programs (lhlint LH301/LH302 shape
  discipline).  Tail lanes carry zeroed columns: every per-lane mask is
  False there, tail arithmetic is garbage-in/garbage-out integer work
  that cannot trap, and callers slice ``[:n]`` — reductions all happen
  host-side, so no masked sum is needed in-kernel.
- The shuffle kernel is pure int32 (positions < 2**31) and runs without
  x64; its per-round source bytes come from one batched SHA-256 sweep
  through ops/sha256 (``sha256_msgs``) instead of 90 hashlib loops.

Supervision: these kernels are dispatched only through the
``state_transition/epoch_processing`` backend seam, whose supervisor
falls back to the numpy reference on any device fault (lhlint LH601
covers this module).
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from lighthouse_tpu.common import device_telemetry as _dtel
from lighthouse_tpu.ops import program_store as _pstore

# AOT program-store coverage (lhlint LH606): the fused epoch pass and
# the device shuffle are prewarmed by their ops/prewarm drivers
_pstore.register_entry(
    "ops/epoch_kernels.py::_epoch_pass_jit@_fused_epoch_pass",
    driver="epoch")
_pstore.register_entry("ops/epoch_kernels.py::_shuffle_jit@_shuffle_rounds",
                       driver="shuffle")

TIMELY_SOURCE_FLAG_INDEX = 0
TIMELY_TARGET_FLAG_INDEX = 1
TIMELY_HEAD_FLAG_INDEX = 2

# index layout of the packed int64 scalar-parameter vector (one h2d
# transfer for all spec scalars; adding a knob = append an index)
P_PREV_EPOCH = 0
P_LEAK = 1
P_SCORE_BIAS = 2
P_SCORE_RECOVERY = 3
P_INACT_DENOM = 4       # inactivity_score_bias * inactivity_penalty_quotient
P_SLASH_TARGET = 5      # cur + EPOCHS_PER_SLASHINGS_VECTOR // 2
P_INCREMENT = 6
P_HYST_DOWN = 7
P_HYST_UP = 8
P_MAX_EFF = 9
N_PARAMS = 10

# memoized jit wrappers (module singletons — constructing jax.jit per
# call would recompile per call; the maps below are the LH302 memo)
_EPOCH_JIT_CACHE: dict = {}
_SHUFFLE_JIT_CACHE: dict = {}


def bucket_size(n: int, floor: int) -> int:
    """Power-of-two shape bucket for a registry of ``n`` lanes."""
    floor = max(int(floor), 1)
    target = max(n, floor, 1)
    return 1 << (target - 1).bit_length()


def _fused_epoch_pass(eff_incr, balances, scores, prev_part, slashed,
                      activation, exit_epoch, withdrawable,
                      reward_t, penalty_t, slash_t, params, *,
                      apply_eb: bool):
    """The single lane-parallel pass (traced under x64; see module doc).

    Sub-transitions in spec order: inactivity-score update →
    rewards/penalties (flag deltas via table gathers + score-scaled
    inactivity penalty) → proportional slashings → (statically gated)
    effective-balance hysteresis.  Registry updates and the electra
    balance queues are serialized host work and stay outside; the
    reordering is verdict-identical because registry updates touch no
    column this pass reads or writes (see epoch_processing seam doc).
    """
    prev = params[P_PREV_EPOCH]
    leak = params[P_LEAK]
    one = jnp.int64(1)

    active_prev = (activation <= prev) & (prev < exit_epoch)
    eligible = active_prev | (slashed & (prev + one < withdrawable))
    unslashed_active = active_prev & ~slashed

    def has_flag(idx: int):
        return (prev_part >> np.uint8(idx)) & np.uint8(1) != 0

    target_participant = unslashed_active & has_flag(TIMELY_TARGET_FLAG_INDEX)

    # --- inactivity updates (process_inactivity_updates) -----------------
    sc = jnp.where(eligible & target_participant,
                   scores - jnp.minimum(one, scores), scores)
    sc = jnp.where(eligible & ~target_participant,
                   sc + params[P_SCORE_BIAS], sc)
    dec = jnp.minimum(params[P_SCORE_RECOVERY], sc)
    sc = jnp.where((leak == 0) & eligible, sc - dec, sc)

    # --- rewards / penalties (process_rewards_and_penalties) -------------
    delta = jnp.zeros_like(balances)
    for flag_index in range(3):
        participated = unslashed_active & has_flag(flag_index)
        delta = delta + jnp.where(
            eligible & participated, reward_t[flag_index][eff_incr], 0)
        if flag_index != TIMELY_HEAD_FLAG_INDEX:
            delta = delta - jnp.where(
                eligible & ~participated, penalty_t[flag_index][eff_incr], 0)
    eff = eff_incr.astype(jnp.int64) * params[P_INCREMENT]
    inactivity_penalty = (eff * sc) // params[P_INACT_DENOM]
    delta = delta - jnp.where(
        eligible & ~target_participant, inactivity_penalty, 0)
    bal = jnp.maximum(balances + delta, 0)

    # --- slashings (process_slashings) ------------------------------------
    slash_mask = slashed & (withdrawable == params[P_SLASH_TARGET])
    bal = jnp.where(slash_mask,
                    jnp.maximum(bal - slash_t[eff_incr], 0), bal)

    # --- effective-balance hysteresis (non-electra; electra's runs host-
    # side after the pending-deposit/consolidation queues mutate bal) ----
    if apply_eb:
        update = ((bal + params[P_HYST_DOWN] < eff)
                  | (eff + params[P_HYST_UP] < bal))
        new_eff = jnp.minimum(bal - bal % params[P_INCREMENT],
                              params[P_MAX_EFF])
        eff_out = jnp.where(update, new_eff, eff)
    else:
        eff_out = eff
    return sc, bal, eff_out


def _epoch_pass_jit():
    fn = _EPOCH_JIT_CACHE.get("epoch_pass")
    if fn is None:
        fn = _EPOCH_JIT_CACHE["epoch_pass"] = jax.jit(
            _fused_epoch_pass, static_argnames=("apply_eb",))
        fn = _EPOCH_JIT_CACHE["epoch_pass"] = _dtel.instrument(
            "ops/epoch_kernels.py::_epoch_pass_jit@_fused_epoch_pass",
            fn)
    return fn


def epoch_pass_device(columns: dict, tables: dict, params: np.ndarray, *,
                      apply_eb: bool, shardings=None):
    """Dispatch the fused pass; returns host numpy (scores, balances, eff).

    ``columns``: bucket-padded host arrays (int32 eff_incr, int64
    balances/scores/epochs, uint8 prev_part, bool slashed).  ``tables``:
    int64 reward/penalty/slash tables.  ``shardings``: optional
    (column_sharding, table_sharding) NamedShardings from
    parallel/epoch_sharded — the same program runs mesh-partitioned.
    """
    fn = _epoch_pass_jit()
    with jax.enable_x64():
        col_sh = tbl_sh = None
        if shardings is not None:
            col_sh, tbl_sh = shardings

        def put(arr, sh):
            a = jnp.asarray(arr)
            return jax.device_put(a, sh) if sh is not None else a

        out = fn(
            put(columns["eff_incr"], col_sh),
            put(columns["balances"], col_sh),
            put(columns["scores"], col_sh),
            put(columns["prev_part"], col_sh),
            put(columns["slashed"], col_sh),
            put(columns["activation"], col_sh),
            put(columns["exit_epoch"], col_sh),
            put(columns["withdrawable"], col_sh),
            put(tables["reward"], tbl_sh),
            put(tables["penalty"], tbl_sh),
            put(tables["slash"], tbl_sh),
            put(params, tbl_sh),
            apply_eb=apply_eb,
        )
        # the pass's single d2h commit point: three column fetches
        sc, bal, eff = (np.asarray(o) for o in out)
    return sc, bal, eff


# --------------------------------------------------------------------------
# Swap-or-not shuffle rounds
# --------------------------------------------------------------------------

def _shuffle_rounds(cur0, pivots, src_bytes, count, *, rounds: int):
    """All ``rounds`` swap-or-not rounds for every position at once.

    cur0: int32[Npad] start positions; pivots: int32[rounds];
    src_bytes: uint8[rounds, Npad // 8] per-round source bytes (lane i's
    decision bit for position p lives at byte p >> 3, bit p & 7 — the
    flattened hash(seed ‖ round ‖ chunk) layout); count: int32 scalar.
    Tail lanes (>= count) compute in-range garbage and are discarded by
    the caller's slice.
    """
    def body(r, cur):
        pivot = pivots[r]
        flip = jnp.mod(pivot - cur, count)
        position = jnp.maximum(cur, flip)
        row = jax.lax.dynamic_index_in_dim(
            src_bytes, r, axis=0, keepdims=False)
        byte = row[position >> 3]
        bit = (byte.astype(jnp.int32) >> (position & 7)) & 1
        return jnp.where(bit == 1, flip, cur)

    return jax.lax.fori_loop(0, rounds, body, cur0)


def _shuffle_jit(rounds: int):
    fn = _SHUFFLE_JIT_CACHE.get(rounds)
    if fn is None:
        fn = _SHUFFLE_JIT_CACHE[rounds] = jax.jit(
            partial(_shuffle_rounds, rounds=rounds))
        fn = _SHUFFLE_JIT_CACHE[rounds] = _dtel.instrument(
            "ops/epoch_kernels.py::_shuffle_jit@_shuffle_rounds", fn)
    return fn


def shuffle_rounds_device(count: int, pivots: np.ndarray,
                          src_bytes: np.ndarray, bucket: int) -> np.ndarray:
    """Forward swap-or-not map for positions [0, count) on device.

    Returns int32[count]: out[i] = final position of the walk started at
    i — exactly ``compute_shuffled_index(i, count, seed, rounds)``.
    ``bucket`` is the pow2 lane count (>= count, multiple of 256 so the
    byte plane is in-bounds for every tail lane).
    """
    rounds = int(pivots.shape[0])
    assert bucket % 256 == 0 and bucket >= count
    padded = np.zeros((rounds, bucket // 8), dtype=np.uint8)
    padded[:, : src_bytes.shape[1]] = src_bytes
    cur0 = np.arange(bucket, dtype=np.int32)
    fn = _shuffle_jit(rounds)
    out = fn(jnp.asarray(cur0), jnp.asarray(pivots.astype(np.int32)),
             jnp.asarray(padded), jnp.int32(count))
    # single d2h commit point for the shuffle program
    return np.asarray(out)[:count]
