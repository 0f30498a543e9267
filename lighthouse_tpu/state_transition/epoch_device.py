"""Host side of the device epoch pass: exact tables, clamps, buckets.

Bridges the columnar beacon state to ops/epoch_kernels: computes the
host reductions the kernel's gather tables need (total active balance,
per-flag unslashed participating increments, the proportional-slashings
numerator) with arbitrary-precision Python ints, clamps the uint64
epoch columns into the int64 lane world, pads everything into the pow2
shape bucket, dispatches, and applies the outputs all-or-nothing.

The table trick is what makes the device pass bit-identical to the
numpy/bigint reference: every spec quantity that depends only on a
validator's effective-balance *increment count* (per-flag reward,
per-flag penalty, proportional slashing penalty) is evaluated host-side
over all ``max_effective_balance // increment + 1`` possible counts and
gathered by lane on device — no runtime division ever runs in-kernel
except the inactivity penalty's division by the constant
``bias * quotient`` denominator (guarded below against int64 overflow;
an overflow-risk state falls back to the reference backend).

This module imports jax only inside :func:`prepare_and_run` — the seam
in epoch_processing guarantees it is reached only when a device rung
was actually selected (fast tests stay zero-XLA).
"""

from __future__ import annotations

import numpy as np

from lighthouse_tpu import types as T
from lighthouse_tpu.common import env as envreg
from lighthouse_tpu.state_transition import misc

#: epoch columns are clamped to this before entering int64 lanes
#: (FAR_FUTURE_EPOCH = 2**64-1 maps here; every comparison the kernel
#: makes is preserved because real epochs are far below it)
EPOCH_CLAMP = 1 << 62

#: default pow2 bucket floor (LHTPU_EPOCH_BUCKET_FLOOR); multiples of
#: 256 keep the shuffle byte plane in-bounds for tail lanes too
BUCKET_FLOOR_DEFAULT = 256


class DeviceEpochOutcome:
    """Applied device pass: scores+balances written; non-electra
    hysteresis output deferred until after registry updates."""

    __slots__ = ("deferred_eff", "stages")

    def __init__(self, deferred_eff, stages):
        self.deferred_eff = deferred_eff
        self.stages = stages


def bucket_floor() -> int:
    floor = envreg.get_int("LHTPU_EPOCH_BUCKET_FLOOR", BUCKET_FLOOR_DEFAULT)
    floor = max(int(floor or BUCKET_FLOOR_DEFAULT), 1)
    # pow2, and >= 256 so shuffle buckets always cover whole hash chunks
    return 1 << max(floor - 1, 255).bit_length()


def _clamp_epochs(col: np.ndarray) -> np.ndarray:
    return np.minimum(col, np.uint64(EPOCH_CLAMP)).astype(np.int64)


def _max_effective_balance(spec, fork: str) -> int:
    if fork == "electra":
        return spec.max_effective_balance_electra
    return spec.max_effective_balance


def build_tables(state, spec, fork: str, *, leak: bool) -> dict | None:
    """Exact per-increment gather tables (Python bigint host math).

    Returns None when the state can't be represented in int64 lanes
    (table value or inactivity product would overflow) — the caller
    then stays on the numpy reference, which computes in objects.
    """
    from lighthouse_tpu.state_transition import epoch_processing as ep

    v = state.validators
    incr = spec.effective_balance_increment
    max_eff = _max_effective_balance(spec, fork)
    k_count = max_eff // incr + 1
    if int(v.effective_balance.max(initial=0)) > max_eff:
        return None  # out-of-spec registry: stay on reference
    total = misc.get_total_active_balance(state, spec)
    brpi = ep.base_reward_per_increment(spec, total)
    total_increments = total // incr

    reward_t = np.zeros((3, k_count), np.int64)
    penalty_t = np.zeros((3, k_count), np.int64)
    ks = range(k_count)
    active_prev = v.is_active(misc.previous_epoch(state, spec))
    unslashed_active = active_prev & ~v.slashed
    for flag_index, weight in enumerate(ep.PARTICIPATION_FLAG_WEIGHTS):
        participated = unslashed_active & ep.has_flag(
            state.previous_epoch_participation, flag_index)
        unslashed_bal = int(v.effective_balance[participated].sum())
        u_incr = max(unslashed_bal, incr) // incr
        denom = total_increments * ep.WEIGHT_DENOMINATOR
        if not leak:
            reward_t[flag_index] = [
                (k * brpi * weight * u_incr) // denom for k in ks]
        if flag_index != ep.TIMELY_HEAD_FLAG_INDEX:
            penalty_t[flag_index] = [
                k * brpi * weight // ep.WEIGHT_DENOMINATOR for k in ks]

    mult = ep._proportional_slashing_multiplier(spec, fork)
    adjusted = min(int(state.slashings.sum()) * mult, total)
    slash_t = np.array(
        [(k * adjusted) // total * incr for k in ks], np.int64)

    # int64 overflow guards: the inactivity product eff * score and the
    # post-delta balances must fit a signed 64-bit lane
    max_score = int(state.inactivity_scores.max(initial=0))
    if max_eff * (max_score + spec.inactivity_score_bias) >= 2 ** 63:
        return None
    if int(state.balances.max(initial=0)) >= EPOCH_CLAMP:
        return None
    return {"reward": reward_t, "penalty": penalty_t, "slash": slash_t}


def build_columns(state, spec, bucket: int) -> dict:
    """Bucket-padded int64/int32 lane columns (tail lanes zeroed: every
    mask is False there, outputs are sliced ``[:n]``)."""
    v = state.validators
    n = len(v)
    incr = spec.effective_balance_increment

    def pad(arr, dtype):
        out = np.zeros(bucket, dtype=dtype)
        out[:n] = arr
        return out

    return {
        "eff_incr": pad((v.effective_balance
                         // np.uint64(incr)).astype(np.int64), np.int32),
        "balances": pad(state.balances.astype(np.int64), np.int64),
        "scores": pad(state.inactivity_scores.astype(np.int64), np.int64),
        "prev_part": pad(state.previous_epoch_participation, np.uint8),
        "slashed": pad(v.slashed, bool),
        "activation": pad(_clamp_epochs(v.activation_epoch), np.int64),
        "exit_epoch": pad(_clamp_epochs(v.exit_epoch), np.int64),
        "withdrawable": pad(_clamp_epochs(v.withdrawable_epoch), np.int64),
    }


def build_params(state, spec, fork: str, *, leak: bool) -> np.ndarray:
    from lighthouse_tpu.ops import epoch_kernels as ek
    from lighthouse_tpu.state_transition import epoch_processing as ep

    cur = misc.current_epoch(state, spec)
    incr = spec.effective_balance_increment
    hysteresis_increment = incr // spec.hysteresis_quotient
    params = np.zeros(ek.N_PARAMS, np.int64)
    params[ek.P_PREV_EPOCH] = misc.previous_epoch(state, spec)
    params[ek.P_LEAK] = int(leak)
    params[ek.P_SCORE_BIAS] = spec.inactivity_score_bias
    params[ek.P_SCORE_RECOVERY] = spec.inactivity_score_recovery_rate
    params[ek.P_INACT_DENOM] = (
        spec.inactivity_score_bias
        * ep._inactivity_penalty_quotient(spec, fork))
    params[ek.P_SLASH_TARGET] = (
        cur + spec.preset.epochs_per_slashings_vector // 2)
    params[ek.P_INCREMENT] = incr
    params[ek.P_HYST_DOWN] = (
        hysteresis_increment * spec.hysteresis_downward_multiplier)
    params[ek.P_HYST_UP] = (
        hysteresis_increment * spec.hysteresis_upward_multiplier)
    params[ek.P_MAX_EFF] = spec.max_effective_balance
    return params


def prepare_and_run(state, spec, fork: str, backend: str):
    """Full device epoch core: prep → one fused dispatch → apply.

    Returns a DeviceEpochOutcome (scores/balances written to ``state``,
    hysteresis deferred) or None when the state is guarded out.  State
    is mutated only after every device fetch has completed, so a fault
    anywhere leaves it untouched for the reference re-run.
    """
    from lighthouse_tpu.state_transition import epoch_processing as ep

    cur = misc.current_epoch(state, spec)
    n = len(state.validators)
    if n == 0 or cur == T.GENESIS_EPOCH:
        return None  # genesis epoch skips inactivity/rewards entirely
    with ep.epoch_stage_span("prep_host") as prep:
        leak = ep.is_in_inactivity_leak(state, spec)
        tables = build_tables(state, spec, fork, leak=leak)
        if tables is None:
            return None
        from lighthouse_tpu.ops import epoch_kernels as ek

        bucket = ek.bucket_size(n, bucket_floor())
        columns = build_columns(state, spec, bucket)
        params = build_params(state, spec, fork, leak=leak)
        apply_eb = fork != "electra"
    with ep.epoch_stage_span("dispatch") as dispatch:
        if backend == "sharded":
            from lighthouse_tpu.parallel.epoch_sharded import (
                epoch_pass_sharded,
            )

            sc, bal, eff = epoch_pass_sharded(
                columns, tables, params, apply_eb=apply_eb)
        else:
            sc, bal, eff = ek.epoch_pass_device(
                columns, tables, params, apply_eb=apply_eb)
    # all-or-nothing apply (every fetch is done; nothing below can raise)
    with ep.epoch_stage_span("apply"):
        state.inactivity_scores = sc[:n].astype(np.uint64)
        state.balances = bal[:n].astype(np.uint64)
        deferred = eff[:n].astype(np.uint64) if apply_eb else None
    return DeviceEpochOutcome(deferred, {
        "prep_host_ms": prep.duration_ms(),
        "dispatch_ms": dispatch.duration_ms(),
    })
