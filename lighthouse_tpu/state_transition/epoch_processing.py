"""Epoch processing (altair+), fully vectorized over validator columns.

Reference: the fused single-pass walk in
/root/reference/consensus/state_processing/src/per_epoch_processing/single_pass.rs:24-62
plus justification/finalization from the progressive-balances cache.

TPU-first rebuild: every sub-transition (inactivity, rewards/penalties,
registry updates, slashings, effective-balance hysteresis) is expressed as
numpy column arithmetic over the whole registry at once — the exact shape a
jax.jit/device version takes (no per-validator Python loop anywhere except
the strictly-ordered activation queue and exit churn serialization).

Backend seam (mirrors the crypto/bls ladder): the per-validator core of
the transition — inactivity updates, rewards/penalties, slashings and
(non-electra) effective-balance hysteresis — can run as ONE fused
device program (ops/epoch_kernels via state_transition/epoch_device,
optionally mesh-sharded through parallel/epoch_sharded).  The ladder is
``device → reference`` (``sharded`` sits beside ``device`` as a forced
or mesh-auto rung): any device fault is recovered by re-running the
numpy reference on the untouched state, a consecutive-fault circuit
breaker (same LHTPU_SUPERVISOR_* knobs as the BLS supervisor) parks a
flapping device path on the reference rung, and the device write-back
is all-or-nothing so a mid-dispatch fault can never leave a torn state.

Why the reordering is verdict-identical: the spec order is inactivity →
rewards → registry-updates → slashings → effective-balance, and the
fused pass computes slashings before the host's registry updates run.
Registry updates mutate only activation/exit/withdrawable epochs of
validators whose ``exit_epoch`` is unset — and a slashed validator's
exit epoch is ALWAYS set (slash_validator initiates the exit), so the
slashings mask (slashed ∧ withdrawable == target) reads columns the
registry pass can never touch, and registry updates read only
effective balances, which the fused pass defers (hysteresis output is
applied after registry updates, matching spec order exactly).
"""

from __future__ import annotations

import sys
import threading
import time
from functools import partial

import numpy as np

from lighthouse_tpu import types as T
from lighthouse_tpu.common import env as envreg
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import REGISTRY, record_swallowed
from lighthouse_tpu.ops.faults import PROGRAM_FAULTS as _PROGRAM_FAULTS
from lighthouse_tpu.state_transition import misc

# Participation flag indices / weights (altair).
TIMELY_SOURCE_FLAG_INDEX = 0
TIMELY_TARGET_FLAG_INDEX = 1
TIMELY_HEAD_FLAG_INDEX = 2
TIMELY_SOURCE_WEIGHT = 14
TIMELY_TARGET_WEIGHT = 26
TIMELY_HEAD_WEIGHT = 14
SYNC_REWARD_WEIGHT = 2
PROPOSER_WEIGHT = 8
WEIGHT_DENOMINATOR = 64

PARTICIPATION_FLAG_WEIGHTS = (
    TIMELY_SOURCE_WEIGHT,
    TIMELY_TARGET_WEIGHT,
    TIMELY_HEAD_WEIGHT,
)


def has_flag(participation: np.ndarray, flag_index: int) -> np.ndarray:
    return (participation >> np.uint8(flag_index)) & np.uint8(1) != 0


def add_flag(participation: np.ndarray, idx: np.ndarray, flag_index: int) -> None:
    participation[idx] |= np.uint8(1 << flag_index)


def _inactivity_penalty_quotient(spec: T.ChainSpec, fork: str) -> int:
    if fork == "altair":
        return spec.inactivity_penalty_quotient_altair
    return spec.inactivity_penalty_quotient_bellatrix


def _proportional_slashing_multiplier(spec: T.ChainSpec, fork: str) -> int:
    if fork == "phase0":
        return spec.proportional_slashing_multiplier
    if fork == "altair":
        return spec.proportional_slashing_multiplier_altair
    return spec.proportional_slashing_multiplier_bellatrix


def base_reward_per_increment(spec: T.ChainSpec, total_active_balance: int) -> int:
    return (
        spec.effective_balance_increment
        * spec.base_reward_factor
        // misc.integer_squareroot(total_active_balance)
    )


def is_in_inactivity_leak(state, spec: T.ChainSpec) -> bool:
    prev = misc.previous_epoch(state, spec)
    return prev - int(state.finalized_checkpoint.epoch) > spec.min_epochs_to_inactivity_penalty


# --- device backend seam (ladder: device/sharded -> reference) --------------

#: auto-routing floor: below this many validators a device dispatch costs
#: more than the numpy pass (and tier-1 test registries must never compile
#: XLA); override with LHTPU_EPOCH_DEVICE_MIN
_DEVICE_MIN_DEFAULT = 1 << 17

# consecutive-fault circuit breaker for the device rung (one per
# process).  The epoch pass itself is serialized under the chain's
# import commit points, but shuffle_list shares this breaker and runs
# from beacon-processor worker threads during concurrent verification,
# so every read-modify-write holds the lock — same discipline as the
# BLS supervisor state in crypto/bls/api
_BREAKER_LOCK = threading.Lock()
_BREAKER = {"fails": 0, "open_until": 0.0, "backoff": 0.0}

_EPOCH_BACKENDS = ("device", "sharded", "reference")

# memoized auto-routing rung for at-threshold registries (None = not
# yet probed; probing imports jax and initializes the platform)
_AUTO_RUNG: str | None = None


def record_epoch_stage(stage: str, seconds: float) -> None:
    """Per-stage wall time of the epoch transition: the sub-transitions
    of process_epoch and, inside the device pass, prep_host / dispatch /
    apply (sole registration site of the epoch_* metric family — lhlint
    LH501 FAMILY_OWNERS)."""
    try:
        REGISTRY.histogram(
            "epoch_stage_seconds",
            "epoch transition stage wall time",
        ).labels(stage=stage).observe(seconds)
    except Exception as e:
        record_swallowed("epoch.record_stage", e)


def epoch_stage_span(stage: str, **attrs):
    """The span ``epoch.<stage>``, whose duration also feeds
    ``epoch_stage_seconds{stage}``."""
    return tracing.span("epoch." + stage,
                        observe=partial(record_epoch_stage, stage), **attrs)


def record_epoch_fault(backend: str, kind: str) -> None:
    """Count a device epoch/shuffle fault recovered by the reference rung."""
    try:
        REGISTRY.counter(
            "epoch_supervisor_faults_total",
            "device epoch faults recovered on the reference backend",
        ).labels(backend=backend, kind=kind).inc()
    except Exception as e:
        record_swallowed("epoch.record_fault", e)


def _record_epoch_batch(backend: str, seconds: float) -> None:
    try:
        REGISTRY.counter(
            "epoch_backend_batches_total",
            "epoch core passes by executing backend",
        ).labels(backend=backend).inc()
        REGISTRY.histogram(
            "epoch_transition_seconds",
            "epoch core pass wall time by backend",
        ).labels(backend=backend).observe(seconds)
    except Exception as e:
        record_swallowed("epoch.record_batch", e)


def reset_epoch_supervisor() -> None:
    """Close the breaker and drop the memoized auto rung (tests /
    operator reset)."""
    global _AUTO_RUNG
    with _BREAKER_LOCK:
        _BREAKER.update(fails=0, open_until=0.0, backoff=0.0)
    _AUTO_RUNG = None


def resolve_epoch_backend(n_validators: int) -> str:
    """Which rung runs the fused epoch core for an ``n_validators``
    registry: LHTPU_EPOCH_BACKEND force first, then the breaker, then
    auto (device only on a real TPU at or above LHTPU_EPOCH_DEVICE_MIN —
    the XLA-CPU fallback defaults to the numpy reference: first-dispatch
    compiles dominate short-lived processes, though the warm fused
    program beats numpy there too, so operators can force the device
    rung on long-lived fallback nodes).  Small registries return
    "reference" without touching jax at all (zero-XLA fast tests)."""
    forced = envreg.get_choice("LHTPU_EPOCH_BACKEND", _EPOCH_BACKENDS)
    if forced:
        return forced
    with _BREAKER_LOCK:
        open_until = _BREAKER["open_until"]
    if open_until > time.monotonic():
        return "reference"
    device_min = envreg.get_int("LHTPU_EPOCH_DEVICE_MIN",
                                _DEVICE_MIN_DEFAULT)
    if n_validators < max(device_min, 1):
        return "reference"
    global _AUTO_RUNG
    rung = _AUTO_RUNG
    if rung is None:
        # probing the platform imports jax (multi-second XLA init on a
        # cold process); memoize under the lock so concurrent thread
        # roots (worker threads, the interop duty loop) pay it once —
        # the losers block on the winner instead of double-probing
        with _BREAKER_LOCK:
            if _AUTO_RUNG is None:
                import jax

                if jax.devices()[0].platform != "tpu":
                    _AUTO_RUNG = "reference"
                else:
                    _AUTO_RUNG = ("sharded" if len(jax.devices()) > 1
                                  else "device")
            rung = _AUTO_RUNG
    return rung


def _breaker_ok() -> None:
    """A successful device dispatch (epoch pass OR shuffle — they share
    the breaker) closes the consecutive-fault count and the backoff."""
    was_tripped = False
    with _BREAKER_LOCK:
        was_tripped = _BREAKER["open_until"] > 0.0
        _BREAKER["fails"] = 0
        _BREAKER["backoff"] = 0.0
        _BREAKER["open_until"] = 0.0
    if was_tripped:
        from lighthouse_tpu.common import flight_recorder as flight

        flight.emit("breaker", plane="epoch", old="open", new="closed")


def _breaker_fault() -> None:
    threshold = envreg.get_int("LHTPU_SUPERVISOR_FAILS", 1) or 1
    backoff_init = float(
        envreg.get_float("LHTPU_SUPERVISOR_BACKOFF_S", 1.0) or 1.0)
    ceiling = float(
        envreg.get_float("LHTPU_SUPERVISOR_BACKOFF_MAX_S", 60.0) or 60.0)
    opened = False
    with _BREAKER_LOCK:
        fails = _BREAKER["fails"] = _BREAKER["fails"] + 1
        if fails >= threshold:
            backoff = _BREAKER["backoff"] or backoff_init
            _BREAKER["open_until"] = time.monotonic() + backoff
            _BREAKER["backoff"] = min(backoff * 2, ceiling)
            _BREAKER["fails"] = 0
            opened = True
    from lighthouse_tpu.common import flight_recorder as flight

    flight.emit("breaker", plane="epoch", old="closed",
                new="open" if opened else "counting", fails=fails)
    if opened:
        # the epoch breaker opening is a trip condition: the dump shows
        # the device faults that benched the fused pass
        flight.trip("epoch_breaker_open", fails=fails)


def _maybe_device_epoch(state, spec: T.ChainSpec, fork: str):
    """Try the fused device pass; None means the caller must run the
    numpy reference sub-transitions (not applicable, guarded out, or a
    recovered device fault — state is untouched in every failure case)."""
    n = len(state.validators)
    backend = resolve_epoch_backend(n)
    if backend == "reference":
        return None
    from lighthouse_tpu.state_transition import epoch_device

    try:
        with tracing.span("epoch.device_pass", backend=backend,
                          n=n) as device_pass:
            out = epoch_device.prepare_and_run(state, spec, fork, backend)
    except _PROGRAM_FAULTS:
        # the device module failed to import or trace — a fault of the
        # program, not of the device: loud, never a breaker fault
        raise
    except Exception as exc:  # device fault: recover on reference
        record_epoch_fault(backend, type(exc).__name__)
        _breaker_fault()
        return None
    if out is None:
        return None
    _breaker_ok()
    _record_epoch_batch(backend, device_pass.duration_s())
    return out


def process_epoch(state, spec: T.ChainSpec) -> None:
    """Full epoch transition, mutating `state` in place (altair+ forks)."""
    fork = spec.fork_at_epoch(misc.current_epoch(state, spec))
    if fork == "phase0":
        from lighthouse_tpu.state_transition.phase0_epoch import (
            process_epoch_phase0,
        )

        process_epoch_phase0(state, spec)
        return
    with epoch_stage_span("justification"):
        process_justification_and_finalization(state, spec)
    dev = _maybe_device_epoch(state, spec, fork)
    if dev is None:
        with epoch_stage_span("inactivity") as inactivity:
            process_inactivity_updates(state, spec)
        with epoch_stage_span("rewards") as rewards:
            process_rewards_and_penalties(state, spec, fork)
    with epoch_stage_span("registry_updates"):
        process_registry_updates(state, spec, fork)
    if dev is None:
        # epoch_transition_seconds{backend=reference} spans exactly the
        # stages the device pass covers (inactivity, rewards/penalties,
        # slashings) — registry updates run on the host under EVERY
        # backend and are excluded, so the two series are comparable
        with epoch_stage_span("slashings") as slashings:
            process_slashings(state, spec, fork)
        _record_epoch_batch("reference", sum(
            sp.duration_s() for sp in (inactivity, rewards, slashings)))
    with epoch_stage_span("resets"):
        process_eth1_data_reset(state, spec)
    if fork == "electra":
        from lighthouse_tpu.state_transition.electra import (
            process_effective_balance_updates_electra,
            process_pending_balance_deposits,
            process_pending_consolidations,
        )

        with epoch_stage_span("pending_balance"):
            process_pending_balance_deposits(state, spec)
            process_pending_consolidations(state, spec)
        with epoch_stage_span("effective_balance"):
            process_effective_balance_updates_electra(state, spec)
    else:
        with epoch_stage_span("effective_balance"):
            if dev is not None and dev.deferred_eff is not None:
                # the fused pass's hysteresis output, applied at the
                # spec's effective-balance-update point (after registry
                # updates)
                state.validators.effective_balance = dev.deferred_eff
            else:
                process_effective_balance_updates(state, spec)
    # the four cheap resets and the flag rotation, one stage with the
    # eth1 reset above
    with epoch_stage_span("resets"):
        process_slashings_reset(state, spec)
        process_randao_mixes_reset(state, spec)
        process_historical_update(state, spec, fork)
        process_participation_flag_updates(state)
    with epoch_stage_span("sync_committee"):
        process_sync_committee_updates(state, spec)
    # registry write-back hook: the epoch boundary is where the prior
    # epoch's deposits have settled into the registry — refresh the
    # device-resident pubkey table eagerly (all-or-nothing swap inside
    # the plane; a no-op unless a device rung is armed).  Guarded on
    # sys.modules so pure state-transition processes never pull the
    # chain package (or jax) just for the hook.  Never raises.
    plane = sys.modules.get("lighthouse_tpu.chain.pubkey_plane")
    if plane is not None:
        plane.notify_registry(state.validators)


# --- justification / finalization ------------------------------------------

def _unslashed_participating_balance(state, spec, flag_index: int, epoch: int) -> int:
    cur = misc.current_epoch(state, spec)
    part = (
        state.current_epoch_participation
        if epoch == cur
        else state.previous_epoch_participation
    )
    active = state.validators.is_active(epoch)
    mask = active & ~state.validators.slashed & has_flag(part, flag_index)
    total = int(state.validators.effective_balance[mask].sum())
    return max(spec.effective_balance_increment, total)


def process_justification_and_finalization(state, spec: T.ChainSpec) -> None:
    cur = misc.current_epoch(state, spec)
    if cur <= T.GENESIS_EPOCH + 1:
        return
    prev = misc.previous_epoch(state, spec)
    total = misc.get_total_active_balance(state, spec)
    prev_target = _unslashed_participating_balance(
        state, spec, TIMELY_TARGET_FLAG_INDEX, prev)
    cur_target = _unslashed_participating_balance(
        state, spec, TIMELY_TARGET_FLAG_INDEX, cur)
    weigh_justification_and_finalization(
        state, spec, total, prev_target, cur_target)


def weigh_justification_and_finalization(
    state, spec: T.ChainSpec, total: int, prev_target: int, cur_target: int
) -> None:
    cur = misc.current_epoch(state, spec)
    prev = misc.previous_epoch(state, spec)
    old_prev_justified = state.previous_justified_checkpoint
    old_cur_justified = state.current_justified_checkpoint

    state.previous_justified_checkpoint = old_cur_justified
    bits = list(state.justification_bits)
    bits = [False] + bits[:-1]
    if prev_target * 3 >= total * 2:
        state.current_justified_checkpoint = T.Checkpoint(
            epoch=prev, root=misc.get_block_root(state, spec, prev))
        bits[1] = True
    if cur_target * 3 >= total * 2:
        state.current_justified_checkpoint = T.Checkpoint(
            epoch=cur, root=misc.get_block_root(state, spec, cur))
        bits[0] = True
    state.justification_bits = bits

    # finalization rules
    if all(bits[1:4]) and int(old_prev_justified.epoch) + 3 == cur:
        state.finalized_checkpoint = old_prev_justified
    if all(bits[1:3]) and int(old_prev_justified.epoch) + 2 == cur:
        state.finalized_checkpoint = old_prev_justified
    if all(bits[0:3]) and int(old_cur_justified.epoch) + 2 == cur:
        state.finalized_checkpoint = old_cur_justified
    if all(bits[0:2]) and int(old_cur_justified.epoch) + 1 == cur:
        state.finalized_checkpoint = old_cur_justified


# --- inactivity -------------------------------------------------------------

def _eligible_validator_mask(state, spec) -> np.ndarray:
    prev = misc.previous_epoch(state, spec)
    v = state.validators
    active_prev = v.is_active(prev)
    return active_prev | (
        v.slashed & (np.uint64(prev + 1) < v.withdrawable_epoch)
    )


def process_inactivity_updates(state, spec: T.ChainSpec) -> None:
    cur = misc.current_epoch(state, spec)
    if cur == T.GENESIS_EPOCH:
        return
    prev = misc.previous_epoch(state, spec)
    v = state.validators
    scores = state.inactivity_scores.astype(np.int64)
    eligible = _eligible_validator_mask(state, spec)
    target = (
        v.is_active(prev)
        & ~v.slashed
        & has_flag(state.previous_epoch_participation, TIMELY_TARGET_FLAG_INDEX)
    )
    scores = np.where(eligible & target, scores - np.minimum(1, scores), scores)
    scores = np.where(
        eligible & ~target, scores + spec.inactivity_score_bias, scores)
    if not is_in_inactivity_leak(state, spec):
        dec = np.minimum(spec.inactivity_score_recovery_rate, scores)
        scores = np.where(eligible, scores - dec, scores)
    state.inactivity_scores = scores.astype(np.uint64)


# --- rewards / penalties ----------------------------------------------------

def process_rewards_and_penalties(state, spec: T.ChainSpec, fork: str) -> None:
    cur = misc.current_epoch(state, spec)
    if cur == T.GENESIS_EPOCH:
        return
    prev = misc.previous_epoch(state, spec)
    v = state.validators
    n = len(v)
    total = misc.get_total_active_balance(state, spec)
    brpi = base_reward_per_increment(spec, total)
    increments = (v.effective_balance // np.uint64(spec.effective_balance_increment)).astype(np.int64)
    base_rewards = increments * brpi

    eligible = _eligible_validator_mask(state, spec)
    active_prev_unslashed = v.is_active(prev) & ~v.slashed
    leak = is_in_inactivity_leak(state, spec)
    total_increments = total // spec.effective_balance_increment

    delta = np.zeros(n, dtype=np.int64)
    for flag_index, weight in enumerate(PARTICIPATION_FLAG_WEIGHTS):
        participated = active_prev_unslashed & has_flag(
            state.previous_epoch_participation, flag_index)
        unslashed_bal = int(v.effective_balance[participated].sum())
        unslashed_increments = max(
            unslashed_bal, spec.effective_balance_increment
        ) // spec.effective_balance_increment
        if not leak:
            reward_num = base_rewards * weight * unslashed_increments
            delta += np.where(
                eligible & participated,
                reward_num // (total_increments * WEIGHT_DENOMINATOR),
                0,
            )
        if flag_index != TIMELY_HEAD_FLAG_INDEX:
            delta -= np.where(
                eligible & ~participated,
                base_rewards * weight // WEIGHT_DENOMINATOR,
                0,
            )
    # inactivity penalties (target non-participants pay score-scaled penalty)
    target_participant = active_prev_unslashed & has_flag(
        state.previous_epoch_participation, TIMELY_TARGET_FLAG_INDEX)
    ipq = _inactivity_penalty_quotient(spec, fork)
    scores = state.inactivity_scores.astype(object)
    eff_obj = v.effective_balance.astype(object)
    penalty = (eff_obj * scores) // (spec.inactivity_score_bias * ipq)
    delta -= np.where(eligible & ~target_participant, penalty.astype(np.int64), 0)

    bal = state.balances.astype(np.int64) + delta
    state.balances = np.maximum(bal, 0).astype(np.uint64)


# --- registry updates -------------------------------------------------------

def initiate_validator_exit(state, spec: T.ChainSpec, index: int) -> None:
    v = state.validators
    if v.exit_epoch[index] != np.uint64(T.FAR_FUTURE_EPOCH):
        return
    exiting = v.exit_epoch[v.exit_epoch != np.uint64(T.FAR_FUTURE_EPOCH)]
    activation_exit = spec.compute_activation_exit_epoch(misc.current_epoch(state, spec))
    exit_queue_epoch = max(
        int(exiting.max()) if exiting.size else 0, activation_exit)
    churn = misc.get_validator_churn_limit(state, spec)
    if int((exiting == np.uint64(exit_queue_epoch)).sum()) >= churn:
        exit_queue_epoch += 1
    v.exit_epoch[index] = exit_queue_epoch
    v.withdrawable_epoch[index] = (
        exit_queue_epoch + spec.min_validator_withdrawability_delay)


def initiate_validator_exits(state, spec: T.ChainSpec, indices) -> None:
    """Batched `initiate_validator_exit` over `indices` (ascending
    registry order), with identical sequential queue semantics.

    The scalar function re-scans every exit epoch AND re-counts the
    active set per call; under a mass ejection (a leak pushing lanes to
    the ejection balance) that is O(ejections x n) — minutes at 2^20.
    The queue state the scan derives (current tail epoch + occupancy)
    and the churn limit (active count at the current epoch, which an
    ejection never changes: exit epochs land strictly in the future)
    are loop-invariant, so one O(n) setup feeds an O(1) walk."""
    v = state.validators
    far = np.uint64(T.FAR_FUTURE_EPOCH)
    activation_exit = spec.compute_activation_exit_epoch(
        misc.current_epoch(state, spec))
    churn = misc.get_validator_churn_limit(state, spec)
    exiting = v.exit_epoch[v.exit_epoch != far]
    queue_epoch = max(int(exiting.max()) if exiting.size else 0,
                      activation_exit)
    queue_count = int((exiting == np.uint64(queue_epoch)).sum())
    delay = spec.min_validator_withdrawability_delay
    for idx in indices:
        if v.exit_epoch[idx] != far:
            continue
        if queue_count >= churn:
            queue_epoch += 1
            queue_count = 0
        v.exit_epoch[idx] = queue_epoch
        v.withdrawable_epoch[idx] = queue_epoch + delay
        queue_count += 1


def process_registry_updates(state, spec: T.ChainSpec,
                             fork: str | None = None) -> None:
    v = state.validators
    cur = misc.current_epoch(state, spec)
    electra = fork == "electra"
    # eligibility for the activation queue (electra EIP-7251: any balance
    # at or above MIN_ACTIVATION_BALANCE qualifies, not only exactly-max)
    if electra:
        eligible = (
            (v.activation_eligibility_epoch
             == np.uint64(T.FAR_FUTURE_EPOCH))
            & (v.effective_balance >= np.uint64(spec.min_activation_balance)))
    else:
        eligible = v.is_eligible_for_activation_queue(
            spec.max_effective_balance)
    v.activation_eligibility_epoch[eligible] = cur + 1
    # ejections
    eject = v.is_active(cur) & (
        v.effective_balance <= np.uint64(spec.ejection_balance))
    eject_idx = np.nonzero(eject)[0]
    if eject_idx.size:
        if electra:
            from lighthouse_tpu.state_transition.electra import (
                get_activation_exit_churn_limit,
                initiate_validator_exit_electra,
            )

            # the balance-weighted churn limit scans the active set;
            # ejections never change it (exit epochs land in the
            # future, effective balances are untouched) — one scan
            # serves the whole sweep
            per_epoch_churn = get_activation_exit_churn_limit(state, spec)
            for idx in eject_idx:
                initiate_validator_exit_electra(
                    state, spec, int(idx), per_epoch_churn=per_epoch_churn)
        else:
            initiate_validator_exits(state, spec, eject_idx)
    # activation queue (ordered by eligibility epoch then index, bounded
    # by finality; electra drops the head-count churn — activations are
    # budgeted by the pending-deposit balance churn instead)
    finalized = int(state.finalized_checkpoint.epoch)
    pending = (
        (v.activation_eligibility_epoch <= np.uint64(finalized))
        & (v.activation_epoch == np.uint64(T.FAR_FUTURE_EPOCH))
    )
    idxs = np.nonzero(pending)[0]
    order = np.lexsort((idxs, v.activation_eligibility_epoch[idxs]))
    if electra:
        dequeued = idxs[order]
    else:
        churn = misc.get_validator_activation_churn_limit(state, spec)
        dequeued = idxs[order][:churn]
    v.activation_epoch[dequeued] = spec.compute_activation_exit_epoch(cur)


# --- slashings --------------------------------------------------------------

def process_slashings(state, spec: T.ChainSpec, fork: str) -> None:
    cur = misc.current_epoch(state, spec)
    total = misc.get_total_active_balance(state, spec)
    mult = _proportional_slashing_multiplier(spec, fork)
    adjusted = min(int(state.slashings.sum()) * mult, total)
    v = state.validators
    target_epoch = cur + spec.preset.epochs_per_slashings_vector // 2
    mask = v.slashed & (v.withdrawable_epoch == np.uint64(target_epoch))
    if not mask.any():
        return
    increment = spec.effective_balance_increment
    eff = v.effective_balance[mask].astype(object)
    penalty = (eff // increment * adjusted) // total * increment
    bal = state.balances[mask].astype(object) - penalty
    state.balances[mask] = np.maximum(bal, 0).astype(np.uint64)


# --- bookkeeping resets -----------------------------------------------------

def process_eth1_data_reset(state, spec: T.ChainSpec) -> None:
    next_epoch = misc.current_epoch(state, spec) + 1
    if next_epoch % spec.preset.epochs_per_eth1_voting_period == 0:
        state.eth1_data_votes = []


def process_effective_balance_updates(state, spec: T.ChainSpec) -> None:
    v = state.validators
    bal = state.balances
    hysteresis_increment = spec.effective_balance_increment // spec.hysteresis_quotient
    downward = hysteresis_increment * spec.hysteresis_downward_multiplier
    upward = hysteresis_increment * spec.hysteresis_upward_multiplier
    eff = v.effective_balance
    update = (bal + np.uint64(downward) < eff) | (
        eff + np.uint64(upward) < bal)
    new_eff = np.minimum(
        bal - bal % np.uint64(spec.effective_balance_increment),
        np.uint64(spec.max_effective_balance),
    )
    v.effective_balance = np.where(update, new_eff, eff)


def process_slashings_reset(state, spec: T.ChainSpec) -> None:
    next_epoch = misc.current_epoch(state, spec) + 1
    state.slashings[next_epoch % spec.preset.epochs_per_slashings_vector] = 0


def process_randao_mixes_reset(state, spec: T.ChainSpec) -> None:
    cur = misc.current_epoch(state, spec)
    next_epoch = cur + 1
    n = spec.preset.epochs_per_historical_vector
    state.randao_mixes[next_epoch % n] = state.randao_mixes[cur % n]


def process_historical_update(state, spec: T.ChainSpec, fork: str) -> None:
    next_epoch = misc.current_epoch(state, spec) + 1
    period = spec.preset.slots_per_historical_root // spec.preset.slots_per_epoch
    if next_epoch % period == 0:
        summary = T.HistoricalSummary(
            block_summary_root=T.RootsVector(
                spec.preset.slots_per_historical_root).hash_tree_root(state.block_roots),
            state_summary_root=T.RootsVector(
                spec.preset.slots_per_historical_root).hash_tree_root(state.state_roots),
        )
        if hasattr(state, "historical_summaries"):
            state.historical_summaries = list(state.historical_summaries) + [summary]
        else:
            # pre-capella: append to historical_roots (HistoricalBatch root)
            t = T.make_types(spec.preset)
            batch = t.HistoricalBatch(
                block_roots=state.block_roots, state_roots=state.state_roots)
            roots = state.historical_roots
            state.historical_roots = np.concatenate(
                [roots.reshape(-1, 32),
                 np.frombuffer(batch.hash_tree_root(), np.uint8)[None, :]])


def process_participation_flag_updates(state) -> None:
    state.previous_epoch_participation = state.current_epoch_participation
    state.current_epoch_participation = np.zeros(
        len(state.validators), dtype=np.uint8)


def process_sync_committee_updates(state, spec: T.ChainSpec) -> None:
    next_epoch = misc.current_epoch(state, spec) + 1
    if next_epoch % spec.preset.epochs_per_sync_committee_period == 0:
        t = T.make_types(spec.preset)
        state.current_sync_committee = state.next_sync_committee
        state.next_sync_committee = misc.get_next_sync_committee(state, spec, t)
