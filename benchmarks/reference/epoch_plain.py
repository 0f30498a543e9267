"""Plain reference for the state cell: ``process_slots`` across one epoch
boundary of a capella state, in numpy and hashlib.

Written from consensus-specs ``specs/{phase0,altair,bellatrix,capella}/
beacon-chain.md`` in the spec's own order (the program fuses and reorders
its passes).  The state is the dict ``ssz_plain.state_root`` hashes; the
constants are the configuration file's ``preset`` and ``config`` groups.
Per-validator arithmetic that can pass 2**63 runs on Python integers.

What a two-slot advance from the last slot of epoch 1 cannot reach raises
instead of being approximated: a sync-committee rotation (needs the
shuffle and a key aggregate), a historical summary, and justification
(the spec skips it while current_epoch <= GENESIS_EPOCH + 1).
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.reference import ssz_plain as ssz

FAR = 2**64 - 1
WEIGHTS = (14, 26, 14)  # TIMELY_SOURCE, TIMELY_TARGET, TIMELY_HEAD
WEIGHT_DENOMINATOR = 64
TARGET_FLAG, HEAD_FLAG = 1, 2


def _flag(part, i):
    return (part >> np.uint8(i)) & np.uint8(1) != 0


def _active(v, epoch):
    e = np.uint64(epoch)
    return (v["activation_epoch"] <= e) & (e < v["exit_epoch"])


def _epoch(s, p):
    return int(s["slot"]) // p["SLOTS_PER_EPOCH"]


def _total_active(s, p, c):
    v = s["validators"]
    total = int(v["effective_balance"][_active(v, _epoch(s, p))].sum(dtype=object))
    return max(c["EFFECTIVE_BALANCE_INCREMENT"], total)


def _eligible(s, prev):
    v = s["validators"]
    return _active(v, prev) | (
        v["slashed"] & (np.uint64(prev + 1) < v["withdrawable_epoch"]))


def _leak(s, prev, c):
    return prev - int(s["finalized_checkpoint"]["epoch"]) \
        > c["MIN_EPOCHS_TO_INACTIVITY_PENALTY"]


def process_epoch(s: dict, p: dict, c: dict, precision: str = "exact") -> None:
    cur = _epoch(s, p)
    if cur <= 1:
        pass  # process_justification_and_finalization returns at once
    else:
        raise NotImplementedError("justification: epoch > 1 is not this cell")
    prev = max(cur - 1, 0)
    v = s["validators"]
    n = v["effective_balance"].shape[0]
    incr = c["EFFECTIVE_BALANCE_INCREMENT"]
    part = s["previous_epoch_participation"]
    eligible = _eligible(s, prev)
    unslashed_prev = _active(v, prev) & ~v["slashed"]
    leak = _leak(s, prev, c)

    # process_inactivity_updates
    if cur != 0:
        scores = s["inactivity_scores"].astype(np.int64)
        target = unslashed_prev & _flag(part, TARGET_FLAG)
        scores = np.where(eligible & target, scores - np.minimum(1, scores), scores)
        scores = np.where(eligible & ~target,
                          scores + c["INACTIVITY_SCORE_BIAS"], scores)
        if not leak:
            scores = np.where(
                eligible,
                scores - np.minimum(c["INACTIVITY_SCORE_RECOVERY_RATE"], scores),
                scores)
        s["inactivity_scores"] = scores.astype(np.uint64)

    # process_rewards_and_penalties
    if cur != 0:
        total = _total_active(s, p, c)
        per_incr = incr * c["BASE_REWARD_FACTOR"] // math.isqrt(total)
        base = (v["effective_balance"] // np.uint64(incr)).astype(np.int64) * per_incr
        if precision == "int32":
            # the control: the flag rewards on 32-bit integers, the nearest
            # width below the 64 bits the spec states and the one a TPU
            # multiplies natively; the products wrap
            def reward(weight, took_incr):
                with np.errstate(over="ignore"):
                    num = (base.astype(np.int32) * np.int32(weight)
                           * np.int64(took_incr).astype(np.int32))
                    den = np.int64(total_incr * WEIGHT_DENOMINATOR).astype(
                        np.int32)
                    return (num // (den if den else np.int32(1))).astype(np.int64)
        else:
            def reward(weight, took_incr):
                return base * weight * took_incr // (
                    total_incr * WEIGHT_DENOMINATOR)
        total_incr = total // incr
        # the spec applies each (rewards, penalties) pair in turn, the
        # decrease saturating at zero every time
        bal = s["balances"].astype(np.int64)
        for flag, weight in enumerate(WEIGHTS):
            took = unslashed_prev & _flag(part, flag)
            took_incr = max(int(v["effective_balance"][took].sum(dtype=object)),
                            incr) // incr
            if not leak:
                bal += np.where(eligible & took, reward(weight, took_incr), 0)
            if flag != HEAD_FLAG:
                bal = np.maximum(bal - np.where(
                    eligible & ~took, base * weight // WEIGHT_DENOMINATOR, 0), 0)
        on_target = unslashed_prev & _flag(part, TARGET_FLAG)
        penalty = (v["effective_balance"].astype(object)
                   * s["inactivity_scores"].astype(object)) // (
            c["INACTIVITY_SCORE_BIAS"] * c["INACTIVITY_PENALTY_QUOTIENT_BELLATRIX"])
        bal = np.maximum(bal - np.where(
            eligible & ~on_target, penalty.astype(np.int64), 0), 0)
        s["balances"] = bal.astype(np.uint64)

    # process_registry_updates
    far = np.uint64(FAR)
    queue = (v["activation_eligibility_epoch"] == far) & (
        v["effective_balance"] == np.uint64(c["MAX_EFFECTIVE_BALANCE"]))
    v["activation_eligibility_epoch"][queue] = cur + 1
    active_cur = _active(v, cur)
    churn = max(c["MIN_PER_EPOCH_CHURN_LIMIT"],
                int(active_cur.sum()) // c["CHURN_LIMIT_QUOTIENT"])
    eject = np.nonzero(active_cur & (
        v["effective_balance"] <= np.uint64(c["EJECTION_BALANCE"])))[0]
    # initiate_validator_exit for each, in index order.  The spec rescans
    # the exit queue per call; its tail epoch and occupancy are carried
    # along instead, which is the same sequence (an ejection only ever
    # appends to the tail, and the churn limit reads the current epoch's
    # active set, which a future exit epoch does not change).
    exits = v["exit_epoch"][v["exit_epoch"] != far]
    tail = max(int(exits.max()) if exits.size else 0,
               cur + 1 + c["MAX_SEED_LOOKAHEAD"])
    occupancy = int((exits == np.uint64(tail)).sum())
    for idx in eject:
        if v["exit_epoch"][idx] != far:
            continue
        if occupancy >= churn:
            tail, occupancy = tail + 1, 0
        v["exit_epoch"][idx] = tail
        v["withdrawable_epoch"][idx] = tail + c["MIN_VALIDATOR_WITHDRAWABILITY_DELAY"]
        occupancy += 1
    finalized = int(s["finalized_checkpoint"]["epoch"])
    pending = np.nonzero(
        (v["activation_eligibility_epoch"] <= np.uint64(finalized))
        & (v["activation_epoch"] == far))[0]
    order = pending[np.lexsort(
        (pending, v["activation_eligibility_epoch"][pending]))]
    v["activation_epoch"][order[:churn]] = cur + 1 + c["MAX_SEED_LOOKAHEAD"]

    # process_slashings
    total = _total_active(s, p, c)
    adjusted = min(int(s["slashings"].sum(dtype=object))
                   * c["PROPORTIONAL_SLASHING_MULTIPLIER_BELLATRIX"], total)
    hit = v["slashed"] & (v["withdrawable_epoch"] == np.uint64(
        cur + p["EPOCHS_PER_SLASHINGS_VECTOR"] // 2))
    if hit.any():
        eff = v["effective_balance"][hit].astype(object)
        pen = (eff // incr * adjusted) // total * incr
        bal = s["balances"][hit].astype(object) - pen
        s["balances"][hit] = np.maximum(bal, 0).astype(np.uint64)

    # process_eth1_data_reset
    nxt = cur + 1
    if nxt % p["EPOCHS_PER_ETH1_VOTING_PERIOD"] == 0:
        s["eth1_data_votes"] = []

    # process_effective_balance_updates
    bal, eff = s["balances"], v["effective_balance"]
    hyst = incr // c["HYSTERESIS_QUOTIENT"]
    down = np.uint64(hyst * c["HYSTERESIS_DOWNWARD_MULTIPLIER"])
    up = np.uint64(hyst * c["HYSTERESIS_UPWARD_MULTIPLIER"])
    move = (bal + down < eff) | (eff + up < bal)
    v["effective_balance"] = np.where(
        move, np.minimum(bal - bal % np.uint64(incr),
                         np.uint64(c["MAX_EFFECTIVE_BALANCE"])), eff)

    # resets and rotations
    s["slashings"][nxt % p["EPOCHS_PER_SLASHINGS_VECTOR"]] = 0
    m = p["EPOCHS_PER_HISTORICAL_VECTOR"]
    s["randao_mixes"][nxt % m] = s["randao_mixes"][cur % m]
    if nxt % (p["SLOTS_PER_HISTORICAL_ROOT"] // p["SLOTS_PER_EPOCH"]) == 0:
        raise NotImplementedError("historical summary: not this cell")
    s["previous_epoch_participation"] = s["current_epoch_participation"]
    s["current_epoch_participation"] = np.zeros(n, np.uint8)
    if nxt % p["EPOCHS_PER_SYNC_COMMITTEE_PERIOD"] == 0:
        raise NotImplementedError("sync committee rotation: not this cell")


def process_slot(s: dict, p: dict) -> bytes:
    root = ssz.state_root(s, p)
    at = int(s["slot"]) % p["SLOTS_PER_HISTORICAL_ROOT"]
    s["state_roots"][at] = np.frombuffer(root, np.uint8)
    if s["latest_block_header"]["state_root"] == b"\x00" * 32:
        s["latest_block_header"]["state_root"] = root
    s["block_roots"][at] = np.frombuffer(
        ssz.header(s["latest_block_header"]), np.uint8)
    return root


def process_slots(s: dict, p: dict, c: dict, target_slot: int,
                  precision: str = "exact") -> list:
    """Advance in place; returns the state root cached at each slot."""
    roots = []
    while int(s["slot"]) < target_slot:
        roots.append(process_slot(s, p))
        if (int(s["slot"]) + 1) % p["SLOTS_PER_EPOCH"] == 0:
            process_epoch(s, p, c, precision)
        s["slot"] = int(s["slot"]) + 1
    return roots
