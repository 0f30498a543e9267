"""Least time for the fused epoch pass: it reads and writes registry
columns once, so the bound is memory bandwidth.

Per validator, in: effective_balance, balance, inactivity_score,
activation_epoch, exit_epoch, withdrawable_epoch (8 bytes each), previous
participation and slashed (1 byte each) = 50 bytes; out: balance,
inactivity_score, effective_balance = 24 bytes.  The handful of integer
operations a lane needs are far below the bandwidth bound.
"""

BYTES_PER_VALIDATOR = 6 * 8 + 2 + 3 * 8


def least_seconds(ctx, peaks: dict, events: int) -> tuple:
    n = ctx["params"]["validators"]
    return events * n * BYTES_PER_VALIDATOR / peaks["hbm_bytes_per_s"], "memory"
