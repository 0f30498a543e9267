"""Per-slot processing and state advance.

Reference: /root/reference/consensus/state_processing/src/per_slot_processing.rs:28
and state_advance.rs (complete/partial advance).
"""

from __future__ import annotations

import numpy as np

from lighthouse_tpu import types as T
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import REGISTRY, record_swallowed
from lighthouse_tpu.state_transition import misc
from lighthouse_tpu.state_transition.epoch_processing import process_epoch


def _record_state_root(seconds: float) -> None:
    """Wall time of the per-slot state root (sole registration site of the
    state_root_* family — lhlint LH501 FAMILY_OWNERS)."""
    try:
        REGISTRY.histogram(
            "state_root_seconds",
            "state.hash_tree_root() inside process_slot",
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 2.5, 5.0,
                     30.0),
        ).observe(seconds)
    except Exception as e:
        record_swallowed("slot.record_state_root", e)


def process_slot(state, spec: T.ChainSpec) -> bytes:
    """Cache the state/block roots for the current slot.  Returns the state
    root that was cached."""
    sphr = spec.preset.slots_per_historical_root
    with tracing.span("state.root", observe=_record_state_root):
        state_root = state.hash_tree_root()
    state.state_roots[int(state.slot) % sphr] = np.frombuffer(state_root, np.uint8)
    if state.latest_block_header.state_root == b"\x00" * 32:
        state.latest_block_header = T.BeaconBlockHeader(
            slot=state.latest_block_header.slot,
            proposer_index=state.latest_block_header.proposer_index,
            parent_root=state.latest_block_header.parent_root,
            state_root=state_root,
            body_root=state.latest_block_header.body_root,
        )
    block_root = state.latest_block_header.hash_tree_root()
    state.block_roots[int(state.slot) % sphr] = np.frombuffer(block_root, np.uint8)
    return state_root


def per_slot_processing(state, spec: T.ChainSpec) -> None:
    """Advance the state by exactly one slot (epoch processing included when
    crossing an epoch boundary, fork upgrades at activation epochs)."""
    with tracing.span("state.slot", slot=int(state.slot)):
        process_slot(state, spec)
        if (int(state.slot) + 1) % spec.preset.slots_per_epoch == 0:
            with tracing.span("epoch.transition"):
                process_epoch(state, spec)
        state.slot = int(state.slot) + 1
        if int(state.slot) % spec.preset.slots_per_epoch == 0:
            from lighthouse_tpu.state_transition.upgrades import (
                upgrade_state_if_due,
            )

            upgrade_state_if_due(state, spec)


def state_advance(state, spec: T.ChainSpec, target_slot: int) -> None:
    """complete_state_advance: run per-slot processing up to target_slot."""
    if target_slot < int(state.slot):
        raise ValueError("cannot advance backwards")
    while int(state.slot) < target_slot:
        per_slot_processing(state, spec)
