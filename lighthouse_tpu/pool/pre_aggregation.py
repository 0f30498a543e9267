"""Pre-BLS coalescing: dedup + blinded same-message merge.

"Performance of EdDSA and BLS Signatures in Committee-Based Consensus"
(PAPERS.md) makes the cost model explicit: the pairing is the unit of
account, and every overlapping committee contribution merged *before*
verification is a pairing never paid for.  At mainnet width one slot's
unaggregated attestations are thousands of signature sets that share a
handful of distinct messages (the AttestationData signing root; since
electra, EIP-7549, ``data.index`` is 0, so one message per head vote of
the slot whatever the committee), so the flood batch the dispatch thread
sweeps up is massively mergeable.

Two stages.  The first is the chain's, applied to the flat
``SignatureSet`` list immediately before ``verify_signature_sets``
(``coalesce_sets``).  The second belongs to the rung that serves the
batch: the reference rung runs ``merge_same_message`` on the host
(crypto/bls/api._merge_on_host), the "tpu" backend merges the same sets
on the device (ops/bls_backend, one device program for the whole batch).

1. **Exact-duplicate dedup** — a hostile duplicate flood (or honest
   gossip re-delivery) puts byte-identical sets in one sweep; the dup
   caches only reject them AFTER signature verification (by design:
   unauthenticated garbage must not suppress honest messages), so
   without this stage every copy costs BLS work.  Byte-equal sets
   verify once.

2. **Blinded same-message merge** — sets sharing a message fold into
   ONE set: ``merged_sig = Σ rᵢ·sigᵢ`` with per-constituent random
   64-bit blinders ``rᵢ`` and pubkeys ``[rᵢ·aggpkᵢ]``.  The blinders
   make the fold sound: without them two adversarially-crafted invalid
   signatures could cancel (``sig₁ = good+δ, sig₂ = good₂−δ``) and ride
   a merged set through verification — exactly the attack the batch
   backends' own random coefficients exist to stop, applied here one
   level earlier.  With blinding, the merged set verifies iff (with
   probability 1 − 2⁻⁶⁴ per constituent) every constituent verifies —
   the property tests/test_pool.py pins.

Failure semantics are strictly conservative: a group whose members
don't decompress (fake-crypto tests), carry an infinity signature, or
fail any step of the fold passes through UNMERGED — the backend then
sees the original sets and the existing bisection fallback attributes
failures item-by-item.  Coalescing can only remove redundant pairings,
never change a verdict.

``LHTPU_PRE_BLS=0`` disables both stages on the host (chaos/debug escape
hatch); the device's merge is the "tpu" backend's own and stays.
"""

from __future__ import annotations

from dataclasses import dataclass

from lighthouse_tpu.common import env as envreg
from lighthouse_tpu.common.metrics import record_swallowed


@dataclass
class CoalesceStats:
    sets_in: int = 0
    sets_out: int = 0
    deduped: int = 0          # byte-identical sets dropped
    merged: int = 0           # constituents folded into merged sets
    merge_groups: int = 0     # merged sets produced
    unmergeable: int = 0      # group members passed through on fold failure

    @property
    def pairings_saved(self) -> int:
        """Pairing lanes removed from the batch: each deduped set and
        each folded constituent beyond its group's first."""
        return self.sets_in - self.sets_out


def enabled() -> bool:
    return envreg.get_bool("LHTPU_PRE_BLS", True)


def _set_key(s) -> tuple:
    return (s.signature.to_bytes(), s.message,
            tuple(pk.to_bytes() for pk in s.pubkeys))


def dedup_sets(sets: list) -> tuple[list, "CoalesceStats"]:
    """Drop byte-identical sets (one verification covers every copy)."""
    stats = CoalesceStats(sets_in=len(sets))
    seen: set[tuple] = set()
    out = []
    for s in sets:
        key = _set_key(s)
        if key in seen:
            stats.deduped += 1
            continue
        seen.add(key)
        out.append(s)
    stats.sets_out = len(out)
    return out, stats


def merge_same_message(sets: list) -> tuple[list, "CoalesceStats"]:
    """Fold same-message sets into one blinded set each (see module
    docstring for the soundness argument).  Unfoldable groups pass
    through unchanged."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import curve as cv

    stats = CoalesceStats(sets_in=len(sets))
    groups: dict[bytes, list] = {}
    order: list[bytes] = []
    for s in sets:
        if s.message not in groups:
            order.append(s.message)
        groups.setdefault(s.message, []).append(s)
    out = []
    for message in order:
        group = groups[message]
        if len(group) == 1:
            out.append(group[0])
            continue
        merged = _fold_group(group, message, bls, cv)
        if merged is None:
            # conservative pass-through: the batch backend + bisection
            # fallback handle whatever made the group unfoldable
            stats.unmergeable += len(group)
            out.extend(group)
            continue
        stats.merged += len(group)
        stats.merge_groups += 1
        out.append(merged)
    stats.sets_out = len(out)
    return out, stats


def _fold_group(group: list, message: bytes, bls, cv):
    """One blinded merged set for a same-message group, or None when any
    constituent resists the fold (bad decompress, infinity, missing
    pubkeys)."""
    import secrets

    sig_acc = cv.INF
    pubkeys = []
    try:
        for s in group:
            sig_pt = s.signature.point  # decompress + subgroup check
            if sig_pt is cv.INF or not s.pubkeys:
                return None
            agg_pk = s.aggregate_pubkey()
            r = 0
            while r == 0:
                r = secrets.randbits(64)
            sig_acc = cv.g2_add(sig_acc, cv.g2_mul(sig_pt, r))
            pk_pt = cv.g1_mul(agg_pk, r)
            pubkeys.append(bls.PublicKey(cv.g1_to_bytes(pk_pt), pk_pt))
        merged_sig = bls.Signature(cv.g2_to_bytes(sig_acc), sig_acc)
    except (bls.BlsError, ValueError, TypeError) as e:
        record_swallowed("pre_aggregation.fold", e)
        return None
    return bls.SignatureSet(merged_sig, pubkeys, message)


def coalesce_sets(sets: list) -> tuple[list, "CoalesceStats"]:
    """The chain's pre-BLS stage: dedup (the merge is the serving rung's,
    module docstring).  Returns the deduplicated list and its stats; with
    LHTPU_PRE_BLS=0 (or fewer than 2 sets) the input passes through
    untouched."""
    stats = CoalesceStats(sets_in=len(sets), sets_out=len(sets))
    if len(sets) < 2 or not enabled():
        return list(sets), stats
    unique, stats = dedup_sets(sets)
    _record(stats)
    return unique, stats


def _record(stats: CoalesceStats) -> None:
    if stats.sets_in == stats.sets_out:
        return
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        REGISTRY.counter(
            "pre_bls_deduped_total",
            "byte-identical signature sets dropped before BLS",
        ).inc(stats.deduped)
        REGISTRY.counter(
            "pre_bls_pairings_saved_total",
            "pairing lanes removed from batches by pre-BLS coalescing",
        ).inc(stats.pairings_saved)
    except (AttributeError, KeyError, TypeError, ValueError) as e:
        record_swallowed("pre_aggregation.record", e)


__all__ = [
    "CoalesceStats",
    "coalesce_sets",
    "dedup_sets",
    "enabled",
    "merge_same_message",
]
