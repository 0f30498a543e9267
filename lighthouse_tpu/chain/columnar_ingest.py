"""Columnar attestation ingest: wire columns -> fork choice/pools.

The vectorized twin of ``BeaconChain.verify_attestations_for_gossip``
for the single-bit gossip firehose (PAPER.md §L5 batch formation).
Where the scalar pipeline pays Python per MESSAGE — container
materialization, an AttestationData hash, a committee lookup, a
signature-set object — this lane pays per GROUP (one distinct
(slot, committee index, beacon_block_root, committee_bits) lane) plus
numpy per row:

- timing/structure checks run as vector masks over the decoded columns;
- signing root, domain, committee and fork-choice ancestry resolve
  once per group;
- attester indices come from the aggregation-bit column + committee
  array, dup suppression from one ``seen_mask`` sweep per group;
- the pre-BLS stage folds each signing-root lane into ONE blinded
  merged set: signature side Σ rᵢ·sigᵢ on host (collapsed per unique
  signature), pubkey side through the chain/pubkey_plane gather+MSM
  (device rung when armed, host point adds otherwise) — the
  ``aggregate_pubkey`` host cost ISSUE 14 profiles;
- full containers are materialized LAZILY, only for rows that survive
  and feed the naive-aggregation pool / slasher.

Semantics parity with the scalar path (property-pinned in
tests/test_columnar.py): same reject vocabulary, dup caches read
before signature verification and claimed under the commit lock after
it, failed fast-path falls back to bisection over the ORIGINAL
per-row sets so attribution is unchanged, and a group whose fold
resists merging (undecompressable signature, identity aggregate,
fake-crypto bytes) passes through UNMERGED — coalescing can remove
redundant pairings, never change a verdict.

Rows the lane cannot handle exactly (electra multi-committee bits,
nonzero electra data.index) are returned as ``fallback_rows`` for the
scalar pipeline rather than approximated."""

from __future__ import annotations

import secrets
import threading
import time

import numpy as np

from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import record_swallowed
from lighthouse_tpu.crypto import bls

#: 2^62: slots/epochs beyond this are adversarial counters that would
#: overflow the int64 vector math; the scalar path rejects them on the
#: slot-window check, this lane pre-rejects identically.
_SANE = np.int64(1) << np.int64(62)

_STAGE_LOCK = threading.Lock()
_STAGE_SECONDS: dict[str, float] = {}
_STAGE_COUNTS: dict[str, int] = {}


def _stage(key: str, seconds: float, count: int = 0) -> None:
    with _STAGE_LOCK:
        _STAGE_SECONDS[key] = _STAGE_SECONDS.get(key, 0.0) + seconds
        if count:
            _STAGE_COUNTS[key] = _STAGE_COUNTS.get(key, 0) + count


def stage_snapshot() -> dict:
    """Cumulative per-stage wall time + counts (the bench's
    stages.firehose.decode_ms/pubkey_gather_ms source)."""
    with _STAGE_LOCK:
        return {"seconds": dict(_STAGE_SECONDS),
                "counts": dict(_STAGE_COUNTS)}


def reset_stages() -> None:
    with _STAGE_LOCK:
        _STAGE_SECONDS.clear()
        _STAGE_COUNTS.clear()


class WireBatchResult:
    """Outcome of one wire-level batch (indices name the caller's
    ``entries`` list, not columnar rows)."""

    __slots__ = ("n", "verified", "rejects")

    def __init__(self, n: int):
        self.n = n
        self.verified = 0
        #: (entry index, reason) — scalar reject vocabulary plus
        #: ``decode_error`` for blobs the scalar deserialize refused
        self.rejects: list[tuple[int, str]] = []


def process_wire_batch(chain, entries: list[tuple[bytes, bool]]
                       ) -> WireBatchResult:
    """THE wire seam shared by Router's processor batch handler and the
    firehose bench: ``entries`` is one admission batch of
    ``(blob, electra)`` pairs.  Blobs are strided-decoded per layout
    class (one parse per class, not one per message), the columnar lane
    verifies and commits survivors, and exactly the rows the lane
    cannot handle — strided-parse rejects and explicit fallback rows
    (electra multi-committee bits, out-of-registry indices) — pay the
    scalar per-object pipeline.  Reject reasons keep the scalar
    vocabulary; a blob the scalar deserialize refuses rejects as
    ``decode_error`` (the fan-in ledger's delivery-time accounting is
    the CALLER's job — the router counts at delivery, this seam never
    double-counts)."""
    from lighthouse_tpu.ssz import columnar

    out = WireBatchResult(len(entries))
    scalar_items: list[tuple[int, object]] = []
    for electra in (False, True):
        idxs = [i for i, (_b, e) in enumerate(entries)
                if bool(e) == electra]
        if not idxs:
            continue
        layout = columnar.layout_for(chain.spec.preset, electra)
        cls = (chain.t.AttestationElectra if electra
               else chain.t.Attestation)
        t0 = time.perf_counter()
        cols, malformed = columnar.decode_batch(
            [entries[i][0] for i in idxs], layout, cls=cls)
        _stage("decode", time.perf_counter() - t0, len(idxs))
        columnar.record_fallback_rows(len(malformed))
        if malformed:
            t0 = time.perf_counter()
            n_ok = 0
            for j in malformed:
                try:
                    scalar_items.append((idxs[j], cls.deserialize(
                        entries[idxs[j]][0])))
                    n_ok += 1
                except Exception:
                    out.rejects.append((idxs[j], "decode_error"))
            columnar.record_decode(
                "scalar", time.perf_counter() - t0, n_ok)
        outcome = ingest_attestation_columns(chain, cols)
        out.verified += len(outcome.verified_rows)
        for row, reason in outcome.rejects:
            out.rejects.append((idxs[int(cols.row_index[row])], reason))
        if outcome.fallback_rows:
            t0 = time.perf_counter()
            for row in outcome.fallback_rows:
                scalar_items.append(
                    (idxs[int(cols.row_index[row])], cols.materialize(row)))
            columnar.record_decode(
                "scalar", time.perf_counter() - t0,
                len(outcome.fallback_rows))
    if scalar_items:
        objs = [obj for _i, obj in scalar_items]
        entry_of = {id(obj): i for i, obj in scalar_items}
        verified, rejects = chain.verify_attestations_for_gossip(objs)
        out.verified += len(verified)
        for item, reason in rejects:
            out.rejects.append((entry_of.get(id(item), -1), reason))
    return out


class _Group:
    __slots__ = ("gid", "rows", "data", "data_root", "signing_root",
                 "committee", "committee_index", "epoch", "slot")

    def __init__(self, gid):
        self.gid = gid
        self.rows = None
        self.data = None
        self.data_root = b""
        self.signing_root = b""
        self.committee = None
        self.committee_index = 0
        self.epoch = 0
        self.slot = 0


class IngestOutcome:
    """Per-row outcomes of one columnar sweep (row ids index the
    ColumnarAttestations batch, NOT the caller's blob list)."""

    __slots__ = ("n", "verified_rows", "rejects", "fallback_rows")

    def __init__(self, n):
        self.n = n
        self.verified_rows: list[int] = []
        self.rejects: list[tuple[int, str]] = []
        self.fallback_rows: list[int] = []


def ingest_attestation_columns(chain, cols) -> IngestOutcome:
    """Run one decoded batch through checks -> BLS -> commit.  Locking
    contract identical to ``_batch_pipeline``: prepare and commit hold
    the import lock, the BLS work runs unlocked."""
    out = IngestOutcome(cols.n)
    reasons: dict[int, str] = {}
    t0 = time.perf_counter()
    with tracing.span("ingest.columnar_prepare", n=cols.n):
        with chain._import_lock:
            prep = _prepare(chain, cols, reasons, out.fallback_rows)
    _stage("prepare", time.perf_counter() - t0, cols.n)
    verdict_of_set = None
    if chain.verify_signatures and prep["n_sets"]:
        with tracing.span("ingest.columnar_bls", sets=prep["n_sets"]):
            verdict_of_set = _verify_sets(chain, prep)
    t0 = time.perf_counter()
    with tracing.span("ingest.columnar_commit"):
        with chain._import_lock:
            _commit(chain, cols, prep, reasons, verdict_of_set, out)
    _stage("commit", time.perf_counter() - t0)
    out.rejects = sorted(reasons.items())
    return out


# -- prepare ------------------------------------------------------------------


def _prepare(chain, cols, reasons, fallback_rows):
    spec = chain.spec
    n = cols.n
    alive = np.ones(n, bool)

    def kill(mask, reason):
        hit = mask & alive
        for r in np.nonzero(hit)[0]:
            reasons[int(r)] = reason
        alive[hit] = False

    slot64 = cols.slot.astype(np.int64, copy=False)
    target64 = cols.target_epoch.astype(np.int64, copy=False)
    # reason parity with the scalar path: an insane slot IS a future
    # slot, but an insane target epoch on a sane slot passes the
    # slot-window checks and fails the epoch compare — exactly like
    # _gossip_checks with python ints
    insane_slot = cols.slot > np.uint64(_SANE)
    insane_tgt = cols.target_epoch > np.uint64(_SANE)
    kill(insane_slot, "future_slot")
    slot64 = np.where(insane_slot, 0, slot64)
    cur = chain.current_slot()
    kill(slot64 > cur, "future_slot")
    kill(slot64 + spec.slots_per_epoch < cur, "past_slot")
    kill(insane_tgt | (target64 != slot64 // spec.slots_per_epoch),
         "target_epoch_mismatch")
    # NOTE: empty_aggregation_bits / not_unaggregated are decided inside
    # the per-group stage AFTER the head/target root checks — scalar
    # _gossip_checks order.  Deciding them here would downscore senders
    # the scalar path treats as benign (unknown_head_block outranks).
    if cols.electra:
        cb = cols.committee_bits
        one_hot = (cb != 0) & ((cb & (cb - np.uint64(1))) == 0)
        odd = alive & (~one_hot | (cols.index != 0))
        for r in np.nonzero(odd)[0]:
            fallback_rows.append(int(r))
        alive[odd] = False

    group_of_row, first_rows = cols.group_keys()
    groups: list[_Group] = []
    attester = np.full(n, -1, np.int64)
    proto = chain.fork_choice.proto
    from lighthouse_tpu.types.containers import AttestationData

    for gid, first in enumerate(first_rows):
        rows = np.nonzero((group_of_row == gid) & alive)[0]
        if rows.size == 0:
            continue
        g = _Group(gid)
        g.rows = rows
        g.slot = int(slot64[rows[0]])
        g.epoch = int(target64[rows[0]])
        head_root = cols.beacon_block_root[rows[0]].tobytes()
        target_root = cols.target_root[rows[0]].tobytes()
        if head_root not in proto:
            kill_rows(reasons, alive, rows, "unknown_head_block")
            continue
        if target_root not in proto:
            kill_rows(reasons, alive, rows, "unknown_target_root")
            continue
        expected = proto.get_ancestor(
            head_root, spec.compute_start_slot_at_epoch(g.epoch))
        if expected != target_root:
            kill_rows(reasons, alive, rows, "invalid_target_root")
            continue
        g.data = AttestationData.deserialize(
            cols.data_raw[rows[0]].tobytes())
        try:
            shim = _DataShim(g.data)
            state = chain._attestation_state(shim)
            shuffle = chain.committee_shuffle(state, g.epoch)
            if cols.electra:
                g.committee_index = int(
                    cols.committee_bits[rows[0]]).bit_length() - 1
            else:
                g.committee_index = int(cols.index[rows[0]])
            from lighthouse_tpu.state_transition.misc import (
                get_beacon_committee,
            )

            g.committee = get_beacon_committee(
                state, spec, g.slot, g.committee_index, shuffle)
        except (ValueError, KeyError) as e:
            record_swallowed("columnar_ingest.committee", e)
            kill_rows(reasons, alive, rows, "invalid_committee")
            continue
        bad_len = rows[cols.bit_count[rows] != g.committee.shape[0]]
        kill_rows(reasons, alive, bad_len, "aggregation_bits_length")
        rows = rows[cols.bit_count[rows] == g.committee.shape[0]]
        if rows.size == 0:
            continue
        kill_rows(reasons, alive, rows[cols.set_bits[rows] == 0],
                  "empty_aggregation_bits")
        kill_rows(reasons, alive, rows[cols.set_bits[rows] > 1],
                  "not_unaggregated")
        rows = rows[cols.set_bits[rows] == 1]
        if rows.size == 0:
            continue
        attester[rows] = g.committee[cols.first_bit[rows]]
        # pubkey rows below gather from the HEAD registry (validator
        # index -> pubkey is fork-independent: deposits apply in
        # deposit-index order on every branch) — an index the head
        # registry does not cover yet (side-branch state with more
        # deposits) rides the scalar pipeline instead
        n_reg = len(chain.head_state.validators)
        oob = rows[attester[rows] >= n_reg]
        if oob.size:
            fallback_rows.extend(int(r) for r in oob)
            alive[oob] = False
            rows = rows[attester[rows] < n_reg]
            if rows.size == 0:
                continue
        seen = chain.observed_attesters.seen_mask(g.epoch, attester[rows])
        kill_rows(reasons, alive, rows[seen], "prior_attestation_known")
        rows = rows[~seen]
        if rows.size == 0:
            continue
        g.rows = rows
        g.data_root = g.data.hash_tree_root()
        from lighthouse_tpu.state_transition import misc

        domain = misc.get_domain(
            state, spec, spec.domain_beacon_attester, g.epoch)
        g.signing_root = misc.compute_signing_root(g.data_root, domain)
        groups.append(g)

    # unique signature sets: (group, attester PUBKEY bytes, signature
    # bytes) — byte-identical sets verify once (the dedup stage);
    # different validators sharing one key (interop fixtures) share a
    # set exactly like pre_aggregation.dedup_sets
    live_rows = np.concatenate([g.rows for g in groups]) if groups else \
        np.zeros(0, np.int64)
    group_of_live = np.concatenate(
        [np.full(g.rows.size, i, np.int64) for i, g in enumerate(groups)]
    ) if groups else np.zeros(0, np.int64)
    n_sets = 0
    set_of_live = np.zeros(0, np.int64)
    set_first = np.zeros(0, np.int64)
    pk_rows = np.zeros((0, 48), np.uint8)
    cols_sig = np.zeros((0, 96), np.uint8)
    if live_rows.size:
        validators = chain.head_state.validators
        pk_rows = np.asarray(
            validators.pubkeys[attester[live_rows]], np.uint8)
        cols_sig = cols.signature[live_rows]
        key = np.empty((live_rows.size, 8 + 48 + 96), np.uint8)
        key[:, :8] = group_of_live.view(np.uint8).reshape(-1, 8)
        key[:, 8:56] = pk_rows
        key[:, 56:] = cols_sig
        view = np.ascontiguousarray(key).view([("k", "V152")]).ravel()
        _, set_first, set_of_live = np.unique(
            view, return_index=True, return_inverse=True)
        n_sets = set_first.size
    return {
        "groups": groups, "attester": attester, "live_rows": live_rows,
        "group_of_live": group_of_live, "set_of_live": set_of_live,
        "set_first": set_first, "n_sets": n_sets, "pk_rows": pk_rows,
        "cols_sig": cols_sig,
    }


class _DataShim:
    """Duck-typed item for chain._attestation_state (wants .data)."""

    __slots__ = ("data",)

    def __init__(self, data):
        self.data = data


def kill_rows(reasons, alive, rows, reason: str) -> None:
    for r in rows:
        reasons[int(r)] = reason
    alive[rows] = False


# -- BLS ----------------------------------------------------------------------


def _unique_set(chain, prep, u: int):
    """Materialize unique set ``u`` as a plain SignatureSet (bisection
    attribution / unmergeable pass-through)."""
    i = int(prep["set_first"][u])
    g = prep["groups"][int(prep["group_of_live"][i])]
    sig = bls.Signature.interned(bytes(prep["sig_bytes"][u]))
    pk = bls.PublicKey.interned(prep["pk_rows"][i].tobytes())
    return bls.SignatureSet(sig, [pk], g.signing_root)


def _should_premerge() -> bool:
    """Merged host folds are redundant when the fused device pipeline
    serves verification — it merges same-message lanes itself
    (ops/bls_backend._merge_same_message), so pre-merging would pay host
    point math for nothing.  Honor the pre-BLS kill switch too."""
    from lighthouse_tpu.pool import pre_aggregation

    if not pre_aggregation.enabled():
        return False
    try:
        from lighthouse_tpu.crypto.bls import api as bls_api

        name = bls_api.get_backend()
        if name == "auto":
            name = bls_api.resolve_auto_backend()
        return name not in ("tpu", "sharded")
    except Exception as e:
        record_swallowed("columnar_ingest.backend_probe", e)
        return True


def _verify_sets(chain, prep) -> np.ndarray:
    """Verdict per unique set: merged fast path + bisection fallback."""
    groups = prep["groups"]
    n_sets = prep["n_sets"]
    set_first = prep["set_first"]
    group_of_live = prep["group_of_live"]
    cols_sig = prep["cols_sig"]

    prep["sig_bytes"] = [cols_sig[int(set_first[u])].tobytes()
                         for u in range(n_sets)]

    verdict = np.zeros(n_sets, bool)
    # merge lanes keyed by SIGNING ROOT (electra committees of one slot
    # share the message, so their sets legally fold together)
    lane_of_root: dict[bytes, int] = {}
    lane_sets: list[list[int]] = []
    for u in range(n_sets):
        g = groups[int(group_of_live[int(set_first[u])])]
        lane = lane_of_root.setdefault(g.signing_root, len(lane_sets))
        if lane == len(lane_sets):
            lane_sets.append([])
        lane_sets[lane].append(u)

    merged: list = []
    singles: list[int] = []
    t_fold0 = time.perf_counter()
    n_folded = 0
    if _should_premerge():
        merged, singles, n_folded = _fold_lanes(chain, prep, lane_sets)
    else:
        singles = list(range(n_sets))
    _stage("pubkey_fold", time.perf_counter() - t_fold0, n_folded)

    t0 = time.perf_counter()
    verify_list = merged + [_unique_set(chain, prep, u) for u in singles]
    ok = bls.verify_signature_sets(verify_list) if verify_list else True
    if ok:
        verdict[:] = True
    else:
        # attribution unchanged: bisect the ORIGINAL per-row sets
        from lighthouse_tpu.chain.attestation_verification import (
            verify_signature_sets_with_bisection,
        )

        originals = [_unique_set(chain, prep, u) for u in range(n_sets)]
        mask = verify_signature_sets_with_bisection(originals)
        verdict[:] = mask
    _stage("verify", time.perf_counter() - t0, len(verify_list))
    return verdict


def _fold_lanes(chain, prep, lane_sets: list[list[int]]
                ) -> tuple[list, list[int], int]:
    """Blinded merged sets for every multi-member signing-root lane.

    Signature side: Σ rᵢ·sigᵢ on host, collapsed per unique signature
    bytes first (r₁·sig + r₂·sig = (r₁+r₂)·sig — one g2_mul per
    distinct signature, the honest-duplication case).  Pubkey side: ONE
    pubkey_plane.fold call over every mergeable lane (the gather+MSM
    batches across lanes).  A lane whose fold resists (bad decompress,
    infinity signature, identity aggregate) passes through UNMERGED —
    mirrors pre_aggregation._fold_group's conservative contract."""
    from lighthouse_tpu.chain import pubkey_plane
    from lighthouse_tpu.crypto.bls import curve as cv
    from lighthouse_tpu.crypto.bls.fields import R as _R

    set_first = prep["set_first"]
    group_of_live = prep["group_of_live"]
    live_rows = prep["live_rows"]
    attester = prep["attester"]
    groups = prep["groups"]

    singles: list[int] = []
    cand: list[dict] = []       # lanes whose sig side folded
    fold_idx: list[int] = []    # plane lanes: validator index
    fold_r: list[int] = []      # plane lanes: blinder
    fold_lane: list[int] = []   # plane lanes: candidate id
    # ONE batched decompress + G2 membership test across every lane's
    # constituents (native, ~150 µs/sig vs ~1.6 ms for the host ψ
    # check) — the per-lane fold below and the reference verifier's
    # per-signature .point path then only re-check signatures that
    # failed here (attack traffic), keeping attribution per lane
    sig_bytes = prep["sig_bytes"]
    every = sorted({u for m in lane_sets for u in m})
    if every:
        batch_sigs = [bls.Signature.interned(sig_bytes[u]) for u in every]
        # decompress result deliberately ignored: one malformed
        # signature must not disable the batched membership test for
        # the whole sweep (the check skips undecompressable entries;
        # their lanes fail per-lane with attribution)
        bls.Signature.decompress_batch(batch_sigs)
        bls.Signature.subgroup_check_batch(batch_sigs)
    for members in lane_sets:
        if len(members) == 1:
            singles.append(members[0])
            continue
        lane = _fold_sig_side(prep, members, cv, _R)
        if lane is None:
            singles.extend(members)     # unmergeable pass-through
            continue
        lane_id = len(cand)
        cand.append(lane)
        for u, r in zip(members, lane["blinders"]):
            pos = int(set_first[u])
            fold_idx.append(int(attester[int(live_rows[pos])]))
            fold_r.append(r)
            fold_lane.append(lane_id)
    merged: list = []
    n_folded = 0
    if cand:
        plane = pubkey_plane.get_plane()
        try:
            pk_pts = plane.fold(
                chain.head_state.validators,
                np.array(fold_idx, np.int64),
                np.array(fold_r, np.uint64),
                np.array(fold_lane, np.int64), len(cand))
        except Exception as e:      # never poison the batch: unmerged
            record_swallowed("columnar_ingest.fold", e)
            pk_pts = [None] * len(cand)
        sig_accs = _sig_accs(cand, cv)
        for lane_id, lane in enumerate(cand):
            pk_pt = pk_pts[lane_id]
            sig_acc = sig_accs[lane_id]
            if pk_pt is None or sig_acc is None:
                singles.extend(lane["members"])
                continue
            g0 = groups[int(group_of_live[int(
                set_first[lane["members"][0]])])]
            merged.append(bls.SignatureSet(
                bls.Signature(cv.g2_to_bytes(sig_acc), sig_acc),
                [bls.PublicKey(cv.g1_to_bytes(pk_pt), pk_pt)],
                g0.signing_root))
            n_folded += len(lane["members"])
    return merged, singles, n_folded


def _fold_sig_side(prep, members: list[int], cv, R: int):
    """Collapsed blinded sig-side terms (Σ rᵢ per unique signature) for
    one lane, or None when a constituent resists.  The scalar muls
    themselves run in ONE native segment-MSM across every lane
    (:func:`_sig_accs`) instead of a ~2.5 ms python g2_mul per term."""
    sig_bytes = prep["sig_bytes"]
    try:
        sigs = [bls.Signature.interned(sig_bytes[u]) for u in members]
        if not bls.Signature.decompress_batch(sigs):
            return None
        blinders: list[int] = []
        sig_sums: dict[bytes, tuple[int, object]] = {}
        for u, sig in zip(members, sigs):
            pt = sig.point_unchecked()
            if pt is cv.INF:
                return None
            # the merged Signature is built with a preset point, which
            # the verifiers trust as subgroup-checked — complete the G2
            # membership test HERE or an on-curve small-subgroup forgery
            # could fold into sig_acc unchecked (the _fold_lanes batch
            # pre-pass marks honest signatures; this per-signature host
            # check only fires for traffic that failed it)
            if not sig.subgroup_checked():
                if not cv.g2_in_subgroup_fast(pt):
                    return None
                sig.mark_subgroup_checked()
            r = 0
            while r == 0:
                r = secrets.randbits(64)
            blinders.append(r)
            key = sig_bytes[u]
            prev = sig_sums.get(key)
            sig_sums[key] = ((prev[0] + r) % R if prev else r, pt)
        terms = [(pt, s) for s, pt in sig_sums.values() if s]
        if not terms:
            return None
        return {"members": members, "blinders": blinders,
                "terms": terms}
    except (bls.BlsError, ValueError, TypeError) as e:
        record_swallowed("columnar_ingest.fold_sig", e)
        return None


def _sig_accs(cand: list[dict], cv) -> list:
    """Σ rᵢ·sigᵢ per candidate lane: one native segment-MSM across all
    lanes (ops/native_bls.g2_lincomb_groups), host point math when the
    native layer is unavailable.  None = identity accumulator (such a
    merged set can never verify — the lane passes through unmerged)."""
    pts: list = []
    scalars: list[int] = []
    gids: list[int] = []
    for lane_id, lane in enumerate(cand):
        for pt, s in lane["terms"]:
            pts.append(pt)
            scalars.append(s)
            gids.append(lane_id)
    try:
        from lighthouse_tpu.ops import native_bls

        if native_bls.available():
            res = native_bls.g2_lincomb_groups(
                pts, scalars, gids, len(cand))
            if res is not None:
                return [None if v is None else
                        (cv.Fq2(v[0][0], v[0][1]),
                         cv.Fq2(v[1][0], v[1][1])) for v in res]
    except Exception as e:
        record_swallowed("columnar_ingest.sig_lincomb", e)
    out: list = []
    for lane in cand:
        acc = cv.INF
        for pt, s in lane["terms"]:
            acc = cv.g2_add(acc, cv.g2_mul(pt, s))
        out.append(None if acc is cv.INF else acc)
    return out


# -- commit -------------------------------------------------------------------


def _commit(chain, cols, prep, reasons, verdict_of_set, out) -> None:
    from lighthouse_tpu.chain import attestation_verification as att_verify

    groups = prep["groups"]
    live_rows = prep["live_rows"]
    set_of_live = prep["set_of_live"]
    attester = prep["attester"]
    if live_rows.size == 0:
        return
    ok_live = (np.ones(live_rows.size, bool) if verdict_of_set is None
               else np.asarray(verdict_of_set)[set_of_live])
    live_pos_of_row = {int(r): i for i, r in enumerate(live_rows)}
    spec = chain.spec
    for gi, g in enumerate(groups):
        rows = g.rows
        pos = np.array([live_pos_of_row[int(r)] for r in rows], np.int64)
        ok_rows = ok_live[pos]
        for r in rows[~ok_rows]:
            reasons[int(r)] = "invalid_signature"
        rows = rows[ok_rows]
        if rows.size == 0:
            continue
        idx = attester[rows]
        # claim dup marks atomically under the commit lock: intra-batch
        # duplicate indices first (order wins), then the cache claim
        order = np.argsort(rows, kind="stable")
        rows_o, idx_o = rows[order], idx[order]
        _uniq, first_pos = np.unique(idx_o, return_index=True)
        keep = np.zeros(rows_o.size, bool)
        keep[first_pos] = True
        for r in rows_o[~keep]:
            reasons[int(r)] = "duplicate_in_batch"
        rows_o, idx_o = rows_o[keep], idx_o[keep]
        already = chain.observed_attesters.observe_batch(g.epoch, idx_o)
        for r in rows_o[already]:
            reasons[int(r)] = "duplicate_in_batch"
        rows_o, idx_o = rows_o[~already], idx_o[~already]
        if rows_o.size == 0:
            continue
        try:
            chain.fork_choice.on_attestation(
                chain.current_slot(), idx_o,
                cols.beacon_block_root[rows_o[0]].tobytes(),
                g.epoch, g.slot)
        except Exception as e:
            record_swallowed("chain.batch_att_fork_choice", e)
        committee_len = int(g.committee.shape[0])
        for r in rows_o:
            chain.naive_pool.insert_single_bit(
                g.data, g.data_root, g.committee_index, committee_len,
                int(cols.first_bit[r]), cols.signature[r].tobytes())
        chain.validator_monitor.on_gossip_attestation(
            idx_o, g.data, spec)
        if chain.slasher is not None:
            for r, vi in zip(rows_o, idx_o):
                try:
                    att = cols.materialize(int(r))
                    chain.slasher.on_verified_attestation(
                        att_verify._as_indexed(
                            chain, att, np.array([vi])))
                except Exception as e:
                    record_swallowed("columnar_ingest.slasher", e)
        out.verified_rows.extend(int(r) for r in rows_o)


__all__ = [
    "IngestOutcome",
    "WireBatchResult",
    "ingest_attestation_columns",
    "process_wire_batch",
    "reset_stages",
    "stage_snapshot",
]
