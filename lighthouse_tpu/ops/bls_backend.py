"""The "tpu" BLS backend: the full batch-verify data plane on device.

Mirrors the reference blst backend's batch semantics
(/root/reference/crypto/bls/src/impls/blst.rs:37-119): per-set nonzero
64-bit random scalars r_i, then ONE combined check

    e(-g1, Σ r_i·sig_i) · Π e(r_i·agg_pk_i, H(m_i)) == 1

Division of labour (the data plane is ONE device program and host
crossings are counted on fingers):

- host: native-C++ batch decompression (ops/native_bls), random scalars,
  hash-to-curve (memoized per message), native final exponentiation of
  the one fetched Fq12;
- device, one fused jit (_pipeline_fused): r_i·agg_pk_i over G1 lanes and
  r_i·sig_i over G2 lanes in ONE merged 4-bit-windowed scan (16 steps of
  shared mul-queue rounds), the G2 tree-sum, every Miller loop (G1 lanes
  in JACOBIAN form via subfield line scaling; the Σ r·sig lane in
  Jacobian Fq2 form via the zq path — no Fermat inversion anywhere), and
  the product tree;
- device, one more jit in front of it when sets share a message (a
  gossip flood: thousands of attestations over a slot's few head votes):
  the blinded same-message merge (msm._blinded_merge), Σ r_i·agg_pk_i and
  Σ r_i·sig_i a message on the limb-major multiply, so that the fused
  program sees one lane a message;
- device, one more jit when signatures are fresh: the batched ψ subgroup
  verdict (bool row home — ec.g2_subgroup_verdict_batch).

Registered as backend "tpu" on import (see crypto/bls/api.py
_resolve_backend's lazy hook).
"""

from __future__ import annotations

import secrets
from functools import partial
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from lighthouse_tpu.common import device_telemetry as _dtel
from lighthouse_tpu.common import tracing
from lighthouse_tpu.crypto.bls import api, curve as cv
from lighthouse_tpu.ops import program_store as _pstore

# AOT program-store coverage (lhlint LH606): the fused verify plane is
# prewarmed by the "bls" driver
_pstore.register_entry("ops/bls_backend.py::_pipeline_fused@_pipeline_fused",
                       driver="bls")
_pstore.register_entry(
    "ops/bls_backend.py::_g2_subgroup_kernel@_g2_subgroup_kernel",
    driver="bls")
_pstore.register_entry(
    "ops/bls_backend.py::_g1_subgroup_kernel@_g1_subgroup_kernel",
    driver="bls")
from lighthouse_tpu.ops import bigint as bi
from lighthouse_tpu.ops import ec
from lighthouse_tpu.ops import msm as _msm
from lighthouse_tpu.ops import faults
from lighthouse_tpu.ops.bls12_381 import (
    batch_miller_loop,
    fq12_from_device,
    multi_pairing_device,
    reduce_product,
)

RAND_BITS = 64

# distinct messages hash to the same G2 point; memoize across batches
# (LRU-bounded: a flood of unique messages evicts oldest, never clears
# the hot set wholesale)
from lighthouse_tpu.common.utils import LruCache

_H2C_CACHE = LruCache(capacity=1 << 16)


def _hash_to_g2_cached(message: bytes):
    from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2

    pt = _H2C_CACHE.get(message)
    if pt is None:
        api.record_cache("h2c", hit=False)
        pt = hash_to_g2(message)
        _H2C_CACHE.put(message, pt)
    else:
        api.record_cache("h2c", hit=True)
    return pt


def prepare_pairs(sets: Sequence[api.SignatureSet]):
    """Host-only prep: [(r·agg_pk, H(m))] per set + the (-g1, Σ r·sig)
    lane, all multiplications in pure Python.  Retained as the oracle and
    for the sharded path; the production route is `verify_sets_pipeline`.
    Returns None if any set is structurally invalid."""
    pairs = []
    sig_acc = cv.INF
    for s in sets:
        if not s.pubkeys:
            return None
        try:
            sig_pt = s.signature.point
            agg_pk = s.aggregate_pubkey()
        except (api.BlsError, ValueError):
            return None
        if sig_pt is cv.INF:
            return None
        rand = 0
        while rand == 0:
            rand = secrets.randbits(RAND_BITS)
        sig_acc = cv.g2_add(sig_acc, cv.g2_mul(sig_pt, rand))
        pairs.append((cv.g1_mul(agg_pk, rand), _hash_to_g2_cached(s.message)))
    pairs.append((cv.g1_neg(cv.g1_generator()), sig_acc))
    return pairs


# --- device pipeline --------------------------------------------------------
# (single jitted callables: jax.jit keys its compile cache on input shapes)

from functools import partial


@partial(jax.jit, static_argnums=(14,))
def _pipeline_fused(pkx, pky, sxa, sxb, sya, syb,
                    hxa, hxb, hya, hyb, bits, lane_mask,
                    g1x, g1y, n_groups):
    """The WHOLE batch-verify data plane as ONE device program.

    Scalar-mults the G1 pubkey and G2 signature lanes, tree-sums Σ r·sig,
    then runs every Miller loop and the product tree.  Host boundary:
    uploads in, ONE Fq12 pytree out (final exp is native C++).
    ``n_groups`` is 0: sets that share a message are merged before this
    program, by `msm._blinded_merge`; the argument stays so that a caller
    naming the program by its fifteen arguments keeps its shapes.

    The Σ r·sig lane enters the Miller loop in JACOBIAN form (zq path —
    its Zq⁵ line factors die in the final exponentiation), so no affine
    conversion runs at all: the round-4 pipeline spent a 381-step
    width-1 Fermat-inversion scan here, ~half its sequential depth.

    The Σ r·sig lane's mask bit is resolved on device too: an identity
    sum degenerates the check to Π e(r·pk_i, H(m_i)) == 1 with the sum
    lane masked out — same semantics the host branch used to implement.

    `bits` carries MSB-first base-16 WINDOW DIGITS (ec.scalars_to_digits):
    the G1 pubkey and G2 signature lanes share their blinding scalars, so
    both run through ONE merged windowed scan (4 bits per step from
    16-entry Jacobian tables, shared mul-queue rounds — ~2.5x fewer
    sequential rounds than the two binary scans it replaces)."""
    assert n_groups == 0
    (Xp, Yp, Zp), (SX, SY, SZ) = _msm.fold_segments_gj(
        pkx, pky, (sxa, sxb), (sya, syb), bits)
    sum_ok = ~(bi.is_zero_mod_p_device(SZ[0])
               & bi.is_zero_mod_p_device(SZ[1]))
    one = jnp.broadcast_to(bi._jconst("one_m"), (1, bi.L))
    ones_q = jnp.broadcast_to(bi._jconst("one_m"), hxa.shape)
    zeros_q = jnp.zeros_like(hxa)
    xp = jnp.concatenate([Xp, g1x])
    yp = jnp.concatenate([Yp, g1y])
    zp = jnp.concatenate([Zp, one])
    xqa = jnp.concatenate([hxa, SX[0]])
    xqb = jnp.concatenate([hxb, SX[1]])
    yqa = jnp.concatenate([hya, SY[0]])
    yqb = jnp.concatenate([hyb, SY[1]])
    zqa = jnp.concatenate([ones_q, SZ[0]])
    zqb = jnp.concatenate([zeros_q, SZ[1]])
    mask = jnp.concatenate([lane_mask, sum_ok])
    f = batch_miller_loop(xp, yp, xqa, xqb, yqa, yqb,
                          zp=zp, zq=(zqa, zqb))
    return reduce_product(f, mask)


_pipeline_fused = _dtel.instrument(
    "ops/bls_backend.py::_pipeline_fused@_pipeline_fused", _pipeline_fused)


@jax.jit
def _g2_subgroup_kernel(xqa, xqb, yqa, yqb):
    return ec.g2_subgroup_verdict_batch(xqa, xqb, yqa, yqb)


_g2_subgroup_kernel = _dtel.instrument(
    "ops/bls_backend.py::_g2_subgroup_kernel@_g2_subgroup_kernel",
    _g2_subgroup_kernel)


def _dispatch_g2_subgroup_kernel(points):
    """Dispatch (no host sync) the batched ψ verdict kernel over affine
    G2 points, generator-padded to a power of two (floor 4) so small
    batches share compiled shapes.  Returns the device bool row; callers
    read [:len(points)] when they sync.  The verdict is computed on
    device (ec.g2_subgroup_verdict_batch) — one bool-row fetch, not six
    limb rows."""
    padded = _next_pow2(len(points), floor=4)
    pts = list(points) + [cv.g2_generator()] * (padded - len(points))
    xqa, xqb, yqa, yqb = (jnp.asarray(a) for a in _g2_limbs(pts))
    return _g2_subgroup_kernel(xqa, xqb, yqa, yqb)


def batch_subgroup_check_g2(points) -> np.ndarray:
    """Device ψ membership test over a list of affine G2 points ->
    bool[n] (synchronous; see _dispatch_g2_subgroup_kernel)."""
    n = len(points)
    if n == 0:
        return np.zeros(0, bool)
    return np.asarray(_dispatch_g2_subgroup_kernel(points))[:n]


@jax.jit
def _g1_subgroup_kernel(xp, yp):
    return ec.g1_subgroup_verdict_batch(xp, yp)


_g1_subgroup_kernel = _dtel.instrument(
    "ops/bls_backend.py::_g1_subgroup_kernel@_g1_subgroup_kernel",
    _g1_subgroup_kernel)


def _next_pow2(x: int, floor: int = 1) -> int:
    return _msm.bucket(x, floor=floor)


# The compiled shapes of a verification are bounded (each fused program is
# a compile of many minutes).  `_pipeline_fused` pads a chunk's lanes to a
# power of two of at least _PIPELINE_MIN_LANES.  A batch whose sets share
# a message, over at most _MERGE_GROUPS messages, merges them first into
# one lane a message: its lanes padded to the smallest of _MERGE_BUCKETS
# that holds them (a longer batch in slices of the largest, a beacon
# processor batch of gossip attestations: max_batch), its groups always
# _MERGE_GROUPS, whatever the count of messages.  Such a batch and every
# half bisection hands the seam then run two merge shapes and the fused
# program at 4, 8 or 16 lanes (`merge_shapes`), the shapes the "msm"
# prewarm driver warms.
_PIPELINE_MIN_LANES = 4
_MERGE_GROUPS = 16
_MERGE_BUCKETS = (1 << 8, 1 << 11)


def merge_shapes(n: int, n_messages: int):
    """(merge lanes, groups, fused lanes) of a batch of ``n`` sets over
    ``n_messages`` messages: the shapes of its `_blinded_merge` slices
    (None when the batch does not merge) and of its `_pipeline_fused`
    chunk when it fits one."""
    if n_messages < n and n_messages <= _MERGE_GROUPS:
        lanes = next((b for b in _MERGE_BUCKETS if n <= b),
                     _MERGE_BUCKETS[-1])
        return (lanes, _MERGE_GROUPS,
                _next_pow2(n_messages, floor=_PIPELINE_MIN_LANES))
    return None, None, _next_pow2(n, floor=_PIPELINE_MIN_LANES)


# blinding pool: lane j carries B_j = [u_j]G alongside the pubkeys, and
# the known total [Σu]G is subtracted after the tree — the device
# Jacobian adds are INCOMPLETE for H == 0 chords (ec._jac_add_full's
# contract), and honest sets DO contain duplicate keys (sync committees
# sample with replacement), so unblinded lanes could collide mid-tree
# and falsely reject a valid batch.  With distinct B_j in every level-0
# pair, equal nodes need a relation over the random u's (~2^-64).
_BLIND_U: list[int] = []
_BLIND_POINTS: list[tuple] = []
_BLIND_NEG_TOTAL: dict[int, tuple] = {}     # max_k -> -[Σ_{j<k} u_j]G limbs
_BLIND_LANES: dict[tuple, tuple] = {}       # (max_k, n_pad) -> bx, by, bz
import threading as _threading

_BLIND_LOCK = _threading.Lock()


def _blinding(max_k: int, n_pad: int):
    """((bx, by, bz), neg_total) for slices of ``n_pad`` segments of
    ``max_k`` key lanes: the blinding half of a slice's lanes on the
    device (lane ``j * n_pad + i`` of it carries B_j), and the limbs of
    -[Σ u_j]G.  Laid out and uploaded once a shape, not once a slice."""
    with _BLIND_LOCK:
        return _blinding_locked(max_k, n_pad)


def _blinding_locked(max_k: int, n_pad: int):
    while len(_BLIND_U) < max_k:
        u = 0
        while u == 0:
            u = secrets.randbits(64)
        _BLIND_U.append(u)
        pt = cv.g1_mul(cv.g1_generator(), u)
        _BLIND_POINTS.append(
            (ec.ints_to_mont_limbs([pt[0]])[0],
             ec.ints_to_mont_limbs([pt[1]])[0]))
    neg = _BLIND_NEG_TOTAL.get(max_k)
    if neg is None:
        total = sum(_BLIND_U[:max_k])
        npt = cv.g1_neg(cv.g1_mul(cv.g1_generator(), total))
        neg = (jnp.asarray(ec.ints_to_mont_limbs([npt[0]])),
               jnp.asarray(ec.ints_to_mont_limbs([npt[1]])))
        _BLIND_NEG_TOTAL[max_k] = neg
    lanes = _BLIND_LANES.get((max_k, n_pad))
    if lanes is None:
        points = _BLIND_POINTS[:max_k]
        lanes = tuple(jnp.asarray(np.repeat(np.stack(c), n_pad, axis=0))
                      for c in zip(*points))
        lanes += (jnp.asarray(np.broadcast_to(bi.ONE_M,
                                              (max_k * n_pad, bi.L))),)
        _BLIND_LANES[(max_k, n_pad)] = lanes
    return lanes, neg


_KEY_ROW_WORDS = 128


class _FoldKeyTable:
    """The fold's resident key table: one row of Montgomery limbs a key
    (x, then y: uint32[capacity, 2L]), on the host and on the device,
    keyed by the key's 48 bytes, so that two `PublicKey` objects of one
    key share a row; each caches it (`PublicKey._fold_row`).

    Rows are assigned under one lock and never move.  The capacity grows
    by powers of two from ``floor``, so the gather program's table shape
    changes a few times in a process's life.  The device copy is replaced
    whole whenever rows were added, its rows padded to the 128 words of a
    TPU tile: at 54 the compiler lays the whole table out again for the
    gather on every call (2^18 keys: 134 MB on the device, 57 MB on the
    host).  A key's row is published on its `PublicKey` only after the
    device copy that holds it, so a reader that has read a row and then
    takes `device` finds it there."""

    def __init__(self, floor: int = 1 << 16):
        self._lock = _threading.Lock()
        self._rows: dict[bytes, int] = {}
        self._host = np.zeros((floor, 2 * bi.L), np.uint32)
        self.device = None

    def add(self, pubkeys) -> range:
        """Give every key of ``pubkeys`` that has none a row: the keys
        new to the table are converted in one vectorised call and the
        table uploaded.  Returns the rows this call added."""
        pending = [pk for pk in pubkeys if pk._fold_row < 0]
        # decompression may raise (an infinity key): before any row moves
        points = [pk.point for pk in pending]
        with self._lock:
            new = {}
            for pk, pt in zip(pending, points):
                if pk.to_bytes() not in self._rows:
                    new.setdefault(pk.to_bytes(), pt)
            lo = len(self._rows)
            if new:
                hi = lo + len(new)
                limbs = ec.ints_to_mont_limbs(
                    [p[0] for p in new.values()] + [p[1] for p in new.values()])
                host = self._host
                if hi > len(host):
                    host = np.zeros((_next_pow2(hi), 2 * bi.L), np.uint32)
                    host[:lo] = self._host[:lo]
                host[lo:hi, :bi.L] = limbs[:len(new)]
                host[lo:hi, bi.L:] = limbs[len(new):]
                self.device = jnp.asarray(np.pad(
                    host, ((0, 0), (0, _KEY_ROW_WORDS - 2 * bi.L))))
                self._host = host
                self._rows.update(zip(new, range(lo, hi)))
            for pk in pending:
                pk._fold_row = self._rows[pk.to_bytes()]
            return range(lo, len(self._rows))


from operator import attrgetter as _attrgetter

_FOLD_KEYS = _FoldKeyTable()
_FOLD_ROW = _attrgetter("_fold_row")

# prewarmed by the "msm" driver, which folds through
# aggregate_pubkeys_device
_pstore.register_entry("ops/bls_backend.py::_blinded_lanes@_blinded_lanes",
                       driver="msm")


@jax.jit
def _blinded_lanes(table, rows, bx, by, bz):
    """(X, Y, Z) uint32[2K, L], the lanes of one `_blinded_fold` slice:
    K key lanes gathered from ``table`` (uint32[T, 128]: a key's x limbs,
    then its y, then zeros) at ``rows`` (int32[K], s-major; -1 is a
    padding lane, x, y and Z zero), Z one under every key, then the
    blinding half (bx, by, bz uint32[K, L])."""
    live = (rows >= 0)[:, None]
    xy = jnp.where(live, jnp.take(table, rows, axis=0, mode="clip"), 0)
    z = jnp.where(live, bi._jconst("one_m"), 0)
    return (jnp.concatenate([xy[:, :bi.L], bx]),
            jnp.concatenate([xy[:, bi.L:2 * bi.L], by]),
            jnp.concatenate([z, bz]))


_blinded_lanes = _dtel.instrument(
    "ops/bls_backend.py::_blinded_lanes@_blinded_lanes", _blinded_lanes)


# lanes one blinded-fold dispatch may carry, whatever the batch: no count
# of sets and no width of a set steps over it.  The cap stands for the
# SHAPE, no longer for the memory: wider batches fold in equal-shaped
# slices of segments, and a set wider than a segment as several
# segments, so one compiled program serves all of them, and the host
# lays out slice k+1 while the device folds slice k.  The 15.5 GB of
# temporaries at 262,144 lanes (a phase0 block's 131 sets x 512 keys in
# one dispatch; one electra aggregate of 64 committees x 2,048 keys) and
# the 1.94 GB at this cap were the materialized multiply's; with the
# segment sum on `mont_mul_lm` the TPU compiler reports 284 MB and 15.2
# GB accessed at 262,144 lanes, 7.9 MB and 1.89 GB accessed at this cap
# (compile rehearsal, PR 36).  A wider slice is another compiled shape
# and a longer first layout in front of the device: not measured.
_AGG_MAX_LANES = 1 << 15


def _fold_shape(widths) -> tuple[int, int]:
    """(key lanes a segment, segments a slice) of the blinded fold for a
    batch of sets with ``widths`` keys.

    A segment is the widest set's pow2 width, up to the widest segment:
    a slice at the cap is cut into segments of its lane count's
    two-thirds power — 32 x 1,024 lanes at 32,768, a mainnet committee's
    512 keys and their 512 blinding lanes — so every batch with a set
    that wide or wider, an electra aggregate of 64 committees like a
    phase0 block of 131, runs the one compiled shape.  A wider set takes
    several segments, a single key none."""
    cap_bits = _AGG_MAX_LANES.bit_length() - 1
    widest_segment = 1 << (2 * cap_bits // 3)     # lanes: keys + blinding
    max_k = min(_next_pow2(max(widths)), max(widest_segment // 2, 1))
    n_segments = sum(-(-k // max_k) for k in widths if k > 1)
    return max_k, min(_next_pow2(n_segments), _AGG_MAX_LANES // (2 * max_k))


def _stage_span(name: str, stage: str, **attrs):
    """One stage of the verify pipeline: a span (on the profiler's clock
    while a trace is live) whose duration also feeds
    ``bls_verify_stage_seconds{backend="tpu",stage=<stage>}``."""
    return tracing.span(name, observe=partial(api.record_stage, "tpu", stage),
                        **attrs)


def aggregate_pubkeys_device(sets):
    """Per-set pubkey aggregation as device segment-sums.

    Replaces the pure-Python per-set point additions (~20 µs each; a
    128-attestation phase0 block carries ~66k member keys, an electra
    block of 8 aggregates over 64 committees 262k).  Returns
    (x_rows, y_rows, inf_flags): affine Montgomery limb rows
    uint32[n, L] per set plus a bool[n] marking identity aggregates
    (opposing keys — such sets can never verify).

    The fold's unit is the segment (`_fold_shape`: the widest set's pow2
    width, up to 512 keys at the cap): a set of up to a segment's keys
    is one, a wider set is cut into sub-segments of that width, a
    single-key set needs none (its aggregate is its key).
    Segment layout (s-major): first half pubkey lanes (infinity-padded),
    second half the blinding lanes B_0..B_{k-1} (see _blinding) — every
    level-0 pair joins a pubkey with a distinct blinding point, so
    duplicate keys never produce the degenerate H == 0 chord.  Segments
    fold in equal-shaped slices of at most _AGG_MAX_LANES lanes, all
    dispatched before the one fetch.

    The host lays out a slice as its key lanes' rows in the resident key
    table (`_FoldKeyTable`: the row each member's `PublicKey` caches, -1
    a padding lane); the device gathers the lanes from the table and
    puts the blinding half beside them (`_blinded_lanes`), then
    folds them.  Keys the table has not seen are given rows, all of the
    request's at once, when the first of them is met.

    The second step (`bls.aggregate.combine`) runs on the host after the
    fetch: a wide set's partial sums — 64 a mainnet electra aggregate —
    are added by complete additions (`msm.host_lincomb_groups`: native
    when the library is there), so a partial sum that is the identity
    adds nothing, two equal ones double, and only a SET whose sum is the
    identity is flagged."""
    n = len(sets)
    widths = [len(s.pubkeys) for s in sets]
    max_k, n_pad = _fold_shape(widths)
    seg = 2 * max_k
    # (set, first key) of every segment, in set order
    segments = [(i, lo) for i, k in enumerate(widths) if k > 1
                for lo in range(0, k, max_k)]
    blinding, neg_total = _blinding(max_k, n_pad)
    tracing.add_attrs(slices=-(-len(segments) // n_pad), lanes=seg * n_pad,
                      sets=n, segments=len(segments), widest=max(widths))
    products = _msm.blinded_fold_products(seg * n_pad, n_pad)
    added = range(0)
    outs = []
    for first in range(0, len(segments), n_pad):
        with _stage_span("bls.aggregate.layout", "aggregate_layout"):
            rows = np.full((max_k, n_pad), -1, np.int32)
            keys = 0
            for i, (set_idx, lo) in enumerate(segments[first:first + n_pad]):
                members = sets[set_idx].pubkeys[lo:lo + max_k]
                keys += len(members)
                got = np.fromiter(map(_FOLD_ROW, members), np.int32,
                                  len(members))
                if got.min() < 0:
                    # keys the table has not seen: every such key of the
                    # request at once
                    added = _FOLD_KEYS.add(
                        [pk for s in sets if len(s.pubkeys) > 1
                         for pk in s.pubkeys])
                    got = np.fromiter(map(_FOLD_ROW, members), np.int32,
                                      len(members))
                rows[:len(members), i] = got
            rows = rows.reshape(-1)      # s-major: lane j * n_pad + i
            uploaded = int(np.count_nonzero(
                (rows >= added.start) & (rows < added.stop)))
            # taken after the rows were read: it holds every one of them
            table = _FOLD_KEYS.device
        with _stage_span("bls.aggregate.dispatch", "aggregate_dispatch"):
            X, Y, Z = _blinded_lanes(table, rows, *blinding)
            outs.append(_msm.blinded_fold_device(
                X, Y, Z, neg_total[0], neg_total[1], n_pad))
        api.count_fold_lanes(key=keys, blinding=max_k * n_pad,
                             padding=max_k * n_pad - keys)
        api.count_fold_key_rows(resident=keys - uploaded, uploaded=uploaded)
        api.count_fold_products(*products)
    with _stage_span("bls.aggregate.fetch", "aggregate_fetch"):
        fetched = jax.device_get(outs)
    with _stage_span("bls.aggregate.combine", "aggregate_combine"):
        return _combine_segments(sets, segments, fetched)


def _combine_segments(sets, segments, fetched):
    """The fold's second step, on the host: the fetched rows of every
    segment (slice by slice, a slice's unused rows behind its last
    segment) -> the rows of every set, in set order."""
    n = len(sets)
    x_rows = np.zeros((n, bi.L), np.uint32)
    y_rows = np.zeros((n, bi.L), np.uint32)
    inf_rows = np.zeros(n, bool)
    for i, s in enumerate(sets):
        if len(s.pubkeys) == 1:
            x_rows[i], y_rows[i] = s.pubkeys[0].mont_limbs()
    if not segments:
        return x_rows, y_rows, inf_rows
    xa, ya, inf = (np.concatenate(cols)[:len(segments)]
                   for cols in zip(*fetched))
    seg_set = np.fromiter((i for i, _ in segments), np.int64, len(segments))
    whole = np.bincount(seg_set, minlength=n)[seg_set] == 1
    x_rows[seg_set[whole]] = xa[whole]
    y_rows[seg_set[whole]] = ya[whole]
    inf_rows[seg_set[whole]] = inf[whole]
    wide = np.unique(seg_set[~whole])
    if wide.size:
        # identity partial sums (opposing keys inside one sub-segment)
        # add nothing; the additions are complete, so equal ones double
        parts = np.nonzero(~whole & ~inf)[0]
        points = list(zip(bi.from_mont(xa[parts]), bi.from_mont(ya[parts])))
        sums = _msm.host_lincomb_groups(
            points, [1] * len(points),
            np.searchsorted(wide, seg_set[parts]), len(wide))
        for i, pt in zip(wide, sums):
            if pt is cv.INF:
                inf_rows[i] = True
            else:
                x_rows[i], y_rows[i] = ec.ints_to_mont_limbs(pt)
    return x_rows, y_rows, inf_rows


def _dispatch_g1_subgroup_kernel(points):
    """Dispatch (no host sync) the [r-1]P membership kernel over affine
    G1 points, generator-padded to a power of two (floor 4).  Returns
    the device bool row; callers read [:len(points)] when they sync."""
    padded = _next_pow2(len(points), floor=4)
    pts = list(points) + [cv.g1_generator()] * (padded - len(points))
    xp = jnp.asarray(ec.ints_to_mont_limbs([p[0] for p in pts]))
    yp = jnp.asarray(ec.ints_to_mont_limbs([p[1] for p in pts]))
    # deliberately outside the supervised verify path: trusted-setup
    # validation, cold-pubkey checks and the blob batch's membership
    # test handle errors directly
    return _g1_subgroup_kernel(xp, yp)  # lhlint: allow(LH601)


def batch_subgroup_check_g1(points) -> np.ndarray:
    """Device [r-1]P membership test over affine G1 points -> bool[n]
    (the trusted-setup validator, which names the failing points, and
    the cold-pubkey batch path).  Synchronous by contract: it fetches
    the row it dispatched; dispatch_subgroup_check_g1 is the form that
    does not."""
    n = len(points)
    if n == 0:
        return np.zeros(0, bool)
    return np.asarray(_dispatch_g1_subgroup_kernel(points))[:n]


def dispatch_subgroup_check_g1(points):
    """The same test WITHOUT a host sync: an AsyncVerdict whose commit()
    reads whether every point passed.  The blob batch verifier
    (crypto/kzg) dispatches it in front of the evaluation slices and
    commits it once they are fetched, so the host feeds the slices
    while the membership program runs."""
    from lighthouse_tpu.ops import dispatch_pipeline as dp

    if not points:
        return dp.AsyncVerdict.immediate(True)
    return dp.AsyncVerdict(_dispatch_g1_subgroup_kernel(points), len(points))


def _dispatch_subgroup_check(sigs):
    """Dispatch the batched ψ verdict kernel WITHOUT a host sync.

    Returns an AsyncVerdict whose commit() reads the bool row (and marks
    the signatures checked on a pass), or None when a pending signature
    decompressed to infinity (the batch can never verify).  The host
    keeps running aggregate/limb prep while the kernel executes."""
    from lighthouse_tpu.ops import dispatch_pipeline as dp

    faults.fire("subgroup")
    pending = [s for s in sigs if not s.subgroup_checked()]
    if not pending:
        return dp.AsyncVerdict.immediate(True)
    pts = []
    for s in pending:
        pt = s.point_unchecked()
        if pt is cv.INF:
            return None
        pts.append(pt)
    dev_ok = _dispatch_g2_subgroup_kernel(pts)

    def mark():
        for s in pending:
            s.mark_subgroup_checked()

    return dp.AsyncVerdict(dev_ok, len(pts), on_pass=mark)


def _g2_limbs(points) -> list[np.ndarray]:
    return [ec.ints_to_mont_limbs(v) for v in (
        [p[0].a for p in points], [p[0].b for p in points],
        [p[1].a for p in points], [p[1].b for p in points])]


_G1_NEG_LIMBS: list[np.ndarray] | None = None


def _g1_neg_limbs():
    global _G1_NEG_LIMBS
    if _G1_NEG_LIMBS is None:
        gx, gy = cv.g1_neg(cv.g1_generator())
        _G1_NEG_LIMBS = [ec.ints_to_mont_limbs([gx]), ec.ints_to_mont_limbs([gy])]
    return _G1_NEG_LIMBS


def _final_exp_is_one(f_host) -> bool:
    """Full final exponentiation of the batch product, result == 1?

    Native C++ (~4 ms on the chip's host), else host Python (~32 ms);
    never the device: one lane through a 315-step sequential scan leaves
    it idle (1.9 s measured on a v5e, BLS_LEDGER_TPU_r04.json)."""
    from lighthouse_tpu.crypto.bls.fields import final_exponentiation_fast

    try:
        from lighthouse_tpu.ops import native_bls

        if native_bls.available():
            return native_bls.final_exp_is_one(f_host)
    except Exception as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls_backend.native_final_exp", e)
    return final_exponentiation_fast(f_host).is_one()


def verify_sets_pipeline(sets: Sequence[api.SignatureSet],
                         chunk_size: int | None = None) -> bool:
    """Batch verification with the scalar work on device (see module doc).

    Batches larger than the chunk size (``chunk_size`` arg >
    LHTPU_BLS_CHUNK env > dispatch_pipeline.DEFAULT_CHUNK_SETS; 0
    disables) run the OVERLAPPED path: fixed power-of-two chunks are
    dispatched back-to-back, host limb prep for chunk k+1 runs while
    chunk k's fused kernel executes, per-chunk Fq12 partials multiply
    down on device, and the batch still pays ONE d2h fetch and ONE final
    exponentiation.  The ψ subgroup kernel is dispatched without a host
    sync and its verdict row is only read at the commit point.  Chunked
    and single-shot verdicts are identical by construction (the combined
    check is multiplicative over chunks).

    Every stage is a child span of ``bls.verify_pipeline`` that also
    feeds ``bls_verify_stage_seconds{backend="tpu",stage}``: ``subgroup``,
    ``aggregate``, ``prep_host``, ``limbs`` and ``pipeline`` (a dispatch
    time: nothing syncs there) per chunk, ``final_exp``; and as parts of
    them ``aggregate_layout`` / ``aggregate_dispatch`` / ``aggregate_fetch``
    / ``aggregate_combine`` and ``subgroup_wait`` / ``pipeline_wait`` /
    ``final_exp_host``.  A stage
    boundary sits only where the code blocks anyway or between two pieces
    of host code, so the spans measure the program that runs."""
    with tracing.span("bls.verify_pipeline", sets=len(sets)):
        return _verify_sets_pipeline(sets, chunk_size)


def _verify_sets_pipeline(sets: Sequence[api.SignatureSet],
                          chunk_size: int | None = None) -> bool:
    from lighthouse_tpu.ops import dispatch_pipeline as dp

    n = len(sets)
    if n == 0:
        return False
    with _stage_span("bls.subgroup", "subgroup"):
        # one native batch call decompresses every fresh signature (vs one
        # ctypes crossing + C++ setup per signature)
        if not api.Signature.decompress_batch([s.signature for s in sets]):
            return False
        sig_pts = []
        h2cs = []
        for s in sets:
            if not s.pubkeys:
                return False
            try:
                sig_pt = s.signature.point_unchecked()
            except (api.BlsError, ValueError):
                return False
            if sig_pt is cv.INF:
                return False
            sig_pts.append(sig_pt)
            h2cs.append(_hash_to_g2_cached(s.message))

        # G2 membership for fresh signatures: one batched device ψ kernel,
        # DISPATCHED here but not synced — the verdict row is read at the
        # commit point below, after the Miller chunks are in flight, so
        # the aggregate/limb host work runs concurrently with the
        # membership test.
        verdict = _dispatch_subgroup_check([s.signature for s in sets])
        if verdict is None:
            return False

    # per-set pubkey aggregation: one device segment-sum when sets carry
    # real member lists (attestation shape); trivial 1-key batches keep
    # the free host path.  An identity aggregate (opposing keys) can
    # never verify — fail the batch, callers bisect to attribute.
    with _stage_span("bls.aggregate", "aggregate"):
        try:
            n_members = sum(len(s.pubkeys) for s in sets)
            if n_members - n >= 16:
                pk_rows_x, pk_rows_y, agg_inf = aggregate_pubkeys_device(sets)
                if agg_inf.any():
                    return False
            else:
                agg_pks = [s.aggregate_pubkey() for s in sets]
                if any(p is cv.INF for p in agg_pks):
                    return False
                pk_rows_x = ec.ints_to_mont_limbs([p[0] for p in agg_pks])
                pk_rows_y = ec.ints_to_mont_limbs([p[1] for p in agg_pks])
        except (api.BlsError, ValueError):
            return False

    with _stage_span("bls.prep_host", "prep_host"):
        scalars = []
        for _ in range(n):
            r = 0
            while r == 0:
                r = secrets.randbits(RAND_BITS)
            scalars.append(r)
        groups: dict[bytes, list[int]] = {}
        for i, s in enumerate(sets):
            groups.setdefault(s.message, []).append(i)

    # sets that share a message: Π e(r_i·pk_i, H(m)) = e(Σ r_i·pk_i, H(m)),
    # so one device program sums them and the fused program sees one lane
    # a message, its blinding already in the sums (scalar 1)
    lanes, g_pad, _ = merge_shapes(n, len(groups))
    if lanes:
        order = list(groups.values())
        with _stage_span("bls.merge", "merge", sets=n, messages=len(order),
                         lanes=lanes * -(-n // lanes)):
            merged = _merge_same_message(order, sig_pts, h2cs, pk_rows_x,
                                         pk_rows_y, scalars, lanes, g_pad)
        if merged is None:
            return False
        sig_pts, h2cs, pk_rows_x, pk_rows_y, scalars = merged
        n = len(scalars)

    # --- chunked double-buffered dispatch: each chunk's host layout runs
    # while the previous chunk's fused kernel is in flight (async JAX
    # dispatch); per-chunk Fq12 partials multiply down on device and the
    # batch pays ONE fetch + ONE final exponentiation.  A single chunk
    # (the default for node-sized batches) is exactly the old
    # single-shot path.
    chunks = dp.plan_chunks(n, dp.chunk_size(chunk_size))
    partials = []
    overlap_s = 0.0
    for ci, (lo, hi) in enumerate(chunks):
        faults.fire("chunk", index=ci)
        with _stage_span("bls.limbs", "limbs", chunk=ci) as limbs:
            args = _chunk_layout(sig_pts[lo:hi], h2cs[lo:hi],
                                 pk_rows_x[lo:hi], pk_rows_y[lo:hi],
                                 scalars[lo:hi])
        # an unsynced dispatch: operand copies and the enqueue, not the
        # program's execution (pipeline_wait below holds that); ``lanes``
        # is the chunk's live Miller lanes
        with _stage_span("bls.pipeline.dispatch", "pipeline",
                         chunk=ci, lanes=hi - lo) as dispatch:
            partials.append(_pipeline_fused(*args))
        if ci:
            # host work done while a dispatched chunk was executing
            overlap_s += dispatch.end - limbs.start
    dp.record_pipeline(len(chunks), overlap_s, n)

    with _stage_span("bls.final_exp", "final_exp"):
        # commit point: the subgroup verdict row is read only now, with
        # the Miller chunks already in flight behind it in the device
        # queue (a wedged kernel surfaces as WatchdogTimeout for the
        # supervisor)
        with _stage_span("bls.subgroup.wait", "subgroup_wait"):
            passed = verdict.commit(timeout=dp.watchdog_deadline_s())
        if not passed:
            return False
        with _stage_span("bls.pipeline.wait", "pipeline_wait"):
            f = dp.combine_partials(partials)
            f_host = fq12_from_device(jax.device_get(f))
        with _stage_span("bls.final_exp.host", "final_exp_host"):
            return _final_exp_is_one(f_host)


def _chunk_layout(sig_pts, h2cs, pk_rows_x, pk_rows_y, scalars):
    """Host-side lane layout for ONE chunk -> _pipeline_fused argument
    tuple: one lane a set (or a message, after the merge), padded to a
    power of two of at least _PIPELINE_MIN_LANES.  Padding lanes carry
    zero scalars (the scalar-mul leaves them at infinity, adding nothing
    to Σ r·sig) and are masked out of the Miller product."""
    n = len(scalars)
    padded = _next_pow2(n, floor=_PIPELINE_MIN_LANES)
    pad = padded - n
    pkx, pky = pk_rows_x, pk_rows_y
    sg = _g2_limbs(sig_pts)
    h2 = _g2_limbs(h2cs)
    if pad:
        ext = np.zeros((pad, bi.L), np.uint32)
        pkx, pky = (np.concatenate([a, ext]) for a in (pkx, pky))
        sg = [np.concatenate([a, ext]) for a in sg]
        h2 = [np.concatenate([a, ext]) for a in h2]
    bits = jnp.asarray(ec.scalars_to_digits(list(scalars) + [0] * pad))
    lane_mask = np.zeros(padded, bool)
    lane_mask[:n] = True
    g1x, g1y = _g1_neg_limbs()
    return (jnp.asarray(pkx), jnp.asarray(pky),
            *[jnp.asarray(a) for a in sg],
            *[jnp.asarray(a) for a in h2],
            bits, jnp.asarray(lane_mask),
            jnp.asarray(g1x), jnp.asarray(g1y), 0)


def _merge_same_message(order, sig_pts, h2cs, pk_rows_x, pk_rows_y,
                        scalars, lanes, g_pad):
    """The blinded same-message merge on the device: sets in ``order``
    (a message's set indices, a list a message) -> one lane a message,
    (sig_pts, h2cs, pk_rows_x, pk_rows_y, scalars) with Σ r_i·sig_i and
    Σ r_i·pk_i of its sets and the scalar 1, or None when a message's
    sum is the identity (the batch fails; bisection attributes).

    Lanes go in slices of ``lanes`` (`msm._blinded_merge` with ``g_pad``
    groups), all dispatched before the one fetch; the rows come back
    Jacobian and the host turns them affine (one inversion a row) and
    adds a longer batch's slices.  The membership program of the batch's
    fresh signatures is queued ahead of the slices, so the fetch
    (``bls.merge.wait``) waits for it too: dispatched after the merge it
    would put the host's key, scalar and merge layouts in front of it
    and lengthen the device's chain by them."""
    n = len(scalars)
    group = np.zeros(n, np.int32)
    for g, members in enumerate(order):
        group[members] = g
    products = _msm.blinded_merge_products(lanes, g_pad)
    outs = []
    for lo in range(0, n, lanes):
        hi = min(lo + lanes, n)
        with _stage_span("bls.merge.layout", "merge_layout"):
            args = _merge_layout(sig_pts[lo:hi], pk_rows_x[lo:hi],
                                 pk_rows_y[lo:hi], scalars[lo:hi],
                                 group[lo:hi], lanes)
        with _stage_span("bls.merge.dispatch", "merge_dispatch"):
            outs.append(_msm._blinded_merge(*args, g_pad))
        api.count_merge_lanes(live=hi - lo, padding=lanes - (hi - lo))
        api.count_merge_products(products)
    with _stage_span("bls.merge.wait", "merge_wait"):
        fetched = jax.device_get(outs)
    api.count_merged("device", sum(len(m) for m in order if len(m) > 1))
    with _stage_span("bls.merge.combine", "merge_combine"):
        pks = [cv.INF] * len(order)
        sigs = [cv.INF] * len(order)
        for (P, S) in fetched:
            for g, pt in enumerate(_msm.jacobian_rows_to_affine(*P)[
                    :len(order)]):
                pks[g] = cv.g1_add(pks[g], pt)
            for g, pt in enumerate(_g2_rows_to_affine(S)[:len(order)]):
                sigs[g] = cv.g2_add(sigs[g], pt)
        if any(p is cv.INF for p in pks + sigs):
            return None
        return (sigs, [h2cs[m[0]] for m in order],
                ec.ints_to_mont_limbs([p[0] for p in pks]),
                ec.ints_to_mont_limbs([p[1] for p in pks]),
                [1] * len(order))


def _merge_layout(sig_pts, pk_x, pk_y, scalars, group, lanes):
    """One `_blinded_merge` slice's operands, its lanes padded to
    ``lanes``: padding lanes carry the scalar 0 (the identity)."""
    pad = lanes - len(scalars)
    ext = np.zeros((pad, bi.L), np.uint32)
    cols = [np.concatenate([a, ext])
            for a in (pk_x, pk_y, *_g2_limbs(sig_pts))]
    digits = ec.scalars_to_digits(list(scalars) + [0] * pad)
    return (*[jnp.asarray(a) for a in cols], jnp.asarray(digits),
            jnp.asarray(np.concatenate([group, np.zeros(pad, np.int32)])))


def _g2_rows_to_affine(S):
    """HOST: Montgomery Jacobian Fq2 rows ((Xa, Xb), (Ya, Yb), (Za, Zb))
    -> affine G2 points (cv.INF for identity rows)."""
    from lighthouse_tpu.crypto.bls.fields import Fq2

    X, Y, Z = ([Fq2(int(a), int(b)) for a, b in
                zip(bi.from_mont(c[0]), bi.from_mont(c[1]))] for c in S)
    out = []
    for x, y, z in zip(X, Y, Z):
        if z == Fq2(0, 0):
            out.append(cv.INF)
            continue
        zi = z.inv()
        zi2 = zi * zi
        out.append((x * zi2, y * zi2 * zi))
    return out


def verify_signature_sets_device(sets: Sequence[api.SignatureSet],
                                 chunk_size: int | None = None) -> bool:
    if not sets:
        return False
    # the supervisor-visible dispatch boundary: an injected entry fault
    # fires before ANY device work, and a corrupt-mode plan substitutes
    # its verdict outright (modelling a device that returned garbage)
    if faults.fire("tpu") == "corrupt":
        return faults.corrupt_verdict()
    return verify_sets_pipeline(sets, chunk_size=chunk_size)


api.register_backend("tpu", verify_signature_sets_device)
