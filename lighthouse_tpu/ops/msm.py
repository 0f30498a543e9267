"""One MSM plane: the windowed-MSM/segment-sum kernel family.

Every multi-scalar-multiplication in the tree used to carry its own
copy of the same idiom — `crypto/kzg.py` had a private jit of
ec.g1_msm_windowed plus the RLC 2-segment fold, `crypto/das.py` a
cell-proof chunk fold, `ops/pubkey_kernels.py` the fused gather+fold,
and `ops/bls_backend.py` the blinded-merge lincomb — four program-store
entries, four padding rules, four routing guesses.  This module is the
single owner ("Enabling AI ASICs for Zero Knowledge Proof", PAPERS.md:
big-field MSM is exactly the workload where matrix hardware wins, so it
deserves ONE tuned home):

- **tracks** — ``g1`` (windowed G1 scalar-mul + segment sum),
  ``gather`` (table-gather front end fused ahead of the same fold, the
  pubkey-registry shape), the blinded fold (segment sum + blinding
  subtraction + affine conversion, the bls_backend merge shape), and
  the joint G1×G2 track (`fold_segments_gj`, traced inline by the
  fused verify pipeline);
- **one pow2 bucket policy** — `bucket()` (floor knob
  ``LHTPU_MSM_BUCKET_FLOOR`` + masked zero-scalar tail lanes, the
  epoch_kernels idiom) so consumers cannot drift apart on padding;
- **one host fallback seam** — `host_lincomb_groups` /
  `host_lincomb_groups_g2` over the native ``lhbls_g1/g2_lincomb``
  kernels (ops/native_bls) with a pure-Python Jacobian tail;
- **data-calibrated routing** — `calibrate_device_thresholds` measures
  the device-vs-host break-even lane count once per platform
  fingerprint (persisted as the ``msm_calibration.json`` sidecar by
  ops/prewarm, the sha_calibration pattern); ``LHTPU_MSM_DEVICE_MIN``
  pins it outright.

Consumers keep their own backend ladders (breaker, supervisor,
reference recovery) and call in here only for the kernel dispatch, so
verdicts and fault behavior are unchanged.  Shape discipline (lhlint
LH301/302): the three jitted programs below are the ONLY jit sites;
compile-cache keys are pure functions of (lane bucket, segment bucket).
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from lighthouse_tpu.common import device_telemetry as _dtel
from lighthouse_tpu.common.metrics import REGISTRY, record_swallowed
from lighthouse_tpu.ops import bigint as bi
from lighthouse_tpu.ops import ec
from lighthouse_tpu.ops import program_store as _pstore

# AOT program-store coverage (lhlint LH606): the whole family is
# prewarmed by the "msm" driver — FIRST in prewarm's DRIVER_ORDER,
# because the BLS verify driver dispatches the blinded fold internally
_pstore.register_entry("ops/msm.py::_fold_kernel@_fold_kernel",
                       driver="msm")
_pstore.register_entry("ops/msm.py::_gather_fold@_gather_fold",
                       driver="msm")
_pstore.register_entry("ops/msm.py::_blinded_fold@_blinded_fold",
                       driver="msm")

from lighthouse_tpu.crypto.bls import curve as cv
from lighthouse_tpu.crypto.bls.fields import P as _P
from lighthouse_tpu.crypto.bls.fields import R as _R

TRACKS = ("g1", "gather")


# -- bucket policy ------------------------------------------------------------


def bucket(n: int, floor: int = 1) -> int:
    """The one pow2 lane/segment bucket: next power of two of ``n``,
    floored at max(``floor``, LHTPU_MSM_BUCKET_FLOOR).  Padding lanes
    carry zero scalars (windowed scan leaves them at exact infinity =
    group identity), so a larger floor only trades FLOPs for fewer
    compiled shapes."""
    from lighthouse_tpu.common import env as envreg

    env_floor = envreg.get_int("LHTPU_MSM_BUCKET_FLOOR")
    f = max(int(floor), env_floor if env_floor is not None else 1, 1)
    return max(f, 1 << max(int(n) - 1, 0).bit_length())


# -- the traceable kernel family (composed inline by fused consumers) ---------


def fold_segments_g1(xs, ys, digits, n_segments):
    """Windowed G1 scalar-mul over lanes + s-major segment sum ->
    Jacobian rows (X, Y, Z) uint32[n_segments, L].  ``digits`` are
    MSB-first base-16 window digits (ec.scalars_to_digits); lane count
    must be a multiple of n_segments with a pow2 segment length.  The
    lanes (xs, ys uint32[N, L]) are turned limb-major on entry and the
    rows back on exit: in between every product is
    `MontField.mont_mul_lm`'s."""
    X, Y, Z = ec.g1_scalar_mul_windowed(xs.T, ys.T, digits)
    return tuple(c.T for c in ec.g1_segment_sum_lm(X, Y, Z, n_segments))


def fold_products(lanes: int, n_segments: int, windows: int) -> int:
    """Fp lane-products of `fold_segments_g1`, all on `mont_mul_lm`: a
    Jacobian doubling is 7 and a full addition 16; the window tables
    double and add 7 entries a lane, a window of the scan is four
    doublings and one addition, and the segment sum adds every lane but
    the last of each segment."""
    dbl, add = 7, 16
    return ((7 * (dbl + add) + windows * (4 * dbl + add)) * lanes
            + add * (lanes - n_segments))


def fold_segments_gj(xp, yp, xq, yq, digits):
    """The joint G1×G2 track: one merged windowed scan over G1 lanes
    (xp, yp) and G2 lanes (xq, yq limb-pair tuples) sharing ``digits``,
    then the G2 tree-sum.  Returns ((Xp, Yp, Zp), (SX, SY, SZ)) exactly
    as the fused verify pipeline consumes them: flat lanes, one a set.
    Sets that share a message are summed before it, by the merge track
    (`_blinded_merge`: the same scan on the limb-major multiply, then a
    segmented sum), so the Miller loops run one lane a message."""
    (Xp, Yp, Zp), (SX, SY, SZ) = ec.gj_scalar_mul_windowed(
        xp, yp, xq, yq, digits)
    SX, SY, SZ = ec.g2_sum_reduce(SX, SY, SZ)
    return (Xp, Yp, Zp), (SX, SY, SZ)


# -- the jitted programs (one store entry per track) --------------------------


@partial(jax.jit, static_argnums=(3,))
def _fold_kernel(xs, ys, digits, n_segments):
    """The plain G1 track: Montgomery affine lanes -> per-segment
    Jacobian rows (kzg lincomb at n_segments=1, das cell-proof chunks
    at the group bucket)."""
    return fold_segments_g1(xs, ys, digits, n_segments)


_fold_kernel = _dtel.instrument(
    "ops/msm.py::_fold_kernel@_fold_kernel", _fold_kernel)


@partial(jax.jit, static_argnums=(4,))
def _gather_fold(tx, ty, lane_idx, digits, n_segments):
    """The gather track: lanes gathered out of a device-resident table
    (tx/ty uint32[T, L]) ahead of the same fold, then affine conversion
    and the device identity verdict (the pubkey-registry shape)."""
    xp = jnp.take(tx, lane_idx, axis=0)
    yp = jnp.take(ty, lane_idx, axis=0)
    Xg, Yg, Zg = fold_segments_g1(xp, yp, digits, n_segments)
    xa, ya = ec.g1_jacobian_to_affine_batch(Xg, Yg, Zg)
    return xa, ya, bi.is_zero_mod_p_device(Zg)


_gather_fold = _dtel.instrument(
    "ops/msm.py::_gather_fold@_gather_fold", _gather_fold)


@partial(jax.jit, static_argnums=(5,))
def _blinded_fold(X, Y, Z, ux, uy, n_segments):
    """The blinded-merge track: segmented G1 sum over (payload +
    blinding) Jacobian lanes, minus the known blinding total (ux, uy),
    then affine conversion.  The infinity flag (Z ≡ 0) is resolved on
    device — one bool row home, not a limb row.  The lanes (uint32[N, L])
    are turned limb-major for the sum, as `fold_segments_g1` turns its
    lanes, and its n_segments rows back: the sum's products are
    `MontField.mont_mul_lm`'s, the rows' behind it `mont_mul`'s
    (`blinded_fold_products`)."""
    Xg, Yg, Zg = (c.T for c in ec.g1_segment_sum_lm(X.T, Y.T, Z.T,
                                                    n_segments))
    one = jnp.broadcast_to(bi._jconst("one_m"), Xg.shape)
    Xr, Yr, Zr = ec._jac_add_full(
        ec._FpAdapter, (Xg, Yg, Zg),
        (jnp.broadcast_to(ux, Xg.shape), jnp.broadcast_to(uy, Yg.shape),
         one))
    xa, ya = ec.g1_jacobian_to_affine_batch(Xr, Yr, Zr)
    return xa, ya, bi.is_zero_mod_p_device(Zr)


_blinded_fold = _dtel.instrument(
    "ops/msm.py::_blinded_fold@_blinded_fold", _blinded_fold)


def blinded_fold_products(lanes: int, n_segments: int) -> tuple[int, int]:
    """(resident, materialized) Fp lane-products of one `_blinded_fold`
    dispatch, as the program routes them: on `mont_mul_lm` the segment
    sum's full additions (`fold_products`' price: 16 for every lane but
    the last of each segment); on `mont_mul`, over the n_segments rows
    behind it, the unblinding addition (16), the affine conversion (a
    381-step inversion ladder of 2, then 4) and the zero test (1)."""
    return 16 * (lanes - n_segments), (16 + 2 * 381 + 4 + 1) * n_segments


# the same-message merge of a batch verification: prewarmed by the "msm"
# driver through the seam, with a batch that takes it
_pstore.register_entry("ops/msm.py::_blinded_merge@_blinded_merge",
                       driver="msm")


@partial(jax.jit, static_argnums=(8,))
def _blinded_merge(pkx, pky, sxa, sxb, sya, syb, digits, group, n_groups):
    """The same-message merge of a batch verification: lane i carries a
    set's aggregate key (pkx, pky) and its signature (sxa + sxb·u,
    sya + syb·u), affine rows uint32[N, L], its blinding scalar r_i as
    window digits (uint32[16, N], `ec.scalars_to_digits`) and the group
    of its message (int32[N]; a padding lane has scalar 0 and adds the
    identity wherever it points).  Returns, for every group g below the
    static ``n_groups``, Σ r_i·pk_i (Jacobian rows (X, Y, Z) uint32[G, L])
    and Σ r_i·sig_i (Jacobian Fq2 rows, pairs of them).

    One windowed scan over both tracks (`ec.gj_scalar_mul_windowed`),
    then every lane spread over the groups, the identity where it is not
    its group's, s-major, and one segmented sum of both tracks
    (`ec.segment_sums_lm`): the lanes are turned limb-major on entry and
    the rows back on exit, and every product is `MontField.mont_mul_lm`'s
    (`blinded_merge_products`)."""
    P, S = ec.gj_scalar_mul_windowed(
        pkx.T, pky.T, (sxa.T, sxb.T), (sya.T, syb.T), digits, lm=True)
    live = group[None, :, None] == jnp.arange(n_groups)[None, None, :]

    def spread(c):
        return jnp.where(live, c[:, :, None], 0).reshape(c.shape[0], -1)

    Pg, Sg = ec.segment_sums_lm(
        [(ec._FpLmAdapter, tuple(spread(c) for c in P)),
         (ec._Fq2LmAdapter, tuple((spread(c[0]), spread(c[1])) for c in S))],
        n_groups)
    return tuple(c.T for c in Pg), tuple((c[0].T, c[1].T) for c in Sg)


_blinded_merge = _dtel.instrument(
    "ops/msm.py::_blinded_merge@_blinded_merge", _blinded_merge)


def blinded_merge_products(lanes: int, n_groups: int,
                           windows: int = 16) -> int:
    """Fp lane-products of one `_blinded_merge` dispatch, all on
    `mont_mul_lm`: `fold_products`' scan and tables on the G1 lanes, three
    times as many on the G2 lanes (an Fq2 product is three Karatsuba
    products), then log2(lanes) levels of the segmented sum, each adding
    half of the lanes x groups in both tracks (16 and 48 products)."""
    scan = fold_products(lanes, lanes, windows)
    levels = lanes.bit_length() - 1
    return 4 * scan + levels * (lanes * n_groups // 2) * (16 + 48)


# -- dispatch wrappers --------------------------------------------------------


def fold_device(xs, ys, digits, n_segments: int):
    """One plain-track dispatch -> HOST Jacobian rows (X, Y, Z)
    uint32[n_segments, L]."""
    X, Y, Z = jax.device_get(_fold_kernel(
        jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(digits),
        int(n_segments)))
    return np.asarray(X), np.asarray(Y), np.asarray(Z)


def gather_fold_device(tx, ty, lane_idx, digits, n_segments: int):
    """One gather-track dispatch (device arrays in, device arrays out —
    the caller owns placement/sharding and the device_get)."""
    return _gather_fold(tx, ty, lane_idx, digits, int(n_segments))


def blinded_fold_device(X, Y, Z, ux, uy, n_segments: int):
    """One blinded-track dispatch (lane rows in, device rows out)."""
    return _blinded_fold(jnp.asarray(X), jnp.asarray(Y), jnp.asarray(Z),
                         ux, uy, int(n_segments))


def jacobian_rows_to_affine(X, Y, Z) -> list:
    """HOST: Montgomery Jacobian limb rows -> affine int points
    (cv.INF for identity rows) — the one d2h conversion every plain-
    track consumer shares."""
    out = []
    for xr, yr, zr in zip(X, Y, Z):
        z = int(bi.from_mont(np.asarray(zr)))
        if z == 0:
            out.append(cv.INF)
            continue
        x = int(bi.from_mont(np.asarray(xr)))
        y = int(bi.from_mont(np.asarray(yr)))
        zi = pow(z, -1, _P)
        out.append((x * zi * zi % _P, y * zi * zi % _P * zi % _P))
    return out


# -- host fallback seam -------------------------------------------------------


def host_lincomb_groups(points, scalars, groups, n_groups: int) -> list:
    """Σ k·P per group over affine G1 int points, on the HOST: the
    native ``lhbls_g1_lincomb`` kernel when the library is present,
    pure-Python Jacobian adds otherwise.  ``groups`` maps each lane to
    its group (None = one group over all lanes).  Returns affine points
    (cv.INF for identity groups)."""
    idx = groups if groups is not None else [0] * len(points)
    pts, ks, gs = [], [], []
    for p, k, g in zip(points, scalars, idx):
        k = k % _R
        if k == 0 or p is cv.INF:
            continue
        pts.append(p)
        ks.append(k)
        gs.append(int(g))
    if pts:
        try:
            from lighthouse_tpu.ops import native_bls

            if native_bls.available():
                rows = native_bls.g1_lincomb_groups(pts, ks, gs, n_groups)
                if rows is not None:
                    return [cv.INF if r is None else r for r in rows]
        except Exception as e:
            record_swallowed("msm.native_lincomb", e)
    acc = [cv.INF] * n_groups
    for p, k, g in zip(pts, ks, gs):
        acc[g] = cv.g1_add(acc[g], cv.g1_mul(p, k))
    return acc


def host_lincomb_groups_g2(points, scalars, groups, n_groups: int) -> list:
    """The G2 half of the seam (native ``lhbls_g2_lincomb`` / pure
    Python) — same contract as host_lincomb_groups over affine Fq2
    points."""
    idx = groups if groups is not None else [0] * len(points)
    pts, ks, gs = [], [], []
    for p, k, g in zip(points, scalars, idx):
        k = k % _R
        if k == 0 or p is cv.INF:
            continue
        pts.append(p)
        ks.append(k)
        gs.append(int(g))
    if pts:
        try:
            from lighthouse_tpu.ops import native_bls

            if native_bls.available():
                rows = native_bls.g2_lincomb_groups(pts, ks, gs, n_groups)
                if rows is not None:
                    return [cv.INF if r is None else r for r in rows]
        except Exception as e:
            record_swallowed("msm.native_lincomb_g2", e)
    acc = [cv.INF] * n_groups
    for p, k, g in zip(pts, ks, gs):
        acc[g] = cv.g2_add(acc[g], cv.g2_mul(p, k))
    return acc


# -- the g1 lincomb front door (the c-kzg g1_lincomb seam) --------------------


def msm_g1(points, scalars, *, device: bool | None = None,
           pad_to: int | None = None):
    """Σ k_i·P_i over affine G1 int points, device-routed by the
    calibrated g1-track threshold (`device` forces a path; ``pad_to``
    rounds the lane bucket up so differently-sized MSMs share one
    compiled program).  Infinity points enter as zero-scalar identity
    lanes; scalars reduce mod the subgroup order."""
    use_device = (device if device is not None
                  else len(points) >= device_min("g1"))
    if not use_device:
        return host_lincomb_groups(points, scalars, None, 1)[0]
    n = len(points)
    padded = bucket(n)
    if pad_to is not None:
        padded = max(padded, pad_to)
    xs, ys, ks = [], [], []
    for p, k in zip(points, scalars):
        if p is cv.INF:
            xs.append(0)
            ys.append(0)
            ks.append(0)
        else:
            xs.append(p[0])
            ys.append(p[1])
            ks.append(k % _R)
    xs += [0] * (padded - n)
    ys += [0] * (padded - n)
    ks += [0] * (padded - n)
    X, Y, Z = fold_device(ec.ints_to_mont_limbs(xs),
                          ec.ints_to_mont_limbs(ys),
                          ec.scalars_to_digits(ks, n_bits=256), 1)
    return jacobian_rows_to_affine(X, Y, Z)[0]


# -- data-calibrated device routing -------------------------------------------

# static default (assumes a real TPU); calibrate_device_thresholds /
# apply_calibration replace it per track with measured break-evens.
# The ceiling means "the device never wins here: route all to host".
_STATIC_DEVICE_MIN = 256
_THRESHOLD_CEIL = 1 << 20
_DEVICE_MIN: dict[str, int] = {}
_CALIBRATED = False


def device_min(track: str = "g1") -> int:
    """Lane count at or above which ``track`` routes to the device.
    An explicit ``LHTPU_MSM_DEVICE_MIN`` pin wins over both the static
    default and any adopted calibration."""
    from lighthouse_tpu.common import env as envreg

    pin = envreg.get_int("LHTPU_MSM_DEVICE_MIN")
    if pin is not None:
        return max(1, pin)
    return _DEVICE_MIN.get(track, _STATIC_DEVICE_MIN)


def _measure_rate(fn, lanes: int, min_s: float = 0.01) -> float:
    """lanes folded per second, repeating until min_s of wall time."""
    done = 0
    t0 = time.perf_counter()
    while True:
        fn()
        done += lanes
        dt = time.perf_counter() - t0
        if dt >= min_s:
            return done / max(dt, 1e-9)


def calibrate_device_thresholds(sample_lanes: int = 2,
                                force: bool = False) -> dict:
    """One-shot micro-calibration of the device-vs-host MSM routing.

    Measures the host lincomb rate (native/pure Python) and the device
    fold rate + per-dispatch overhead at one small pow2 lane bucket,
    then solves the break-even lane count
    n* = overhead / (1/host − 1/device) per track — below n* a device
    dispatch loses even when its asymptotic rate wins.  The gather
    track shares the g1 break-even (same fold core behind a take).
    Publishes ``msm_device_threshold_lanes{track}`` and returns the
    measurement object the ``msm_calibration.json`` sidecar persists.

    ``LHTPU_MSM_DEVICE_MIN`` bypasses measurement entirely (operator
    pin).  Runs once per process unless ``force``; the sample bucket is
    deliberately the prewarm driver's 2-lane shape so a warm store
    serves the measurement dispatches."""
    global _CALIBRATED
    from lighthouse_tpu.common import env as envreg

    if _CALIBRATED and not force:
        return {"tracks": {t: {"threshold_lanes": device_min(t)}
                           for t in TRACKS}, "cached": True}
    _CALIBRATED = True
    pin = envreg.get_int("LHTPU_MSM_DEVICE_MIN")
    if pin is not None:
        for t in TRACKS:
            _DEVICE_MIN[t] = max(1, pin)
        _publish_thresholds()
        return {"tracks": {t: {"threshold_lanes": _DEVICE_MIN[t]}
                           for t in TRACKS}, "source": "env"}
    n = bucket(sample_lanes)
    g = cv.g1_generator()
    pts = [cv.g1_mul(g, 3 + i) for i in range(n)]
    ks = [(0x9E3779B97F4A7C15 * (i + 1)) % _R for i in range(n)]
    xs = jnp.asarray(ec.ints_to_mont_limbs([p[0] for p in pts]))
    ys = jnp.asarray(ec.ints_to_mont_limbs([p[1] for p in pts]))
    dg = jnp.asarray(ec.scalars_to_digits(ks, n_bits=256))
    # compile outside the timing (persistent cache makes this a load)
    jax.block_until_ready(_fold_kernel(xs, ys, dg, 1))
    dev_rate = _measure_rate(
        lambda: jax.block_until_ready(_fold_kernel(xs, ys, dg, 1)), n)
    host_rate = _measure_rate(
        lambda: host_lincomb_groups(pts, ks, None, 1), n)
    # per-dispatch overhead: repeated already-compiled-shape calls
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        jax.block_until_ready(_fold_kernel(xs, ys, dg, 1))
    overhead_s = (time.perf_counter() - t0) / reps
    if dev_rate <= host_rate:
        threshold = _THRESHOLD_CEIL
    else:
        n_star = overhead_s / (1.0 / host_rate - 1.0 / dev_rate)
        threshold = 1 << max(int(n_star) - 1, 1).bit_length()
        threshold = min(max(threshold, 16), _THRESHOLD_CEIL)
    for t in TRACKS:
        _DEVICE_MIN[t] = threshold
    _publish_thresholds()
    g1_track = {
        "threshold_lanes": threshold,
        "host_lanes_per_s": round(host_rate, 1),
        "device_lanes_per_s": round(dev_rate, 1),
        "dispatch_overhead_ms": round(overhead_s * 1000, 3),
    }
    return {"tracks": {"g1": g1_track,
                       "gather": {"threshold_lanes": threshold}},
            "source": "measured"}


def apply_calibration(data: dict) -> bool:
    """Adopt a persisted calibration measurement (the program store's
    ``msm_calibration`` sidecar for this platform fingerprint) instead
    of re-measuring.  Returns False — and changes nothing — when the
    record does not carry a usable g1 threshold, so a damaged sidecar
    falls back to measurement; a missing gather track inherits g1's."""
    global _CALIBRATED
    try:
        g1 = int(data["tracks"]["g1"]["threshold_lanes"])
    except (KeyError, TypeError, ValueError):
        return False
    if g1 < 1:
        return False
    thresholds = {"g1": min(g1, _THRESHOLD_CEIL)}
    try:
        gather = int(data["tracks"]["gather"]["threshold_lanes"])
        if gather < 1:
            gather = thresholds["g1"]
    except (KeyError, TypeError, ValueError):
        gather = thresholds["g1"]
    thresholds["gather"] = min(gather, _THRESHOLD_CEIL)
    _DEVICE_MIN.update(thresholds)
    _CALIBRATED = True
    _publish_thresholds()
    return True


def _publish_thresholds() -> None:
    try:
        for t in TRACKS:
            REGISTRY.gauge(
                "msm_device_threshold_lanes",
                "lane count above which the MSM track routes to the "
                "device (static default, operator pin, or calibration)",
            ).labels(track=t).set(_DEVICE_MIN.get(t, _STATIC_DEVICE_MIN))
    except Exception as e:
        record_swallowed("msm.publish_thresholds", e)


# -- FK20: every cell proof of a blob by two transforms over G1 ----------------
#
# Feist and Khovratovich 2020 (ePrint 2023/033), for the spec's cells.  With
# n the blob's width, l the cell size, K = n / l and m = 2K cells, cell c's
# proof is pi_c = sum_{k < K-1} a_c^k H_k, a_c = h_c^l an m-th root of
# unity, and H_k = sum_{i < l} sum_{j <= K-2-k} S_{jl+i} f_{(k+1+j)l+i}
# (S_j = g1_monomial[j], f the blob's coefficients): for each i a Toeplitz
# product, embedded in a circulant of size m.  With T_i the transform of
# setup column i (made once, `_fk20_tables`) and F_i that of coefficient
# column i, H is the inverse transform of sum_i T_i F_i (entry k of the
# inverse is entry -k of the transform, over m), and the proofs are the
# transform of H, which lands them in cell order (a_c = omega_m^brp(c)).
# The fixed-base stage is one windowed sum over the l columns a (cell,
# blob), on tables the blobs share (ec.proj_straus_lm), then two G1
# transforms of size m a blob; complete formulas, so zeros need no flag.

import functools as _functools  # noqa: E402

_pstore.register_entry("ops/msm.py::_fk20_kernel@_fk20_kernel", driver="msm")
_pstore.register_entry("ops/msm.py::_fk20_tables@_fk20_tables", driver="msm")


@jax.jit
def _fk20_tables(sx, sy, sz):
    """The FK20 setup: the l circulant setup columns as projective
    limb-major points uint32[27, m, l] each (`fk20_setup_columns`: entry k
    of column i at [:, k, i]) -> their transforms uint32[27, l, m], entry u
    of column i at [:, i, u] in bit-reversed order."""
    _, m, ell = sx.shape
    T = ec.g1_fft_lm(tuple(c.reshape(bi.L, -1) for c in (sx, sy, sz)),
                     jnp.asarray(fk20_twiddle_digits(m)), ell)
    return tuple(c.reshape(bi.L, m, ell).transpose(0, 2, 1) for c in T)


_fk20_tables = _dtel.instrument(
    "ops/msm.py::_fk20_tables@_fk20_tables", _fk20_tables)


def _window_digits_lm(digits15: list, windows: int = 64) -> jax.Array:
    """Exact 15-bit digit rows of values below 2^(4·windows) -> their
    MSB-first base-16 window digits uint32[windows, lanes]."""
    out = []
    for w in range(windows - 1, -1, -1):
        j, off = divmod(4 * w, 15)
        v = digits15[j] >> off
        if off > 11 and j + 1 < len(digits15):
            v = v | (digits15[j + 1] << (15 - off))
        out.append(v & 15)
    return jnp.stack(out)


@jax.jit
def _fk20_kernel(coeffs, tx, ty, tz):
    """Every cell proof of R blobs.

    coeffs: uint32[18, n·R] raw Fr limb-major lanes j·R + r, coefficient j
    of blob r (`fr._recover_kernel`'s, or the host's); tx, ty, tz: the
    setup transforms uint32[27, l, m] of `_fk20_tables`.  Returns Jacobian
    rows (X, Y, Z) uint32[R, m, 27]: row r, cell c."""
    from lighthouse_tpu.ops import fr

    _, ell, m = tx.shape
    K = m // 2
    rows = coeffs.shape[1] // (K * ell)
    fr_tw, inv_m = _fk20_constants(m)
    # coefficient column i: f_{(t+1)l+i} at t < K-1, zero to m
    cols = coeffs.reshape(fr.L, K, ell * rows)[:, 1:]
    cols = jnp.concatenate(
        [cols, jnp.zeros((fr.L, m - K + 1, ell * rows), jnp.uint32)], axis=1)
    F = fr.ntt_lm(cols.reshape(fr.L, -1), jnp.asarray(fr_tw), ell * rows)
    # F / m as a plain value below 2^256, its 64 window digits; lanes from
    # (u, i, r) to axes (r, i, u), the setup's (i, u) minor
    plain = fr.mont_mul_lm(F, inv_m)
    digits = _window_digits_lm(fr._carry_digits_lm(plain))
    digits = digits.reshape(64, m, ell, rows).transpose(0, 3, 2, 1)
    # sum_i (F_i / m)·T_i for every (u, r): one windowed sum over the l
    # columns, on one table of multiples a setup point (i, u)
    tab = ec.proj_multiples_lm(tuple(c.reshape(bi.L, -1)
                                     for c in (tx, ty, tz)))
    P = ec.proj_straus_lm(tuple(c.reshape(16, bi.L, ell, m) for c in tab),
                          digits)
    P = tuple(c.transpose(0, 2, 1).reshape(bi.L, -1) for c in P)
    # both transforms of size m in one scan of forward stages: after the
    # first, entry -k of m·IDFT is H_k (natural order once the transform's
    # bit-reversed order is undone), entries K-1 and up are no part of H
    k = jnp.arange(m * rows) // rows
    one = ec.proj_identity(P[0])

    def to_h(X):
        X = ec.bit_reverse_lm(X, m, rows).reshape(bi.L, m, rows)
        return jnp.concatenate([X[:, :1], X[:, :0:-1]], axis=1).reshape(
            bi.L, -1)

    def stage(P, xs):
        dg, s = xs
        P = ec.g1_fft_stage_lm(P, dg, rows)
        H = tuple(jnp.where((k < K - 1)[None], to_h(c), e)
                  for c, e in zip(P, one))
        return tuple(jnp.where(s == log_m - 1, h, c)
                     for c, h in zip(P, H)), None

    log_m = m.bit_length() - 1
    digits2 = jnp.asarray(np.concatenate([fk20_twiddle_digits(m)] * 2))
    P = tuple(ec.bit_reverse_lm(c, m, rows) for c in P)
    P, _ = jax.lax.scan(stage, P, (digits2, jnp.arange(2 * log_m)))
    return tuple(c.reshape(bi.L, m, rows).transpose(2, 1, 0)
                 for c in ec.proj_to_jacobian_lm(P))


_fk20_kernel = _dtel.instrument(
    "ops/msm.py::_fk20_kernel@_fk20_kernel", _fk20_kernel)


def fk20_products(blobs: int, ell: int = 64, m: int = 128
                  ) -> tuple[int, int]:
    """(resident, materialized) lane-products of one `_fk20_kernel`
    dispatch over ``blobs`` blobs of cell size ``ell`` and ``m`` cells, all
    on `mont_mul_lm`: in Fr the coefficient columns' transforms and the
    scaling by 1/m; in Fp the multiples of the m·l setup points, the
    windowed sums of the m·blobs (cell, blob) lanes, the two G1 transforms
    and the Jacobian rows."""
    from lighthouse_tpu.ops import fr

    lanes = m * ell * blobs
    fr_side = fr.ntt_products(ell * blobs, m) + lanes
    fp_side = (m * ell * ec.PROJ_MULTIPLES_PRODUCTS
               + m * blobs * ec.proj_straus_products(64, ell)
               + 2 * blobs * ec.g1_fft_products(m) + 3 * m * blobs)
    return fr_side + fp_side, 0


def fk20_setup_columns(g1_monomial, ell: int, m: int):
    """HOST: the l circulant setup columns of FK20 as projective Montgomery
    limb-major points uint32[27, m, l] (`_fk20_tables`' input): column i
    holds S_i at entry 0, S_{(m-q)l+i} at entries q = K+2 .. m-1 and the
    identity elsewhere."""
    K = m // 2
    xs, ys, zs = [], [], []
    for q in range(m):
        for i in range(ell):
            j = (m - q) % m
            p = g1_monomial[j * ell + i] if j <= K - 2 else cv.INF
            if p is cv.INF:
                xs.append(0), ys.append(1), zs.append(0)
            else:
                xs.append(p[0]), ys.append(p[1]), zs.append(1)
    return tuple(ec.ints_to_mont_limbs(v).T.reshape(bi.L, m, ell)
                 for v in (xs, ys, zs))


@_functools.cache
def fk20_twiddle_digits(m: int) -> np.ndarray:
    """HOST: the window digits of `ec.g1_fft_lm`'s twiddles at size m."""
    from lighthouse_tpu.crypto.kzg import _compute_roots_of_unity

    roots = _compute_roots_of_unity(m)
    return np.stack([
        ec.scalars_to_digits([roots[(k >> s) << s] for k in range(m // 2)],
                             n_bits=256)
        for s in range(m.bit_length() - 1)])


@_functools.cache
def _fk20_constants(m: int):
    """The FK20 program's constants at m cells: the Fr transform's
    twiddles (`fr.ntt_lm`'s form) and Montgomery 1/m as a host limb
    table."""
    from lighthouse_tpu.crypto.kzg import _compute_roots_of_unity
    from lighthouse_tpu.ops import fr

    roots = _compute_roots_of_unity(m)
    stages = m.bit_length() - 1
    tw = np.stack([fr.to_mont_host([roots[(k >> s) << s]
                                    for k in range(m // 2)]).T
                   for s in range(stages)])
    return tw, fr.to_mont_host(pow(m, -1, fr.R_INT))
