"""The unified MSM plane (ops/msm + parallel/msm_sharded, ISSUE 17).

Digest-identity contract: every consumer migrated onto the plane (kzg
lincomb, das cell-proof chunks, the pubkey-plane gather fold, the
blinded merge, the RLC 2-segment fold) must produce bit-identical
results to the pre-refactor per-consumer idioms it replaced — including
zero-scalar padding lanes, non-pow2 counts, and identity points.
Calibration contract: a corrupt/truncated msm_calibration sidecar is a
COUNTED quarantined miss followed by re-measure + re-save, never a
crash, and an explicit LHTPU_MSM_DEVICE_MIN pin always wins.

Device dispatches here share lane buckets (pad_to / tiny shapes) so the
whole file costs a handful of XLA compiles; the 8-virtual-device
sharded rung is @slow.
"""

from __future__ import annotations

import json
import os
import pickle

import numpy as np
import pytest

from lighthouse_tpu.common import device_telemetry as dtel
from lighthouse_tpu.crypto.bls import curve as cv
from lighthouse_tpu.crypto.bls.fields import R
from lighthouse_tpu.ops import program_store as ps

slow = pytest.mark.skipif(
    os.environ.get("LHTPU_SLOW") != "1",
    reason="compiles extra device shapes; set LHTPU_SLOW=1")

GOLDEN = 0x9E3779B97F4A7C15


def _points(n, start=3):
    g = cv.g1_generator()
    return [cv.g1_mul(g, start + i) for i in range(n)]


def _scalars(n):
    return [(GOLDEN * (i + 1)) % R for i in range(n)]


def _host_lincomb(points, scalars):
    acc = cv.INF
    for p, k in zip(points, scalars):
        if p is cv.INF or k % R == 0:
            continue
        acc = cv.g1_add(acc, cv.g1_mul(p, k % R))
    return acc


# -- digest identity: the plain g1 track --------------------------------------


def test_fold_matches_legacy_windowed_msm():
    """fold_device(..., 1) is limb-identical to the legacy
    jax.jit(ec.g1_msm_windowed) composition every consumer used to
    carry privately (same windowed scan, same pairing tree)."""
    import jax

    from lighthouse_tpu.ops import ec
    from lighthouse_tpu.ops import msm

    pts = _points(3) + [cv.INF]          # non-pow2 real count, padded
    ks = _scalars(3) + [0]               # zero-scalar padding lane
    xs = ec.ints_to_mont_limbs([p[0] if p is not cv.INF else 0
                                for p in pts])
    ys = ec.ints_to_mont_limbs([p[1] if p is not cv.INF else 0
                                for p in pts])
    digits = ec.scalars_to_digits(ks, n_bits=256)
    import jax.numpy as jnp

    X, Y, Z = msm.fold_device(xs, ys, digits, 1)
    lx, ly, lz = jax.device_get(jax.jit(ec.g1_msm_windowed)(
        jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(digits)))
    assert np.array_equal(X, np.asarray(lx).reshape(X.shape))
    assert np.array_equal(Y, np.asarray(ly).reshape(Y.shape))
    assert np.array_equal(Z, np.asarray(lz).reshape(Z.shape))


def test_kzg_lincomb_device_host_identity():
    """kzg.g1_lincomb routed to the device fold equals the host lincomb
    seam on mixed inputs: identity points, zero scalars, a non-pow2
    real count (the pad_to=4 bucket shares the compile above)."""
    from lighthouse_tpu.crypto import kzg

    pts = [_points(1)[0], cv.INF, _points(1, start=7)[0]]
    ks = [_scalars(1)[0], _scalars(2)[1], 0]
    dev = kzg.g1_lincomb(pts, ks, device=True, pad_to=4)
    host = kzg.g1_lincomb(pts, ks, device=False)
    assert dev == host == _host_lincomb(pts, ks)


def test_kzg_lincomb_all_identity():
    from lighthouse_tpu.crypto import kzg

    pts = [cv.INF, cv.INF, _points(1)[0]]
    assert kzg.g1_lincomb(pts, [5, 7, 0], device=True, pad_to=4) is cv.INF
    assert kzg.g1_lincomb(pts, [5, 7, 0], device=False) is cv.INF
    assert kzg.g1_lincomb([], [], device=False) is cv.INF


def test_das_cell_proof_chunk_identity():
    """The cell proofs of the plane's FK20 program (ops/msm._fk20_kernel)
    equal the per-cell host monomial lincomb of each cell's quotient (the
    spec's per-cell idiom), blob for blob: a random blob and p(x) = 0,
    whose proofs are all the identity."""
    import random

    import jax.numpy as jnp

    from lighthouse_tpu.crypto import das, kzg

    settings = kzg.KzgSettings.dev(width=16)
    n_cells, size = das._cell_geometry(16)
    rng = random.Random(19)
    polys = [[rng.randrange(R) for _ in range(16)], [0] * 16]
    lanes = [polys[r][j] for j in range(16) for r in range(2)]
    got = das._fk20_proofs(jnp.asarray(das._plain_limbs(lanes).T), 2,
                           settings)
    ext_roots = das._compute_roots_of_unity(32)
    brp = das._bit_reversal_permutation(list(range(32)))
    for f, proofs in zip(polys, got):
        for cid in range(n_cells):
            a = pow(ext_roots[brp[cid * size]], size, R)
            q = [0] * (16 - size)
            for j in range(15 - size, -1, -1):
                carry = q[j + size] if j + size < len(q) else 0
                q[j] = (f[j + size] + a * carry) % R
            want = _host_lincomb(settings.g1_monomial[:len(q)], q)
            assert proofs[cid] == cv.g1_to_bytes(want)


def test_rlc_two_segment_fold():
    """The RLC fold geometry (2 segments in one dispatch) equals two
    independent single-segment folds — the kzg fused-verify front end's
    contract with the plane."""
    import jax

    from lighthouse_tpu.ops import ec
    from lighthouse_tpu.ops import msm

    pts = _points(4, start=11)
    ks = _scalars(4)
    xs = ec.ints_to_mont_limbs([p[0] for p in pts])
    ys = ec.ints_to_mont_limbs([p[1] for p in pts])
    digits = ec.scalars_to_digits(ks, n_bits=256)
    X, Y, Z = msm.fold_device(xs, ys, digits, 2)
    both = msm.jacobian_rows_to_affine(X, Y, Z)
    # segment layout is s-major: segment j owns lanes j, j+2
    for j in range(2):
        want = _host_lincomb([pts[j], pts[j + 2]], [ks[j], ks[j + 2]])
        assert both[j] == want


def test_gj_joint_track_matches_direct_composition():
    """fold_segments_gj is the same trace as the direct ec composition
    (joint G1 pubkey scan + G2 signature sum) — limb-identical."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops import ec
    from lighthouse_tpu.ops import msm

    rng = np.random.default_rng(17)
    pts = _points(2, start=5)
    xp = jnp.asarray(ec.ints_to_mont_limbs([p[0] for p in pts]))
    yp = jnp.asarray(ec.ints_to_mont_limbs([p[1] for p in pts]))
    g2 = cv.g2_generator()
    sigs = [cv.g2_mul(g2, 3), cv.g2_mul(g2, 4)]
    sxa = jnp.asarray(ec.ints_to_mont_limbs([s[0].a for s in sigs]))
    sxb = jnp.asarray(ec.ints_to_mont_limbs([s[0].b for s in sigs]))
    sya = jnp.asarray(ec.ints_to_mont_limbs([s[1].a for s in sigs]))
    syb = jnp.asarray(ec.ints_to_mont_limbs([s[1].b for s in sigs]))
    blinders = rng.integers(1, 1 << 63, size=2, dtype=np.uint64)
    bits = jnp.asarray(ec.scalars_to_digits(blinders))

    def unified(xp, yp, sxa, sxb, sya, syb, bits):
        return msm.fold_segments_gj(xp, yp, (sxa, sxb), (sya, syb), bits)

    def direct(xp, yp, sxa, sxb, sya, syb, bits):
        (Xp, Yp, Zp), (SX, SY, SZ) = ec.gj_scalar_mul_windowed(
            xp, yp, (sxa, sxb), (sya, syb), bits)
        SX, SY, SZ = ec.g2_sum_reduce(SX, SY, SZ)
        return (Xp, Yp, Zp), (SX, SY, SZ)

    args = (xp, yp, sxa, sxb, sya, syb, bits)
    got = jax.device_get(jax.jit(unified)(*args))
    want = jax.device_get(jax.jit(direct)(*args))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- digest identity: the gather track ----------------------------------------


def test_gather_fold_matches_host_adds():
    """Non-pow2 group count + uneven group sizes through the fused
    gather fold vs host point adds."""
    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import pubkey_kernels

    pts = _points(3, start=9)
    table = pubkey_kernels.build_table(pts)
    rows = np.array([0, 1, 2, 0, 1], np.int64)
    scalars = np.array([3, 5, 7, 11, 13], np.uint64)
    groups = np.array([0, 0, 1, 2, 2], np.int64)   # 3 groups (non-pow2)
    xa, ya, inf = pubkey_kernels.gather_fold(table, rows, scalars,
                                             groups, 3)
    assert xa.shape[0] == 3
    for gi in range(3):
        want = cv.INF
        for r, s, g in zip(rows, scalars, groups):
            if g == gi:
                want = cv.g1_add(want, cv.g1_mul(pts[int(r)], int(s)))
        assert not bool(inf[gi])
        got = (int(bi.from_mont(xa[gi])), int(bi.from_mont(ya[gi])))
        assert got == want


@slow
def test_sharded_rung_digest_identity():
    """The one sharded mesh rung (parallel/msm_sharded) over the 8
    virtual devices the conftest forces is digest-identical to the
    single-device gather fold."""
    from lighthouse_tpu.ops import pubkey_kernels
    from lighthouse_tpu.parallel import msm_sharded

    pts = _points(4, start=21)
    table = pubkey_kernels.build_table(pts)
    rng = np.random.default_rng(23)
    n = 32
    rows = rng.integers(0, 4, size=n).astype(np.int64)
    scalars = rng.integers(1, 1 << 63, size=n, dtype=np.uint64)
    groups = rng.integers(0, 4, size=n).astype(np.int64)
    mesh = msm_sharded.msm_mesh()
    assert mesh.devices.size > 1
    sx, sy, sinf = msm_sharded.gather_fold_sharded(
        table, rows, scalars, groups, 4, mesh=mesh)
    dx, dy, dinf = pubkey_kernels.gather_fold(table, rows, scalars,
                                              groups, 4)
    assert np.array_equal(np.asarray(sx), np.asarray(dx))
    assert np.array_equal(np.asarray(sy), np.asarray(dy))
    assert np.array_equal(np.asarray(sinf), np.asarray(dinf))


# -- host fallback seam -------------------------------------------------------


def test_host_lincomb_groups_matches_pure_python():
    """The native seam (when available) and the pure-python fallback
    agree, identity rows filter correctly, and grouping works."""
    from lighthouse_tpu.ops import msm

    pts = _points(4, start=31) + [cv.INF]
    ks = _scalars(4) + [9]
    groups = [0, 1, 0, 1, 0]
    got = msm.host_lincomb_groups(pts, ks, groups, 2)
    for gi in range(2):
        want = _host_lincomb(
            [p for p, g in zip(pts, groups) if g == gi],
            [k for k, g in zip(ks, groups) if g == gi])
        assert got[gi] == want


# -- routing: bucket + threshold knobs ----------------------------------------


def test_bucket_pow2_and_floor(monkeypatch):
    from lighthouse_tpu.ops import msm

    assert [msm.bucket(n) for n in (0, 1, 2, 3, 5, 8)] == \
        [1, 1, 2, 4, 8, 8]
    assert msm.bucket(3, floor=16) == 16
    monkeypatch.setenv("LHTPU_MSM_BUCKET_FLOOR", "8")
    assert msm.bucket(2) == 8
    assert msm.bucket(33) == 64


def test_device_min_env_pin_wins(monkeypatch):
    from lighthouse_tpu.ops import msm

    saved = dict(msm._DEVICE_MIN)
    try:
        msm._DEVICE_MIN["g1"] = 1024
        monkeypatch.setenv("LHTPU_MSM_DEVICE_MIN", "32")
        assert msm.device_min("g1") == 32
        assert msm.device_min("gather") == 32
        monkeypatch.delenv("LHTPU_MSM_DEVICE_MIN")
        assert msm.device_min("g1") == 1024
        assert msm.device_min("gather") == msm._STATIC_DEVICE_MIN
    finally:
        msm._DEVICE_MIN.clear()
        msm._DEVICE_MIN.update(saved)


def test_apply_calibration_matrix():
    """Malformed records change nothing and report False; a valid one
    sets every track (gather inherits g1 when absent/malformed)."""
    from lighthouse_tpu.ops import msm

    saved = (dict(msm._DEVICE_MIN), msm._CALIBRATED)
    try:
        msm._DEVICE_MIN.clear()
        for bad in ({}, {"tracks": {}}, {"tracks": {"g1": {}}},
                    {"tracks": {"g1": {"threshold_lanes": 0}}},
                    {"tracks": {"g1": {"threshold_lanes": "no"}}}):
            assert not msm.apply_calibration(bad)
            assert msm._DEVICE_MIN == {}
        assert msm.apply_calibration(
            {"tracks": {"g1": {"threshold_lanes": 64},
                        "gather": {"threshold_lanes": 128}}})
        assert msm._DEVICE_MIN == {"g1": 64, "gather": 128}
        assert msm.apply_calibration(
            {"tracks": {"g1": {"threshold_lanes": 256},
                        "gather": {"threshold_lanes": "bogus"}}})
        assert msm._DEVICE_MIN == {"g1": 256, "gather": 256}
    finally:
        msm._DEVICE_MIN.clear()
        msm._DEVICE_MIN.update(saved[0])
        msm._CALIBRATED = saved[1]


# -- calibration sidecar robustness (zero-XLA, fake store seam) ---------------


@pytest.fixture
def store(tmp_path, monkeypatch):
    monkeypatch.setattr(ps, "_fingerprint", lambda: {"fake": "fp-msm"})
    monkeypatch.setattr(
        ps, "_serialize_compiled",
        lambda compiled: pickle.dumps(("fake-exe", "t")))
    monkeypatch.delenv("LHTPU_AOT_STORE", raising=False)
    st = ps.configure(tmp_path / "aot")
    assert st is not None
    yield st
    ps.deactivate()
    dtel.reset()


VALID = {"tracks": {"g1": {"threshold_lanes": 64},
                    "gather": {"threshold_lanes": 64}},
         "source": "measured"}


def test_msm_calibration_roundtrip_and_corruption(store, tmp_path,
                                                  monkeypatch):
    """The PR 12 envelope corruption matrix on the msm record: each
    damage mode is a counted quarantined miss -> None, and the
    re-measure path can always re-save."""
    rec = ps.MSM_CALIBRATION_RECORD
    assert ps.save_calibration(VALID, record=rec)
    assert ps.load_calibration(record=rec) == VALID
    # the sha record is a DIFFERENT sidecar: untouched by the msm one
    assert ps.load_calibration() is None

    path = store._calibration_path(record=rec)
    for damage in (lambda: path.write_bytes(path.read_bytes()[:8]),
                   lambda: path.write_text("{not json"),
                   lambda: path.write_text(json.dumps(["not", "obj"]))):
        assert ps.save_calibration(VALID, record=rec)
        corrupt = ps.REGISTRY.counter("aot_store_misses_total").labels(
            reason="corrupt")
        before = corrupt.value
        damage()
        assert ps.load_calibration(record=rec) is None   # never a crash
        assert not path.exists()                         # quarantined
        assert corrupt.value == before + 1               # counted
    assert ps.save_calibration(VALID, record=rec)        # re-save works
    assert ps.load_calibration(record=rec) == VALID


def test_msm_calibration_step_remeasures_after_corruption(store, tmp_path,
                                                          monkeypatch):
    """prewarm.msm_calibration_step on a corrupt sidecar: quarantined
    miss -> re-measure -> re-save, and the NEXT step adopts from the
    store (measurement stubbed: this stays zero-XLA)."""
    from lighthouse_tpu.ops import msm, prewarm

    measured = {"n": 0}

    def fake_measure(sample_lanes=2, force=False):
        measured["n"] += 1
        return dict(VALID)

    monkeypatch.setattr(msm, "calibrate_device_thresholds", fake_measure)
    monkeypatch.delenv("LHTPU_MSM_DEVICE_MIN", raising=False)
    monkeypatch.delenv("LHTPU_MSM_CALIBRATION", raising=False)
    saved = (dict(msm._DEVICE_MIN), msm._CALIBRATED)
    try:
        rec = ps.MSM_CALIBRATION_RECORD
        path = store._calibration_path(record=rec)
        assert ps.save_calibration(VALID, record=rec)
        path.write_text("garbage")
        rep = prewarm.msm_calibration_step()
        assert rep["source"] == "measured" and measured["n"] == 1
        assert ps.load_calibration(record=rec) == VALID   # re-saved
        rep2 = prewarm.msm_calibration_step()
        assert rep2["source"] == "store" and measured["n"] == 1
        assert msm._DEVICE_MIN["g1"] == 64
    finally:
        msm._DEVICE_MIN.clear()
        msm._DEVICE_MIN.update(saved[0])
        msm._CALIBRATED = saved[1]


def test_msm_calibration_step_env_pin_and_disable(store, monkeypatch):
    from lighthouse_tpu.ops import msm, prewarm

    saved = (dict(msm._DEVICE_MIN), msm._CALIBRATED)
    try:
        monkeypatch.setenv("LHTPU_MSM_DEVICE_MIN", "128")
        rep = prewarm.msm_calibration_step()
        assert rep["source"] == "env"
        assert msm.device_min("g1") == 128
        monkeypatch.delenv("LHTPU_MSM_DEVICE_MIN")
        monkeypatch.setenv("LHTPU_MSM_CALIBRATION", "0")
        assert prewarm.msm_calibration_step() == {"source": "disabled"}
    finally:
        msm._DEVICE_MIN.clear()
        msm._DEVICE_MIN.update(saved[0])
        msm._CALIBRATED = saved[1]


# -- the blinded fold's sum on the limb-major multiply (ISSUE 36) -------------

_BF_KEYS, _BF_SEGS = 8, 4     # the slice tests/test_electra_fold.py compiles


def _bf_cases():
    """{case: the affine members of each of the slice's four segments}."""
    p = _points(32, start=101)
    opposing = [p[1], cv.g1_neg(p[1])]
    return {
        "random_keys": [p[:8], p[8:16], p[16:24], p[24:]],
        "infinity_padded_key_lanes": [p[:3], p[3:8], p[8:9], p[9:16]],
        "segment_of_padding_alone": [p[:8], p[8:13], [], p[13:15]],
        "one_key_through_a_segment": [[p[5]] * 8, [p[6]] * 5, p[:8],
                                      [p[5], p[6]] * 3],
        "opposing_keys": [opposing * 4, opposing + [p[7]], p[:8],
                          opposing * 2 + p[:4]],
    }


@pytest.fixture(scope="module")
def limbs_last_fold():
    """The composition `_blinded_fold` had before ISSUE 36: the segment
    sum on limbs-last rows (`ec.g1_segment_sum`, `mont_mul`), the same
    tail."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import ec

    @partial(jax.jit, static_argnums=(5,))
    def fold(X, Y, Z, ux, uy, n_segments):
        Xg, Yg, Zg = ec.g1_segment_sum(X, Y, Z, n_segments)
        one = jnp.broadcast_to(bi._jconst("one_m"), Xg.shape)
        Xr, Yr, Zr = ec._jac_add_full(
            ec._FpAdapter, (Xg, Yg, Zg),
            (jnp.broadcast_to(ux, Xg.shape), jnp.broadcast_to(uy, Yg.shape),
             one))
        xa, ya = ec.g1_jacobian_to_affine_batch(Xr, Yr, Zr)
        return xa, ya, bi.is_zero_mod_p_device(Zr)

    return fold


@pytest.mark.parametrize("case", [
    "random_keys", "infinity_padded_key_lanes", "segment_of_padding_alone",
    "one_key_through_a_segment", "opposing_keys"])
def test_blinded_fold_equals_limbs_last_composition(limbs_last_fold, case):
    """`_blinded_fold` (segment sum limb-major, on `mont_mul_lm`) against
    the limbs-last composition it replaces, on one slice laid out as
    `aggregate_pubkeys_device` lays it out: the same flags, and under
    every clear flag the same point, which is the members' sum."""
    import jax

    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import bls_backend as bb
    from lighthouse_tpu.ops import ec, msm

    members = _bf_cases()[case]
    blinding, neg = bb._blinding(_BF_KEYS, _BF_SEGS)
    X, Y, Z = (np.concatenate([np.zeros_like(b), b])
               for b in map(np.asarray, blinding))
    for i, seg in enumerate(members):
        if not seg:
            continue
        lanes = [j * _BF_SEGS + i for j in range(len(seg))]   # s-major
        X[lanes] = ec.ints_to_mont_limbs([q[0] for q in seg])
        Y[lanes] = ec.ints_to_mont_limbs([q[1] for q in seg])
        Z[lanes] = bi.ONE_M
    args = (X, Y, Z, neg[0], neg[1], _BF_SEGS)
    xa, ya, inf = jax.device_get(msm.blinded_fold_device(*args))
    xb, yb, inf_b = jax.device_get(limbs_last_fold(*args))
    assert xa.shape == xb.shape == (_BF_SEGS, bi.L)
    want = [_host_lincomb(seg, [1] * len(seg)) for seg in members]
    assert inf.tolist() == inf_b.tolist() == [w is cv.INF for w in want]
    for i, w in enumerate(want):
        if w is cv.INF:
            continue            # a flagged row's limbs are not read
        got = (int(bi.from_mont(xa[i])), int(bi.from_mont(ya[i])))
        assert got == (int(bi.from_mont(xb[i])), int(bi.from_mont(yb[i])))
        assert got == w, (case, i)
    if case == "opposing_keys":
        assert inf.tolist() == [True, False, False, False]


@pytest.mark.parametrize("lanes,segments", [(64, 4), (128, 2), (32768, 32)])
def test_blinded_fold_products_are_what_the_program_traces(
        monkeypatch, lanes, segments):
    """`blinded_fold_products` (what `bls_fold_products_total` grows by a
    slice) against a tally of the lanes each multiply of `_blinded_fold`
    is traced with, a scan's body counted once a step; the last shape is
    both block cells'."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import msm

    tally = {"resident": 0, "materialized": 0}
    steps = [1]
    lm, mm, scan = bi.FP.mont_mul_lm, bi.mont_mul, jax.lax.scan

    def count_lm(a, b):
        tally["resident"] += steps[-1] * int(np.prod(a.shape[1:]))
        return lm(a, b)

    def count_mm(a, b):
        tally["materialized"] += steps[-1] * int(np.prod(a.shape[:-1]))
        return mm(a, b)

    def count_scan(f, init, xs, *args, **kwargs):
        steps.append(steps[-1] * jax.tree_util.tree_leaves(xs)[0].shape[0])
        try:
            return scan(f, init, xs, *args, **kwargs)
        finally:
            steps.pop()

    monkeypatch.setattr(bi.FP, "mont_mul_lm", count_lm)
    monkeypatch.setattr(bi, "mont_mul", count_mm)
    monkeypatch.setattr(jax.lax, "scan", count_scan)
    rows = jax.ShapeDtypeStruct((lanes, bi.L), jnp.uint32)
    row = jax.ShapeDtypeStruct((bi.L,), jnp.uint32)
    jax.eval_shape(lambda *a: msm._blinded_fold._fn(*a, segments),
                   rows, rows, rows, row, row)
    res, mat = msm.blinded_fold_products(lanes, segments)
    assert (res, mat) == (tally["resident"], tally["materialized"])
    assert msm.blinded_fold_products(32768, 32) == (523776, 25056)
    # the sum's additions at `fold_products`' price: that fold with no
    # window, less its tables' seven doublings and additions a lane
    assert res == msm.fold_products(lanes, segments, 0) - 7 * (7 + 16) * lanes


# -- the manifest actually shrank ---------------------------------------------


def test_manifest_msm_family_unified():
    """One program-store registration point per (track, bucket): the
    four per-consumer MSM kernels are gone from the shape manifest,
    replaced by exactly three ops/msm.py entries — the MSM-family entry
    count went DOWN (4 legacy -> 3 unified).  The same-message merge of
    a batch verification is a fourth track of the file that replaced no
    consumer's kernel, and FK20's cell proofs and their setup tables
    (which replaced the cell-proof chunk fold's use of the plain track)
    two more (26 entries in all, with ops/fr.py's recovery)."""
    import pathlib

    manifest = pathlib.Path(__file__).parent.parent / "tools" / "lint" \
        / "shape_manifest.json"
    entries = json.loads(manifest.read_text())["entries"]
    ids = {e["id"] for e in entries}
    legacy = {
        "crypto/kzg.py::_msm_device@ec.g1_msm_windowed",
        "crypto/das.py::_batched_cell_proof_msms@_f",
        "ops/pubkey_kernels.py::_gather_fold_kernel@_gather_fold_kernel",
        "ops/bls_backend.py::_aggregate_kernel@_aggregate_kernel",
    }
    assert not (ids & legacy), ids & legacy
    unified = sorted(i for i in ids if i.startswith("ops/msm.py::"))
    assert unified == ["ops/msm.py::_blinded_fold@_blinded_fold",
                       "ops/msm.py::_blinded_merge@_blinded_merge",
                       "ops/msm.py::_fk20_kernel@_fk20_kernel",
                       "ops/msm.py::_fk20_tables@_fk20_tables",
                       "ops/msm.py::_fold_kernel@_fold_kernel",
                       "ops/msm.py::_gather_fold@_gather_fold"]
    replacing = [i for i in unified
                 if "_blinded_merge" not in i and "_fk20" not in i]
    assert len(replacing) < len(legacy)
    assert len(entries) == 26
    # and every unified entry is registered at runtime with the msm
    # prewarm driver (the one registration point)
    from lighthouse_tpu.ops import msm  # noqa: F401  (registers)

    regs = ps.registered_entries()
    assert all(regs.get(i) == "msm" for i in unified), regs
