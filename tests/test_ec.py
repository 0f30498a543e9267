"""Device EC kernel tests: batched scalar mult + point sums vs host oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lighthouse_tpu.crypto.bls import curve as cv
from lighthouse_tpu.crypto.bls.fields import Fq2, P
from lighthouse_tpu.ops import bigint as bi
from lighthouse_tpu.ops import ec


def _g1_lanes(points):
    xs = ec.ints_to_mont_limbs([p[0] for p in points])
    ys = ec.ints_to_mont_limbs([p[1] for p in points])
    return jnp.asarray(xs), jnp.asarray(ys)


def _g2_lanes(points):
    cols = []
    for get in (lambda p: p[0].a, lambda p: p[0].b,
                lambda p: p[1].a, lambda p: p[1].b):
        cols.append(jnp.asarray(ec.ints_to_mont_limbs([get(p) for p in points])))
    return cols


def _jac_to_affine_fp(X, Y, Z, lane):
    x, y, z = (int(bi.from_mont(np.asarray(c)[lane])) for c in (X, Y, Z))
    if z == 0:
        return cv.INF
    zi = pow(z, -1, P)
    return (x * zi * zi % P, y * zi * zi * zi % P)


def _jac_to_affine_fq2(X, Y, Z, lane):
    def fq2(c):
        return Fq2(int(bi.from_mont(np.asarray(c[0])[lane])),
                   int(bi.from_mont(np.asarray(c[1])[lane])))

    x, y, z = fq2(X), fq2(Y), fq2(Z)
    if z.is_zero():
        return cv.INF
    zi = z.inv()
    zi2 = zi.square()
    return (x * zi2, y * zi2 * zi)


def test_windowed_merged_scalar_mul_matches_oracle():
    """gj_scalar_mul_windowed (the fused pipeline's production scan):
    both tracks, window-edge scalars, zero-scalar infinity lanes, and
    the exact-zero canonical form the sum reduce requires."""
    g1, g2 = cv.g1_generator(), cv.g2_generator()
    scalars = [1, 0, 16, 15, 0xD201000000010000, 0xFFFFFFFFFFFFFFFF,
               0x8000000000000000, 0x9AB]
    p1 = [cv.g1_mul(g1, 3 + i) for i in range(8)]
    p2 = [cv.g2_mul(g2, 5 + i) for i in range(8)]
    xs, ys = _g1_lanes(p1)
    xqa, xqb, yqa, yqb = _g2_lanes(p2)
    digits = jnp.asarray(ec.scalars_to_digits(scalars))
    (X1, Y1, Z1), (X2, Y2, Z2) = jax.jit(ec.gj_scalar_mul_windowed)(
        xs, ys, (xqa, xqb), (yqa, yqb), digits)
    for i, k in enumerate(scalars):
        want1 = cv.g1_mul(p1[i], k) if k else cv.INF
        assert _jac_to_affine_fp(X1, Y1, Z1, i) == want1, f"g1 lane {i}"
        want2 = cv.g2_mul(p2[i], k) if k else cv.INF
        assert _jac_to_affine_fq2(X2, Y2, Z2, i) == want2, f"g2 lane {i}"
    # zero-scalar lanes canonicalize to EXACT zero limbs (identity form)
    assert not np.asarray(X2[0])[1].any() and not np.asarray(Z1)[1].any()


def test_g1_windowed_msm_matches_oracle():
    g = cv.g1_generator()
    pts = [cv.g1_mul(g, 7 + i) for i in range(8)]
    scalars = [3, 0, (1 << 255) - 19, 5, 1, 2, 12345, 99]
    xs, ys = _g1_lanes(pts)
    Xw, Yw, Zw = jax.jit(ec.g1_msm_windowed)(
        xs, ys, jnp.asarray(ec.scalars_to_digits(scalars, n_bits=256)))
    want = cv.INF
    for p, k in zip(pts, scalars):
        want = cv.g1_add(want, cv.g1_mul(p, k))
    assert _jac_to_affine_fp(Xw, Yw, Zw, 0) == want


# --- the limb-major G1 fold (ops/msm.fold_segments_g1: the windowed scan,
# its tables and the segment sum on `MontField.mont_mul_lm`) -----------------
#
# One compiled shape serves every case: 8 lanes of 256-bit scalars in two
# segments, lane 2s + g the s-th lane of segment g.

_R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
_FOLD_CASES = {
    # scalars whose windows are zero at the top, in the middle, at the
    # bottom, everywhere but one, and nowhere
    "zero_windows": ([1, 16, 1 << 252, 0xF0F0, 15 << 128, _R - 1,
                      (1 << 255) - 19, 0x1000000000000001], None),
    # zero scalars beside live ones: lanes that stay at infinity
    "zero_scalars": ([0, 5, 7, 0, 0, 11, 13, 0], None),
    # points at infinity enter as (0, 0) under a zero scalar
    "lanes_at_infinity": ([3, 0, 0, 9, 4, 0, 0, 6], (1, 2, 5, 6)),
    # segment 1 is padding alone: its row is the exact-zero infinity
    "all_padding_segment": ([3, 0, 5, 0, 7, 0, 9, 0], (1, 3, 5, 7)),
    # segment 0 holds k·P and (r - k)·P twice over: every chord of its tree
    # is a degenerate one and the row comes back with Z = 0 mod p, the
    # lane `_kzg_fused` masks to e(INF, ·) = 1
    "segment_folds_to_infinity": ([12345, 2, 99, 3, _R - 12345, 4,
                                   _R - 99, 5], None),
}


@pytest.fixture(scope="module")
def fold_2x4():
    from lighthouse_tpu.ops import msm

    return jax.jit(lambda xs, ys, dg: msm.fold_segments_g1(xs, ys, dg, 2))


def _fold_points():
    # lanes 0/4 and 2/6 share a point, so that k·P + (r - k)·P is a chord
    g = cv.g1_generator()
    return [cv.g1_mul(g, 21 + (i % 4 if i % 2 == 0 else i))
            for i in range(8)]


def _fold_case(scalars, at_infinity):
    pts = _fold_points()
    for i in at_infinity or ():
        pts[i] = cv.INF
    xs = jnp.asarray(ec.ints_to_mont_limbs(
        [0 if p is cv.INF else p[0] for p in pts]))
    ys = jnp.asarray(ec.ints_to_mont_limbs(
        [0 if p is cv.INF else p[1] for p in pts]))
    want = []
    for seg in range(2):
        acc = cv.INF
        for p, k in list(zip(pts, scalars))[seg::2]:
            if p is not cv.INF and k:
                acc = cv.g1_add(acc, cv.g1_mul(p, k))
        want.append(acc)
    return xs, ys, jnp.asarray(
        ec.scalars_to_digits(scalars, n_bits=256)), want


@pytest.mark.parametrize("case", sorted(_FOLD_CASES))
def test_limb_major_fold_matches_oracle(fold_2x4, case):
    scalars, at_infinity = _FOLD_CASES[case]
    xs, ys, digits, want = _fold_case(scalars, at_infinity)
    X, Y, Z = jax.device_get(fold_2x4(xs, ys, digits))
    assert X.shape == (2, bi.L)
    for seg in range(2):
        assert _jac_to_affine_fp(X, Y, Z, seg) == want[seg], (case, seg)
    if case == "all_padding_segment":
        # never added to: exact zero limbs, not a multiple of p
        assert not (X[1].any() or Y[1].any() or Z[1].any())
    if case == "segment_folds_to_infinity":
        assert want[0] is cv.INF and want[1] is not cv.INF
        assert np.asarray(bi.is_zero_mod_p_device(jnp.asarray(Z))).tolist() \
            == [True, False]


def test_limb_major_scan_and_sum_one_segment():
    """The scan's lanes one by one (zero scalars canonical: exact zeros),
    then the same lanes through the one-segment sum `g1_msm_windowed`
    ends in."""
    scalars, _ = _FOLD_CASES["zero_scalars"]
    xs, ys, digits, _ = _fold_case(scalars, None)
    pts = _fold_points()

    @jax.jit
    def both(xs, ys, digits):
        lanes = ec.g1_scalar_mul_windowed(xs.T, ys.T, digits)
        return lanes, ec.g1_segment_sum_lm(*lanes, 1)

    (X, Y, Z), (Xs, Ys, Zs) = jax.device_get(both(xs, ys, digits))
    assert X.shape == (bi.L, 8) and Xs.shape == (bi.L, 1)
    total = cv.INF
    for i, k in enumerate(scalars):
        want = cv.g1_mul(pts[i], k) if k else cv.INF
        assert _jac_to_affine_fp(X.T, Y.T, Z.T, i) == want, i
        if not k:
            assert not (X[:, i].any() or Y[:, i].any() or Z[:, i].any())
        total = cv.g1_add(total, want)
    assert _jac_to_affine_fp(Xs.T, Ys.T, Zs.T, 0) == total


def test_g2_sum_reduce_matches_oracle():
    g = cv.g2_generator()
    pts = [cv.g2_mul(g, k) for k in (11, 22, 33, 44)]
    cols = _g2_lanes(pts)
    one = jnp.broadcast_to(bi._jconst("one_m"), cols[0].shape)
    zero = jnp.zeros_like(cols[0])
    X = (cols[0], cols[1])
    Y = (cols[2], cols[3])
    Z = (one, zero)

    Xs, Ys, Zs = jax.jit(ec.g2_sum_reduce)(X, Y, Z)
    want = cv.g2_mul(g, 11 + 22 + 33 + 44)
    assert _jac_to_affine_fq2(Xs, Ys, Zs, 0) == want


def test_g2_sum_reduce_with_infinity_padding():
    g = cv.g2_generator()
    pts = [cv.g2_mul(g, 5), cv.g2_mul(g, 6)]
    cols = _g2_lanes(pts)
    one = jnp.broadcast_to(bi._jconst("one_m"), cols[0].shape)
    zero = jnp.zeros_like(cols[0])
    pad = jnp.zeros((2, bi.L), jnp.uint32)
    X = (jnp.concatenate([cols[0], pad]), jnp.concatenate([cols[1], pad]))
    Y = (jnp.concatenate([cols[2], pad]), jnp.concatenate([cols[3], pad]))
    Z = (jnp.concatenate([one, pad]), jnp.concatenate([zero, pad]))

    Xs, Ys, Zs = jax.jit(ec.g2_sum_reduce)(X, Y, Z)
    assert _jac_to_affine_fq2(Xs, Ys, Zs, 0) == cv.g2_mul(g, 11)


def test_ints_to_limbs_matches_scalar_path():
    vals = [0, 1, bi.P_INT - 1, 123456789 << 350]
    got = ec.ints_to_limbs(vals)
    for i, v in enumerate(vals):
        assert np.array_equal(got[i], bi._int_to_limbs(v)), i
    gotm = ec.ints_to_mont_limbs(vals)
    for i, v in enumerate(vals):
        assert int(bi.from_mont(gotm[i])) == v % bi.P_INT, i


def _g2_cofactor_points(n: int):
    rng = np.random.default_rng(9)
    out = []
    while len(out) < n:
        x = Fq2(int.from_bytes(rng.bytes(47), "big") % P,
                int.from_bytes(rng.bytes(47), "big") % P)
        y = (x.square() * x + cv.B2).sqrt()
        if y is not None and not cv.g2_in_subgroup((x, y)):
            out.append((x, y))
    return out


class TestPsiSubgroupCheck:
    """The ψ membership test (curve.py g2_in_subgroup_fast + the batched
    device mirror) vs the definitional [r]Q oracle."""

    def test_host_fast_check_agrees_with_oracle(self):
        g = cv.g2_generator()
        for k in (1, 7, 123456789):
            q = cv.g2_mul(g, k)
            assert cv.g2_in_subgroup_fast(q)
            assert cv.g2_in_subgroup(q)
        for pt in _g2_cofactor_points(2):
            assert not cv.g2_in_subgroup_fast(pt)
        assert cv.g2_in_subgroup_fast(cv.INF)

    def test_psi_eigenvalue_is_x(self):
        from lighthouse_tpu.crypto.bls.fields import BLS_X

        g = cv.g2_generator()
        q = cv.g2_mul(g, 424242)
        assert cv.g2_psi(q) == cv.g2_mul(q, -BLS_X)

    def test_device_batch_check(self):
        from lighthouse_tpu.ops.bls_backend import batch_subgroup_check_g2

        g = cv.g2_generator()
        members = [cv.g2_mul(g, k) for k in (1, 5, 7)]
        bad = _g2_cofactor_points(2)
        ok = batch_subgroup_check_g2(members[:2] + bad + members[2:])
        assert list(ok) == [True, True, False, False, True]


# A twist point of exact order 13 (13² | h2, the Sylow-13 subgroup of
# E'(Fq2) has rank 2; made as [n2/13²]·random, then reduced by 13 until
# its order is 13).
G2_ORDER13 = (
    Fq2(0x50c3dd2263b07fd4c50559754c4f0d4c4ab0cdc4a685b8b5cab7bd39bd46ceda6663d15c194176fc6e15f40a70b76bc,
        0x2fce515472b308fa3da1ac9a6fa4019d7a8700cb6ca215771c98d4bc59edddbedf882c6cae0f702b73c6bdcb93746ac),
    Fq2(0xdc3af5921e8ecd27695da0f537a9197d849deabb8cf404f28ba31790ce2e89a26bb85188dab735e6782210cd0a30381,
        0x2eaa3a19068450560e6cc5788d89c55226e62b286277cecfaa019ad4712e2db26a4495408885d5923bed176515a1bb1),
)

_g2_check = jax.jit(ec.g2_subgroup_check_batch)


def test_g2_order13_point_is_what_it_claims():
    pt = G2_ORDER13
    assert cv.g2_is_on_curve(pt)
    assert cv.g2_mul(pt, 13) is cv.INF          # exact small order
    assert not cv.g2_in_subgroup(pt)            # oracle
    assert not cv.g2_in_subgroup_fast(pt)       # host ψ test


@pytest.mark.parametrize("where", ["among", "between", "last_of_full"])
def test_small_order_point_fails_closed(where):
    """g2_subgroup_check_batch's fail-closed invariant (see its docstring)
    on the limb-major multiply: the order-13 twist point meets the
    degenerate H == 0 chord inside the fixed-|x| scalar mul, which drives
    Z to a value ≡ 0 (mod P) however its limbs read after Karatsuba's
    subtractions, and the lane REJECTS wherever it sits in a padded
    bucket: among members, between them, last of a full bucket."""
    from lighthouse_tpu.ops.bls_backend import batch_subgroup_check_g2

    pt, g = G2_ORDER13, cv.g2_generator()
    g7 = cv.g2_mul(g, 7)
    batch = {"among": [pt, g, pt, g7, pt],           # 5 lanes of 8
             "between": [g, pt, g7, pt, g, g7],      # 6 lanes of 8
             "last_of_full": [g7, g, g7, g, g7, g, g7, pt]}[where]
    want = [p is not pt for p in batch]
    assert list(batch_subgroup_check_g2(batch)) == want
    # the reject is the Z ≡ 0 branch: the chord, not the residues
    lanes = _g2_lanes(batch + [g] * (8 - len(batch)))
    _, _, Z = _g2_check(*lanes)
    z0 = [ec.is_zero_mod_p(np.asarray(c).T) for c in Z]
    assert list(z0[0] & z0[1]) == [not w for w in want] + [False] * (
        8 - len(batch))


@pytest.mark.parametrize("n", [6, 13])
def test_g2_membership_against_the_oracle(n):
    """The ψ membership program's verdicts against cv.g2_in_subgroup over
    members, cofactor points (random points of E'(Fq2)), the order-13
    point and a point of mixed order (a member plus the order-13 point:
    on the curve, off the subgroup), at buckets of 8 and 16 lanes; the
    generator lanes that pad a batch to its bucket pass."""
    import random

    from lighthouse_tpu.crypto.bls.fields import R
    from lighthouse_tpu.ops import bls_backend as bb

    rng = random.Random(n)
    g = cv.g2_generator()
    members = [cv.g2_mul(g, rng.randrange(1, R)) for _ in range(3)]
    distinct = (members + _g2_cofactor_points(2)
                + [G2_ORDER13, cv.g2_add(members[0], G2_ORDER13)])
    want = [cv.g2_in_subgroup(p) for p in distinct]
    assert want == [True] * 3 + [False] * 4
    picks = [i % len(distinct) for i in range(n)]
    rng.shuffle(picks)
    row = np.asarray(bb._dispatch_g2_subgroup_kernel(
        [distinct[i] for i in picks]))
    assert row.shape == (bb._next_pow2(n, floor=4),)
    assert row[:n].tolist() == [want[i] for i in picks]
    assert row[n:].all()


@pytest.mark.parametrize("lanes", [4, 16, 256])
def test_g2_subgroup_products_are_what_the_program_traces(monkeypatch,
                                                           lanes):
    """`_g2_subgroup_products` (what `g2_subgroup_products_total` grows by
    a dispatch) against a tally of the lanes each multiply of
    `_g2_subgroup_kernel` is traced with, a scan's body counted once a
    step: every product on `mont_mul_lm`, none an array of the program
    (`mont_mul`); 2,048 lanes, `flood-2048`'s shape, by the closed form."""
    from lighthouse_tpu.ops import bls_backend as bb

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
    # a new function object: a jit's cache would hide a shape traced before
    jax.eval_shape(lambda *a: bb._g2_subgroup_kernel._fn.__wrapped__(*a),
                   rows, rows, rows, rows)
    assert bb._g2_subgroup_products(lanes) == (
        tally["resident"], tally["materialized"])
    assert bb._g2_subgroup_products(2048) == (7_127_040, 0)


def test_g2_subgroup_products_counted_once_a_dispatch():
    """A dispatch of the ψ membership program grows
    `g2_subgroup_products_total` by its padded shape's reckoning, on the
    resident multiply."""
    from benchmarks import counters
    from lighthouse_tpu.common.metrics import REGISTRY
    from lighthouse_tpu.ops import bls_backend as bb

    g = cv.g2_generator()
    before = counters.samples()
    row = np.asarray(bb._dispatch_g2_subgroup_kernel([g] * 5))
    after = counters.samples()
    assert row.tolist() == [True] * 8
    for multiply, want in zip(("resident", "materialized"),
                              bb._g2_subgroup_products(8)):
        assert counters.delta(
            before, after, "g2_subgroup_products_total",
            lambda labels, m=multiply: labels.get("multiply") == m) == want
    assert bb._g2_subgroup_products(8)[0] == 8 * 3_480
    assert "# TYPE g2_subgroup_products_total counter" in REGISTRY.render()


# Points of small prime order d on E(Fp), whose order is
# h1·r with h1 = 3 · 11² · 10177² · 859267² · 52437899²: each made on the
# host by crypto/bls/curve as [h1·r / d^e]·(a point from a seeded x), then
# multiplied by d until [d]P is the identity.  The [r-1]P scan of
# g1_subgroup_check_batch meets the degenerate H == 0 chord with them at
# its step 1 (order 3), 5 (order 11) and 222 (order 10177).
G1_SMALL_ORDER = {
    3: (0x0, 0x2),
    11: (0x1147cbb50494bb589add054c469d2952269ebc12a4acdcaa223a73ea4d76d431c775c748666973e42cc8d4dd5cf29f0c,
         0x19a94b4e74f2e4b18b259de5a6a8cb318ccb2fa3b3ecd28c3ba93f550bbc68bd00c7294c1e0856c6e312bc802c540d90),
    10177: (0x147f5096f1506db1f243a63c0ff09a21fb3292aa247b896d2d9d7b6ed4b230cd4bfbc4fefb73b8bee5ee950d5512f08c,
            0xae794cd9fc9299e82c2235e9861dd8c8cab5ba347b4bb33c4f1098991e0c20b18d09add5a91d109889f45b8942cf387),
}


def test_g1_small_order_points_are_what_they_claim():
    from lighthouse_tpu.crypto.bls.fields import R

    h1 = 3 * 11**2 * 10177**2 * 859267**2 * 52437899**2
    assert h1 == 0x396C8C005555E1568C00AAAB0000AAAB
    for d, pt in G1_SMALL_ORDER.items():
        assert cv.g1_is_on_curve(pt)
        assert cv.g1_mul(pt, d) is cv.INF
        assert not cv.g1_in_subgroup(pt)
        assert (R - 1) % d != d - 1     # [r-1]P != -P: the test must reject


@pytest.mark.parametrize("order", sorted(G1_SMALL_ORDER))
def test_g1_small_order_point_fails_closed(order):
    """g1_subgroup_check_batch's fail-closed invariant (see
    g2_subgroup_check_batch's docstring) on the limb-major multiply: the
    H == 0 chord a small-order point meets inside the [r-1]P scan drives Z
    to a value ≡ 0 (mod P), however its limbs read after `add_lm`,
    `sub_lm` and `scale_small_lm`, and the lane REJECTS wherever it sits in
    a padded batch: among members, between them, last of a full bucket."""
    from lighthouse_tpu.ops.bls_backend import batch_subgroup_check_g1

    pt, g = G1_SMALL_ORDER[order], cv.g1_generator()
    g7 = cv.g1_mul(g, 7)
    padded = [pt, g, pt, g7, pt]                  # 5 lanes of 8
    assert list(batch_subgroup_check_g1(padded)) == [
        False, True, False, True, False]
    full = [g7, g, g7, g, g7, g, g7, pt]          # 8 lanes of 8
    assert list(batch_subgroup_check_g1(full)) == [True] * 7 + [False]
    # the reject is the Z ≡ 0 branch: the chord, not the residues
    xp, yp = _g1_lanes(padded + [g] * 3)
    _, _, Z = jax.jit(ec.g1_subgroup_check_batch)(xp, yp)
    assert list(ec.is_zero_mod_p(np.asarray(Z).T)) == [
        True, False, True, False, True, False, False, False]


@pytest.mark.parametrize("n", [6, 1536])
def test_g1_membership_against_the_oracle(n):
    """The membership program's verdicts against cv.g1_in_subgroup over
    members, points of small order and points of mixed order (a member
    plus a small-order point: on the curve, off the subgroup, meeting no
    chord), at a small bucket (6 points, 8 lanes) and at the blob batch's
    (1,536 points, 2,048 lanes); the generator lanes that pad a batch to
    its bucket pass."""
    import random

    from lighthouse_tpu.crypto.bls.fields import R
    from lighthouse_tpu.ops import bls_backend as bb

    rng = random.Random(n)
    g = cv.g1_generator()
    members = [cv.g1_mul(g, rng.randrange(1, R)) for _ in range(4)]
    mixed = [cv.g1_add(members[0], G1_SMALL_ORDER[11]),
             cv.g1_add(members[1], G1_SMALL_ORDER[10177]),
             cv.g1_add(members[2], G1_SMALL_ORDER[3])]
    distinct = members + list(G1_SMALL_ORDER.values()) + mixed
    want = [cv.g1_in_subgroup(p) for p in distinct]
    assert want == [True] * 4 + [False] * 6
    picks = [rng.randrange(len(distinct)) for _ in range(n)]
    row = np.asarray(bb._dispatch_g1_subgroup_kernel(
        [distinct[i] for i in picks]))
    assert row.shape == (bb._next_pow2(n, floor=4),)
    assert row[:n].tolist() == [want[i] for i in picks]
    assert row[n:].all()
