"""Limb-arithmetic tests: the device field construction (ops/bigint's
MontField) vs Python bigints, for BOTH of its instances — the base field
Fp and the scalar field Fr.

The differential oracle strategy from SURVEY.md §7 gate (b): every device
op is checked against plain modular integers, including bound-stressing
chains and edge values.  The asserts on limbs and top limbs are the
value-bound ledger of bigint.py's header, enforced.
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lighthouse_tpu.ops import bigint as bi
from lighthouse_tpu.ops import fr

P = bi.P_INT

# the ledger's limb bound, and its top-limb bounds
LIMB_BOUND = (1 << 15) + (1 << 11)
TOP_BOUND = 1 << 5


@pytest.fixture(scope="module", params=["fp", "fr"])
def field(request):
    return {"fp": bi.FP, "fr": fr.FR}[request.param]


def _batch(vals, F):
    return jnp.asarray(F.to_mont(vals))


def _rand(F):
    rng = random.Random(7)
    xs = [rng.randrange(F.P_INT) for _ in range(32)]
    ys = [rng.randrange(F.P_INT) for _ in range(32)]
    return xs, ys


def _edge(F):
    N = F.P_INT
    return [0, 1, 2, N - 1, N - 2, (N + 1) // 2,
            (1 << (N.bit_length() - 1)) % N, 12345]


@pytest.fixture(scope="module")
def rand_vals():
    return _rand(bi.FP)


def _ints(F, limbs):
    return [int(g) for g in F.from_mont(np.asarray(limbs))]


def _in_ledger(z, top_bound):
    z = np.asarray(z)
    return z.max() < LIMB_BOUND and z[..., -1].max() < top_bound


def test_constants(field):
    F, N, L = field, field.P_INT, field.L
    assert F.R_INT == 1 << (bi.B * L)
    # the fold bit F = C - 11 sits at least 4 bits over the modulus
    assert bi.B * L - 11 - N.bit_length() >= 4
    assert F.limbs_to_int(F.tables["p"]) == N
    assert (F.NPRIME_INT * N) % F.R_INT == F.R_INT - 1
    assert F.limbs_to_int(F.tables["nprime"]) == F.NPRIME_INT
    assert F.limbs_to_int(F.tables["foldq"]) == (
        1 << (bi.B * (L - 1) + 4)) % N
    assert F.limbs_to_int(F.tables["one_m"]) == F.R_INT % N
    assert F.limbs_to_int(F.tables["r2"]) == F.R_INT ** 2 % N
    # sub's constant: a multiple of N whose limbs dominate any operand's
    neg = F.tables["neg"]
    assert F.limbs_to_int(neg) % N == 0
    assert neg[:-1].min() >= LIMB_BOUND - (1 << 10)
    assert neg[:-1].max() < (1 << 16) + (1 << 10)
    assert (1 << 6) <= neg[-1] < (1 << 7)
    # one device object a name: a second trace reference adds no constant
    assert F.jconst("neg") is F.jconst("neg")


def test_module_names_are_the_instances():
    """The names callers use are bound to the two instances."""
    assert (bi.L, fr.L) == (27, 18) and bi.B == fr.B == 15
    assert bi.R_INT == 1 << 405 and bi.FP.P_INT == P
    assert fr.FR.P_INT == fr.R_INT and fr.FR.R_INT == 1 << 270
    assert bi.mont_mul == bi.FP.mont_mul and fr.sub == fr.FR.sub
    assert fr.to_mont_host == fr.FR.to_mont and bi.ONE_M is bi.FP.tables["one_m"]


def test_roundtrip(field):
    F = field
    xs, _ = _rand(F)
    for x in xs[:8] + _edge(F):
        assert F.from_mont(F.to_mont(x)) == x


def test_host_boundary_takes_scalars_and_arrays(field):
    """to_mont / from_mont: one implementation for an int, a list and an
    array of any rank."""
    F = field
    xs, _ = _rand(F)
    one = F.to_mont(xs[0])
    assert one.shape == (F.L,) and one.dtype == np.uint32
    assert isinstance(F.from_mont(one), int)
    rows = F.to_mont(xs[:6])
    assert rows.shape == (6, F.L) and (rows[0] == one).all()
    grid = F.to_mont(np.array(xs[:6], dtype=object).reshape(2, 3))
    assert grid.shape == (2, 3, F.L)
    back = F.from_mont(grid)
    assert back.shape == (2, 3) and back.ravel().tolist() == xs[:6]
    assert (F.int_to_limbs(xs[0]) < (1 << 15)).all()
    with pytest.raises(AssertionError):
        F.int_to_limbs(F.R_INT)


def test_mont_mul(field):
    F, N = field, field.P_INT
    xs, ys = _rand(F)
    out = np.asarray(jax.jit(F.mont_mul)(_batch(xs, F), _batch(ys, F)))
    assert _ints(F, out) == [(x * y) % N for x, y in zip(xs, ys)]
    # mul out < 2^(C-19): nothing reaches the top limb's bit 4
    assert _in_ledger(out, 1 << 4)


def test_add_sub_neg(field):
    F, N = field, field.P_INT
    xs, ys = _rand(F)
    ax, ay = _batch(xs, F), _batch(ys, F)
    s, d, n = F.add(ax, ay), jax.jit(F.sub)(ax, ay), F.neg(ax)
    assert _ints(F, s) == [(x + y) % N for x, y in zip(xs, ys)]
    assert _ints(F, d) == [(x - y) % N for x, y in zip(xs, ys)]
    assert _ints(F, n) == [(-x) % N for x in xs]
    assert all(_in_ledger(z, TOP_BOUND) for z in (s, d, n))


def test_scale_small(field):
    F, N = field, field.P_INT
    xs, _ = _rand(F)
    ax = _batch(xs, F)
    for k in (2, 3, 8, 16):
        got = F.scale_small(ax, k)
        assert _ints(F, got) == [(k * x) % N for x in xs]
    # the ledger's one two-sided line: Fr's fold bit sits 4 bits over its
    # modulus, so sixteen folded values reach the top limb's bit 5
    top = TOP_BOUND if F is bi.FP else 2 * TOP_BOUND
    z = F.neg(ax)                       # a fold output, not a canonical one
    for _ in range(4):
        z = F.scale_small(z, 16)
        assert _in_ledger(z, top)
    assert _ints(F, z) == [(-(16 ** 4) * x) % N for x in xs]
    assert _in_ledger(F.sub(ax, z), TOP_BOUND)
    assert _in_ledger(F.add(z, z), TOP_BOUND)


def test_edge_values(field):
    F, N = field, field.P_INT
    edge = _edge(F)
    ae = _batch(edge, F)
    assert _ints(F, jax.jit(F.mont_mul)(ae, ae)) == [
        (x * x) % N for x in edge]
    assert _ints(F, F.sub(ae, ae)) == [0] * len(edge)


def test_deep_chain_keeps_bounds(field):
    """60 rounds of mul/sub/add/neg: redundant-representation invariants
    hold after every op and values stay exact."""
    F, N = field, field.P_INT
    xs, ys = _rand(F)
    ax, ay = _batch(xs, F), _batch(ys, F)
    mm = jax.jit(F.mont_mul)
    z, zv = ax, list(xs)
    for _ in range(60):
        z = mm(z, ay)
        assert _in_ledger(z, 1 << 4)
        zv = [(a * b) % N for a, b in zip(zv, ys)]
        z = F.sub(z, ax)
        assert _in_ledger(z, TOP_BOUND)
        zv = [(a - b) % N for a, b in zip(zv, xs)]
        z = F.add(z, z)
        assert _in_ledger(z, TOP_BOUND)
        zv = [(2 * a) % N for a in zv]
        z = F.neg(z)
        assert _in_ledger(z, TOP_BOUND)
        zv = [(-a) % N for a in zv]
    assert _ints(F, z) == zv


def test_mxu_redc_matches_schoolbook(field):
    """The int8-matmul REDC is bit-value-equal to the schoolbook REDC on
    random, edge and worst-case-spread inputs, and keeps the output limb
    bound (the device programs switch paths by platform — both must be
    the same function; forced here, so the oracle holds on every
    platform)."""
    F, N = field, field.P_INT

    def mxu(a, b):
        return F._redc(bi._carry(bi._mul_cols(a, b, 2 * F.L)), mxu=True)

    def schoolbook(a, b):
        return F._redc(bi._carry(bi._mul_cols(a, b, 2 * F.L)), mxu=False)

    xs, ys = _rand(F)
    edge = _edge(F)
    ax = jnp.concatenate([_batch(xs, F), _batch(edge, F)])
    ay = jnp.concatenate([_batch(ys, F), _batch(edge[::-1], F)])
    want = np.asarray(jax.jit(schoolbook)(ax, ay))
    got = np.asarray(jax.jit(mxu)(ax, ay))
    assert _ints(F, got) == _ints(F, want) == [
        (x * y) % N for x, y in zip(xs + edge, ys + edge[::-1])]
    assert _in_ledger(got, 1 << 4)

    # worst-case redundant encodings (limbs at the op-invariant bound)
    rows = np.stack([_spread_limbs(x + (x % 4) * N, F) for x in xs[:8]])
    aw = jnp.asarray(rows)
    assert _ints(F, mxu(aw, ay[:8])) == _ints(F, schoolbook(aw, ay[:8]))

    # deep chain through the MXU path: bounds must not drift
    z = ax
    mm = jax.jit(mxu)
    for _ in range(30):
        z = F.add(mm(z, ay), ax)
        assert _in_ledger(z, TOP_BOUND)


# --- the limb-major operations (the limb axis leads) --------------------------

def _extreme_rows(F):
    """Limb rows at the extremes the ledger allows: every limb at
    2^15 + 2^11 - 1 with the top limb at its bound, 0, N - 1, a lone top
    limb, and worst-case spreads of random values."""
    N, L = F.P_INT, F.L
    full = np.full(L, LIMB_BOUND - 1, np.uint32)
    full[-1] = TOP_BOUND - 1
    top_only = np.zeros(L, np.uint32)
    top_only[-1] = TOP_BOUND - 1
    xs, _ = _rand(F)
    return np.stack(
        [full, np.zeros(L, np.uint32), F.int_to_limbs(N - 1), top_only,
         F.int_to_limbs(1)]
        + [_spread_limbs(x + (x % 4) * N, F) for x in xs[:11]])


def _residues(F, rows):
    return [F.limbs_to_int(r) % F.P_INT for r in np.asarray(rows)]


def test_mont_mul_lm_at_the_ledgers_extremes(field):
    """The limb-major multiply (whole arrays: the launcher every backend
    but a TPU takes) against Python integers and against `mont_mul`, with
    its output inside the ledger's line for `mul out`."""
    F, N = field, field.P_INT
    a = _extreme_rows(F)
    b = a[::-1].copy()
    want = [(F.limbs_to_int(x) * F.limbs_to_int(y) * F.R_INV) % N
            for x, y in zip(a, b)]
    got = np.asarray(jax.jit(F.mont_mul_lm)(jnp.asarray(a.T),
                                            jnp.asarray(b.T))).T
    assert _residues(F, got) == want
    assert _residues(F, jax.jit(F.mont_mul)(jnp.asarray(a),
                                            jnp.asarray(b))) == want
    # mul out < 2^(2(F+1) - C) + N: top limb 0 (Fp), <= 1 (Fr)
    assert _in_ledger(got, 1 if F is bi.FP else 2)
    cap = 2 * (bi.B * F.L - 10) - bi.B * F.L
    assert all(F.limbs_to_int(r) < (1 << cap) + N + (N >> 8) for r in got)


def test_mont_mul_lm_lane_shapes_and_fixed_multiplicand(field):
    """Any lane shape, and a host limb table as the fixed multiplicand
    (the way into Montgomery form multiplies by R^2 mod N)."""
    F, N = field, field.P_INT
    xs, ys = _rand(F)
    a = jnp.asarray(F.to_mont(xs)).T.reshape(F.L, 4, 8)
    b = jnp.asarray(F.to_mont(ys)).T.reshape(F.L, 4, 8)
    out = np.asarray(jax.jit(F.mont_mul_lm)(a, b)).reshape(F.L, 32).T
    assert _ints(F, out) == [(x * y) % N for x, y in zip(xs, ys)]
    raw = jnp.asarray(np.stack([F.int_to_limbs(x) for x in xs]).T)
    mont = np.asarray(
        jax.jit(lambda v: F.mont_mul_lm(v, F.tables["r2"]))(raw)).T
    assert _ints(F, mont) == xs
    assert _in_ledger(mont, 2)


def test_resident_kernel_interpreted(field):
    """The Pallas launcher, interpreted on the CPU at one small shape
    (lanes off a block's edge, so padded): the same limbs as the whole-
    array launcher, for the multiply, its fixed form and add/sub."""
    F = field
    a = jnp.asarray(_extreme_rows(F).T)                  # [L, 16]
    b = jnp.asarray(_extreme_rows(F)[::-1].copy().T)
    blocks = [bi._to_blocks(a), bi._to_blocks(b)]
    assert blocks[0].shape == (F.L, 8, 128)
    for fn in (F._mont_mul_lm, F._add_lm, F._sub_lm):
        got = bi._from_blocks(
            F._resident_call(fn, 1, blocks, interpret=True), a.shape)
        assert (np.asarray(got) == np.asarray(fn(a, b))).all()
    scale = F._scale_fn(3)
    got = bi._from_blocks(
        F._resident_call(scale, 1, blocks[:1], interpret=True), a.shape)
    assert (np.asarray(got) == np.asarray(scale(a))).all()
    k = F.tables["r2"]
    fixed = lambda x: F._mont_mul_lm(  # noqa: E731
        x, bi._splat_lm(k.tolist(), x))
    got = bi._from_blocks(
        F._resident_call(fixed, 1, blocks[:1], interpret=True), a.shape)
    assert (np.asarray(got) == np.asarray(F.mont_mul_lm(a, k))).all()


def test_blocks_keep_a_blocked_shape_and_pad_any_other():
    x = jnp.arange(3 * 16 * 256, dtype=jnp.uint32).reshape(3, 16, 256)
    assert bi._to_blocks(x) is x
    y = jnp.arange(3 * 5 * 7, dtype=jnp.uint32).reshape(3, 5, 7)
    blocked = bi._to_blocks(y)
    assert blocked.shape == (3, 8, 128)
    assert (np.asarray(bi._from_blocks(blocked, y.shape))
            == np.asarray(y)).all()
    assert int(np.asarray(blocked).reshape(3, -1)[:, 35:].max()) == 0


def test_add_sub_lm_and_a_deep_chain_keep_the_ledger(field):
    """add_lm / sub_lm against integers, and 30 rounds of mul/sub/add on
    limb-major arrays: every output inside the ledger, values exact."""
    F, N = field, field.P_INT
    xs, ys = _rand(F)
    ax, ay = jnp.asarray(F.to_mont(xs)).T, jnp.asarray(F.to_mont(ys)).T
    assert _ints(F, np.asarray(F.add_lm(ax, ay)).T) == [
        (x + y) % N for x, y in zip(xs, ys)]
    assert _ints(F, np.asarray(jax.jit(F.sub_lm)(ax, ay)).T) == [
        (x - y) % N for x, y in zip(xs, ys)]
    mm = jax.jit(F.mont_mul_lm)
    z, zv = ax, list(xs)
    for _ in range(30):
        z = mm(z, ay)
        assert _in_ledger(np.asarray(z).T, 1 << 4)
        zv = [(a * b) % N for a, b in zip(zv, ys)]
        z = F.sub_lm(z, ax)
        assert _in_ledger(np.asarray(z).T, TOP_BOUND)
        zv = [(a - b) % N for a, b in zip(zv, xs)]
        z = F.add_lm(z, z)
        assert _in_ledger(np.asarray(z).T, TOP_BOUND)
        zv = [(2 * a) % N for a in zv]
    assert _ints(F, np.asarray(z).T) == zv
    # the same limbs as the limb-row operations give
    assert (np.asarray(F.sub_lm(ax, ay)).T
            == np.asarray(F.sub(ax.T, ay.T))).all()
    assert (np.asarray(F.add_lm(ax, ay)).T
            == np.asarray(F.add(ax.T, ay.T))).all()


@pytest.mark.parametrize("k", [2, 3, 4, 8, 16])
def test_scale_small_lm_gives_a_legal_multiplicand(field, k):
    """scale_small_lm at the ledger's extremes: k·x against integers, limb
    for limb what `scale_small` gives, inside the ledger's line (so THREE
    raw products of its limbs fit uint32), and exact through the multiply
    that takes it next."""
    F, N = field, field.P_INT
    rows = _extreme_rows(F)
    a = jnp.asarray(rows.T)
    got = np.asarray(jax.jit(F.scale_small_lm, static_argnums=1)(a, k)).T
    assert _residues(F, got) == [(k * F.limbs_to_int(r)) % N for r in rows]
    assert (got == np.asarray(F.scale_small(jnp.asarray(rows), k))).all()
    assert _in_ledger(got, TOP_BOUND if F is bi.FP else 2 * TOP_BOUND)
    assert 3 * int(got.max()) ** 2 < 1 << 32
    sq = np.asarray(F.mont_mul_lm(jnp.asarray(got.T), jnp.asarray(got.T))).T
    assert _residues(F, sq) == [
        ((k * F.limbs_to_int(r)) ** 2 * F.R_INV) % N for r in rows]
    assert _in_ledger(sq, 1 << 4)


def _spread_limbs(v: int, F, limit: int = LIMB_BOUND - 1) -> np.ndarray:
    """Worst-case redundant encoding of v: same value, limbs pushed to
    the op-invariant bound by borrowing 2^15-units from higher limbs."""
    d = [int(x) for x in F.int_to_limbs(v)]
    for i in range(F.L - 1):
        m = min(d[i + 1], (limit - d[i]) >> bi.B)
        d[i] += m << bi.B
        d[i + 1] -= m
    out = np.array(d, np.uint32)
    assert F.limbs_to_int(out) == v
    return out


def test_is_zero_mod_p_device_bound_coupling():
    """is_zero_mod_p_device's completeness rests on the mont-mul-by-one
    output staying inside the {0..4P} comparison set; exercise redundant
    encodings of kP and kP+eps (k=0..4, worst-case limb spreads, plus a
    near-2^394 value at the documented input bound) and assert both the
    verdicts and the <5P output-value bound directly, so a future
    mont_mul bound regression fails HERE instead of silently corrupting
    subgroup/infinity verdicts."""
    rows, want = _zero_test_rows()
    x = jnp.asarray(rows)
    got = np.asarray(bi.is_zero_mod_p_device(x))
    assert got.tolist() == want

    one = jnp.broadcast_to(jnp.asarray(bi._int_to_limbs(1)), x.shape)
    w = np.asarray(bi.mont_mul(x, one))
    worst = max(bi._limbs_to_int(r) for r in w)
    assert worst < 5 * P, hex(worst)


def _zero_test_rows():
    """Redundant encodings of kP and kP+eps (k=0..4, worst-case limb
    spreads) and a near-2^394 value at the documented input bound, with
    whether each is ≡ 0 (mod P)."""
    eps = (1 << 380) % P  # nonzero residue
    rows, want = [], []
    for k in range(5):
        rows.append(_spread_limbs(k * P, bi.FP))
        want.append(True)
        rows.append(bi._int_to_limbs(k * P))
        want.append(True)
        rows.append(_spread_limbs(k * P + 1, bi.FP))
        want.append(False)
        rows.append(_spread_limbs(k * P + eps, bi.FP))
        want.append(False)
    near_bound = (1 << 394) - 12345
    assert near_bound % P != 0
    rows.append(bi._int_to_limbs(near_bound))
    want.append(False)
    return np.stack(rows), want


def test_is_zero_mod_p_lm_bound_coupling():
    """The limb-major zero test (the G1 membership program's) on the same
    encodings: the verdicts, and its multiply by plain 1 on `mont_mul_lm`
    inside the {0..4P} comparison set (under 2P by its header's m)."""
    rows, want = _zero_test_rows()
    x = jnp.asarray(rows.T)                     # limb-major [27, n]
    assert np.asarray(bi.is_zero_mod_p_lm(x)).tolist() == want
    w = np.asarray(bi.FP.mont_mul_lm(x, bi.FP.tables["one_plain"])).T
    worst = max(bi._limbs_to_int(r) for r in w)
    assert worst < 2 * P, hex(worst)


def test_fp2_tower_ops(rand_vals):
    """Spot-check the Fq2 layer against the python field."""
    from lighthouse_tpu.crypto.bls.fields import Fq2
    from lighthouse_tpu.ops import bls12_381 as dev

    xs, ys = rand_vals
    x = (_batch(xs[:4], bi.FP), _batch(ys[:4], bi.FP))
    y = (_batch(ys[4:8], bi.FP), _batch(xs[4:8], bi.FP))
    got = dev.fp2_mul(x, y)
    for i in range(4):
        want = Fq2(xs[i], ys[i]) * Fq2(ys[4 + i], xs[4 + i])
        assert int(bi.from_mont(np.asarray(got[0])[i])) == want.a
        assert int(bi.from_mont(np.asarray(got[1])[i])) == want.b
    got = dev.fp2_sqr(x)
    for i in range(4):
        want = Fq2(xs[i], ys[i]).square()
        assert int(bi.from_mont(np.asarray(got[0])[i])) == want.a
        assert int(bi.from_mont(np.asarray(got[1])[i])) == want.b
