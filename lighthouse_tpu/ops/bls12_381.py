"""Batched BLS12-381 pairing on TPU: tower fields + Miller loop (jnp).

The device data plane for BLS batch signature verification (the #1 kernel
target, SURVEY.md §2.1: blst's verify_multiple_aggregate_signatures at
/root/reference/crypto/bls/src/impls/blst.rs:37-119).  Every value is a
batch of Fp elements in redundant Montgomery limb form (ops/bigint.py);
the tower (Fq2 = Fq[u]/(u²+1), Fq6 = Fq2[v]/(v³-(1+u)), Fq12 = Fq6[w]/(w²-v))
is nested tuples of limb arrays — pytrees that flow through lax.scan.

The Miller loop is the inversion-free projective form with sparse line
evaluation validated in crypto/bls/pairing_fast.py (same formula sequence,
so device lanes are bit-exact against the scalar oracle).  The loop is a
lax.scan over the 63 static bits of |x|; the rare addition step is
computed unconditionally and masked in (x has hamming weight 6, so this
wastes ~40% of line work in exchange for a compilable, uniform body).

One batch = one multi-pairing: per-lane Miller values are tree-reduced to
a single Fq12 product on device; the single final exponentiation runs on
the host oracle (once per batch, off the per-set critical path).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from lighthouse_tpu.common import device_telemetry as _dtel
from lighthouse_tpu.ops import bigint as bi
from lighthouse_tpu.ops import program_store as _pstore

# AOT program-store coverage (lhlint LH606): the multi-pairing reduce
# is prewarmed by the "pairing" driver in ops/prewarm
_pstore.register_entry("ops/bls12_381.py::_miller_reduce_jit@run",
                       driver="pairing")

# --- Fp2 -------------------------------------------------------------------
# element: (a, b) = a + b·u, each uint32[..., 27]

def fp2_add(x, y):
    return (bi.add(x[0], y[0]), bi.add(x[1], y[1]))


def fp2_sub(x, y):
    return (bi.sub(x[0], y[0]), bi.sub(x[1], y[1]))


def fp2_neg(x):
    return (bi.neg(x[0]), bi.neg(x[1]))


def fp2_scale(x, k: int):
    return (bi.scale_small(x[0], k), bi.scale_small(x[1], k))


def fp2_mul(x, y):
    # Karatsuba over u²=-1 (fields.py Fq2.__mul__)
    t0 = bi.mont_mul(x[0], y[0])
    t1 = bi.mont_mul(x[1], y[1])
    t2 = bi.mont_mul(bi.add(x[0], x[1]), bi.add(y[0], y[1]))
    return (bi.sub(t0, t1), bi.sub(bi.sub(t2, t0), t1))


def fp2_sqr(x):
    # (a+b)(a-b) + 2ab·u
    return (
        bi.mont_mul(bi.add(x[0], x[1]), bi.sub(x[0], x[1])),
        bi.mont_mul(bi.add(x[0], x[0]), x[1]),
    )


def fp2_mul_by_xi(x):
    """·(1+u): (a - b) + (a + b)u."""
    return (bi.sub(x[0], x[1]), bi.add(x[0], x[1]))


# --- Fp6 -------------------------------------------------------------------
# element: (c0, c1, c2) over Fp2, v³ = ξ

def fp6_add(x, y):
    return tuple(fp2_add(a, b) for a, b in zip(x, y))


def fp6_sub(x, y):
    return tuple(fp2_sub(a, b) for a, b in zip(x, y))


def fp6_neg(x):
    return tuple(fp2_neg(a) for a in x)


def fp6_mul(x, y):
    a0, a1, a2 = x
    b0, b1, b2 = y
    t0 = fp2_mul(a0, b0)
    t1 = fp2_mul(a1, b1)
    t2 = fp2_mul(a2, b2)
    c0 = fp2_add(t0, fp2_mul_by_xi(
        fp2_sub(fp2_sub(fp2_mul(fp2_add(a1, a2), fp2_add(b1, b2)), t1), t2)))
    c1 = fp2_add(
        fp2_sub(fp2_sub(fp2_mul(fp2_add(a0, a1), fp2_add(b0, b1)), t0), t1),
        fp2_mul_by_xi(t2))
    c2 = fp2_add(
        fp2_sub(fp2_sub(fp2_mul(fp2_add(a0, a2), fp2_add(b0, b2)), t0), t2),
        t1)
    return (c0, c1, c2)


def fp6_mul_by_v(x):
    return (fp2_mul_by_xi(x[2]), x[0], x[1])


# --- Fp12 ------------------------------------------------------------------
# element: (c0, c1) over Fp6, w² = v

def fp12_mul(x, y):
    t0 = fp6_mul(x[0], y[0])
    t1 = fp6_mul(x[1], y[1])
    c0 = fp6_add(t0, fp6_mul_by_v(t1))
    c1 = fp6_sub(fp6_sub(
        fp6_mul(fp6_add(x[0], x[1]), fp6_add(y[0], y[1])), t0), t1)
    return (c0, c1)


def fp12_conj(x):
    return (x[0], fp6_neg(x[1]))


# --- product batching -------------------------------------------------------
#
# The Miller-loop body contains ~80 Fq2 multiplications (~240 Fp products).
# Instantiating mont_mul per product made the scan body ~125k HLO ops and
# XLA compiles took minutes.  Instead, every data-independent set of Fp
# products is queued and executed as ONE stacked mont_mul over [k, N, 27]
# — the body becomes 7 mont_mul instantiations (one per dependency round),
# which also feeds the vector units k·N-wide lanes.

class _MulQueue:
    """Collects Fp products; `run` executes them in one mont_mul — and
    the limb-major products of a round (`fp_lm`: the G1 fold of ops/ec.py)
    in one `mont_mul_lm` beside it."""

    def __init__(self):
        self._a: list = []
        self._b: list = []
        self._out = None
        self._lm: list = []
        self._lm_out: list = []

    def fp(self, a, b) -> int:
        self._a.append(a)
        self._b.append(b)
        return len(self._a) - 1

    def fp_lm(self, a, b):
        """Queue a product of limb-major arrays uint32[L, lanes]; returns
        a resolver."""
        i = len(self._lm)
        self._lm.append((a, b))
        return lambda: self._lm_out[i]

    def fp2(self, x, y):
        """Queue a Karatsuba Fq2 product; returns a resolver."""
        i0 = self.fp(x[0], y[0])
        i1 = self.fp(x[1], y[1])
        i2 = self.fp(bi.add(x[0], x[1]), bi.add(y[0], y[1]))
        q = self

        def resolve():
            t0, t1, t2 = q[i0], q[i1], q[i2]
            return (bi.sub(t0, t1), bi.sub(bi.sub(t2, t0), t1))

        return resolve

    def fp6(self, x, y):
        a0, a1, a2 = x
        b0, b1, b2 = y
        r0 = self.fp2(a0, b0)
        r1 = self.fp2(a1, b1)
        r2 = self.fp2(a2, b2)
        r12 = self.fp2(fp2_add(a1, a2), fp2_add(b1, b2))
        r01 = self.fp2(fp2_add(a0, a1), fp2_add(b0, b1))
        r02 = self.fp2(fp2_add(a0, a2), fp2_add(b0, b2))

        def resolve():
            t0, t1, t2 = r0(), r1(), r2()
            c0 = fp2_add(t0, fp2_mul_by_xi(
                fp2_sub(fp2_sub(r12(), t1), t2)))
            c1 = fp2_add(fp2_sub(fp2_sub(r01(), t0), t1), fp2_mul_by_xi(t2))
            c2 = fp2_add(fp2_sub(fp2_sub(r02(), t0), t2), t1)
            return (c0, c1, c2)

        return resolve

    def fp12(self, x, y):
        r0 = self.fp6(x[0], y[0])
        r1 = self.fp6(x[1], y[1])
        rm = self.fp6(fp6_add(x[0], x[1]), fp6_add(y[0], y[1]))

        def resolve():
            t0, t1 = r0(), r1()
            return (fp6_add(t0, fp6_mul_by_v(t1)),
                    fp6_sub(fp6_sub(rm(), t0), t1))

        return resolve

    def sparse(self, f, a0, a1, b1):
        """Queue f·(a0 + a1 v + b1 vw) — the 16-Fq2-product line mul."""
        (x0, x1, x2), (y0, y1, y2) = f
        rt0 = self.fp2(x0, a0)
        rt1 = self.fp2(x1, a1)
        rx12 = self.fp2(fp2_add(x1, x2), a1)
        rx01 = self.fp2(fp2_add(x0, x1), fp2_add(a0, a1))
        rx02 = self.fp2(fp2_add(x0, x2), a0)
        rs0 = self.fp2(y2, b1)
        rs1 = self.fp2(y0, b1)
        rs2 = self.fp2(y1, b1)
        ru0 = self.fp2(x2, b1)
        ru1 = self.fp2(x0, b1)
        ru2 = self.fp2(x1, b1)
        rv0 = self.fp2(y0, a0)
        rv1 = self.fp2(y1, a1)
        ry12 = self.fp2(fp2_add(y1, y2), a1)
        ry01 = self.fp2(fp2_add(y0, y1), fp2_add(a0, a1))
        ry02 = self.fp2(fp2_add(y0, y2), a0)

        def resolve():
            t0, t1 = rt0(), rt1()
            ca0 = fp2_add(t0, fp2_mul_by_xi(fp2_sub(rx12(), t1)))
            ca1 = fp2_sub(fp2_sub(rx01(), t0), t1)
            ca2 = fp2_add(fp2_sub(rx02(), t0), t1)
            cb = (fp2_mul_by_xi(rs0()), rs1(), rs2())
            new_c0 = fp6_add((ca0, ca1, ca2), fp6_mul_by_v(cb))
            v0t, v1t = rv0(), rv1()
            va0 = fp2_add(v0t, fp2_mul_by_xi(fp2_sub(ry12(), v1t)))
            va1 = fp2_sub(fp2_sub(ry01(), v0t), v1t)
            va2 = fp2_add(fp2_sub(ry02(), v0t), v1t)
            new_c1 = fp6_add(
                (fp2_mul_by_xi(ru0()), ru1(), ru2()), (va0, va1, va2))
            return (new_c0, new_c1)

        return resolve

    def run(self):
        if self._a:
            self._out = bi.mont_mul(jnp.stack(self._a), jnp.stack(self._b))
        if self._lm:
            # one launch a round: the products side by side along the lanes
            out = bi.FP.mont_mul_lm(
                jnp.concatenate([a for a, _ in self._lm], axis=1),
                jnp.concatenate([b for _, b in self._lm], axis=1))
            ends = np.cumsum([a.shape[1] for a, _ in self._lm]).tolist()
            self._lm_out = jnp.split(out, ends[:-1], axis=1)

    def __getitem__(self, i: int):
        return self._out[i]


# --- Miller loop ------------------------------------------------------------

BLS_X = 0xD201000000010000
_X_BITS = np.array([int(b) for b in bin(BLS_X)[3:]], np.uint32)  # 63 bits


def _ones_like_fp12(batch_shape):
    one = jnp.broadcast_to(
        bi._jconst("one_m"), batch_shape + (bi.L,))
    zero = jnp.zeros(batch_shape + (bi.L,), jnp.uint32)
    z2 = (zero, zero)
    return ((( one, zero), z2, z2), (z2, z2, z2))


def _select(bit, a, b):
    """Per-lane pytree select: bit uint32[...] broadcast over limbs."""
    m = (bit != 0)[..., None]
    return jax.tree_util.tree_map(lambda x, y: jnp.where(m, x, y), a, b)


def batch_miller_loop(xp, yp, xqa, xqb, yqa, yqb, zp=None, zq=None):
    """Batched Miller loops: lane i computes miller(P_i, Q_i).

    xp, yp: uint32[N, 27] (G1 Montgomery limbs); (xqa+xqb·u, yqa+yqb·u):
    G2 affine.  Returns a batched Fq12 pytree.  Formula-for-formula the
    scalar pairing_fast.miller_loop_fast.

    With ``zp`` given, P lanes are JACOBIAN (X, Y, Z) — the line is scaled
    per step by the subfield factor Zp³ (killed by the final
    exponentiation): l' = a0·Zp³ + a1·(Xp·Zp)·v + b1·Yp·v·w.  The Zp³
    factors reach the chord line through the loop-invariant products
    zxq = xq·Zp³ / zyq = yq·Zp³, so the dependency-round structure is
    unchanged.  This lets r·agg_pk lanes flow straight from the device
    scalar-mul kernel (ops/ec.py) without per-lane host inversions.

    With ``zq`` given (an Fq2 limb pair), Q lanes are JACOBIAN too: every
    Q interaction is rewritten over U1 = X·Zq², S1 = Y·Zq³ with the chord
    line scaled by the Fq2 factor Zq⁵ — also killed by the final
    exponentiation, since r has embedding degree 12 so (p¹²−1)/r is
    divisible by p²−1 and any Fq2* factor maps to 1.  The T+Q update
    becomes a full Jacobian add (Z3a gains a ·Zq).  This removes the
    Σ r·sig affine conversion — a 381-step width-1 Fermat inversion —
    from the fused verify pipeline's critical path."""
    xq = (xqa, xqb)
    yq = (yqa, yqb)
    batch = xp.shape[:-1]
    f = _ones_like_fp12(batch)
    zero = jnp.zeros_like(xp)
    one = jnp.broadcast_to(bi._jconst("one_m"), xp.shape)
    X, Y, Z = xq, yq, ((one, zero) if zq is None else zq)

    if zp is None:
        zp3 = one
        xz = xp
        zxq, zyq = xq, yq
    else:
        q0 = _MulQueue()
        i_zp2 = q0.fp(zp, zp)
        i_xz = q0.fp(xp, zp)
        q0.run()
        zp2, xz = q0[i_zp2], q0[i_xz]
        q0 = _MulQueue()
        i_zp3 = q0.fp(zp2, zp)
        q0.run()
        zp3 = q0[i_zp3]
        q0 = _MulQueue()
        i_zxa = q0.fp(xq[0], zp3)
        i_zxb = q0.fp(xq[1], zp3)
        i_zya = q0.fp(yq[0], zp3)
        i_zyb = q0.fp(yq[1], zp3)
        q0.run()
        zxq = (q0[i_zxa], q0[i_zxb])
        zyq = (q0[i_zya], q0[i_zyb])

    if zq is not None:
        # loop invariants for the Jacobian-Q chord: Zq², Zq³, and the
        # P-side line factors pre-scaled so all three chord coefficients
        # share the single overall Zq⁵ (xz·Zq² for c1, yp·Zq³ for d1)
        qz = _MulQueue()
        r_zq2 = qz.fp2(zq, zq)
        qz.run()
        zq2 = r_zq2()
        qz = _MulQueue()
        r_zq3 = qz.fp2(zq2, zq)
        i_xzq2a = qz.fp(xz, zq2[0])
        i_xzq2b = qz.fp(xz, zq2[1])
        qz.run()
        zq3 = r_zq3()
        xzq2 = (qz[i_xzq2a], qz[i_xzq2b])
        qz = _MulQueue()
        i_ypq3a = qz.fp(yp, zq3[0])
        i_ypq3b = qz.fp(yp, zq3[1])
        qz.run()
        ypq3 = (qz[i_ypq3a], qz[i_ypq3b])

    def step(carry, bit):
        # 7 dependency rounds, each one stacked mont_mul.  Formula-for-
        # formula identical to pairing_fast.miller_loop_fast's sequence:
        # tangent line at T → f²·l → double T → chord line → f·l' →
        # add T+Q (mixed for affine Q, full Jacobian for zq lanes), with
        # the add half masked by the bit.
        f, X, Y, Z = carry

        q1 = _MulQueue()
        r_xx = q1.fp2(X, X)
        r_yy = q1.fp2(Y, Y)
        r_zz = q1.fp2(Z, Z)
        r_yz = q1.fp2(Y, Z)
        r_fsq = q1.fp12(f, f)
        q1.run()
        xx, yy, zz, yz = r_xx(), r_yy(), r_zz(), r_yz()
        fsq = r_fsq()
        Z3 = fp2_scale(yz, 2)          # doubled point's Z
        E = fp2_scale(xx, 3)

        q2 = _MulQueue()
        r_xxx = q2.fp2(xx, X)
        r_xxzz = q2.fp2(xx, zz)
        r_yzzz = q2.fp2(yz, zz)
        r_c4 = q2.fp2(yy, yy)          # C = (Y²)²
        xb = fp2_add(X, yy)
        r_t = q2.fp2(xb, xb)           # (X + Y²)²
        r_ff = q2.fp2(E, E)            # (3X²)²
        r_zz2 = q2.fp2(Z3, Z3)         # new Z² (for the add step)
        if zq is not None:
            r_z3zq = q2.fp2(Z3, zq)    # toward Z3a = 2·(Z3·Zq)·H
        q2.run()
        xxx, xxzz, yzzz, c4, t, ff, zz2 = (
            r_xxx(), r_xxzz(), r_yzzz(), r_c4(), r_t(), r_ff(), r_zz2())
        z3zq = r_z3zq() if zq is not None else None
        D = fp2_scale(fp2_sub(fp2_sub(t, xx), c4), 2)
        X3 = fp2_sub(ff, fp2_scale(D, 2))
        a0 = fp2_sub(fp2_scale(xxx, 3), fp2_scale(yy, 2))
        s_a1 = fp2_scale(xxzz, 3)
        s_b1 = fp2_scale(yzzz, 2)

        q3 = _MulQueue()
        r_ey = q3.fp2(E, fp2_sub(D, X3))
        i_a1a = q3.fp(s_a1[0], xz)
        i_a1b = q3.fp(s_a1[1], xz)
        i_b1a = q3.fp(s_b1[0], yp)
        i_b1b = q3.fp(s_b1[1], yp)
        i_a0a = q3.fp(a0[0], zp3)
        i_a0b = q3.fp(a0[1], zp3)
        r_zzz = q3.fp2(Z3, zz2)
        r_xqzz2 = q3.fp2(xq, zz2)      # U2 = Xq·Z3²
        if zq is not None:
            r_u1 = q3.fp2(X3, zq2)     # U1 = X3·Zq²
        q3.run()
        Y3 = fp2_sub(r_ey(), fp2_scale(c4, 8))
        a1 = (bi.neg(q3[i_a1a]), bi.neg(q3[i_a1b]))
        b1 = (q3[i_b1a], q3[i_b1b])
        a0s = (q3[i_a0a], q3[i_a0b])
        zzz, xqzz2 = r_zzz(), r_xqzz2()
        u1 = r_u1() if zq is not None else X3
        H = fp2_sub(xqzz2, u1)          # U2 - U1
        # (X3, Y3, Z3) is the doubled point; (a0s, a1, b1) the tangent line
        # (scaled by the subfield factor Zp³ — a no-op for affine P)

        q4 = _MulQueue()
        r_fd = q4.sparse(fsq, a0s, a1, b1)
        r_yqzzz = q4.fp2(yq, zzz)      # S2 = Yq·Z3³
        r_dl = q4.fp2(fp2_neg(H), Z3)  # dl = (U1 - U2)·Z3
        if zq is not None:
            r_s1 = q4.fp2(Y3, zq3)     # S1 = Y3·Zq³
            r_z3ah = q4.fp2(z3zq, H)   # (Z3·Zq)·H
        q4.run()
        f_dbl = r_fd()
        yqzzz = r_yqzzz()
        dl = r_dl()
        s1 = r_s1() if zq is not None else Y3
        Nl = fp2_sub(s1, yqzzz)        # S1 - S2

        q5 = _MulQueue()
        r_nxq = q5.fp2(Nl, zxq)
        r_dyq = q5.fp2(dl, zyq)
        if zq is not None:
            r_c1 = q5.fp2(Nl, xzq2)    # c1 = -Nl·(xz·Zq²)
            r_d1 = q5.fp2(dl, ypq3)    # d1 = dl·(yp·Zq³)
        else:
            i_c1a = q5.fp(Nl[0], xz)
            i_c1b = q5.fp(Nl[1], xz)
            i_d1a = q5.fp(dl[0], yp)
            i_d1b = q5.fp(dl[1], yp)
        r_hh = q5.fp2(H, H)
        q5.run()
        c0a = fp2_sub(r_nxq(), r_dyq())
        if zq is not None:
            c1a = fp2_neg(r_c1())
            d1a = r_d1()
        else:
            c1a = (bi.neg(q5[i_c1a]), bi.neg(q5[i_c1b]))
            d1a = (q5[i_d1a], q5[i_d1b])
        hh = r_hh()
        I = fp2_scale(hh, 4)
        r_vec = fp2_scale(fp2_sub(yqzzz, s1), 2)  # r = 2(S2 - S1)

        q6 = _MulQueue()
        r_fa = q6.sparse(f_dbl, c0a, c1a, d1a)
        r_j = q6.fp2(H, I)
        r_v = q6.fp2(u1, I)            # V = U1·I
        r_rr = q6.fp2(r_vec, r_vec)
        q6.run()
        f_add = r_fa()
        j, v, rr = r_j(), r_v(), r_rr()
        X3a = fp2_sub(fp2_sub(rr, j), fp2_scale(v, 2))

        q7 = _MulQueue()
        r_rv = q7.fp2(r_vec, fp2_sub(v, X3a))
        r_yj = q7.fp2(s1, j)           # S1·J
        if zq is None:
            zph = fp2_add(Z3, H)
            r_zph2 = q7.fp2(zph, zph)
        q7.run()
        Y3a = fp2_sub(r_rv(), fp2_scale(r_yj(), 2))
        if zq is None:
            Z3a = fp2_sub(fp2_sub(r_zph2(), zz2), hh)
        else:
            Z3a = fp2_scale(r_z3ah(), 2)   # 2·Z3·Zq·H

        f = _select(bit, f_add, f_dbl)
        X, Y, Z = _select(bit, (X3a, Y3a, Z3a), (X3, Y3, Z3))
        return (f, X, Y, Z), None

    (f, X, Y, Z), _ = jax.lax.scan(
        step, (f, X, Y, Z), jnp.asarray(_X_BITS))
    # x < 0 for BLS12-381: conjugate
    return fp12_conj(f)


def reduce_product(f, mask):
    """Tree-reduce lane Fq12 values to one product; masked lanes -> 1.

    f: batched Fq12 pytree over leading dim N (a power of two);
    mask: bool[N] (True = real lane)."""
    n = mask.shape[0]
    ones = _ones_like_fp12((n,))
    f = jax.tree_util.tree_map(
        lambda x, o: jnp.where(mask[:, None], x, o), f, ones)
    # pad to a power of two with identity lanes (callers may pass n+1
    # lanes, e.g. the (-g1, Σ r·sig) lane appended to a pow2 batch)
    pow2 = 1 << max(n - 1, 0).bit_length()
    if pow2 != n:
        pad_ones = _ones_like_fp12((pow2 - n,))
        f = jax.tree_util.tree_map(
            lambda x, o: jnp.concatenate([x, o]), f, pad_ones)
        n = pow2
    while n > 1:
        n //= 2
        lo = jax.tree_util.tree_map(lambda x: x[:n], f)
        hi = jax.tree_util.tree_map(lambda x: x[n:], f)
        # queue the whole level's Fq12 product into ONE stacked mont_mul
        # (an inline fp12_mul instantiates 54 — trace-size poison)
        q = _MulQueue()
        r = q.fp12(lo, hi)
        q.run()
        f = r()
    return f


# --- one queued Fq12 product, and the shared Fq2 constant conversion ---------

def _fp12_mul_q(x, y):
    q = _MulQueue()
    r = q.fp12(x, y)
    q.run()
    return r()


def fq2_const_limbs(v) -> tuple:
    """Host Fq2 -> single-row Montgomery limb pair (the one conversion
    shared by every device-constant site; keep limb layout changes here)."""
    with jax.ensure_compile_time_eval():
        return (jnp.asarray(bi.to_mont(v.a)[None, :], jnp.uint32),
                jnp.asarray(bi.to_mont(v.b)[None, :], jnp.uint32))


# --- host boundary ----------------------------------------------------------

def fq12_to_device(f) -> tuple:
    """Python Fq12 -> single-lane device Fq12 pytree (Montgomery limbs)."""
    def fq6(x):
        return (fq2_const_limbs(x.c0), fq2_const_limbs(x.c1),
                fq2_const_limbs(x.c2))

    return (fq6(f.c0), fq6(f.c1))


def fq12_from_device(f) -> "object":
    """Batched (or single) device Fq12 pytree -> python Fq12 (lane 0)."""
    from lighthouse_tpu.crypto.bls.fields import Fq2, Fq6, Fq12

    def fp(x):
        v = bi.from_mont(np.asarray(x)[0] if np.asarray(x).ndim == 2 else np.asarray(x))
        return int(v)

    def fq2(x):
        return Fq2(fp(x[0]), fp(x[1]))

    def fq6(x):
        return Fq6(fq2(x[0]), fq2(x[1]), fq2(x[2]))

    return Fq12(fq6(f[0]), fq6(f[1]))


def points_to_device(pairs):
    """[(G1 affine ints, G2 affine Fq2)] -> six uint32[N, 27] arrays.

    Infinity entries are replaced by generator points and must be masked
    out by the caller (their Miller value is garbage)."""
    from lighthouse_tpu.crypto.bls import curve as cv

    n = len(pairs)
    cols = [np.empty((n, bi.L), np.uint32) for _ in range(6)]
    mask = np.ones(n, bool)
    for i, (p, q) in enumerate(pairs):
        if p is cv.INF or q is cv.INF:
            mask[i] = False
            p, q = cv.g1_generator(), cv.g2_generator()
        cols[0][i] = bi.to_mont(p[0])
        cols[1][i] = bi.to_mont(p[1])
        cols[2][i] = bi.to_mont(q[0].a)
        cols[3][i] = bi.to_mont(q[0].b)
        cols[4][i] = bi.to_mont(q[1].a)
        cols[5][i] = bi.to_mont(q[1].b)
    return cols, mask


_JIT_CACHE: dict[int, object] = {}


def _miller_reduce_jit(n: int):
    if n not in _JIT_CACHE:
        def run(xp, yp, xqa, xqb, yqa, yqb, mask):
            f = batch_miller_loop(xp, yp, xqa, xqb, yqa, yqb)
            return reduce_product(f, mask)

        _JIT_CACHE[n] = jax.jit(run)
        _JIT_CACHE[n] = _dtel.instrument(
            "ops/bls12_381.py::_miller_reduce_jit@run", _JIT_CACHE[n])
    return _JIT_CACHE[n]


def multi_pairing_device(pairs) -> "object":
    """Device multi-pairing: prod Miller(P_i, Q_i), final exp on host.

    Returns a python Fq12 (compare with .is_one()).  Lane count is padded
    to the next power of two (padded/infinity lanes masked to 1)."""
    from lighthouse_tpu.crypto.bls.fields import final_exponentiation_fast

    cols, mask = points_to_device(pairs)
    n = len(pairs)
    # floor of 4 lanes so small batches share one compiled program
    padded = max(4, 1 << max(n - 1, 0).bit_length())
    if padded != n:
        cols = [np.concatenate([c, np.tile(c[-1:], (padded - n, 1))])
                for c in cols]
        mask = np.concatenate([mask, np.zeros(padded - n, bool)])
    fn = _miller_reduce_jit(padded)
    f = fn(*[jnp.asarray(c) for c in cols], jnp.asarray(mask))
    f_host = fq12_from_device(jax.device_get(f))
    try:
        from lighthouse_tpu.ops import native_bls
        if native_bls.available():
            return native_bls.final_exp(f_host)
    except Exception as e:
        from lighthouse_tpu.common.metrics import record_swallowed

        record_swallowed("bls12_381.native_final_exp", e)
    return final_exponentiation_fast(f_host)
