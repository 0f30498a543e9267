"""Batched elliptic-curve ops on TPU: scalar mults and point sums (jnp).

The round-1 "tpu" BLS backend still did per-set host work in pure Python —
g1_mul/g2_mul at ~1-4 ms per 64-bit scalar made the 10x target unreachable
(VERDICT.md weak #5).  This module moves that work onto the device:

- `_scalar_mul_batch`: lane i computes r_i · P_i by MSB-first
  double-and-add over the scalar's bit planes, one `lax.scan` with a
  mul-queue body (7 stacked mont_muls per step) — the same
  uniform-control-flow pattern as the Miller loop.  The subgroup checks
  run it; the blinding scalars take the windowed scan below it.
- `g2_sum_reduce`: tree-reduction of G2 Jacobian lanes to one point
  (Σ r_i·sig_i), full Jacobian adds, log2(N) levels.

Representation: Jacobian (X, Y, Z) over redundant Montgomery limb lanes
(ops/bigint.py); infinity is Z == 0 with EXACT zero limbs (products keep
exact zeros, so the infinity flag survives doubling; the mixed-add select
handles the accumulator-is-infinity case — the only degenerate case a
<2^64-scalar double-and-add can hit, since m·P = ±P requires m ≡ ±1 mod r).

Degenerate H == 0 chords in `g2_sum_reduce` (colliding partial sums) are
cryptographically unreachable for honest-random 64-bit blinding scalars
(~n²/2^64); a freak hit yields a wrong product, a failed batch, and the
caller's bisection fallback — correctness is preserved by construction.

Counterpart of blst's scalar-mult core consumed via
/root/reference/crypto/bls/src/impls/blst.rs:37-119 (r·sig / r·agg_pk
blinding in verify_multiple_aggregate_signatures).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from lighthouse_tpu.ops import bigint as bi
from lighthouse_tpu.ops.bls12_381 import (
    _MulQueue,
    fp2_add,
    fp2_scale,
    fp2_sub,
)


# --- field adapters ---------------------------------------------------------
#
# The Jacobian formulas below are written once against this tiny protocol;
# G1 instantiates it over Fp lanes (uint32[N, 27]), G2 over Fq2 pairs, and
# the G1 fold and membership scan over limb-major Fp lanes (uint32[27, N]).
# `lane_axis` says where an array's lanes are, for the formulas that
# concatenate, split, tile or mask along them.

class _FpAdapter:
    lane_axis = 0

    @staticmethod
    def mul(q: _MulQueue, x, y):
        i = q.fp(x, y)
        return lambda: q[i]

    add = staticmethod(bi.add)
    sub = staticmethod(bi.sub)
    scale = staticmethod(bi.scale_small)

    @staticmethod
    def is_zero(x):
        return jnp.all(x == 0, axis=-1)

    @staticmethod
    def select(cond, a, b):
        return jnp.where(cond[..., None], a, b)

    @staticmethod
    def zeros_like(x):
        return jnp.zeros_like(x)

    @staticmethod
    def one_like(x):
        return jnp.broadcast_to(bi._jconst("one_m"), x.shape)


class _FpLmAdapter:
    """Fp over limb-major lanes uint32[27, N], on `MontField.mont_mul_lm`
    (ops/bigint.py: the partial products stay in the core): a round's
    products go side by side along the lanes into one launch."""

    lane_axis = 1

    @staticmethod
    def mul(q: _MulQueue, x, y):
        return q.fp_lm(x, y)

    add = staticmethod(bi.FP.add_lm)
    sub = staticmethod(bi.FP.sub_lm)
    scale = staticmethod(bi.FP.scale_small_lm)

    @staticmethod
    def is_zero(x):
        return jnp.all(x == 0, axis=0)

    @staticmethod
    def select(cond, a, b):
        return jnp.where(cond[None], a, b)

    @staticmethod
    def zeros_like(x):
        return jnp.zeros_like(x)

    @staticmethod
    def one_like(x):
        return jnp.broadcast_to(bi._jconst("one_m")[:, None], x.shape)


class _Fq2Adapter:
    lane_axis = 0

    @staticmethod
    def mul(q: _MulQueue, x, y):
        return q.fp2(x, y)

    add = staticmethod(fp2_add)
    sub = staticmethod(fp2_sub)
    scale = staticmethod(fp2_scale)

    @staticmethod
    def is_zero(x):
        return jnp.all(x[0] == 0, axis=-1) & jnp.all(x[1] == 0, axis=-1)

    @staticmethod
    def select(cond, a, b):
        c = cond[..., None]
        return (jnp.where(c, a[0], b[0]), jnp.where(c, a[1], b[1]))

    @staticmethod
    def zeros_like(x):
        return (jnp.zeros_like(x[0]), jnp.zeros_like(x[1]))

    @staticmethod
    def one_like(x):
        one = jnp.broadcast_to(bi._jconst("one_m"), x[0].shape)
        return (one, jnp.zeros_like(x[1]))


def _dbl_add_step(F, X, Y, Z, inf, xb, yb, bit):
    """One double-and-add step: (2T) and (2T + B), select by `bit`.

    6 dependency rounds, each one stacked mont_mul.  Double: 2007
    Bernstein-Lange a=0 Jacobian doubling; add: mixed Jacobian+affine,
    complete w.r.t. T = infinity, exactly like the host oracle curve.py
    _jac_double/_jac_add.  Infinity is an EXPLICIT per-lane flag `inf`
    (testing Z's limbs cannot work: the redundant representation renders
    value-zero as a nonzero multiple of P after any subtraction)."""
    q1 = _MulQueue()
    r_xx = F.mul(q1, X, X)
    r_yy = F.mul(q1, Y, Y)
    r_yz = F.mul(q1, Y, Z)
    q1.run()
    xx, yy, yz = r_xx(), r_yy(), r_yz()
    E = F.scale(xx, 3)
    Z3 = F.scale(yz, 2)

    q2 = _MulQueue()
    r_c4 = F.mul(q2, yy, yy)
    xb_ = F.add(X, yy)
    r_t = F.mul(q2, xb_, xb_)
    r_ff = F.mul(q2, E, E)
    r_zz = F.mul(q2, Z3, Z3)
    q2.run()
    c4, t, ff, zz = r_c4(), r_t(), r_ff(), r_zz()
    D = F.scale(F.sub(F.sub(t, xx), c4), 2)
    X3 = F.sub(ff, F.scale(D, 2))

    q3 = _MulQueue()
    r_ey = F.mul(q3, E, F.sub(D, X3))
    r_u2 = F.mul(q3, xb, zz)
    r_zzz = F.mul(q3, Z3, zz)
    q3.run()
    Y3 = F.sub(r_ey(), F.scale(c4, 8))
    u2, zzz = r_u2(), r_zzz()
    H = F.sub(u2, X3)
    # (X3, Y3, Z3) = 2T done; now mixed-add the affine base point

    q4 = _MulQueue()
    r_s2 = F.mul(q4, yb, zzz)
    r_hh = F.mul(q4, H, H)
    q4.run()
    s2, hh = r_s2(), r_hh()
    rv = F.scale(F.sub(s2, Y3), 2)

    q5 = _MulQueue()
    r_rr = F.mul(q5, rv, rv)
    r_j = F.mul(q5, H, hh)
    r_v = F.mul(q5, X3, hh)
    zph = F.add(Z3, H)
    r_zph2 = F.mul(q5, zph, zph)
    q5.run()
    rr, j, v, zph2 = r_rr(), r_j(), r_v(), r_zph2()
    J = F.scale(j, 4)
    V = F.scale(v, 4)
    X3a = F.sub(F.sub(rr, J), F.scale(V, 2))

    q6 = _MulQueue()
    r_ry = F.mul(q6, rv, F.sub(V, X3a))
    r_yj = F.mul(q6, Y3, j)
    q6.run()
    Y3a = F.sub(r_ry(), F.scale(r_yj(), 8))
    Z3a = F.sub(F.sub(zph2, zz), hh)

    # T infinity -> add result is the affine base itself (2*INF + B = B)
    Xa = F.select(inf, xb, X3a)
    Ya = F.select(inf, yb, Y3a)
    Za = F.select(inf, F.one_like(Z3), Z3a)

    # select add vs double by the scalar bit
    b = bit != 0
    Xn = F.select(b, Xa, X3)
    Yn = F.select(b, Ya, Y3)
    Zn = F.select(b, Za, Z3)
    inf_n = inf & ~b  # leaves infinity exactly when a set bit adds the base
    return Xn, Yn, Zn, inf_n


def _scalar_mul_batch(F, xb, yb, bits):
    """MSB-first double-and-add scan: bits uint32[64, ...] per lane.

    All-zero-bit lanes (padding) come back as infinity with EXACT zero
    limbs, the form g2_sum_reduce's identity detection requires."""
    X = F.zeros_like(xb)
    Y = F.zeros_like(yb)
    Z = F.zeros_like(xb)  # Z = 0: infinity
    inf = jnp.ones(bits.shape[1:], bool)

    def step(carry, bit):
        X, Y, Z, inf = carry
        return _dbl_add_step(F, X, Y, Z, inf, xb, yb, bit), None

    (X, Y, Z, inf), _ = jax.lax.scan(step, (X, Y, Z, inf), bits)
    # canonicalize still-infinity lanes to exact zeros
    zero = F.zeros_like(xb)
    X = F.select(inf, zero, X)
    Y = F.select(inf, zero, Y)
    Z = F.select(inf, zero, Z)
    return X, Y, Z


# --- merged windowed scalar mul (the fused pipeline's production path) ------
#
# The binary double-and-add scan above runs 6 mul rounds per scalar bit
# per group; the blinded batch-verify scalars drive BOTH a G1 lane set
# (r·agg_pk) and a G2 lane set (r·sig) with the SAME scalars, so the
# production path (a) processes 4 bits per step from a 16-entry Jacobian
# table (4 cheap doublings + 1 table add ≈ 40% fewer field products) and
# (b) runs the two groups through SHARED mul-queue rounds, halving the
# sequential round count again.  The binary scan stays for the subgroup
# checks, whose fail-closed behaviour on adversarial points is pinned to
# its formulas (g2_subgroup_check_batch docstring).


def _jac_double_multi(items):
    """One Jacobian doubling (2007 Bernstein–Lange a=0) per (F, (X,Y,Z))
    item, all tracks sharing the 3 mul-queue rounds.  Z == 0 lanes keep
    an EXACT-zero Z (Y·Z products stay exact zeros), so infinity flows
    through scan steps without an explicit flag."""
    q1 = _MulQueue()
    rs1 = [(F.mul(q1, X, X), F.mul(q1, Y, Y), F.mul(q1, Y, Z))
           for F, (X, Y, Z) in items]
    q1.run()
    mids = []
    q2 = _MulQueue()
    for (F, (X, Y, Z)), (r_xx, r_yy, r_yz) in zip(items, rs1):
        xx, yy, yz = r_xx(), r_yy(), r_yz()
        E = F.scale(xx, 3)
        Z3 = F.scale(yz, 2)
        xb = F.add(X, yy)
        mids.append((F, xx, yy, E, Z3,
                     F.mul(q2, yy, yy), F.mul(q2, xb, xb),
                     F.mul(q2, E, E)))
    q2.run()
    outs = []
    q3 = _MulQueue()
    for F, xx, yy, E, Z3, r_c4, r_t, r_ff in mids:
        c4, t, ff = r_c4(), r_t(), r_ff()
        D = F.scale(F.sub(F.sub(t, xx), c4), 2)
        X3 = F.sub(ff, F.scale(D, 2))
        outs.append((F, X3, Z3, c4, F.mul(q3, E, F.sub(D, X3))))
    q3.run()
    return [(X3, F.sub(r_ey(), F.scale(c4, 8)), Z3)
            for F, X3, Z3, c4, r_ey in outs]


def _jac_add_full_multi(items, infs=None):
    """_jac_add_full for several (F, p, q) tracks over shared queues.

    ``infs``: optional per-item (p_inf, q_inf) bool lanes REPLACING the
    Z exact-zero probes.  The windowed scan needs this: over Fq2 a
    doubling of an infinity lane runs fp2_mul, whose internal
    subtractions render the value-zero Z as a nonzero multiple of P —
    exact-zero testing only works when infinity provably flows through
    plain mont_muls (see _scalar_mul_batch's explicit-flag note)."""
    q = _MulQueue()
    rs = [(F.mul(q, p[2], p[2]), F.mul(q, q2_[2], q2_[2]))
          for F, p, q2_ in items]
    q.run()
    st1 = []
    q = _MulQueue()
    for (F, p, q2_), (r_z11, r_z22) in zip(items, rs):
        z11, z22 = r_z11(), r_z22()
        zs = F.add(p[2], q2_[2])
        st1.append((F, p, q2_, z11, z22,
                    F.mul(q, p[0], z22), F.mul(q, q2_[0], z11),
                    F.mul(q, p[2], z11), F.mul(q, q2_[2], z22),
                    F.mul(q, zs, zs)))
    q.run()
    st2 = []
    q = _MulQueue()
    for F, p, q2_, z11, z22, r_u1, r_u2, r_z1c, r_z2c, r_zz12 in st1:
        u1, u2 = r_u1(), r_u2()
        z1c, z2c, zz12 = r_z1c(), r_z2c(), r_zz12()
        h = F.sub(u2, u1)
        st2.append((F, p, q2_, z11, z22, u1, u2, h, zz12,
                    F.mul(q, p[1], z2c), F.mul(q, q2_[1], z1c),
                    F.mul(q, h, h)))
    q.run()
    st3 = []
    q = _MulQueue()
    for F, p, q2_, z11, z22, u1, u2, h, zz12, r_s1, r_s2, r_hh in st2:
        s1, s2, hh = r_s1(), r_s2(), r_hh()
        rv = F.scale(F.sub(s2, s1), 2)
        i4 = F.scale(hh, 4)
        zmul = F.sub(F.sub(zz12, z11), z22)
        st3.append((F, p, q2_, s1, rv,
                    F.mul(q, h, i4), F.mul(q, u1, i4),
                    F.mul(q, rv, rv), F.mul(q, zmul, h)))
    q.run()
    st4 = []
    q = _MulQueue()
    for F, p, q2_, s1, rv, r_j, r_v, r_rr, r_z3 in st3:
        j, v, rr, Z3 = r_j(), r_v(), r_rr(), r_z3()
        X3 = F.sub(F.sub(rr, j), F.scale(v, 2))
        st4.append((F, p, q2_, X3, Z3,
                    F.mul(q, rv, F.sub(v, X3)), F.mul(q, s1, j)))
    q.run()
    outs = []
    for i, (F, p, q2_, X3, Z3, r_ry, r_sj) in enumerate(st4):
        Y3 = F.sub(r_ry(), F.scale(r_sj(), 2))
        if infs is not None:
            p_inf, q_inf = infs[i]
        else:
            p_inf = F.is_zero(p[2])
            q_inf = F.is_zero(q2_[2])
        X3 = F.select(p_inf, q2_[0], F.select(q_inf, p[0], X3))
        Y3 = F.select(p_inf, q2_[1], F.select(q_inf, p[1], Y3))
        Z3 = F.select(p_inf, q2_[2], F.select(q_inf, p[2], Z3))
        outs.append((X3, Y3, Z3))
    return outs


def _window_tables(bases, width: int = 4):
    """Per-track Jacobian tables [0·P .. (2^w-1)·P], built level by level
    (double all existing entries, add the base) with all tracks stacked
    through shared queues — ~24 mul rounds total.

    bases: [(F, (xb, yb))].  Returns per track a (X, Y, Z) tuple whose
    leaves are [2^w, N, L] stacks ([2^w, L, N] on the limb-major track;
    Fq2 leaves are pairs of stacks)."""
    n_entries = 1 << width

    def cat(F, entries, coord):
        if F in _PAIRS:
            return tuple(jnp.concatenate([e[coord][i] for e in entries],
                                         F.lane_axis) for i in (0, 1))
        return jnp.concatenate([e[coord] for e in entries], F.lane_axis)

    def split(F, arr, count):
        if F in _PAIRS:
            a0 = jnp.split(arr[0], count, F.lane_axis)
            a1 = jnp.split(arr[1], count, F.lane_axis)
            return list(zip(a0, a1))
        return jnp.split(arr, count, F.lane_axis)

    tabs = []
    for F, (xb, yb) in bases:
        inf = (F.zeros_like(xb), F.zeros_like(yb), F.zeros_like(xb))
        tabs.append([inf, (xb, yb, F.one_like(xb))])
    level = 0
    while len(tabs[0]) < n_entries:
        lo = 1 << level
        count = lo
        items = []
        for (F, _), tab in zip(bases, tabs):
            ent = tab[lo:lo + count]
            items.append((F, (cat(F, ent, 0), cat(F, ent, 1),
                              cat(F, ent, 2))))
        doubles = _jac_double_multi(items)
        add_items = []
        for (F, (xb, yb)), dbl in zip(bases, doubles):
            if F in _PAIRS:
                base_j = ((jnp.tile(xb[0], _reps(F, count)),
                           jnp.tile(xb[1], _reps(F, count))),
                          (jnp.tile(yb[0], _reps(F, count)),
                           jnp.tile(yb[1], _reps(F, count))),
                          F.one_like((jnp.tile(xb[0], _reps(F, count)),
                                      jnp.tile(xb[1], _reps(F, count)))))
            else:
                reps = (count, 1) if F.lane_axis == 0 else (1, count)
                base_j = (jnp.tile(xb, reps), jnp.tile(yb, reps),
                          F.one_like(jnp.tile(xb, reps)))
            add_items.append((F, dbl, base_j))
        odds = _jac_add_full_multi(add_items)
        for (F, _), tab, dbl, odd in zip(bases, tabs, doubles, odds):
            dbl_s = split(F, dbl[0], count), split(F, dbl[1], count), \
                split(F, dbl[2], count)
            odd_s = split(F, odd[0], count), split(F, odd[1], count), \
                split(F, odd[2], count)
            for k in range(count):
                tab.append((dbl_s[0][k], dbl_s[1][k], dbl_s[2][k]))
                tab.append((odd_s[0][k], odd_s[1][k], odd_s[2][k]))
        # append order per k is (2·(lo+k), 2·(lo+k)+1) = tab indices
        # (2lo+2k, 2lo+2k+1): list index == multiple by construction
        level += 1
    out = []
    for (F, _), tab in zip(bases, tabs):
        if F in _PAIRS:
            stack = lambda c: (jnp.stack([e[c][0] for e in tab]),  # noqa: E731
                               jnp.stack([e[c][1] for e in tab]))
        else:
            stack = lambda c: jnp.stack([e[c] for e in tab])  # noqa: E731
        out.append((stack(0), stack(1), stack(2)))
    return out


def _table_pick(F, tab, digit):
    """Per-lane table pick: tab leaves [2^w, N, L] ([2^w, L, N] on the
    limb-major track), digit uint32[N].

    One-hot select chain instead of a dynamic gather: XLA:CPU's AOT
    serializer (the persistent compile-cache writer) segfaults on
    executables containing the gather (jax 0.9.0,
    compilation_cache.put_executable_and_time), and 15 masked selects
    over [N, L] rows are noise next to the field products anyway."""
    def g(arr):
        out = arr[0]
        for d in range(1, arr.shape[0]):
            out = jnp.where(jnp.expand_dims(digit == d, 1 - F.lane_axis),
                            arr[d], out)
        return out

    def pick(coord):
        return (g(coord[0]), g(coord[1])) if F in _PAIRS else g(coord)

    return (pick(tab[0]), pick(tab[1]), pick(tab[2]))


def g1_scalar_mul_windowed(xp, yp, digits):
    """Single-track windowed scalar mul over G1 lanes (the MSM's form:
    arbitrary-width scalars as [W, N] window digits), LIMB-MAJOR: xp, yp
    and the Jacobian (X, Y, Z) it returns are uint32[27, N].  Same
    table/flag machinery as the merged scan, on `_FpLmAdapter`."""
    F1 = _FpLmAdapter
    (tab1,) = _window_tables([(F1, (xp, yp))])
    s1 = (F1.zeros_like(xp), F1.zeros_like(yp), F1.zeros_like(xp))
    inf = jnp.ones(digits.shape[1:], bool)

    def step(carry, digit):
        t1, inf = carry
        for _ in range(4):
            (t1,) = _jac_double_multi([(F1, t1)])
        p1 = _table_pick(F1, tab1, digit)
        pick_inf = digit == 0
        (t1,) = _jac_add_full_multi([(F1, t1, p1)],
                                    infs=[(inf, pick_inf)])
        return (t1, inf & pick_inf), None

    (s1, inf), _ = jax.lax.scan(step, (s1, inf), digits)
    zero = F1.zeros_like(s1[0])
    return tuple(F1.select(inf, zero, c) for c in s1)


def gj_scalar_mul_windowed(xp, yp, xq, yq, digits, lm=False):
    """r_i·P_i (G1) and r_i·Q_i (G2) in ONE windowed scan.

    xp, yp: uint32[N, L] G1 affine; xq, yq: Fq2 limb pairs; digits:
    uint32[W, N] MSB-first base-16 window digits of the shared scalars
    (ec.scalars_to_digits); ``lm``: every array limb-major, uint32[L, N],
    on `_FpLmAdapter` and `_Fq2LmAdapter`.  Returns (G1 Jacobian, G2
    Jacobian); zero-scalar lanes come back as exact-zero-limb infinity.
    Collision (H == 0) chords carry the same honest-random-blinding
    contract as the binary scan — do NOT feed adversarial scalars."""
    F1, F2 = _LM_TRACKS if lm else (_FpAdapter, _Fq2Adapter)
    tab1, tab2 = _window_tables([(F1, (xp, yp)), (F2, ((xq[0], xq[1]),
                                                       (yq[0], yq[1])))])

    s1 = (F1.zeros_like(xp), F1.zeros_like(yp), F1.zeros_like(xp))
    zq = (jnp.zeros_like(xq[0]), jnp.zeros_like(xq[1]))
    s2 = (zq, zq, zq)
    # EXPLICIT infinity flag shared by both tracks (same scalars):
    # fp2_mul's internal subtractions destroy exact-zero Z limbs on the
    # Fq2 track, so Z probing cannot detect accumulator infinity here
    inf = jnp.ones(digits.shape[1:], bool)

    def step(carry, digit):
        t1, t2, inf = carry
        for _ in range(4):
            t1, t2 = _jac_double_multi([(F1, t1), (F2, t2)])
        p1 = _table_pick(F1, tab1, digit)
        p2 = _table_pick(F2, tab2, digit)
        pick_inf = digit == 0          # entry 0 is the only INF entry
        t1, t2 = _jac_add_full_multi(
            [(F1, t1, p1), (F2, t2, p2)],
            infs=[(inf, pick_inf), (inf, pick_inf)])
        return (t1, t2, inf & pick_inf), None

    (s1, s2, inf), _ = jax.lax.scan(step, (s1, s2, inf), digits)
    # canonicalize never-added lanes to exact-zero limbs (the
    # g2_sum_reduce identity form)
    out = []
    for F, s in ((F1, s1), (F2, s2)):
        zero = F.zeros_like(s[0])
        out.append(tuple(F.select(inf, zero, c) for c in s))
    return out[0], out[1]


def _jac_add_full(F, p, q2_):
    """Full Jacobian add, complete w.r.t. either side = infinity.
    (H == 0 degenerate chords excluded by the caller's contract.)"""
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q2_
    q = _MulQueue()
    r_z11 = F.mul(q, Z1, Z1)
    r_z22 = F.mul(q, Z2, Z2)
    q.run()
    z11, z22 = r_z11(), r_z22()

    q = _MulQueue()
    r_u1 = F.mul(q, X1, z22)
    r_u2 = F.mul(q, X2, z11)
    r_z1c = F.mul(q, Z1, z11)   # Z1^3
    r_z2c = F.mul(q, Z2, z22)   # Z2^3
    zs = F.add(Z1, Z2)
    r_zz12 = F.mul(q, zs, zs)
    q.run()
    u1, u2 = r_u1(), r_u2()
    z1c, z2c, zz12 = r_z1c(), r_z2c(), r_zz12()

    q = _MulQueue()
    r_s1 = F.mul(q, Y1, z2c)
    r_s2 = F.mul(q, Y2, z1c)
    h = F.sub(u2, u1)
    r_hh = F.mul(q, h, h)
    q.run()
    s1, s2, hh = r_s1(), r_s2(), r_hh()
    rv = F.scale(F.sub(s2, s1), 2)
    i4 = F.scale(hh, 4)

    q = _MulQueue()
    r_j = F.mul(q, h, i4)
    r_v = F.mul(q, u1, i4)
    r_rr = F.mul(q, rv, rv)
    zmul = F.sub(F.sub(zz12, z11), z22)
    r_z3 = F.mul(q, zmul, h)
    q.run()
    j, v, rr, Z3 = r_j(), r_v(), r_rr(), r_z3()
    X3 = F.sub(F.sub(rr, j), F.scale(v, 2))

    q = _MulQueue()
    r_ry = F.mul(q, rv, F.sub(v, X3))
    r_sj = F.mul(q, s1, j)
    q.run()
    Y3 = F.sub(r_ry(), F.scale(r_sj(), 2))

    p_inf = F.is_zero(Z1)
    q_inf = F.is_zero(Z2)
    X3 = F.select(p_inf, X2, F.select(q_inf, X1, X3))
    Y3 = F.select(p_inf, Y2, F.select(q_inf, Y1, Y3))
    Z3 = F.select(p_inf, Z2, F.select(q_inf, Z1, Z3))
    return X3, Y3, Z3


def _sum_reduce(F, take, X, Y, Z, n):
    assert n & (n - 1) == 0
    while n > 1:
        n //= 2
        lo = (take(X, slice(0, n)), take(Y, slice(0, n)), take(Z, slice(0, n)))
        hi = (take(X, slice(n, 2 * n)), take(Y, slice(n, 2 * n)),
              take(Z, slice(n, 2 * n)))
        X, Y, Z = _jac_add_full(F, lo, hi)
    return X, Y, Z


def g2_sum_reduce(X, Y, Z):
    """Tree-reduce G2 Jacobian lanes to one point: Σ lanes (infinity lanes
    are identity).  Leading dim must be a power of two."""
    take = lambda t, sl: (t[0][sl], t[1][sl])  # noqa: E731
    return _sum_reduce(_Fq2Adapter, take, X, Y, Z, X[0].shape[0])


def g1_segment_sum(X, Y, Z, n_segments: int):
    """Segmented Jacobian tree-sum: lanes laid out s-major ([S*G] with
    lane index s·G + g) reduce to one point per segment g.

    The enabler for message-grouped batch verification: sets sharing a
    message fold into Σ r_i·pk_i BEFORE the Miller loop
    (e(Σ r_i·pk_i, H(m)) = Π e(r_i·pk_i, H(m))), shrinking the pairing
    lane count from n sets to G distinct messages."""
    total = X.shape[0]
    assert total % n_segments == 0
    S = total // n_segments
    assert S & (S - 1) == 0, "segment size must be a power of two"
    shape = (S, n_segments, bi.L)
    Xr, Yr, Zr = (t.reshape(shape) for t in (X, Y, Z))
    take = lambda t, sl: t[sl]  # noqa: E731
    Xo, Yo, Zo = _sum_reduce(_FpAdapter, take, Xr, Yr, Zr, S)
    return Xo[0], Yo[0], Zo[0]


def g1_segment_sum_lm(X, Y, Z, n_segments: int):
    """`g1_segment_sum` for limb-major lanes uint32[27, S*G] (s-major as
    there: the first half of the lanes is the first half of every
    segment) -> (X, Y, Z) uint32[27, G]."""
    total = X.shape[1]
    assert total % n_segments == 0
    S = total // n_segments
    assert S & (S - 1) == 0, "segment size must be a power of two"
    take = lambda t, sl: t[:, sl.start * n_segments:  # noqa: E731
                           sl.stop * n_segments]
    return _sum_reduce(_FpLmAdapter, take, X, Y, Z, S)


def g1_msm_windowed(xp, yp, digits):
    """Multi-scalar multiplication Σ k_i·P_i over G1 lanes uint32[N, 27],
    from window digits ([W, N] from scalars_to_digits): ~40% fewer
    products and ~1.4x fewer sequential rounds than a binary scan for the
    KZG MSM's 255-bit scalars.  Returns one Jacobian point, rows
    uint32[1, 27]; inside, the limb-major scan and segment sum."""
    X, Y, Z = g1_scalar_mul_windowed(xp.T, yp.T, digits)
    return tuple(c.T for c in g1_segment_sum_lm(X, Y, Z, 1))


# --- batched G2 subgroup check (ψ test) -------------------------------------
#
# ψ(Q) == [x]Q characterizes G2 membership on E'(Fq2) (Scott 2021; the
# host oracle/fast pair lives in crypto/bls/curve.py).  On device the
# 64-bit |x| scalar mul is one fixed-bit _scalar_mul_batch scan shared by
# every lane — ~4x cheaper than a [r]Q check and batched over all fresh
# signatures of a verify call (the 14 ms/signature host check was the
# flood-path killer, round-3 ledger).

import functools as _functools


@_functools.cache
def _psi_const_limbs():
    from lighthouse_tpu.crypto.bls import curve as cv
    from lighthouse_tpu.ops.bls12_381 import fq2_const_limbs

    return (fq2_const_limbs(cv.PSI_CX), fq2_const_limbs(cv.PSI_CY))


@_functools.cache
def _x_bits_const():
    from lighthouse_tpu.crypto.bls.fields import BLS_X

    with jax.ensure_compile_time_eval():
        return jnp.asarray(
            [[int(b)] for b in bin(BLS_X)[2:]], jnp.uint32)  # [64, 1]


def g2_psi_batch(xqa, xqb, yqa, yqb):
    """ψ per lane: (c_x·x̄, c_y·ȳ), x̄ the Frobenius conjugate."""
    cx, cy = _psi_const_limbs()
    bcast = lambda c: (jnp.broadcast_to(c[0], xqa.shape),  # noqa: E731
                       jnp.broadcast_to(c[1], xqa.shape))
    q = _MulQueue()
    r_x = q.fp2((xqa, bi.neg(xqb)), bcast(cx))
    r_y = q.fp2((yqa, bi.neg(yqb)), bcast(cy))
    q.run()
    return r_x(), r_y()


def g2_subgroup_check_batch(xqa, xqb, yqa, yqb):
    """Device half of the batched ψ membership test.

    Inputs: affine G2 lanes (on-curve already guaranteed by
    decompression).  Computes S = [|x|]Q and ψ(Q), and returns the
    Jacobian-vs-affine equality residues for ψ(Q) == -S (x is negative):

        d1 = x_ψ·Z_S² - X_S,   d2 = y_ψ·Z_S³ + Y_S,   Z_S

    each an Fq2 limb pair.  A lane is in G2 iff d1 ≡ d2 ≡ 0 (mod P) and
    Z_S ≢ 0 — the host finishes with is_zero_mod_p (redundant limbs can't
    be zero-tested on device).

    Fail-closed invariant (adversarial inputs!): unlike the blinded-scalar
    callers, these lanes are attacker-chosen twist points, so the
    degenerate H == 0 addition chord IS reachable (a small-order point
    whose order divides m±1 for a bit-prefix m of |x|).  The chord then
    produces Z ≡ 0 (mod P), and Z ≡ 0 propagates through every later
    double/add step, so such lanes land in the Z_S ≡ 0 reject branch —
    they can never false-accept.  tests/test_ec.py pins this with a
    small-order cofactor point; keep that property if _dbl_add_step is
    ever refactored."""
    bits = jnp.broadcast_to(_x_bits_const(), (64, xqa.shape[0]))
    X, Y, Z = _scalar_mul_batch(_Fq2Adapter, (xqa, xqb), (yqa, yqb), bits)
    px, py = g2_psi_batch(xqa, xqb, yqa, yqb)

    q = _MulQueue()
    r_z2 = q.fp2(Z, Z)
    q.run()
    z2 = r_z2()
    q = _MulQueue()
    r_xz = q.fp2(px, z2)
    r_z3 = q.fp2(z2, Z)
    q.run()
    xz, z3 = r_xz(), r_z3()
    q = _MulQueue()
    r_yz = q.fp2(py, z3)
    q.run()
    d1 = fp2_sub(xz, X)
    d2 = fp2_add(r_yz(), Y)
    return d1, d2, Z


def _fq2_zero_mod_p(c) -> jax.Array:
    return bi.is_zero_mod_p_device(c[0]) & bi.is_zero_mod_p_device(c[1])


def g2_subgroup_verdict_batch(xqa, xqb, yqa, yqb) -> jax.Array:
    """Full ψ membership verdict per lane, ON DEVICE -> bool[n].

    Folds the residue zero-tests (bi.is_zero_mod_p_device) into the same
    program as g2_subgroup_check_batch so callers fetch one bool row
    instead of six Fq limb rows."""
    d1, d2, Z = g2_subgroup_check_batch(xqa, xqb, yqa, yqb)
    return (_fq2_zero_mod_p(d1) & _fq2_zero_mod_p(d2)
            & ~_fq2_zero_mod_p(Z))


def g1_subgroup_verdict_batch(xp, yp) -> jax.Array:
    """Device [r-1]P membership verdict per lane -> bool[n]."""
    d1, d2, Z = g1_subgroup_check_batch(xp, yp)
    return (bi.is_zero_mod_p_lm(d1) & bi.is_zero_mod_p_lm(d2)
            & ~bi.is_zero_mod_p_lm(Z))


@_functools.cache
def _p_minus_2_bits_const():
    with jax.ensure_compile_time_eval():
        return jnp.asarray(
            [[int(b)] for b in bin(bi.P_INT - 2)[2:]], jnp.uint32)


def fq_inv_batch(a):
    """Batched Fq inversion by Fermat: a^(P-2), Montgomery domain.

    One fixed-exponent square-and-multiply scan shared by all lanes
    (381 steps × 2 mont_muls); a ≡ 0 lanes produce 0 — callers that can
    meet zero must detect it separately (is_zero_mod_p on the host)."""
    bits = jnp.broadcast_to(_p_minus_2_bits_const(),
                            (_p_minus_2_bits_const().shape[0], a.shape[0]))
    one = jnp.broadcast_to(bi._jconst("one_m"), a.shape)

    def step(out, bit):
        sq = bi.mont_mul(out, out)
        withmul = bi.mont_mul(sq, a)
        return jnp.where((bit != 0)[:, None], withmul, sq), None

    out, _ = jax.lax.scan(step, one, bits)
    return out


def g1_jacobian_to_affine_batch(X, Y, Z):
    """Jacobian -> affine over G1 lanes: (X/Z², Y/Z³) via one Fermat
    inversion chain.  Z ≡ 0 (infinity) lanes come out as garbage — the
    caller tests Z on the host (is_zero_mod_p)."""
    zi = fq_inv_batch(Z)
    q = _MulQueue()
    i_zi2 = q.fp(zi, zi)
    q.run()
    zi2 = q[i_zi2]
    q = _MulQueue()
    i_x = q.fp(X, zi2)
    i_zi3 = q.fp(zi2, zi)
    q.run()
    x, zi3 = q[i_x], q[i_zi3]
    q = _MulQueue()
    i_y = q.fp(Y, zi3)
    q.run()
    return x, q[i_y]


@_functools.cache
def _r_minus_1_bits_const():
    from lighthouse_tpu.crypto.bls.fields import R

    with jax.ensure_compile_time_eval():
        return jnp.asarray(
            [[int(b)] for b in bin(R - 1)[2:]], jnp.uint32)  # [255, 1]


def g1_subgroup_check_batch(xp, yp):
    """Device half of the batched G1 membership test: [r-1]P == -P.

    For P of order r, [r-1]P = -P exactly; for a cofactor-order point d,
    (r-1) ≡ -1 (mod d) would force d | r.  Returns the residues

        d1 = x_P·Z² - X_S,   d2 = y_P·Z³ + Y_S,   Z

    for S = [r-1]P, limb-major uint32[27, N] from rows xp, yp uint32[N, 27]:
    a lane is in G1 iff d1 ≡ d2 ≡ 0 (mod P) and Z ≢ 0.  The scan and the
    residues run on `_FpLmAdapter` (a round's products in one
    `mont_mul_lm` launch).  Same fail-closed shape as
    g2_subgroup_check_batch: a small-order lane that hits the degenerate
    H == 0 chord mid-scan drives Z ≡ 0 and lands in the reject branch."""
    F, xl, yl = _FpLmAdapter, xp.T, yp.T
    bits = jnp.broadcast_to(_r_minus_1_bits_const(), (255, xp.shape[0]))
    X, Y, Z = _scalar_mul_batch(F, xl, yl, bits)
    q = _MulQueue()
    r_z2 = F.mul(q, Z, Z)
    q.run()
    z2 = r_z2()
    q = _MulQueue()
    r_xz = F.mul(q, xl, z2)
    r_z3 = F.mul(q, z2, Z)
    q.run()
    q = _MulQueue()
    r_yz = F.mul(q, yl, r_z3())
    q.run()
    d1, d2 = F.sub(r_xz(), X), F.add(r_yz(), Y)
    return d1, d2, Z


# --- host boundary helpers --------------------------------------------------


def limbs_to_int_vec(arr) -> np.ndarray:
    """uint32[N, L] limb rows -> object[N] python ints (vectorized fold;
    the per-row python loop in bigint.from_mont is too slow for lane-count
    host tails)."""
    a = np.asarray(arr, dtype=object)
    acc = np.zeros(a.shape[0], dtype=object)
    for i in range(a.shape[1] - 1, -1, -1):
        acc = (acc << bi.B) + a[:, i]
    return acc


def is_zero_mod_p(arr) -> np.ndarray:
    """Per-row test value ≡ 0 (mod P) for redundant limb rows."""
    return np.array([int(v) % bi.P_INT == 0 for v in limbs_to_int_vec(arr)],
                    dtype=bool)

def ints_to_limbs(vals) -> np.ndarray:
    """Vectorized int -> 27x15-bit limb rows (no Montgomery scaling).

    [v_0, ..., v_{n-1}] (each < 2^405) -> uint32[n, 27]; replaces the
    per-int 27-step python loop (bigint._int_to_limbs) on batch paths."""
    n = len(vals)
    if n == 0:
        return np.zeros((0, bi.L), np.uint32)
    buf = b"".join(int(v).to_bytes(51, "little") for v in vals)
    byts = np.frombuffer(buf, np.uint8).reshape(n, 51)
    bits = np.unpackbits(byts, axis=1, bitorder="little")[:, : bi.B * bi.L]
    w = (1 << np.arange(bi.B, dtype=np.uint32))
    return (bits.reshape(n, bi.L, bi.B).astype(np.uint32) * w).sum(
        axis=2, dtype=np.uint32)


def ints_to_mont_limbs(vals) -> np.ndarray:
    """Vectorized to_mont: ints -> Montgomery limb rows uint32[n, 27]."""
    return ints_to_limbs([(int(v) * bi.R_INT) % bi.P_INT for v in vals])


def scalars_to_digits(scalars, n_bits: int = 64, w: int = 4) -> np.ndarray:
    """Scalars -> uint32[n_bits//w, n] MSB-first base-2^w window digits
    (the gj_scalar_mul_windowed input form)."""
    n = len(scalars)
    n_dig = n_bits // w
    if n == 0:
        return np.zeros((n_dig, 0), np.uint32)
    n_bytes = (n_bits + 7) // 8
    if (isinstance(scalars, np.ndarray) and scalars.dtype == np.uint64
            and n_bits == 64):
        # machine-word fast path: vectorized big-endian reinterpret
        # instead of a per-scalar int.to_bytes join
        byts = scalars.astype(">u8").view(np.uint8).reshape(n, n_bytes)
    else:
        buf = b"".join(int(s).to_bytes(n_bytes, "big") for s in scalars)
        byts = np.frombuffer(buf, np.uint8).reshape(n, n_bytes)
    bits = np.unpackbits(byts, axis=1, bitorder="big")[:, -n_bits:]
    weights = 1 << np.arange(w - 1, -1, -1, dtype=np.uint32)
    digs = (bits.reshape(n, n_dig, w).astype(np.uint32) * weights).sum(
        axis=2, dtype=np.uint32)
    return np.ascontiguousarray(digs.T)


# --- the limb-major Fq2 track (the same-message merge, ops/msm) -------------

class _Fq2LmAdapter:
    """Fq2 over pairs of limb-major lanes (uint32[27, N] each), on
    `MontField.mont_mul_lm`: Karatsuba's three products go into the
    round's one launch beside every other limb-major product of it."""

    lane_axis = 1

    @staticmethod
    def mul(q: _MulQueue, x, y):
        f = bi.FP
        r0 = q.fp_lm(x[0], y[0])
        r1 = q.fp_lm(x[1], y[1])
        r2 = q.fp_lm(f.add_lm(x[0], x[1]), f.add_lm(y[0], y[1]))

        def resolve():
            t0, t1 = r0(), r1()
            return (f.sub_lm(t0, t1), f.sub_lm(f.sub_lm(r2(), t0), t1))

        return resolve

    @staticmethod
    def add(x, y):
        return (bi.FP.add_lm(x[0], y[0]), bi.FP.add_lm(x[1], y[1]))

    @staticmethod
    def sub(x, y):
        return (bi.FP.sub_lm(x[0], y[0]), bi.FP.sub_lm(x[1], y[1]))

    @staticmethod
    def scale(x, k: int):
        return (bi.FP.scale_small_lm(x[0], k), bi.FP.scale_small_lm(x[1], k))

    @staticmethod
    def is_zero(x):
        return jnp.all(x[0] == 0, axis=0) & jnp.all(x[1] == 0, axis=0)

    @staticmethod
    def select(cond, a, b):
        return (jnp.where(cond[None], a[0], b[0]),
                jnp.where(cond[None], a[1], b[1]))

    @staticmethod
    def zeros_like(x):
        return (jnp.zeros_like(x[0]), jnp.zeros_like(x[1]))

    @staticmethod
    def one_like(x):
        return (_FpLmAdapter.one_like(x[0]), jnp.zeros_like(x[1]))


_PAIRS = (_Fq2Adapter, _Fq2LmAdapter)
_LM_TRACKS = (_FpLmAdapter, _Fq2LmAdapter)


def _reps(F, count: int):
    """`jnp.tile`'s repetitions that repeat F's lanes ``count`` times."""
    return (count, 1) if F.lane_axis == 0 else (1, count)


def segment_sums_lm(tracks, n_segments: int):
    """Segmented Jacobian sums of several limb-major tracks at once, in
    one traced level: ``tracks`` is [(F, (X, Y, Z))] whose lanes are laid
    out s-major over (S, n_segments) as in `g1_segment_sum_lm`; returns
    per track its n_segments sums, lanes [0, n_segments).

    Every level adds row 2j + 1 of every segment into row 2j, puts the
    sums in rows [0, S/2) and exact zeros (the identity) above them: the
    live rows stay a prefix, so the lane count never changes and one
    `lax.scan` body of shared product rounds serves all log2(S) levels,
    with more additions than a halving tree (log2(S)/2 times as many)
    and one traced level where the tree traces log2(S)."""
    total = tracks[0][1][0][0].shape[1] if tracks[0][0] in _PAIRS \
        else tracks[0][1][0].shape[1]
    S = total // n_segments
    assert S * n_segments == total and S & (S - 1) == 0

    def rows(F, c, parity):
        def one(a):
            r = a.reshape(a.shape[0], S // 2, 2, n_segments)
            return r[:, :, parity, :].reshape(a.shape[0], -1)
        return (one(c[0]), one(c[1])) if F in _PAIRS else one(c)

    def level(coords, _):
        items = []
        for (F, _), (X, Y, Z) in zip(tracks, coords):
            lo = tuple(rows(F, c, 0) for c in (X, Y, Z))
            hi = tuple(rows(F, c, 1) for c in (X, Y, Z))
            items.append((F, lo, hi))
        out = []
        for (F, _, _), sums in zip(items, _jac_add_full_multi(items)):
            out.append(tuple(_pad_lanes(F, c) for c in sums))
        return tuple(out), None

    levels = S.bit_length() - 1
    coords = tuple(pts for _, pts in tracks)
    if levels:
        coords, _ = jax.lax.scan(level, coords, jnp.arange(levels))
    return [tuple(_lanes(F, c, 0, n_segments) for c in pts)
            for (F, _), pts in zip(tracks, coords)]


def _lanes(F, c, lo, hi):
    if F in _PAIRS:
        return (c[0][:, lo:hi], c[1][:, lo:hi])
    return c[:, lo:hi]


def _pad_lanes(F, c):
    """Lanes doubled with exact zeros above them (the identity)."""
    if F in _PAIRS:
        return tuple(jnp.concatenate([a, jnp.zeros_like(a)], 1) for a in c)
    return jnp.concatenate([c, jnp.zeros_like(c)], 1)
