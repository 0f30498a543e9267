"""Plain reference for the gossip flood cell: signing from a fixed-base table
and a verdict for every attestation of a processor batch, on the
pure-Python arithmetic of ``bls_py``.

It judges a batch from public data alone (each attestation's key point,
message and compressed signature) the way the beacon chain attributes one:
one randomized check of the whole batch, its attestations merged by
message,

    prod_m e(sum_{i in m} r_i * pk_i, H(m)) * e(-G1, sum_i r_i * sig_i) == 1

and, when it fails, the same check on each half until every failing range
is one attestation.  Each attestation's r_i * pk_i and r_i * sig_i are
computed once and summed for every range the bisection checks.  A
signature that does not decompress, lies outside the subgroup or is the
identity fails every range that holds it.

``blind=False`` is the control: every r_i = 1.  Two attestations of one
message with their signatures swapped then cancel in the sum, and the
batch is accepted, which breaks the configuration's stated guarantee.
"""

from __future__ import annotations

from benchmarks.reference import bls_plain
from benchmarks.reference.bls_py import curve as cv
from benchmarks.reference.bls_py import pairing_fast as pf
from benchmarks.reference.bls_py.fields import (
    R,
    Fq2,
    Fq12,
    final_exponentiation_fast,
)

RAND_BITS = 64
_ZERO = Fq2(0, 0)


# -- G2 in Jacobian coordinates over Fq2 --------------------------------------


def _jac_double(X, Y, Z):
    if Y == _ZERO:
        return _ZERO, Fq2.ONE, _ZERO
    A = X.square()
    B = Y.square()
    C = B.square()
    D = ((X + B).square() - A - C).scale(2)
    E = A.scale(3)
    X3 = E.square() - D.scale(2)
    return X3, E * (D - X3) - C.scale(8), (Y * Z).scale(2)


def _jac_add_affine(X, Y, Z, x, y):
    """(X:Y:Z) + (x, y); Z == 0 is the identity."""
    if Z == _ZERO:
        return x, y, Fq2.ONE
    ZZ = Z.square()
    H = x * ZZ - X
    r = y * Z * ZZ - Y
    if H == _ZERO:
        if r == _ZERO:
            return _jac_double(X, Y, Z)
        return _ZERO, Fq2.ONE, _ZERO
    HH = H.square()
    HHH = H * HH
    V = X * HH
    X3 = r.square() - HHH - V.scale(2)
    return X3, r * (V - X3) - Y * HHH, Z * H


def _batch_affine(points):
    """Jacobian -> affine with one inversion (Montgomery's trick)."""
    prefix, acc = [], Fq2.ONE
    for _, _, Z in points:
        prefix.append(acc)
        if Z != _ZERO:
            acc = acc * Z
    inv = acc.inv()
    out = [None] * len(points)
    for i in range(len(points) - 1, -1, -1):
        X, Y, Z = points[i]
        if Z == _ZERO:
            out[i] = cv.INF
            continue
        zi = inv * prefix[i]
        inv = inv * Z
        z2 = zi.square()
        out[i] = (X * z2, Y * z2 * zi)
    return out


def sign_table(point):
    """Fixed-base comb of a G2 point: 32 byte-windows of its 255 multiples,
    affine, so that a signature is at most 31 mixed additions."""
    tables = []
    base = (point[0], point[1], Fq2.ONE)
    for _ in range(32):
        bx, by = _batch_affine([base])[0]
        row, acc = [], (_ZERO, Fq2.ONE, _ZERO)
        for _ in range(255):
            acc = _jac_add_affine(*acc, bx, by)
            row.append(acc)
        tables.append(_batch_affine(row))
        for _ in range(8):
            base = _jac_double(*base)
    return tables


def sign_many(tables, secrets) -> list[bytes]:
    """Compressed signatures secret * P for the point whose ``sign_table``
    is ``tables``, one inversion for all of them."""
    out = []
    for k in secrets:
        k %= R
        acc = (_ZERO, Fq2.ONE, _ZERO)
        for w in range(32):
            b = (k >> (8 * w)) & 0xFF
            if b:
                acc = _jac_add_affine(*acc, *tables[w][b - 1])
        out.append(acc)
    return [cv.g2_to_bytes(p) for p in _batch_affine(out)]


# -- per-attestation verdicts -------------------------------------------------


def verdicts(attestations, rng, *, blind: bool = True,
             decompressed=None) -> list[bool]:
    """``attestations``: [(key point, message, compressed signature)] of one
    batch -> a verdict each, by the batch check and bisection above.
    ``decompressed`` (bytes -> point or None) is shared between calls that
    meet the same signatures."""
    cache = {} if decompressed is None else decompressed
    terms = []
    for pk, message, sig_bytes in attestations:
        sig = cache.get(sig_bytes, _ZERO)
        if sig is _ZERO:
            try:
                sig = cv.g2_from_bytes(sig_bytes)
            except ValueError:
                sig = None
            cache[sig_bytes] = sig
        if sig is None or sig is cv.INF or pk is cv.INF:
            terms.append(None)
            continue
        r = rng.getrandbits(RAND_BITS) | 1 if blind else 1
        terms.append((cv.g1_mul(pk, r), message, cv.g2_mul(sig, r)))

    def holds(lo, hi):
        part = terms[lo:hi]
        if any(t is None for t in part):
            return False
        keys, sig_acc = {}, cv.INF
        for rpk, message, rsig in part:
            keys.setdefault(message, []).append(rpk)
            sig_acc = cv.g2_add(sig_acc, rsig)
        f = pf.miller_loop_fast(cv.g1_neg(cv.g1_generator()), sig_acc) \
            if sig_acc is not cv.INF else Fq12.ONE
        for message, points in keys.items():
            apk = bls_plain.g1_sum(points)
            if apk is not cv.INF:
                f = f * pf.miller_loop_fast(apk,
                                            bls_plain.hash_to_g2(message))
        return final_exponentiation_fast(f).is_one()

    out = [False] * len(terms)

    def bisect(lo, hi, known_failed):
        if not known_failed and holds(lo, hi):
            out[lo:hi] = [True] * (hi - lo)
            return
        if hi - lo == 1:
            return
        mid = (lo + hi) // 2
        bisect(lo, mid, False)
        bisect(mid, hi, False)

    if terms:
        bisect(0, len(terms), False)
    return out
