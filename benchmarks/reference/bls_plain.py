"""Plain reference for the BLS cells: interop keys, signing and batch
verification on the pure-Python arithmetic of ``bls_py``.

It makes the traffic's keys and signatures itself and judges a batch from
public data alone (member key points, message, signature bytes), by the
randomized batch check every consensus client uses:

    prod_i e(r_i * apk_i, H(m_i)) * e(-G1, sum_i r_i * sig_i) == 1

``blind=False`` is the control: every r_i = 1, the step a later PR would
be tempted by (it removes the 64-bit window scan from the device program).
Two sets with swapped signatures then cancel in the sum and the batch is
accepted, which breaks the configuration's stated guarantee.
"""

from __future__ import annotations

import hashlib
import random

from benchmarks.reference.bls_py import curve as cv
from benchmarks.reference.bls_py import hash_to_curve as h2c
from benchmarks.reference.bls_py import pairing_fast as pf
from benchmarks.reference.bls_py.fields import (
    P,
    R,
    Fq12,
    final_exponentiation_fast,
)

RAND_BITS = 64
_H2G = {}


def interop_secret(index: int) -> int:
    """sk_i = int_le(sha256(le32(i))) mod r (eth2 interop keypairs)."""
    return int.from_bytes(
        hashlib.sha256(index.to_bytes(32, "little")).digest(), "little") % R


# -- G1 in Jacobian coordinates over Python integers --------------------------


def _jac_double(X, Y, Z):
    if Y == 0:
        return 0, 1, 0
    A = X * X % P
    B = Y * Y % P
    C = B * B % P
    D = 2 * ((X + B) * (X + B) - A - C) % P
    E = 3 * A % P
    X3 = (E * E - 2 * D) % P
    return X3, (E * (D - X3) - 8 * C) % P, 2 * Y * Z % P


def _jac_add_affine(X, Y, Z, x, y):
    """(X:Y:Z) + (x, y); Z == 0 is the identity."""
    if Z == 0:
        return x, y, 1
    ZZ = Z * Z % P
    U2 = x * ZZ % P
    S2 = y * Z * ZZ % P
    H = (U2 - X) % P
    r = (S2 - Y) % P
    if H == 0:
        if r == 0:
            return _jac_double(X, Y, Z)
        return 0, 1, 0
    HH = H * H % P
    HHH = H * HH % P
    V = X * HH % P
    X3 = (r * r - HHH - 2 * V) % P
    return X3, (r * (V - X3) - Y * HHH) % P, Z * H % P


def _batch_affine(points):
    """Jacobian -> affine with one inversion (Montgomery's trick);
    identity -> cv.INF."""
    prefix, acc = [], 1
    for _, _, Z in points:
        prefix.append(acc)
        if Z:
            acc = acc * Z % P
    inv = pow(acc, P - 2, P)
    out = [None] * len(points)
    for i in range(len(points) - 1, -1, -1):
        X, Y, Z = points[i]
        if not Z:
            out[i] = cv.INF
            continue
        zi = inv * prefix[i] % P
        inv = inv * Z % P
        z2 = zi * zi % P
        out[i] = (X * z2 % P, Y * z2 * zi % P)
    return out


def g1_sum(points):
    """Sum of affine G1 points (None/INF skipped) -> affine point."""
    X, Y, Z = 0, 1, 0
    for p in points:
        if p is cv.INF:
            continue
        X, Y, Z = _jac_add_affine(X, Y, Z, p[0], p[1])
    return _batch_affine([(X, Y, Z)])[0]


def public_keys(n: int):
    """[sk_i * G1 for i < n] as affine points, by a fixed-base comb:
    32 byte-windows of 255 precomputed multiples, 31 mixed additions a
    key and one shared inversion — ~0.3 ms a key instead of ~4 ms."""
    G = cv.g1_generator()
    tables = []
    base = (G[0], G[1], 1)
    for _ in range(32):
        bx, by = _batch_affine([base])[0]
        row, acc = [], (0, 1, 0)
        for _ in range(255):
            acc = _jac_add_affine(*acc, bx, by)
            row.append(acc)
        tables.append(_batch_affine(row))
        for _ in range(8):
            base = _jac_double(*base)
    out = []
    for i in range(n):
        k = interop_secret(i)
        acc = (0, 1, 0)
        for w in range(32):
            b = (k >> (8 * w)) & 0xFF
            if b:
                x, y = tables[w][b - 1]
                acc = _jac_add_affine(*acc, x, y)
        out.append(acc)
    return _batch_affine(out)


def hash_to_g2(message: bytes):
    pt = _H2G.get(message)
    if pt is None:
        pt = _H2G[message] = h2c.hash_to_g2(message)
    return pt


def sign(secret: int, message: bytes) -> bytes:
    """Compressed signature secret * H(message)."""
    return cv.g2_to_bytes(cv.g2_mul(hash_to_g2(message), secret % R))


def verify_batch(sets, rng: random.Random, *, blind: bool = True) -> bool:
    """``sets``: [(member key points, message, compressed signature)].
    False on any malformed or out-of-subgroup signature, empty member
    list or identity aggregate; else the batch equation above."""
    if not sets:
        return False
    pairs = []
    sig_acc = cv.INF
    for members, message, sig_bytes in sets:
        if not members:
            return False
        try:
            sig = cv.g2_from_bytes(sig_bytes)
        except ValueError:
            return False
        apk = g1_sum(members)
        if sig is cv.INF or apk is cv.INF:
            return False
        r = rng.getrandbits(RAND_BITS) | 1 if blind else 1
        sig_acc = cv.g2_add(sig_acc, cv.g2_mul(sig, r))
        pairs.append((cv.g1_mul(apk, r), hash_to_g2(message)))
    pairs.append((cv.g1_neg(cv.g1_generator()), sig_acc))
    f = Fq12.ONE
    for p, q in pairs:
        f = f * pf.miller_loop_fast(p, q)
    return final_exponentiation_fast(f).is_one()
