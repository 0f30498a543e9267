"""Plain reference for the KZG blob cells: consensus-specs
``specs/deneb/polynomial-commitments.md`` on Python integers and the
pure-Python curve of ``bls_py``.

``verify_blob_kzg_proof_batch`` is the spec's: per blob
``compute_challenge`` and ``evaluate_polynomial_in_evaluation_form``, then
``verify_kzg_proof_batch`` with the spec's own Fiat-Shamir ``r`` (a hash of
every commitment, z, y and proof) and one two-pairing check

    e(sum r^i pi_i, -[tau]G2) * e(sum r^i (C_i - y_i G1) + sum r^i z_i pi_i, G2) == 1

It judges a batch from public data alone: blobs, commitments and proofs as
bytes, [tau]G2 and the roots of unity.  Sizes come from the configuration
file (``Setup.from_config``).  Departures from the spec's text, none of
them changing a verdict:

- the program under test adds ``secrets.token_bytes(32)`` to the seed of
  its ``r``; the spec's ``r`` is a pure hash.  Both are sound: ``r`` need
  only be out of the prover's reach;
- ``sum r^i (C_i - y_i G1)`` is computed as ``sum r^i C_i - (sum r^i y_i) G1``
  (one scalar multiplication of G1 instead of one a blob), and
  ``g1_lincomb`` is a bucket sum over 8-bit windows in Jacobian
  coordinates instead of one double-and-add a point, and the barycentric
  formula's denominators are inverted together (Montgomery's trick)
  instead of one division a term (768 x 4,096 modular inversions are two
  minutes of Python a batch);
- a malformed input (wrong length, non-canonical field element, a point
  off the curve or outside the subgroup) makes the verdict False where the
  spec asserts;
- decoded points and (blob, commitment) evaluations are memoized by their
  bytes: the bad variants of a pool entry share all but a few of its
  blobs, and a 4,096-element evaluation is ~7 ms of Python.

``blind=False`` is the control: every power of ``r`` is 1, the step a
later PR would be tempted by (it removes the 255-bit scalar multiplication
from half of the device program's lanes).  Two forged proofs whose errors
cancel in the unweighted sum are then accepted.

With tau known (the configuration's insecure setup) ``commit`` and
``prove`` work in the scalar field, ``C = [p(tau)]G1`` and
``pi = [(p(tau) - y) / (tau - z)]G1``: traffic needs no 4,096-point
multi-scalar multiplication.
"""

from __future__ import annotations

import hashlib

from benchmarks.reference import bls_plain
from benchmarks.reference.bls_py import curve as cv
from benchmarks.reference.bls_py import pairing_fast as pf
from benchmarks.reference.bls_py.fields import (
    R as BLS_MODULUS,
    final_exponentiation_fast,
)

KZG_ENDIANNESS = "big"
FIAT_SHAMIR_PROTOCOL_DOMAIN = b"FSBLOBVERIFY_V1_"
RANDOM_CHALLENGE_KZG_BATCH_DOMAIN = b"RCKZGBATCH___V1_"
PRIMITIVE_ROOT_OF_UNITY = 7


def bit_reversal_permutation(values: list) -> list:
    bits = len(values).bit_length() - 1
    assert 1 << bits == len(values)
    return [values[int(format(i, f"0{bits}b")[::-1], 2)]
            for i in range(len(values))]


def compute_roots_of_unity(order: int) -> list:
    root = pow(PRIMITIVE_ROOT_OF_UNITY, (BLS_MODULUS - 1) // order,
               BLS_MODULUS)
    out = [1]
    for _ in range(order - 1):
        out.append(out[-1] * root % BLS_MODULUS)
    return out


class Setup:
    """What verification reads of a trusted setup, at the configuration's
    sizes: the width, the bit-reversed roots of unity and [tau]G2.  ``tau``
    itself is kept only because the configuration's setup is an insecure
    known-tau one; no verdict reads it."""

    def __init__(self, width: int, bytes_per_field_element: int, tau: int):
        self.width = width
        self.bytes_per_field_element = bytes_per_field_element
        self.bytes_per_blob = width * bytes_per_field_element
        self.tau = tau % BLS_MODULUS
        self.roots_brp = bit_reversal_permutation(
            compute_roots_of_unity(width))
        self.root_index = {w: i for i, w in enumerate(self.roots_brp)}
        self.g2_tau = cv.g2_mul(cv.g2_generator(), self.tau)
        self._lagrange_at_tau = None
        self._comb = None

    @classmethod
    def from_config(cls, config: dict, width: int | None = None) -> "Setup":
        preset = config["preset"]
        return cls(width or preset["FIELD_ELEMENTS_PER_BLOB"],
                   preset["BYTES_PER_FIELD_ELEMENT"],
                   int(config["trusted_setup"]["tau"], 16))

    # -- the prover's side, in the scalar field (tau known) ------------------

    def lagrange_at_tau(self) -> list:
        """L_i(tau) = w_i (tau^n - 1) / (n (tau - w_i))."""
        if self._lagrange_at_tau is None:
            n, tau = self.width, self.tau
            top = (pow(tau, n, BLS_MODULUS) - 1) % BLS_MODULUS
            self._lagrange_at_tau = [
                w * top % BLS_MODULUS
                * pow(n * (tau - w) % BLS_MODULUS, -1, BLS_MODULUS)
                % BLS_MODULUS for w in self.roots_brp]
        return self._lagrange_at_tau

    def g1_times(self, scalars: list) -> list:
        """[k]G1 for every k, compressed, by a fixed-base comb (32 byte
        windows of 255 multiples) and one shared inversion."""
        if self._comb is None:
            G = cv.g1_generator()
            base, tables = (G[0], G[1], 1), []
            for _ in range(32):
                bx, by = bls_plain._batch_affine([base])[0]
                row, acc = [], (0, 1, 0)
                for _ in range(255):
                    acc = bls_plain._jac_add_affine(*acc, bx, by)
                    row.append(acc)
                tables.append(bls_plain._batch_affine(row))
                for _ in range(8):
                    base = bls_plain._jac_double(*base)
            self._comb = tables
        out = []
        for k in scalars:
            k %= BLS_MODULUS
            acc = (0, 1, 0)
            for w in range(32):
                b = (k >> (8 * w)) & 0xFF
                if b:
                    acc = bls_plain._jac_add_affine(*acc, *self._comb[w][b - 1])
            out.append(acc)
        return [cv.g1_to_bytes(p) for p in bls_plain._batch_affine(out)]

    def commit(self, polynomial: list) -> tuple:
        """(p(tau), compressed [p(tau)]G1)."""
        p_tau = sum(a * b for a, b in zip(
            polynomial, self.lagrange_at_tau())) % BLS_MODULUS
        return p_tau, self.g1_times([p_tau])[0]

    def quotient_at_tau(self, p_tau: int, z: int, y: int) -> int:
        """q(tau) for q(X) = (p(X) - y) / (X - z)."""
        return (p_tau - y) * pow((self.tau - z) % BLS_MODULUS, -1,
                                 BLS_MODULUS) % BLS_MODULUS


# -- the spec's verification ---------------------------------------------------


def bytes_to_bls_field(b: bytes) -> int:
    v = int.from_bytes(b, KZG_ENDIANNESS)
    if v >= BLS_MODULUS:
        raise ValueError("field element not canonical")
    return v


def blob_to_polynomial(blob: bytes, setup: Setup) -> list:
    if len(blob) != setup.bytes_per_blob:
        raise ValueError("blob has the wrong length")
    n = setup.bytes_per_field_element
    return [bytes_to_bls_field(blob[i:i + n])
            for i in range(0, len(blob), n)]


def hash_to_bls_field(data: bytes) -> int:
    return int.from_bytes(hashlib.sha256(data).digest(),
                          KZG_ENDIANNESS) % BLS_MODULUS


def compute_challenge(blob: bytes, commitment: bytes, setup: Setup) -> int:
    degree_poly = setup.width.to_bytes(16, KZG_ENDIANNESS)
    return hash_to_bls_field(
        FIAT_SHAMIR_PROTOCOL_DOMAIN + degree_poly + blob + commitment)


def evaluate_polynomial_in_evaluation_form(polynomial: list, z: int,
                                           setup: Setup) -> int:
    """p(z) = (z^W - 1) / W * sum_i f_i w_i / (z - w_i), or f_i itself where
    z is the root w_i.  The W denominators are inverted together
    (Montgomery's trick), where the spec's text divides term by term."""
    width, roots = setup.width, setup.roots_brp
    hit = setup.root_index.get(z)
    if hit is not None:
        return polynomial[hit]
    prefix, acc = [], 1
    for w_i in roots:
        prefix.append(acc)
        acc = acc * (z - w_i) % BLS_MODULUS
    inverse = pow(acc, -1, BLS_MODULUS)
    result = 0
    for i in range(width - 1, -1, -1):
        result += (polynomial[i] * roots[i] % BLS_MODULUS
                   * (inverse * prefix[i] % BLS_MODULUS))
        inverse = inverse * (z - roots[i]) % BLS_MODULUS
    return (result % BLS_MODULUS * (pow(z, width, BLS_MODULUS) - 1)
            * pow(width, -1, BLS_MODULUS)) % BLS_MODULUS


def g1_lincomb(points: list, scalars: list):
    """sum k_i P_i over affine points (cv.INF allowed), by buckets over
    8-bit windows."""
    total = (0, 1, 0)
    for w in range(31, -1, -1):
        for _ in range(8):
            total = bls_plain._jac_double(*total)
        buckets = [(0, 1, 0)] * 256
        for p, k in zip(points, scalars):
            b = (k >> (8 * w)) & 0xFF
            if b and p is not cv.INF:
                buckets[b] = bls_plain._jac_add_affine(*buckets[b], *p)
        # sum_b b * bucket[b] by a running sum from the top
        run, acc = (0, 1, 0), (0, 1, 0)
        for aff in reversed(bls_plain._batch_affine(buckets[1:])):
            if aff is not cv.INF:
                run = bls_plain._jac_add_affine(*run, *aff)
            acc = cv._jac_add(acc, run, cv._IntField)
        total = cv._jac_add(total, acc, cv._IntField)
    return bls_plain._batch_affine([total])[0]


def g1_in_subgroup(p) -> bool:
    """[r]P is the identity, by double-and-add from the top bit."""
    acc = (0, 1, 0)
    for bit in bin(BLS_MODULUS)[2:]:
        acc = bls_plain._jac_double(*acc)
        if bit == "1":
            acc = bls_plain._jac_add_affine(*acc, *p)
    return acc[2] == 0


_POINTS: dict = {}
_EVALS: dict = {}


def bytes_to_g1(encoding: bytes):
    """Decompression with the curve and subgroup checks (KZGCommitment and
    KZGProof validation); what passed is memoized by encoding."""
    if encoding not in _POINTS:
        p = cv.g1_from_bytes(encoding, subgroup_check=False)
        if p is not cv.INF and not g1_in_subgroup(p):
            raise ValueError("G1 point not in subgroup")
        _POINTS[encoding] = p
    return _POINTS[encoding]


def challenge_and_evaluation(blob: bytes, commitment: bytes,
                             setup: Setup) -> tuple:
    key = (hashlib.sha256(blob).digest(), commitment, setup.width)
    if key not in _EVALS:
        z = compute_challenge(blob, commitment, setup)
        y = evaluate_polynomial_in_evaluation_form(
            blob_to_polynomial(blob, setup), z, setup)
        _EVALS[key] = (z, y)
    return _EVALS[key]


def forget():
    """Drop the memoized points and evaluations."""
    _POINTS.clear()
    _EVALS.clear()


def verify_kzg_proof_batch(commitments: list, zs: list, ys: list,
                           proofs: list, setup: Setup, *,
                           blind: bool = True) -> bool:
    """``commitments`` and ``proofs`` as bytes (already validated), ``zs``
    and ``ys`` as integers."""
    n = len(commitments)
    data = (RANDOM_CHALLENGE_KZG_BATCH_DOMAIN
            + setup.width.to_bytes(8, KZG_ENDIANNESS)
            + n.to_bytes(8, KZG_ENDIANNESS))
    for c, z, y, proof in zip(commitments, zs, ys, proofs):
        data += (c + z.to_bytes(32, KZG_ENDIANNESS)
                 + y.to_bytes(32, KZG_ENDIANNESS) + proof)
    r = hash_to_bls_field(data) if blind else 1
    r_powers = [pow(r, i, BLS_MODULUS) for i in range(n)]
    c_points = [bytes_to_g1(c) for c in commitments]
    proof_points = [bytes_to_g1(p) for p in proofs]
    proof_lincomb = g1_lincomb(proof_points, r_powers)
    proof_z_lincomb = g1_lincomb(
        proof_points, [z * rp % BLS_MODULUS for z, rp in zip(zs, r_powers)])
    y_lincomb = sum(y * rp for y, rp in zip(ys, r_powers)) % BLS_MODULUS
    c_minus_y_lincomb = cv.g1_add(
        g1_lincomb(c_points, r_powers),
        cv.g1_mul(cv.g1_generator(), (-y_lincomb) % BLS_MODULUS))
    f = pf.multi_miller_fast([
        pair for pair in (
            (proof_lincomb, cv.g2_neg(setup.g2_tau)),
            (cv.g1_add(c_minus_y_lincomb, proof_z_lincomb),
             cv.g2_generator()))
        if pair[0] is not cv.INF])
    return final_exponentiation_fast(f).is_one()


def verify_blob_kzg_proof_batch(blobs: list, commitments: list, proofs: list,
                                setup: Setup, *, blind: bool = True) -> bool:
    if not len(blobs) == len(commitments) == len(proofs):
        return False
    if not blobs:
        return True
    try:
        for encoding in list(commitments) + list(proofs):
            bytes_to_g1(encoding)
        pairs = [challenge_and_evaluation(blob, c, setup)
                 for blob, c in zip(blobs, commitments)]
    except ValueError:
        return False
    return verify_kzg_proof_batch(
        commitments, [z for z, _ in pairs], [y for _, y in pairs], proofs,
        setup, blind=blind)

