"""Plain reference for the data-column cells: consensus-specs
``specs/fulu/polynomial-commitments-sampling.md`` on Python integers and the
pure-Python curve of ``bls_py``.  Nothing of the program is imported.

``verify_cell_kzg_proof_batch`` is the spec's: the commitments deduplicated
in order of first appearance, the spec's own Fiat-Shamir ``r``
(``compute_verify_cell_kzg_proof_batch_challenge``) and the universal
verification equation of ``verify_cell_kzg_proof_batch_impl``

    e(sum_k r^k pi_k, [tau^n]G2)
      == e(sum_i w_i C_i - [sum_k r^k I_k(tau)]G1 + sum_k r^k h_k^n pi_k, G2)

with n = FIELD_ELEMENTS_PER_CELL, I_k the interpolation polynomial of cell
k on its coset and h_k the coset's shift.  It judges a batch from public
data alone: commitments, cells and proofs as bytes, ``g1_monomial[:n]``,
``[tau^n]G2`` and the roots of unity.  ``compute_cells`` is the spec's too
(inverse FFT to coefficients, FFT on the doubled domain, bit-reversal,
split).  Sizes come from the configuration file (``Setup.from_config``).
Departures from the spec's text, none of them changing a verdict:

- ``interpolate_polynomialcoeff`` is an inverse FFT on the coset (the
  cell's evaluations un-bit-reversed, ``fft_field`` of size n, the shift
  divided out of coefficient m as h^-m) where the spec multiplies out
  Lagrange polynomials: 2,688 cells a block at n^3 field multiplications
  each are half an hour of Python;
- ``g1_lincomb`` is ``kzg_plain``'s bucket sum, and decoded points are
  memoized by their bytes there (a block's commitments come with each of
  its 128 sidecars);
- a malformed input (wrong length, a cell index out of range, a
  non-canonical field element, a point off the curve or outside the
  subgroup) makes the verdict False where the spec asserts.

``blind=False`` is the control: every power of ``r`` is 1.  Two forged
proofs of one column whose errors cancel in the unweighted sum are then
accepted.

With tau known (the configuration's insecure setup) ``commit`` and
``cell_proofs`` work in the scalar field: ``C = [p(tau)]G1`` and
``pi = [(p(tau) - I(tau)) / (tau^n - h^n)]G1``, I(tau) by the Lagrange
basis of the coset at tau.  Traffic needs no multi-scalar multiplication.
"""

from __future__ import annotations

from benchmarks.reference import kzg_plain
from benchmarks.reference.bls_py import curve as cv
from benchmarks.reference.bls_py import pairing_fast as pf
from benchmarks.reference.bls_py.fields import (
    R as BLS_MODULUS,
    final_exponentiation_fast,
)
from benchmarks.reference.kzg_plain import (
    KZG_ENDIANNESS,
    bit_reversal_permutation,
    blob_to_polynomial,
    bytes_to_bls_field,
    bytes_to_g1,
    compute_roots_of_unity,
    g1_lincomb,
    hash_to_bls_field,
)

RANDOM_CHALLENGE_KZG_CELL_BATCH_DOMAIN = b"RCKZGCBATCH__V1_"


class Setup(kzg_plain.Setup):
    """What cell verification reads of a trusted setup, at the
    configuration's sizes: the width, the cell geometry, the roots of
    unity of the doubled domain, ``g1_monomial[:n]`` and ``[tau^n]G2``."""

    def __init__(self, width: int, bytes_per_field_element: int, tau: int,
                 cells_per_ext_blob: int):
        super().__init__(width, bytes_per_field_element, tau)
        ext = 2 * width
        self.cells_per_ext_blob = min(cells_per_ext_blob, ext)
        self.cell_size = ext // self.cells_per_ext_blob
        self.bytes_per_cell = self.cell_size * bytes_per_field_element
        self.ext_roots = compute_roots_of_unity(ext)
        self.ext_roots_brp = bit_reversal_permutation(self.ext_roots)
        self.cell_roots = compute_roots_of_unity(self.cell_size)
        powers = [pow(self.tau, i, BLS_MODULUS)
                  for i in range(self.cell_size + 1)]
        self.g1_monomial = [
            cv.g1_from_bytes(b, subgroup_check=False)
            for b in self.g1_times(powers[:self.cell_size])]
        self.g2_tau_n = cv.g2_mul(cv.g2_generator(), powers[self.cell_size])
        self._prover = None

    @classmethod
    def from_config(cls, config: dict, width: int | None = None) -> "Setup":
        preset = config["preset"]
        return cls(width or preset["FIELD_ELEMENTS_PER_BLOB"],
                   preset["BYTES_PER_FIELD_ELEMENT"],
                   int(config["trusted_setup"]["tau"], 16),
                   preset["CELLS_PER_EXT_BLOB"])

    def coset_shift_for_cell(self, cell_index: int) -> int:
        return self.ext_roots_brp[self.cell_size * cell_index]

    # -- the prover's side, in the scalar field (tau known) ------------------

    def cell_proofs(self, p_tau: int, cells: list) -> list:
        """Compressed cell proofs of one blob from p(tau) and its cells'
        field elements: q_c(tau) = (p(tau) - I_c(tau)) / Z_c(tau) with
        Z_c(X) = X^n - h_c^n and I_c(tau) = Z_c(tau) / (n h_c^n) *
        sum_j y_j x_j / (tau - x_j) over the coset's points x_j."""
        return self.g1_times(self.quotients_at_tau(p_tau, cells))

    def quotients_at_tau(self, p_tau: int, cells: list) -> list:
        n, tau = self.cell_size, self.tau
        if self._prover is None:
            x_over = batch_inverse(
                [(tau - x) % BLS_MODULUS for x in self.ext_roots_brp])
            x_over = [x * d % BLS_MODULUS
                      for x, d in zip(self.ext_roots_brp, x_over)]
            a = [pow(self.coset_shift_for_cell(c), n, BLS_MODULUS)
                 for c in range(self.cells_per_ext_blob)]
            tau_n = pow(tau, n, BLS_MODULUS)
            z_inv = batch_inverse(
                [(tau_n - a_c) % BLS_MODULUS for a_c in a])
            na_inv = batch_inverse(
                [n * a_c % BLS_MODULUS for a_c in a])
            self._prover = (x_over, z_inv, na_inv)
        x_over, z_inv, na_inv = self._prover
        out = []
        for c, ys in enumerate(cells):
            s = sum(y * t for y, t in zip(ys, x_over[n * c:n * (c + 1)]))
            # (p - I) / Z = p / Z - s / (n a)
            out.append((p_tau * z_inv[c] - s % BLS_MODULUS * na_inv[c])
                       % BLS_MODULUS)
        return out


def batch_inverse(values: list) -> list:
    """Every inverse by one modular inversion (Montgomery's trick)."""
    prefix, acc = [], 1
    for v in values:
        prefix.append(acc)
        acc = acc * v % BLS_MODULUS
    inverse = pow(acc, -1, BLS_MODULUS)
    out = [0] * len(values)
    for i in range(len(values) - 1, -1, -1):
        out[i] = inverse * prefix[i] % BLS_MODULUS
        inverse = inverse * values[i] % BLS_MODULUS
    return out


# -- the spec's FFTs and cells ---------------------------------------------------


def _fft_field(vals: list, roots_of_unity: list) -> list:
    if len(vals) == 1:
        return vals
    left = _fft_field(vals[::2], roots_of_unity[::2])
    right = _fft_field(vals[1::2], roots_of_unity[::2])
    out = [0] * len(vals)
    for i, (x, y) in enumerate(zip(left, right)):
        y_times_root = y * roots_of_unity[i] % BLS_MODULUS
        out[i] = (x + y_times_root) % BLS_MODULUS
        out[i + len(left)] = (x - y_times_root) % BLS_MODULUS
    return out


def fft_field(vals: list, roots_of_unity: list, inv: bool = False) -> list:
    if inv:
        invlen = pow(len(vals), -1, BLS_MODULUS)
        return [x * invlen % BLS_MODULUS for x in _fft_field(
            vals, roots_of_unity[0:1] + roots_of_unity[:0:-1])]
    return _fft_field(vals, roots_of_unity)


def compute_cells(blob: bytes, setup: Setup) -> list:
    """The blob's cells as lists of field elements (``cell_to_bytes``
    makes them bytes): its evaluations extended onto the doubled domain,
    bit-reversed, split."""
    polynomial = blob_to_polynomial(blob, setup)
    coeff = fft_field(bit_reversal_permutation(polynomial),
                      compute_roots_of_unity(setup.width), inv=True)
    extended = bit_reversal_permutation(
        fft_field(coeff + [0] * setup.width, setup.ext_roots))
    n = setup.cell_size
    return [extended[i:i + n] for i in range(0, len(extended), n)]


def cell_to_bytes(coset_evals: list) -> bytes:
    return b"".join(v.to_bytes(32, KZG_ENDIANNESS) for v in coset_evals)


def cell_to_coset_evals(cell: bytes, setup: Setup) -> list:
    if len(cell) != setup.bytes_per_cell:
        raise ValueError("cell has the wrong length")
    n = setup.bytes_per_field_element
    return [bytes_to_bls_field(cell[i:i + n])
            for i in range(0, len(cell), n)]


def interpolate_polynomialcoeff(cell_index: int, ys: list,
                                setup: Setup) -> list:
    """Coefficients of the polynomial of degree < n through the cell's
    evaluations on its coset {h w^brp(j)}: the inverse FFT of the
    evaluations in natural order gives p(h X), and coefficient m of p is
    that of p(h X) over h^m."""
    shifted = fft_field(bit_reversal_permutation(ys), setup.cell_roots,
                        inv=True)
    h_inv = pow(setup.coset_shift_for_cell(cell_index), -1, BLS_MODULUS)
    out, scale = [], 1
    for c in shifted:
        out.append(c * scale % BLS_MODULUS)
        scale = scale * h_inv % BLS_MODULUS
    return out


# -- the spec's verification -----------------------------------------------------


def compute_verify_cell_kzg_proof_batch_challenge(
        commitments: list, commitment_indices: list, cell_indices: list,
        cosets_evals: list, proofs: list, setup: Setup) -> int:
    data = [RANDOM_CHALLENGE_KZG_CELL_BATCH_DOMAIN,
            setup.width.to_bytes(8, KZG_ENDIANNESS),
            setup.cell_size.to_bytes(8, KZG_ENDIANNESS),
            len(commitments).to_bytes(8, KZG_ENDIANNESS),
            len(cell_indices).to_bytes(8, KZG_ENDIANNESS)]
    data += commitments
    for k, coset_evals in enumerate(cosets_evals):
        data.append(commitment_indices[k].to_bytes(8, KZG_ENDIANNESS))
        data.append(cell_indices[k].to_bytes(8, KZG_ENDIANNESS))
        data.append(cell_to_bytes(coset_evals))
        data.append(proofs[k])
    return hash_to_bls_field(b"".join(data))


def verify_cell_kzg_proof_batch_impl(
        commitments: list, commitment_indices: list, cell_indices: list,
        cosets_evals: list, proofs: list, setup: Setup, *,
        blind: bool = True) -> bool:
    """``commitments`` (distinct) and ``proofs`` as bytes, already
    validated; ``cosets_evals`` as lists of integers."""
    n_cells, n = len(cell_indices), setup.cell_size
    r = compute_verify_cell_kzg_proof_batch_challenge(
        commitments, commitment_indices, cell_indices, cosets_evals, proofs,
        setup) if blind else 1
    r_powers = [pow(r, k, BLS_MODULUS) for k in range(n_cells)]
    proof_points = [bytes_to_g1(p) for p in proofs]
    # LL = sum_k r^k proofs[k], LR = [tau^n]G2
    ll = g1_lincomb(proof_points, r_powers)
    # RLC = sum_i weights[i] commitments[i]
    weights = [0] * len(commitments)
    for k in range(n_cells):
        i = commitment_indices[k]
        weights[i] = (weights[i] + r_powers[k]) % BLS_MODULUS
    rlc = g1_lincomb([bytes_to_g1(c) for c in commitments], weights)
    # RLI = [sum_k r^k interpolation_poly_k(tau)]
    sum_interp = [0] * n
    for k in range(n_cells):
        coeff = interpolate_polynomialcoeff(cell_indices[k], cosets_evals[k],
                                            setup)
        for m in range(n):
            sum_interp[m] += r_powers[k] * coeff[m]
    rli = g1_lincomb(setup.g1_monomial,
                     [c % BLS_MODULUS for c in sum_interp])
    # RLP = sum_k (r^k * h_k^n) proofs[k]
    weighted = [r_powers[k] * pow(setup.coset_shift_for_cell(cell_indices[k]),
                                  n, BLS_MODULUS) % BLS_MODULUS
                for k in range(n_cells)]
    rlp = g1_lincomb(proof_points, weighted)
    rl = cv.g1_add(cv.g1_add(rlc, cv.g1_neg(rli)), rlp)
    f = pf.multi_miller_fast([
        pair for pair in ((ll, setup.g2_tau_n),
                          (rl, cv.g2_neg(cv.g2_generator())))
        if pair[0] is not cv.INF])
    return final_exponentiation_fast(f).is_one()


def verify_cell_kzg_proof_batch(commitments_bytes: list, cell_indices: list,
                                cells: list, proofs_bytes: list,
                                setup: Setup, *, blind: bool = True) -> bool:
    """The spec's public entry: a commitment, a cell index, a cell and a
    proof a cell."""
    if not (len(commitments_bytes) == len(cells) == len(proofs_bytes)
            == len(cell_indices)):
        return False
    if not cells:
        return True
    try:
        for encoding in list(dict.fromkeys(commitments_bytes)) + list(
                proofs_bytes):
            bytes_to_g1(encoding)
        if any(not 0 <= c < setup.cells_per_ext_blob for c in cell_indices):
            return False
        cosets_evals = [cell_to_coset_evals(cell, setup) for cell in cells]
    except ValueError:
        return False
    deduplicated = list(dict.fromkeys(commitments_bytes))
    index_of = {c: i for i, c in enumerate(deduplicated)}
    return verify_cell_kzg_proof_batch_impl(
        deduplicated, [index_of[c] for c in commitments_bytes],
        list(cell_indices), cosets_evals, list(proofs_bytes), setup,
        blind=blind)


def forget():
    """Drop the memoized points."""
    kzg_plain.forget()
