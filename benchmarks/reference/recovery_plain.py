"""Plain reference for a supernode's reconstruction: consensus-specs
``specs/fulu/polynomial-commitments-sampling.md`` ``recover_polynomialcoeff``
and ``recover_cells_and_kzg_proofs`` on Python integers, over the FFT and
the ``Setup`` of ``das_plain``.  Nothing of the program is imported.

``recover_cells_and_kzg_proofs`` is the spec's: the received cells placed
in the bit-reversed extended evaluations, the vanishing polynomial of the
missing cells (``construct_vanishing_polynomial``: the short polynomial
over the 128 cells' roots, spread by the cell size), E·Z, the inverse FFT,
the division on the coset of ``PRIMITIVE_ROOT_OF_UNITY`` and the inverse
coset FFT, then every cell and proof of the recovered polynomial.
Departures from the spec's text, none of them changing a byte:

- the cells of the recovered polynomial are its extension by one FFT and
  the bit-reversal (``das_plain.compute_cells``' way) where the spec
  evaluates each coset with ``compute_kzg_proof_multi_impl``;
- the proofs come from ``das_plain.Setup.cell_proofs``: with tau known
  (the configuration's insecure setup) each is one scalar multiplication of
  G1 by ``(p(tau) - I_c(tau)) / (tau^n - h_c^n)``, computed in the scalar
  field, where the spec commits each cell's quotient polynomial on the
  monomial points.  The proof is a function of the polynomial and the
  setup, so they are the same points, and no multi-scalar multiplication
  or FK20 of any kind is in them;
- a malformed input raises ValueError where the spec asserts.

``natural_cosets=True`` is the control: the received cells' evaluations go
in natural order instead of bit-reversed, the step a later change could get
wrong.  At exactly half of the cells every input is a codeword, so the
control answers without complaint, and wrongly.
"""

from __future__ import annotations

from benchmarks.reference.das_plain import (
    BLS_MODULUS,
    Setup,
    bit_reversal_permutation,
    cell_to_bytes,
    cell_to_coset_evals,
    compute_roots_of_unity,
    fft_field,
)
from benchmarks.reference.kzg_plain import PRIMITIVE_ROOT_OF_UNITY


def reverse_bits(n: int, order: int) -> int:
    bits = order.bit_length() - 1
    return int(format(n, f"0{bits}b")[::-1], 2)


def vanishing_polynomialcoeff(xs: list) -> list:
    """Coefficients of prod (X - x) over xs."""
    p = [1]
    for x in xs:
        q = [0] * (len(p) + 1)
        for i, c in enumerate(p):
            q[i] = (q[i] - c * x) % BLS_MODULUS
            q[i + 1] = (q[i + 1] + c) % BLS_MODULUS
        p = q
    return p


def construct_vanishing_polynomial(missing: list, setup: Setup) -> list:
    cells = setup.cells_per_ext_blob
    reduced = compute_roots_of_unity(cells)
    short = vanishing_polynomialcoeff(
        [reduced[reverse_bits(c, cells)] for c in missing])
    out = [0] * (2 * setup.width)
    for i, c in enumerate(short):
        out[i * setup.cell_size] = c
    return out


def coset_fft_field(vals: list, roots: list, inv: bool = False) -> list:
    def shift(values, factor):
        out, s = [], 1
        for v in values:
            out.append(v * s % BLS_MODULUS)
            s = s * factor % BLS_MODULUS
        return out

    g = PRIMITIVE_ROOT_OF_UNITY
    if inv:
        return shift(fft_field(vals, roots, inv=True),
                     pow(g, -1, BLS_MODULUS))
    return fft_field(shift(vals, g), roots)


def recover_polynomialcoeff(cell_indices: list, cosets_evals: list,
                            setup: Setup, *,
                            natural_cosets: bool = False) -> list:
    ext = 2 * setup.width
    n = setup.cell_size
    roots = setup.ext_roots
    evals_rbo = [0] * ext
    for c, ys in zip(cell_indices, cosets_evals):
        evals_rbo[c * n:(c + 1) * n] = ys
    evals = (evals_rbo if natural_cosets
             else bit_reversal_permutation(evals_rbo))
    missing = [c for c in range(setup.cells_per_ext_blob)
               if c not in set(cell_indices)]
    zero_coeff = construct_vanishing_polynomial(missing, setup)
    zero_eval = fft_field(zero_coeff, roots)
    ez = [a * b % BLS_MODULUS for a, b in zip(zero_eval, evals)]
    ez_coeff = fft_field(ez, roots, inv=True)
    ez_coset = coset_fft_field(ez_coeff, roots)
    zero_coset = coset_fft_field(zero_coeff, roots)
    quotient = [a * pow(b, -1, BLS_MODULUS) % BLS_MODULUS
                for a, b in zip(ez_coset, zero_coset)]
    return coset_fft_field(quotient, roots, inv=True)[:setup.width]


def recover_cells_and_kzg_proofs(cell_indices: list, cells: list,
                                 setup: Setup, *,
                                 natural_cosets: bool = False) -> tuple:
    """(cells, proofs) of one blob, all of them, as bytes, in cell order,
    from the cells at ``cell_indices`` (bytes)."""
    if len(cell_indices) != len(cells):
        raise ValueError("as many cells as indices")
    if len(set(cell_indices)) != len(cell_indices):
        raise ValueError("repeated cell index")
    if any(not 0 <= c < setup.cells_per_ext_blob for c in cell_indices):
        raise ValueError("cell index out of range")
    if 2 * len(cell_indices) < setup.cells_per_ext_blob:
        raise ValueError("fewer than half of the cells")
    coeff = recover_polynomialcoeff(
        list(cell_indices), [cell_to_coset_evals(c, setup) for c in cells],
        setup, natural_cosets=natural_cosets)
    extended = bit_reversal_permutation(
        fft_field(coeff + [0] * setup.width, setup.ext_roots))
    n = setup.cell_size
    evals = [extended[i:i + n] for i in range(0, len(extended), n)]
    p_tau = 0
    for c in reversed(coeff):
        p_tau = (p_tau * setup.tau + c) % BLS_MODULUS
    return ([cell_to_bytes(e) for e in evals],
            setup.cell_proofs(p_tau, evals))
