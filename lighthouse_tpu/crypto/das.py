"""PeerDAS data-availability-sampling cells (EIP-7594 shape).

The cell functions of consensus-specs
specs/fulu/polynomial-commitments-sampling.md (compute_cells,
recover_cells_and_kzg_proofs, verify_cell_kzg_proof_batch): a blob's
evaluations extend onto the doubled domain (Reed-Solomon rate-1/2), cells are the bit-reversal-permuted cosets of that extended
domain, and any half of the cells recovers the rest via the
vanishing-polynomial / coset-division algorithm.

Cell KZG multi-proofs ride the setup's monomial halves
(compute_cells_and_kzg_proofs / verify_cell_kzg_proof below);
`verify_cells_match_blob` remains the data-level check for callers
holding the blob.  Corruption among RECEIVED cells during recovery is
detected whenever the caller supplies more than the minimum half (at
exactly half there is no redundancy — proof-verify cells first).

All arithmetic is over the BLS scalar field.  Batch verification runs on
the device (the aggregated interpolation in ops/fr.py, the two sums and
the pairing in kzg._kzg_fused); so do, at production widths, a block's
recovery (ops/fr._recover_kernel) and every cell proof (FK20,
ops/msm._fk20_kernel).  Cell computation (`compute_cells`) and the
small-width rung are host-side Python integers.
"""

from __future__ import annotations

from lighthouse_tpu.crypto.kzg import (
    BLS_MODULUS,
    KzgError,
    _bit_reversal_permutation,
    _compute_roots_of_unity,
    bls_field_to_bytes,
    bytes_to_bls_field,
)


def _bytes_to_field_elements(data: bytes, count: int) -> list[int]:
    if len(data) != count * 32:
        raise KzgError(f"expected {count} field elements")
    return [bytes_to_bls_field(data[i:i + 32])
            for i in range(0, len(data), 32)]

# mainnet: 4096-wide blobs -> 8192 extended evaluations -> 128 cells of
# 64 field elements.  Smaller (dev) widths scale the cell size down,
# keeping 128 cells whenever the extension has at least 128 points.
CELLS_PER_EXT_BLOB = 128


def _cell_geometry(width: int) -> tuple[int, int]:
    ext = 2 * width
    n_cells = min(CELLS_PER_EXT_BLOB, ext)
    return n_cells, ext // n_cells


def _fft(vals: list[int], roots: list[int], inverse: bool = False) -> list[int]:
    """Iterative radix-2 NTT over the scalar field; `roots` is the full
    n-th root-of-unity list for n == len(vals)."""
    n = len(vals)
    if n == 1:
        return list(vals)
    assert n & (n - 1) == 0
    out = _bit_reversal_permutation(list(vals))
    step = 1
    while step < n:
        stride = n // (2 * step)
        for start in range(0, n, 2 * step):
            for k in range(step):
                idx = (n - k * stride) % n if inverse else k * stride
                w = roots[idx]
                a = out[start + k]
                b = out[start + k + step] * w % BLS_MODULUS
                out[start + k] = (a + b) % BLS_MODULUS
                out[start + k + step] = (a - b) % BLS_MODULUS
        step *= 2
    if inverse:
        n_inv = pow(n, -1, BLS_MODULUS)
        out = [v * n_inv % BLS_MODULUS for v in out]
    return out


def _poly_coeffs_from_blob(blob: bytes, width: int) -> list[int]:
    """Blob evaluations (brp domain order) -> monomial coefficients."""
    evals_brp = _bytes_to_field_elements(blob, width)
    evals = _bit_reversal_permutation(evals_brp)   # brp is an involution
    roots = _compute_roots_of_unity(width)
    return _fft(evals, roots, inverse=True)


def compute_cells(blob: bytes, settings) -> list[bytes]:
    """Extend the blob onto the doubled domain and split into cells.

    Cell c holds the extended evaluations at positions
    [c·cell_size, (c+1)·cell_size) of the BIT-REVERSED extended domain
    (so each cell is a coset — the structure recovery relies on)."""
    width = settings.width
    n_cells, cell_size = _cell_geometry(width)
    coeffs = _poly_coeffs_from_blob(blob, width)
    ext_roots = _compute_roots_of_unity(2 * width)
    ext_evals = _fft(coeffs + [0] * width, ext_roots)
    ext_brp = _bit_reversal_permutation(ext_evals)
    return [
        b"".join(bls_field_to_bytes(v)
                 for v in ext_brp[c * cell_size:(c + 1) * cell_size])
        for c in range(n_cells)
    ]


def cells_to_blob(cells: list[bytes], settings) -> bytes:
    """First half of the (brp) extended evaluations IS the blob."""
    width = settings.width
    n_cells, cell_size = _cell_geometry(width)
    if len(cells) != n_cells:
        raise KzgError(f"need all {n_cells} cells, got {len(cells)}")
    joined = b"".join(cells)
    return joined[: width * 32]


def _cell_field_elements(cell: bytes, cell_size: int) -> list[int]:
    if len(cell) != cell_size * 32:
        raise KzgError("cell has the wrong size")
    return _bytes_to_field_elements(cell, cell_size)


def recover_all_cells(cell_ids: list[int], cells: list[bytes],
                      settings) -> list[bytes]:
    """Erasure recovery: any >= half of the cells reconstructs all of
    them (vanishing-polynomial + coset-division, the spec's
    recover_cells_and_kzg_proofs).

    Steps: build Z(x) vanishing on the missing cells' cosets (each coset
    is {h·w : w^cell_size = 1}, so its vanishing factor is the sparse
    x^cell_size - h^cell_size); FFT-multiply E·Z, divide on a shifted
    coset where Z has no roots, and re-extend."""
    width = settings.width
    ext = 2 * width
    n_cells, cell_size = _cell_geometry(width)
    if len(cell_ids) != len(cells):
        raise KzgError("cell_ids and cells length mismatch")
    if len(set(cell_ids)) != len(cell_ids):
        raise KzgError("duplicate cell ids")
    if any(not 0 <= c < n_cells for c in cell_ids):
        raise KzgError("cell id out of range")
    if len(cell_ids) < n_cells // 2:
        raise KzgError(
            f"need at least {n_cells // 2} cells, got {len(cell_ids)}")
    have = dict(zip(cell_ids, cells))
    if len(have) == n_cells:
        return [have[c] for c in range(n_cells)]

    ext_roots = _compute_roots_of_unity(ext)
    # brp position -> natural extended-domain position
    nat_of_brp = _bit_reversal_permutation(list(range(ext)))

    # received evaluations in NATURAL order (0 at missing positions)
    e_nat = [0] * ext
    for cid, cell in have.items():
        for k, v in enumerate(_cell_field_elements(cell, cell_size)):
            e_nat[nat_of_brp[cid * cell_size + k]] = v

    # Z(x) = prod over missing cells of (x^cell_size - h_c^cell_size),
    # h_c the first root of the cell's coset
    z = [1]
    for cid in range(n_cells):
        if cid in have:
            continue
        h = ext_roots[nat_of_brp[cid * cell_size]]
        hc = pow(h, cell_size, BLS_MODULUS)
        nz = [0] * (len(z) + cell_size)
        for i, c in enumerate(z):
            nz[i] = (nz[i] - c * hc) % BLS_MODULUS
            nz[i + cell_size] = (nz[i + cell_size] + c) % BLS_MODULUS
        z = nz
    z_coeffs = z + [0] * (ext - len(z))

    z_evals = _fft(z_coeffs, ext_roots)
    ez_evals = [e * zv % BLS_MODULUS for e, zv in zip(e_nat, z_evals)]
    ez_coeffs = _fft(ez_evals, ext_roots, inverse=True)

    # divide on the coset g·domain (g a non-root shift): DZ/Z there,
    # then unshift (the primitive root is outside every power-of-two
    # root subgroup, so Z has no roots on the shifted coset)
    from lighthouse_tpu.crypto.kzg import PRIMITIVE_ROOT_OF_UNITY

    shift = PRIMITIVE_ROOT_OF_UNITY
    shift_pows = [pow(shift, i, BLS_MODULUS) for i in range(ext)]
    ezc_shift = [c * s % BLS_MODULUS for c, s in zip(ez_coeffs, shift_pows)]
    zc_shift = [c * s % BLS_MODULUS
                for c, s in zip(z_coeffs, shift_pows)]
    ez_on_coset = _fft(ezc_shift, ext_roots)
    z_on_coset = _fft(zc_shift, ext_roots)
    d_on_coset = [
        e * pow(zv, -1, BLS_MODULUS) % BLS_MODULUS
        for e, zv in zip(ez_on_coset, z_on_coset)
    ]
    d_shift = _fft(d_on_coset, ext_roots, inverse=True)
    shift_inv = pow(shift, -1, BLS_MODULUS)
    inv_pows = [pow(shift_inv, i, BLS_MODULUS) for i in range(ext)]
    d_coeffs = [c * s % BLS_MODULUS for c, s in zip(d_shift, inv_pows)]
    if any(v != 0 for v in d_coeffs[width:]):
        raise KzgError("recovered polynomial exceeds blob degree "
                       "(inconsistent cells)")

    full_evals = _fft(d_coeffs, ext_roots)
    full_brp = _bit_reversal_permutation(full_evals)
    out = []
    for c in range(n_cells):
        got = have.get(c)
        if got is None:
            got = b"".join(
                bls_field_to_bytes(v)
                for v in full_brp[c * cell_size:(c + 1) * cell_size])
        out.append(got)
    # received cells must be consistent with the recovered polynomial
    for cid, cell in have.items():
        want = full_brp[cid * cell_size:(cid + 1) * cell_size]
        if _cell_field_elements(cell, cell_size) != want:
            raise KzgError(f"cell {cid} inconsistent with recovery")
    return out


# --- cell KZG multi-proofs ---------------------------------------------------
#
# Proof for cell c: π_c = [q_c(τ)]₁ with q_c = (p − I_c) / Z_c, where
# I_c interpolates p on cell c's coset and Z_c(x) = x^cs − h_c^cs is the
# coset's vanishing polynomial (sparse — synthetic division is O(n)).
# Verification: e(C − [I_c(τ)]₁, −G₂) · e(π_c, [Z_c(τ)]₂) == 1 with
# [Z_c(τ)]₂ = τ^cs·G₂ − h_c^cs·G₂ from the setup's G2 monomials.


def _coset_start(cid: int, cell_size: int, ext_roots, nat_of_brp) -> int:
    return ext_roots[nat_of_brp[cid * cell_size]]


def _require_monomials(settings, cell_size: int):
    if settings.g1_monomial is None or settings.g2_monomial is None \
            or len(settings.g2_monomial) <= cell_size:
        raise KzgError(
            "cell proofs need the setup's monomial points "
            "(g1_monomial/g2_monomial in the ceremony file)")


# the two device programs of cells and proofs (ops/fr._recover_kernel,
# ops/msm._fk20_kernel) serve at and above this width; below it the host's
# Python integers and per-cell g1_lincomb do
_DEVICE_MIN_WIDTH = 256


def compute_cells_and_kzg_proofs(blob: bytes, settings
                                 ) -> tuple[list[bytes], list[bytes]]:
    """Cells + one KZG multi-proof per cell.

    At and above `_DEVICE_MIN_WIDTH` every proof of the blob comes from
    one FK20 dispatch (`_fk20_proofs`); below it each cell's quotient
    (synthetic division by x^cs - a) is committed by `kzg.g1_lincomb`."""
    from lighthouse_tpu.crypto import kzg as _kzg
    from lighthouse_tpu.crypto.bls import curve as cv

    width = settings.width
    n_cells, cell_size = _cell_geometry(width)
    _require_monomials(settings, cell_size)
    cells = compute_cells(blob, settings)
    coeffs = _poly_coeffs_from_blob(blob, width)
    if width >= _DEVICE_MIN_WIDTH:
        import jax.numpy as jnp

        limbs = _plain_limbs(coeffs).T
        return cells, _fk20_proofs(jnp.asarray(limbs), 1, settings)[0]
    ext_roots = _compute_roots_of_unity(2 * width)
    nat_of_brp = _bit_reversal_permutation(list(range(2 * width)))
    proofs = []
    for cid in range(n_cells):
        h = _coset_start(cid, cell_size, ext_roots, nat_of_brp)
        a = pow(h, cell_size, BLS_MODULUS)
        # synthetic division by x^cs − a: top-down, q_j = p_{j+cs} + a·q_{j+cs}
        q = [0] * max(width - cell_size, 1)
        for j in range(width - cell_size - 1, -1, -1):
            carry = q[j + cell_size] if j + cell_size < len(q) else 0
            q[j] = (coeffs[j + cell_size] + a * carry) % BLS_MODULUS
        proofs.append(cv.g1_to_bytes(
            _kzg.g1_lincomb(settings.g1_monomial[:len(q)], q)))
    return cells, proofs


def _plain_limbs(values: list[int]):
    """Raw (non-Montgomery) limb rows uint32[n, L] of field elements."""
    import numpy as np

    from lighthouse_tpu.ops import fr

    raw = b"".join(v.to_bytes(32, "big") for v in values)
    return fr.be32_bytes_to_limbs(np.frombuffer(raw, np.uint8).reshape(-1, 32))


def _fk20_tables(settings):
    """The setup's FK20 tables on the device, made once a settings object
    from ``g1_monomial[:width]`` by `msm._fk20_tables` (c-kzg-4844 keeps
    its FK20 tables the same way)."""
    tables = getattr(settings, "_fk20_device_tables", None)
    if tables is None:
        import jax.numpy as jnp

        from lighthouse_tpu.ops import msm as _msm

        n_cells, cell_size = _cell_geometry(settings.width)
        if len(settings.g1_monomial or ()) < settings.width:
            raise KzgError("FK20 needs g1_monomial[:width] of the setup")
        columns = _msm.fk20_setup_columns(settings.g1_monomial, cell_size,
                                          n_cells)
        tables = _msm._fk20_tables(*(jnp.asarray(c) for c in columns))
        settings._fk20_device_tables = tables
    return tables


def _fk20_dispatch(coeffs, blobs: int, settings):
    """One `msm._fk20_kernel` dispatch over device coefficients
    uint32[18, width·blobs] (lane j·blobs + r), not waited for: the
    Jacobian rows uint32[blobs, cells, 27] of every proof."""
    from lighthouse_tpu.crypto import kzg as _kzg
    from lighthouse_tpu.ops import msm as _msm

    n_cells, cell_size = _cell_geometry(settings.width)
    rows = _msm._fk20_kernel(coeffs, *_fk20_tables(settings))
    _kzg.count_fk20_products(*_msm.fk20_products(blobs, cell_size, n_cells))
    return rows


def _compressed_proofs(X, Y, Z) -> list[list[bytes]]:
    """Fetched Montgomery Jacobian rows uint32[blobs, cells, 27] ->
    compressed points, [blob][cell]: the limbs read as integers a column
    at a time and every Z inverted by one inversion (Montgomery's trick),
    so a block's 2,688 proofs are no 2,688 modular inversions."""
    from lighthouse_tpu.crypto.bls import curve as cv
    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import ec

    p = bi.P_INT
    blobs, cells, _ = X.shape
    xs, ys, zs = (ec.limbs_to_int_vec(a.reshape(-1, bi.L)) for a in (X, Y, Z))
    zs = [int(z) % p for z in zs]
    live = [k for k, z in enumerate(zs) if z]
    prefix, acc = [], 1
    for k in live:
        prefix.append(acc)
        acc = acc * zs[k] % p
    inv = pow(acc, -1, p)
    out = [bytes([0xC0]) + bytes(47)] * len(zs)
    r1, r2 = bi.R_INT % p, bi.R_INT * bi.R_INT % p
    for j in range(len(live) - 1, -1, -1):
        k = live[j]
        zi = inv * prefix[j] % p          # 1 / Z_m
        inv = inv * zs[k] % p
        zi2 = zi * zi % p
        # Montgomery X_m = X·R, Z_m = Z·R: X/Z^2 = X_m·R/Z_m^2 and
        # Y/Z^3 = Y_m·R^2/Z_m^3
        out[k] = cv.g1_to_bytes((int(xs[k]) * r1 % p * zi2 % p,
                                 int(ys[k]) * r2 % p * zi2 % p * zi % p))
    return [out[b * cells:(b + 1) * cells] for b in range(blobs)]


def _fk20_proofs(coeffs, blobs: int, settings) -> list[list[bytes]]:
    import jax

    return _compressed_proofs(*jax.device_get(
        _fk20_dispatch(coeffs, blobs, settings)))


# --- recovery: cells and proofs of every blob of a block -------------------


def recovery_path(settings) -> str:
    """Which rung `recover_cells_and_kzg_proofs_batch` serves a width on:
    ``device`` at and above `_DEVICE_MIN_WIDTH`, ``host`` below it."""
    return "device" if settings.width >= _DEVICE_MIN_WIDTH else "host"


class _RecoveryDomain:
    """What recovery reads of the extended domain, once a width: each
    cell's a_c = h_c^l, g^l for the shifted coset (g the primitive root),
    and for the device the shift g^j / N, the unshift g^-j / N and the
    twiddles of the size-N transforms (N = 2·width)."""

    def __init__(self, width: int):
        from lighthouse_tpu.crypto.kzg import PRIMITIVE_ROOT_OF_UNITY as g

        self.width = width
        self.n_cells, self.size = _cell_geometry(width)
        self.ext_roots = _compute_roots_of_unity(2 * width)
        nat_of_brp = _bit_reversal_permutation(list(range(2 * width)))
        self.a = [pow(_coset_start(c, self.size, self.ext_roots, nat_of_brp),
                      self.size, BLS_MODULUS) for c in range(self.n_cells)]
        self.g = g
        self.g_l = pow(g, self.size, BLS_MODULUS)
        self._device = None

    def device_constants(self):
        """(shift, unshift, tw, itw) as device arrays, made on first use."""
        if self._device is None:
            import jax.numpy as jnp
            import numpy as np

            ext, roots = 2 * self.width, self.ext_roots
            g_inv = pow(self.g, -1, BLS_MODULUS)
            shift = [pow(ext, -1, BLS_MODULUS)]
            unshift = [shift[0]]
            for _ in range(ext - 1):
                shift.append(shift[-1] * self.g % BLS_MODULUS)
                unshift.append(unshift[-1] * g_inv % BLS_MODULUS)
            stages = range(ext.bit_length() - 1)
            tw = [_mont_limbs([roots[(k >> s) << s]
                               for k in range(self.width)]) for s in stages]
            itw = [_mont_limbs([roots[-((k >> s) << s) % ext]
                                for k in range(self.width)])
                   for s in reversed(stages)]
            self._device = tuple(jnp.asarray(v) for v in (
                _mont_limbs(shift), _mont_limbs(unshift), np.stack(tw),
                np.stack(itw)))
        return self._device

    def vanishing(self, missing: list[int]):
        """Z(X) = z(X^l), z(Y) = prod over the missing cells d of (Y - a_d):
        Z on each cell's coset, z(a_c), and 1/Z on its shifted coset,
        1/z(g^l a_c), as Montgomery limb rows uint32[cells, L]."""
        from lighthouse_tpu.crypto.kzg import _batch_inverse

        on, shifted = [], []
        for a_c in self.a:
            y, p, q = self.g_l * a_c % BLS_MODULUS, 1, 1
            for d in missing:
                p = p * (a_c - self.a[d]) % BLS_MODULUS
                q = q * (y - self.a[d]) % BLS_MODULUS
            on.append(p)
            shifted.append(q)
        return _mont_limbs(on), _mont_limbs(_batch_inverse(shifted))


def _recovery_domain(settings) -> _RecoveryDomain:
    dom = getattr(settings, "_recovery_domain", None)
    if dom is None:
        dom = settings._recovery_domain = _RecoveryDomain(settings.width)
    return dom


def _check_recovery_input(cell_ids: list[int], cells_by_blob, n_cells: int,
                          cell_size: int) -> None:
    """The spec's asserts of recover_cells_and_kzg_proofs, as KzgError."""
    if len(set(cell_ids)) != len(cell_ids):
        raise KzgError("duplicate cell ids")
    if any(not 0 <= c < n_cells for c in cell_ids):
        raise KzgError("cell id out of range")
    if len(cell_ids) < n_cells // 2:
        raise KzgError(
            f"need at least {n_cells // 2} cells, got {len(cell_ids)}")
    for cells in cells_by_blob:
        if len(cells) != len(cell_ids):
            raise KzgError("cell_ids and cells length mismatch")
        if any(len(c) != cell_size * 32 for c in cells):
            raise KzgError("cell has the wrong size")


def recover_cells_and_kzg_proofs_batch(cell_indices: list[int],
                                       cells_by_blob: list[list[bytes]],
                                       settings
                                       ) -> tuple[list[list[bytes]],
                                                  list[list[bytes]]]:
    """The spec's recover_cells_and_kzg_proofs
    (polynomial-commitments-sampling.md) for every blob of a block: the
    blobs' cells at the same ``cell_indices`` (what a node holds of a
    block's columns) -> per blob all its cells, in cell order (a received
    cell as given), and all its cell proofs.

    Raises KzgError as the spec asserts: a repeated or out-of-range index,
    fewer than half of the cells, a cell of the wrong size or a
    non-canonical field element; and where more than half are given and
    they are no codeword (the recovered polynomial's degree reaches the
    width).  At and above `_DEVICE_MIN_WIDTH` two device programs serve
    every blob at once (``device``): `fr._recover_kernel` (the spec's
    recover_polynomialcoeff as radix-2 transforms, then the extension) and
    `msm._fk20_kernel` (every proof by FK20), one fetch after both; below
    it `recover_all_cells` and `compute_cells_and_kzg_proofs` a blob at a
    time (``host``).  Counter ``das_recovered_cells_total{path}``."""
    from lighthouse_tpu.crypto import kzg as _kzg

    n_cells, cell_size = _cell_geometry(settings.width)
    ids = [int(c) for c in cell_indices]
    _check_recovery_input(ids, cells_by_blob, n_cells, cell_size)
    _require_monomials(settings, cell_size)
    held = set(ids)
    missing = [c for c in range(n_cells) if c not in held]
    if recovery_path(settings) == "host":
        out_cells, out_proofs = [], []
        for cells in cells_by_blob:
            full = recover_all_cells(ids, cells, settings)
            out_cells.append(full)
            out_proofs.append(compute_cells_and_kzg_proofs(
                cells_to_blob(full, settings), settings)[1])
        _kzg.count_recovered_cells("host", len(missing) * len(cells_by_blob))
        return out_cells, out_proofs
    out = _recover_on_device(ids, missing, cells_by_blob, settings)
    _kzg.count_recovered_cells("device", len(missing) * len(cells_by_blob))
    return out


def _recover_on_device(ids, missing, cells_by_blob, settings):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lighthouse_tpu.crypto import kzg as _kzg
    from lighthouse_tpu.ops import fr

    span = _kzg.das_stage_span
    blobs = len(cells_by_blob)
    n_cells, cell_size = _cell_geometry(settings.width)
    dom = _recovery_domain(settings)
    with span("das.recover.layout", "recover_layout", cells=len(ids) * blobs):
        raw = np.frombuffer(b"".join(c for cells in cells_by_blob
                                     for c in cells), np.uint8).reshape(
            blobs, len(ids), cell_size, 32)
        if not _kzg._blob_fields_canonical(raw):
            raise KzgError("field element not canonical")
        e = np.zeros((blobs, n_cells, cell_size, fr.L), np.uint32)
        e[:, ids] = fr.be32_bytes_to_limbs(raw)
        z, zinv = dom.vanishing(missing)
    with span("das.recover.dispatch", "recover_dispatch"):
        cells_d, coeffs, ok = fr._recover_kernel(
            jnp.asarray(e.reshape(blobs, n_cells * cell_size, fr.L)),
            jnp.asarray(z), jnp.asarray(zinv), *dom.device_constants())
    _kzg.count_ntt_products(*fr._ntt_products(blobs, 2 * settings.width))
    with span("das.fk20.dispatch", "fk20_dispatch"):
        rows = _fk20_dispatch(coeffs, blobs, settings)
    with span("das.fetch", "fetch"):
        got, ok, (X, Y, Z) = jax.device_get((
            cells_d.reshape(blobs, n_cells, cell_size, fr.L)[:, missing], ok,
            rows))
        if not bool(np.all(ok)):
            raise KzgError("recovered polynomial exceeds blob degree "
                           "(inconsistent cells)")
        got = fr.limbs_to_be32_bytes(np.asarray(got))
    with span("das.proofs.affine", "proofs_affine",
              proofs=X.shape[0] * X.shape[1]):
        proofs = _compressed_proofs(X, Y, Z)
    slot_of = {c: k for k, c in enumerate(ids)}
    rebuilt = {c: k for k, c in enumerate(missing)}
    cells = [[cells_by_blob[r][slot_of[c]] if c in slot_of
              else got[r, rebuilt[c]].tobytes() for c in range(n_cells)]
             for r in range(blobs)]
    return cells, proofs


def _interpolation_commitment(cell: bytes, cid: int, settings):
    """[I_c(τ)]₁ for the cell's claimed evaluations."""
    from lighthouse_tpu.crypto import kzg as _kzg

    n_cells, cell_size = _cell_geometry(settings.width)
    coeffs = _interpolation_coeffs(cell, cid, settings)
    return _kzg.g1_lincomb(settings.g1_monomial[:cell_size], coeffs)


def _interpolation_coeffs(cell: bytes, cid: int, settings) -> list[int]:
    """Monomial coefficients of I_c (coset inverse-NTT, cs ≤ 64 so the
    O(cs²) direct transform is fine) — split out so the fused batch
    verifier can fold them straight onto the monomial setup points."""
    width = settings.width
    n_cells, cell_size = _cell_geometry(width)
    ext_roots = _compute_roots_of_unity(2 * width)
    nat_of_brp = _bit_reversal_permutation(list(range(2 * width)))
    vals = _cell_field_elements(cell, cell_size)
    # evaluation points: x_k = ext_roots[nat_of_brp[cid*cs + k]] = h·ω^{e_k}
    h = _coset_start(cid, cell_size, ext_roots, nat_of_brp)
    h_inv = pow(h, -1, BLS_MODULUS)
    # coset exponents e_k with x_k = h·ω^{e_k}, ω of order cs on the
    # doubled domain: ω = ext_roots[2*width // cell_size ... ] — recover
    # e_k directly from the position ratio
    omega = ext_roots[(2 * width // cell_size) % (2 * width)]
    # map each point to its ω-power via a lookup (cs entries)
    pow_of = {pow(omega, j, BLS_MODULUS): j for j in range(cell_size)}
    reordered = [0] * cell_size
    for k in range(cell_size):
        x = ext_roots[nat_of_brp[cid * cell_size + k]]
        j = pow_of[x * h_inv % BLS_MODULUS]
        reordered[j] = vals[k]
    cs_inv = pow(cell_size, -1, BLS_MODULUS)
    coeffs = []
    for m in range(cell_size):
        acc = 0
        for j, v in enumerate(reordered):
            acc = (acc + v * pow(omega, (-j * m) % cell_size, BLS_MODULUS)
                   ) % BLS_MODULUS
        coeffs.append(acc * cs_inv % BLS_MODULUS
                      * pow(h_inv, m, BLS_MODULUS) % BLS_MODULUS)
    return coeffs


def verify_cell_kzg_proof(commitment_bytes: bytes, cell_id: int,
                          cell: bytes, proof_bytes: bytes,
                          settings) -> bool:
    """e(C − [I(τ)]₁, −G₂) · e(π, [Z(τ)]₂) == 1."""
    from lighthouse_tpu.crypto.bls import curve as cv

    width = settings.width
    n_cells, cell_size = _cell_geometry(width)
    _require_monomials(settings, cell_size)
    if not 0 <= int(cell_id) < n_cells:
        return False
    try:
        commitment = cv.g1_from_bytes(commitment_bytes)
        proof = cv.g1_from_bytes(proof_bytes)
        interp = _interpolation_commitment(cell, int(cell_id), settings)
    except (ValueError, KzgError):
        return False
    ext_roots = _compute_roots_of_unity(2 * width)
    nat_of_brp = _bit_reversal_permutation(list(range(2 * width)))
    h = _coset_start(int(cell_id), cell_size, ext_roots, nat_of_brp)
    a = pow(h, cell_size, BLS_MODULUS)
    z_tau_g2 = cv.g2_add(
        settings.g2_monomial[cell_size],
        cv.g2_neg(cv.g2_mul(cv.g2_generator(), a)))
    c_minus_i = cv.g1_add(commitment, cv.g1_neg(interp)) \
        if interp is not cv.INF else commitment
    from lighthouse_tpu.crypto.kzg import _pairing_check

    return _pairing_check([
        (c_minus_i, cv.g2_neg(cv.g2_generator())),
        (proof, z_tau_g2),
    ])


RANDOM_CHALLENGE_KZG_CELL_BATCH_DOMAIN = b"RCKZGCBATCH__V1_"

# below this many cells the device round-trips are not worth it
_CELL_BATCH_FUSED_MIN = 8
# lanes of one multi-scalar multiplication of a check: the bucket whose
# `_kzg_fused` program (2 x 2,048 lanes) a node already holds for a full
# blob_sidecars_by_range response.  A cell batch runs as the fewest
# checks that fit it: an 8,192-lane program for one full block's 2,773
# lanes would cost minutes of compile, a minute of reload at every start
# and the same device seconds
_FUSED_MSM_LANES = 2048
# lanes of one interpolation dispatch (rows x slots x groups x cell
# size): 32 x 64 x 2 x 64 for one full block of 21 blobs
_INTERP_MAX_LANES = 1 << 18


def compute_verify_cell_kzg_proof_batch_challenge(
        commitments: list[bytes], commitment_indices: list[int],
        cell_indices: list[int], cells: list[bytes], proofs: list[bytes],
        settings) -> int:
    """The spec's Fiat-Shamir challenge of a cell batch
    (polynomial-commitments-sampling.md): the domain, the blob and cell
    widths, the numbers of distinct commitments and of cells, the
    commitments, then a cell's commitment index, cell index, bytes and
    proof in turn."""
    import hashlib

    _, cell_size = _cell_geometry(settings.width)
    h = hashlib.sha256(RANDOM_CHALLENGE_KZG_CELL_BATCH_DOMAIN)
    for v in (settings.width, cell_size, len(commitments), len(cells)):
        h.update(v.to_bytes(8, "big"))
    h.update(b"".join(commitments))
    for ci, cid, cell, proof in zip(commitment_indices, cell_indices, cells,
                                    proofs):
        h.update(ci.to_bytes(8, "big") + cid.to_bytes(8, "big"))
        h.update(cell)
        h.update(proof)
    return int.from_bytes(h.digest(), "big") % BLS_MODULUS


class _CellDomain:
    """What cell verification reads of the extended domain, once a
    settings object: a column's coset shift h_c (as a_c = h_c^size and as
    the Montgomery limb rows of h_c^(-m) / size) and the inverse
    transform's omega^(-e_j m), e_j the bit-reversed position of
    evaluation j in its coset."""

    def __init__(self, width: int):
        self.n_cells, self.size = n_cells, size = _cell_geometry(width)
        ext_roots = _compute_roots_of_unity(2 * width)
        nat_of_brp = _bit_reversal_permutation(list(range(2 * width)))
        shifts = [ext_roots[nat_of_brp[c * size]] for c in range(n_cells)]
        self.a = [pow(h, size, BLS_MODULUS) for h in shifts]
        size_inv = pow(size, -1, BLS_MODULUS)
        scale = []
        for h in shifts:
            h_inv, acc = pow(h, -1, BLS_MODULUS), size_inv
            for _ in range(size):
                scale.append(acc)
                acc = acc * h_inv % BLS_MODULUS
        self.scale = _mont_limbs(scale).reshape(n_cells, size, -1)
        omega_inv = pow(ext_roots[(2 * width // size) % (2 * width)], -1,
                        BLS_MODULUS)
        powers = [pow(omega_inv, i, BLS_MODULUS) for i in range(size)]
        e = _bit_reversal_permutation(list(range(size)))
        self.idft = _mont_limbs(
            [powers[e[j] * m % size] for j in range(size)
             for m in range(size)]).reshape(size, size, -1)


def _cell_domain(settings) -> _CellDomain:
    dom = getattr(settings, "_cell_domain", None)
    if dom is None:
        dom = settings._cell_domain = _CellDomain(settings.width)
    return dom


def _mont_limbs(values: list[int]):
    """Montgomery limb rows uint32[n, L] of field elements, through the
    vectorized byte layout."""
    import numpy as np

    from lighthouse_tpu.ops import fr

    raw = b"".join((v * fr.FR.R_INT % BLS_MODULUS).to_bytes(32, "big")
                   for v in values)
    return fr.be32_bytes_to_limbs(
        np.frombuffer(raw, np.uint8).reshape(-1, 32))


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _split_groups(cell_ids: list[int], commitment_idx: list[int],
                  cell_size: int) -> list[tuple[int, int]]:
    """[(lo, hi)]: the cells of a batch as the fewest runs of whole
    sidecars, equal to within one, whose check fits the compiled bucket
    (its distinct commitments + its cells + the monomial points <=
    `_FUSED_MSM_LANES`).  A sidecar is a stretch of one cell index; one
    too long to fit alone is cut."""
    n = len(cell_ids)
    longest = (_FUSED_MSM_LANES - cell_size) // 2
    bounds = [0]
    for k in range(1, n):
        if cell_ids[k] != cell_ids[k - 1] or k - bounds[-1] >= longest:
            bounds.append(k)
    bounds.append(n)
    units = len(bounds) - 1
    for g in range(-(-(n + cell_size) // _FUSED_MSM_LANES), units + 1):
        cuts = [bounds[i * units // g] for i in range(g + 1)]
        groups = list(zip(cuts, cuts[1:]))
        if all(len(set(commitment_idx[lo:hi])) + hi - lo + cell_size
               <= _FUSED_MSM_LANES for lo, hi in groups):
            return groups
    raise AssertionError("a unit fits alone")


def _interp_layouts(groups, cell_ids, dom: _CellDomain):
    """The interpolation dispatches of a batch: [(first cell, shape
    (rows, slots, groups), position of each cell, column of each slot)].
    A slot is one column of one group; a cell's position is (row, slot,
    group) flattened.  Groups go together as long as the lanes allow;
    where a group's columns would not fit (few columns with many cells
    beside many with few), each of its cells takes a slot."""
    per_group = []
    for lo, hi in groups:
        keys = cell_ids[lo:hi]
        slot_of, rows = {}, {}
        place = []
        for c in keys:
            s = slot_of.setdefault(c, len(slot_of))
            place.append((rows.get(s, 0), s))
            rows[s] = place[-1][0] + 1
        if (_pow2(max(rows.values())) * _pow2(len(slot_of)) * dom.size
                > _INTERP_MAX_LANES):
            place = [(0, s) for s in range(hi - lo)]
            columns = list(keys)
        else:
            columns = list(slot_of)
        per_group.append((place, columns))
    out, g0 = [], 0
    while g0 < len(groups):
        take = 1
        while g0 + take < len(groups):
            b, c, g = _layout_shape(per_group[g0:g0 + take + 1])
            if b * c * g * dom.size > _INTERP_MAX_LANES:
                break
            take += 1
        chunk = per_group[g0:g0 + take]
        b, c, g = _layout_shape(chunk)
        position, columns = [], [[0] * g for _ in range(c)]
        for gi, (place, cols) in enumerate(chunk):
            position += [(row * c + s) * g + gi for row, s in place]
            for s, col in enumerate(cols):
                columns[s][gi] = col
        out.append((groups[g0][0], (b, c, g), position, columns))
        g0 += take
    return out


def _layout_shape(chunk) -> tuple[int, int, int]:
    """(rows, slots, groups) of groups laid out together, each a power
    of two."""
    return (_pow2(max(row for place, _ in chunk for row, _ in place) + 1),
            _pow2(max(len(cols) for _, cols in chunk)), _pow2(len(chunk)))


def verify_cell_kzg_proof_batch(commitments: list[bytes],
                                cell_ids: list[int], cells: list[bytes],
                                proofs: list[bytes], settings) -> bool:
    """The spec's verify_cell_kzg_proof_batch
    (consensus-specs specs/fulu/polynomial-commitments-sampling.md, the
    universal verification equation): every (commitment, cell index,
    cell, proof) must hold.

    With n cells, D distinct commitments C_i (deduplicated in order of
    first appearance), r the spec's challenge, h_c the coset shift of
    column c and a_c = h_c^size:

      e(sum_k r^k pi_k, [tau^size]G2)
        == e(sum_i w_i C_i - [A(tau)]G1 + sum_k r^k a_c(k) pi_k, G2)

    w_i the sum of r^k over the cells of commitment i, A(X) = sum_k r^k
    I_k(X) the aggregated interpolation polynomial (degree < size,
    committed on g1_monomial[:size]): the two-sum, two-pairing shape of
    kzg._kzg_fused.  Batches of >= _CELL_BATCH_FUSED_MIN cells ride the
    device plane: commitments (once each) and proofs decompressed and
    their membership dispatched, not waited for; the cells' field check,
    the challenge and limbs; A's coefficients from one device program
    (ops/fr._cell_interp_kernel, summed by column first); then the batch
    as the fewest GROUPS of whole sidecars that fit the compiled bucket
    (`_split_groups`), each its own check under its own powers of the one
    r, the verdict their conjunction; a group is packed while the device
    runs the one before.  Smaller batches keep the per-cell loop.  Which
    served is the ``path`` of the ``kzg.verify_cell_batch`` span and of
    ``kzg_cells_verified_total``."""
    from lighthouse_tpu.crypto import kzg as _kzg

    n = len(commitments)
    if not (n == len(cell_ids) == len(cells) == len(proofs)):
        return False
    if n == 0:
        return True
    n_cells, _ = _cell_geometry(settings.width)
    cell_ids = [int(c) for c in cell_ids]
    if any(not 0 <= c < n_cells for c in cell_ids):
        return False
    fused = n >= _CELL_BATCH_FUSED_MIN
    path = "fused" if fused else "host"
    with _kzg.stage_span("kzg.verify_cell_batch", "verify_cell_batch",
                         cells=n, columns=len(set(cell_ids)), path=path):
        if fused:
            verdict = _verify_cell_batch_fused(
                commitments, cell_ids, cells, proofs, settings)
        else:
            verdict = all(
                verify_cell_kzg_proof(c, cid, cell, pf, settings)
                for c, cid, cell, pf in zip(commitments, cell_ids, cells,
                                            proofs))
    _kzg.count_cells_verified(path, n)
    return verdict


def _verify_cell_batch_fused(commitments, cell_ids, cells, proofs,
                             settings) -> bool:
    import numpy as np

    from lighthouse_tpu.common import tracing
    from lighthouse_tpu.crypto import kzg as _kzg
    from lighthouse_tpu.crypto.bls import curve as cv
    from lighthouse_tpu.ops import fr
    from lighthouse_tpu.ops import msm as _msm
    from lighthouse_tpu.ops.bls_backend import dispatch_subgroup_check_g1

    span = _kzg.stage_span
    n = len(cells)
    try:
        _require_monomials(settings, _cell_geometry(settings.width)[1])
    except KzgError:
        return False
    dom = _cell_domain(settings)
    size = dom.size
    if any(len(cell) != size * 32 for cell in cells):
        return False
    index_of: dict[bytes, int] = {}
    commitment_idx = [index_of.setdefault(c, len(index_of))
                      for c in commitments]
    distinct = list(index_of)
    groups = _split_groups(cell_ids, commitment_idx, size)
    tracing.add_attrs(commitments=len(distinct), groups=len(groups))

    c_pts: dict[int, object] = {}
    pi_pts: list = []
    memberships = []

    def decode(lo, hi):
        """The points of cells [lo, hi): their proofs, and the
        commitments no earlier group brought; their membership
        dispatched, not waited for."""
        with span("kzg.decode", "decode", points=hi - lo):
            fresh = [i for i in dict.fromkeys(commitment_idx[lo:hi])
                     if i not in c_pts]
            for i in fresh:
                c_pts[i] = cv.g1_from_bytes(distinct[i],
                                            subgroup_check=False)
            pi_pts.extend(cv.g1_from_bytes(p, subgroup_check=False)
                          for p in proofs[lo:hi])
            memberships.append(dispatch_subgroup_check_g1(
                [p for p in [c_pts[i] for i in fresh] + pi_pts[lo:hi]
                 if p is not cv.INF]))

    try:
        decode(*groups[0])
        with span("kzg.canonical", "canonical"):
            raw = np.frombuffer(b"".join(cells), np.uint8).reshape(
                n, size, 32)
            if not _kzg._blob_fields_canonical(raw):
                return False
        with span("kzg.challenge", "challenge"):
            r = compute_verify_cell_kzg_proof_batch_challenge(
                distinct, commitment_idx, cell_ids, cells, proofs,
                settings)
            r_pows = [1] * n
            for k in range(1, n):
                r_pows[k] = r_pows[k - 1] * r % BLS_MODULUS
        with span("kzg.limbs", "limbs"):
            limbs = fr.be32_bytes_to_limbs(raw)
            rk = _mont_limbs(r_pows)
        interpolations = []
        with span("kzg.interp.dispatch", "interp_dispatch"):
            for lo, (b, c, g), position, columns in _interp_layouts(
                    groups, cell_ids, dom):
                hi = lo + len(position)
                v = np.zeros((b * c * g, size, fr.L), np.uint32)
                w = np.zeros((b * c * g, fr.L), np.uint32)
                v[position] = limbs[lo:hi]
                w[position] = rk[lo:hi]
                out, products = fr.interpolate_cells_dispatch(
                    v.reshape(b, c, g, size, fr.L),
                    w.reshape(b, c, g, fr.L), dom.idft,
                    dom.scale[np.asarray(columns)])
                _kzg.count_interp_products(products)
                interpolations.append(out)
            del limbs, raw
        for lo, hi in groups[1:]:
            decode(lo, hi)
    except ValueError:  # a point that is no point; in-flight work dropped
        return False
    with span("kzg.rlc", "rlc"):
        weights = []   # a group's ({commitment: w_i}, [r^k a_c])
        for lo, hi in groups:
            w_i: dict[int, int] = {}
            for k in range(lo, hi):
                i = commitment_idx[k]
                w_i[i] = (w_i.get(i, 0) + r_pows[k]) % BLS_MODULUS
            weights.append((w_i, [r_pows[k] * dom.a[cell_ids[k]]
                                  % BLS_MODULUS for k in range(lo, hi)]))
    with span("kzg.interp.fetch", "interp_fetch"):
        a_rows = [row for out in interpolations
                  for row in fr.interpolation_scalars(out)]
    checks = []
    for gi, ((lo, hi), (w_i, ra)) in enumerate(zip(groups, weights)):
        # no point enters a fold before its membership verdict is in; the
        # programs of every group were dispatched before the first check
        with span("kzg.decode.verdict", "decode"):
            if not memberships[gi].commit():
                return False
        lhs_points = ([c_pts[i] for i in w_i] + pi_pts[lo:hi]
                      + list(settings.g1_monomial[:size]))
        lhs_scalars = (list(w_i.values()) + ra
                       + [-a % BLS_MODULUS for a in a_rows[gi][:size]])
        live = len(lhs_points) + hi - lo
        _kzg.count_cell_lanes(
            live, 2 * _msm.bucket(len(lhs_points)) - live)
        checks.append(_kzg._kzg_fused_dispatch(
            lhs_points, lhs_scalars, pi_pts[lo:hi], r_pows[lo:hi],
            settings, tau_g2=settings.g2_monomial[size],
            cache_attr="_fused_g2_rows_cell"))
    # every check is read, so that a rejected batch is the same work
    return all([_kzg._kzg_fused_verdict(f) for f in checks])


def verify_cells_match_blob(cells: list[bytes], cell_ids: list[int],
                            blob: bytes, settings) -> bool:
    """Check cells against the blob they claim to extend (the data-level
    check available without cell multi-proofs)."""
    n_cells, _ = _cell_geometry(settings.width)
    if len(cells) != len(cell_ids):
        return False
    if any(not 0 <= cid < n_cells for cid in cell_ids):
        return False
    expected = compute_cells(blob, settings)
    return all(expected[cid] == cell
               for cid, cell in zip(cell_ids, cells))


__all__ = [
    "CELLS_PER_EXT_BLOB",
    "cells_to_blob",
    "compute_cells",
    "compute_cells_and_kzg_proofs",
    "compute_verify_cell_kzg_proof_batch_challenge",
    "recover_all_cells",
    "recover_cells_and_kzg_proofs_batch",
    "verify_cell_kzg_proof",
    "verify_cell_kzg_proof_batch",
    "verify_cells_match_blob",
]
