"""PeerDAS cells: extension, cell split, erasure recovery.

The reference's equivalents are TODO stubs returning zeros
(crypto/kzg/src/lib.rs:169-216); these tests pin the real math."""

import numpy as np
import pytest

from lighthouse_tpu.crypto import das, kzg
from lighthouse_tpu.crypto.bls.fields import R


@pytest.fixture(scope="module")
def setup():
    s = kzg.KzgSettings.dev(width=64)
    rng = np.random.default_rng(7)
    blob = b"".join(kzg.bls_field_to_bytes(int(v) % R)
                    for v in rng.integers(0, 2**62, size=s.width))
    return s, blob, das.compute_cells(blob, s)


def test_geometry_and_roundtrip(setup):
    s, blob, cells = setup
    n_cells, cell_size = das._cell_geometry(s.width)
    assert len(cells) == n_cells == 128
    assert all(len(c) == cell_size * 32 for c in cells)
    assert das.cells_to_blob(cells, s) == blob


def test_recovery_from_any_half(setup):
    s, blob, cells = setup
    n = len(cells)
    for ids in (list(range(n // 2)),                 # first half
                [i for i in range(n) if i % 2 == 0],  # even cells
                list(range(n // 4, 3 * n // 4))):     # middle half
        rec = das.recover_all_cells(ids, [cells[i] for i in ids], s)
        assert rec == cells


def test_recovery_needs_half(setup):
    s, blob, cells = setup
    n = len(cells)
    ids = list(range(n // 2 - 1))
    with pytest.raises(kzg.KzgError, match="need at least"):
        das.recover_all_cells(ids, [cells[i] for i in ids], s)


def test_corrupt_cell_detected_with_redundancy(setup):
    s, blob, cells = setup
    n = len(cells)
    ids = list(range(3 * n // 4))
    bad = bytearray(cells[0])
    bad[5] ^= 1
    with pytest.raises(kzg.KzgError):
        das.recover_all_cells(
            ids, [bytes(bad)] + [cells[i] for i in ids[1:]], s)


def test_verify_cells_match_blob(setup):
    s, blob, cells = setup
    assert das.verify_cells_match_blob(cells[:4], [0, 1, 2, 3], blob, s)
    assert not das.verify_cells_match_blob([cells[1]], [0], blob, s)


def test_extension_is_polynomial(setup):
    """The extension really is the SAME degree<width polynomial: the
    second-half evaluations interpolate back to the first half."""
    s, blob, cells = setup
    n = len(cells)
    # recover using ONLY second-half cells; blob must come back exactly
    ids = list(range(n // 2, n))
    rec = das.recover_all_cells(ids, [cells[i] for i in ids], s)
    assert das.cells_to_blob(rec, s) == blob


class TestCellProofs:
    def test_compute_and_verify(self, setup):
        s, blob, cells = setup
        commitment = kzg.blob_to_kzg_commitment(blob, s)
        cells2, proofs = das.compute_cells_and_kzg_proofs(blob, s)
        assert cells2 == cells
        n_cells, _ = das._cell_geometry(s.width)
        for cid in (0, 1, n_cells // 2, n_cells - 1):
            assert das.verify_cell_kzg_proof(
                commitment, cid, cells[cid], proofs[cid], s)
        assert das.verify_cell_kzg_proof_batch(
            [commitment] * 3, [0, 5, 9],
            [cells[i] for i in (0, 5, 9)],
            [proofs[i] for i in (0, 5, 9)], s)

    def test_rejections(self, setup):
        s, blob, cells = setup
        commitment = kzg.blob_to_kzg_commitment(blob, s)
        _, proofs = das.compute_cells_and_kzg_proofs(blob, s)
        bad = bytearray(cells[0])
        bad[5] ^= 1
        assert not das.verify_cell_kzg_proof(
            commitment, 0, bytes(bad), proofs[0], s)
        assert not das.verify_cell_kzg_proof(
            commitment, 0, cells[0], proofs[1], s)   # wrong proof
        assert not das.verify_cell_kzg_proof(
            commitment, 1, cells[0], proofs[0], s)   # wrong id
        assert not das.verify_cell_kzg_proof_batch(
            [commitment], [0, 1], [cells[0]], [proofs[0]], s)  # ragged


class TestCellProofKnownAnswers:
    """Hand-derived pins (VERDICT r3 #7): expected values come from
    algebra on the INSECURE dev setup's known tau, never from the cell
    code under test.

    For p(x) = c (constant): commitment = c*G1 (sum of all Lagrange
    bases is 1), every extended evaluation is c, and every cell quotient
    poly is 0, so every cell proof is the point at infinity."""

    def test_constant_blob_commitment_is_c_times_g1(self):
        from lighthouse_tpu.crypto.bls import curve as cv

        s = kzg.KzgSettings.dev(width=64)
        c = 7
        blob = kzg.bls_field_to_bytes(c) * s.width
        commitment = kzg.blob_to_kzg_commitment(blob, s)
        expected = cv.g1_to_bytes(cv.g1_mul(cv.g1_generator(), c))
        assert commitment == expected

    def test_constant_blob_cells_and_infinity_proofs(self):
        s = kzg.KzgSettings.dev(width=64)
        c = 7
        blob = kzg.bls_field_to_bytes(c) * s.width
        cells, proofs = das.compute_cells_and_kzg_proofs(blob, s)
        want_elem = kzg.bls_field_to_bytes(c)
        for cell in cells:
            for k in range(0, len(cell), 32):
                assert cell[k:k + 32] == want_elem
        inf = bytes([0xC0]) + b"\x00" * 47
        assert all(p == inf for p in proofs)
        # and the infinity proofs VERIFY against c*G1
        commitment = kzg.blob_to_kzg_commitment(blob, s)
        for cid in (0, 1, len(cells) - 1):
            assert das.verify_cell_kzg_proof(
                commitment, cid, cells[cid], proofs[cid], s)

    def test_identity_poly_commitment_is_tau_g1(self):
        """p(x) = x: blob evaluations are the domain points themselves,
        commitment must equal tau*G1 = g1_monomial[1] (computed in the
        dev setup by scalar-multiplying the generator, independent of
        the Lagrange MSM under test).  Degree-2 likewise."""
        from lighthouse_tpu.crypto.bls import curve as cv

        s = kzg.KzgSettings.dev(width=64)
        blob_x = b"".join(kzg.bls_field_to_bytes(w) for w in s.roots_brp)
        assert kzg.blob_to_kzg_commitment(blob_x, s) == \
            cv.g1_to_bytes(s.g1_monomial[1])
        blob_x2 = b"".join(kzg.bls_field_to_bytes(w * w % R)
                           for w in s.roots_brp)
        assert kzg.blob_to_kzg_commitment(blob_x2, s) == \
            cv.g1_to_bytes(s.g1_monomial[2])

    def test_identity_poly_cell_contents_are_coset_points(self):
        """For p(x) = x the extended evaluations ARE the extended domain
        points: cell j must contain exactly the coset's roots of unity,
        computed here from first principles (2w-th primitive root)."""
        s = kzg.KzgSettings.dev(width=64)
        blob_x = b"".join(kzg.bls_field_to_bytes(w) for w in s.roots_brp)
        cells, proofs = das.compute_cells_and_kzg_proofs(blob_x, s)
        n_cells, cell_size = das._cell_geometry(s.width)
        ext_roots = das._compute_roots_of_unity(2 * s.width)
        brp = das._bit_reversal_permutation(list(range(2 * s.width)))
        for cid in (0, 3, n_cells - 1):
            got = das._cell_field_elements(cells[cid], cell_size)
            want = [ext_roots[brp[cid * cell_size + k]]
                    for k in range(cell_size)]
            assert got == want
        # known answer for the proofs themselves: p(x) - I(x) = x - a
        # on every coset, so the quotient is the CONSTANT 1 polynomial
        # and every cell proof is exactly 1*G1 = the generator
        from lighthouse_tpu.crypto.bls import curve as cv

        gen = cv.g1_to_bytes(cv.g1_generator())
        assert all(p == gen for p in proofs)
        commitment = kzg.blob_to_kzg_commitment(blob_x, s)
        assert das.verify_cell_kzg_proof(
            commitment, 0, cells[0], proofs[0], s)
        # a forged proof (2*G1 here — anything but the true quotient
        # commitment) must fail the pairing check
        forged = cv.g1_to_bytes(cv.g1_mul(cv.g1_generator(), 2))
        assert not das.verify_cell_kzg_proof(
            commitment, 0, cells[0], forged, s)


class TestFusedCellBatch:
    """The >=8-cell RLC fold (one fused dispatch) must agree with the
    per-cell pairing loop and reject forgeries."""

    def test_fused_batch_verifies_and_matches_percell(self, setup):
        s, blob, cells = setup
        commitment = kzg.blob_to_kzg_commitment(blob, s)
        _, proofs = das.compute_cells_and_kzg_proofs(blob, s)
        ids = [0, 3, 5, 9, 17, 31, 64, 100, 127]  # 9 >= fused threshold
        cms = [commitment] * len(ids)
        cls = [cells[i] for i in ids]
        pfs = [proofs[i] for i in ids]
        assert das.verify_cell_kzg_proof_batch(cms, ids, cls, pfs, s)
        # per-cell oracle agrees
        assert all(das.verify_cell_kzg_proof(commitment, i, cells[i],
                                             proofs[i], s) for i in ids)

    def test_fused_batch_rejects_forgery(self, setup):
        s, blob, cells = setup
        commitment = kzg.blob_to_kzg_commitment(blob, s)
        _, proofs = das.compute_cells_and_kzg_proofs(blob, s)
        ids = list(range(8))
        cms = [commitment] * 8
        cls = [cells[i] for i in ids]
        pfs = [proofs[i] for i in ids]
        # one tampered cell poisons the whole batch
        bad_cells = list(cls)
        bad = bytearray(bad_cells[4])
        bad[1] ^= 1
        bad_cells[4] = bytes(bad)
        assert not das.verify_cell_kzg_proof_batch(
            cms, ids, bad_cells, pfs, s)
        # swapped proofs poison it too
        pfs_sw = list(pfs)
        pfs_sw[0], pfs_sw[1] = pfs_sw[1], pfs_sw[0]
        assert not das.verify_cell_kzg_proof_batch(
            cms, ids, cls, pfs_sw, s)
        # wrong cell id
        bad_ids = list(ids)
        bad_ids[2] = 99
        assert not das.verify_cell_kzg_proof_batch(
            cms, bad_ids, cls, pfs, s)
        # out-of-range id fails closed
        assert not das.verify_cell_kzg_proof_batch(
            cms, [0, 1, 2, 3, 4, 5, 6, 999], cls, pfs, s)

    def test_fused_batch_multi_element_cells(self):
        """Width 256 -> cell_size 4: the monomial-coefficient fold
        covers more than one lane per cell."""
        import numpy as np

        s = kzg.KzgSettings.dev(width=256)
        rng = np.random.default_rng(23)
        blob = b"".join(kzg.bls_field_to_bytes(int(v))
                        for v in rng.integers(0, 2**62, size=s.width))
        commitment = kzg.blob_to_kzg_commitment(blob, s)
        # fixture via the per-cell builder: the FK20 COMPUTE path has
        # its own equivalence tests (tests/test_das_recovery.py); here
        # only the fused VERIFY shape is under test
        orig = das._DEVICE_MIN_WIDTH
        das._DEVICE_MIN_WIDTH = 1 << 30
        try:
            cells, proofs = das.compute_cells_and_kzg_proofs(blob, s)
        finally:
            das._DEVICE_MIN_WIDTH = orig
        ids = list(range(0, 96, 12))  # 8 cells
        assert das.verify_cell_kzg_proof_batch(
            [commitment] * len(ids), ids, [cells[i] for i in ids],
            [proofs[i] for i in ids], s)
        bad = bytearray(cells[ids[3]])
        bad[33] ^= 1
        cls = [cells[i] for i in ids]
        cls[3] = bytes(bad)
        assert not das.verify_cell_kzg_proof_batch(
            [commitment] * len(ids), ids, cls,
            [proofs[i] for i in ids], s)


@pytest.mark.skipif(
    __import__("os").environ.get("LHTPU_SLOW") != "1",
    reason="256 per-cell MSMs are minutes of host Python; set LHTPU_SLOW=1")
def test_batched_cell_proofs_match_percell_path():
    """Width 256 reaches _DEVICE_MIN_WIDTH: the FK20 program's proofs
    must be byte-identical to the per-cell g1_lincomb path's."""
    import numpy as np

    s = kzg.KzgSettings.dev(width=256)
    rng = np.random.default_rng(31)
    blob = b"".join(kzg.bls_field_to_bytes(int(v))
                    for v in rng.integers(0, 2**62, size=s.width))
    cells, proofs = das.compute_cells_and_kzg_proofs(blob, s)
    # per-cell oracle: force the g1_lincomb path on the same quotients
    import lighthouse_tpu.crypto.das as das_mod

    orig = das_mod._DEVICE_MIN_WIDTH
    das_mod._DEVICE_MIN_WIDTH = 1 << 30
    try:
        cells2, proofs2 = das.compute_cells_and_kzg_proofs(blob, s)
    finally:
        das_mod._DEVICE_MIN_WIDTH = orig
    assert cells == cells2
    assert proofs == proofs2
    # and the proofs actually verify (fused batch verifier)
    commitment = kzg.blob_to_kzg_commitment(blob, s)
    ids = list(range(0, 128, 16))
    assert das.verify_cell_kzg_proof_batch(
        [commitment] * len(ids), ids, [cells[i] for i in ids],
        [proofs[i] for i in ids], s)
