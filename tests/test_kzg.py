"""KZG commitment tests on a dev trusted setup (width 16)."""

import numpy as np
import pytest

from lighthouse_tpu.crypto import kzg
from lighthouse_tpu.crypto.bls import curve as cv
from lighthouse_tpu.crypto.bls.fields import R


@pytest.fixture(scope="module")
def settings():
    return kzg.KzgSettings.dev(width=16)


def _blob(settings, seed=0):
    rng = np.random.default_rng(seed)
    vals = [int(rng.integers(0, 2**63)) % R for _ in range(settings.width)]
    return b"".join(kzg.bls_field_to_bytes(v) for v in vals)


def test_roots_of_unity(settings):
    for w in settings.roots_brp:
        assert pow(w, 16, R) == 1
    assert len(set(settings.roots_brp)) == 16


def test_commitment_matches_direct_evaluation(settings):
    """Commitment from Lagrange setup == [p(τ)]G1 computed directly."""
    blob = _blob(settings, 1)
    poly = kzg.blob_to_polynomial(blob, settings)
    commitment = kzg.blob_to_kzg_commitment(blob, settings)
    # dev setup τ is known: evaluate p(τ) via barycentric and compare
    tau = 0x123456789ABCDEF
    p_tau = kzg.evaluate_polynomial_in_evaluation_form(poly, tau, settings)
    want = cv.g1_to_bytes(cv.g1_mul(cv.g1_generator(), p_tau))
    assert commitment == want


def test_eval_at_domain_point(settings):
    blob = _blob(settings, 2)
    poly = kzg.blob_to_polynomial(blob, settings)
    for i in (0, 5, 15):
        z = settings.roots_brp[i]
        assert kzg.evaluate_polynomial_in_evaluation_form(
            poly, z, settings) == poly[i]


def test_kzg_proof_roundtrip(settings):
    blob = _blob(settings, 3)
    commitment = kzg.blob_to_kzg_commitment(blob, settings)
    z = kzg.bls_field_to_bytes(987654321)
    proof, y = kzg.compute_kzg_proof(blob, z, settings)
    assert kzg.verify_kzg_proof(commitment, z, y, proof, settings)
    # wrong evaluation rejected
    y_bad = kzg.bls_field_to_bytes(
        (kzg.bytes_to_bls_field(y) + 1) % R)
    assert not kzg.verify_kzg_proof(commitment, z, y_bad, proof, settings)


def test_proof_at_domain_point(settings):
    blob = _blob(settings, 4)
    commitment = kzg.blob_to_kzg_commitment(blob, settings)
    z = kzg.bls_field_to_bytes(settings.roots_brp[7])
    proof, y = kzg.compute_kzg_proof(blob, z, settings)
    poly = kzg.blob_to_polynomial(blob, settings)
    assert kzg.bytes_to_bls_field(y) == poly[7]
    assert kzg.verify_kzg_proof(commitment, z, y, proof, settings)


def test_blob_proof_roundtrip(settings):
    blob = _blob(settings, 5)
    commitment = kzg.blob_to_kzg_commitment(blob, settings)
    proof = kzg.compute_blob_kzg_proof(blob, commitment, settings)
    assert kzg.verify_blob_kzg_proof(blob, commitment, proof, settings)
    # tampered blob rejected
    other = _blob(settings, 6)
    assert not kzg.verify_blob_kzg_proof(other, commitment, proof, settings)


def test_blob_proof_batch(settings):
    blobs = [_blob(settings, 10 + i) for i in range(4)]
    cs = [kzg.blob_to_kzg_commitment(b, settings) for b in blobs]
    proofs = [kzg.compute_blob_kzg_proof(b, c, settings)
              for b, c in zip(blobs, cs)]
    assert kzg.verify_blob_kzg_proof_batch(blobs, cs, proofs, settings)
    # one bad proof fails the batch
    bad = list(proofs)
    bad[2] = proofs[1]
    assert not kzg.verify_blob_kzg_proof_batch(blobs, cs, bad, settings)
    # empty batch verifies vacuously (reference behavior)
    assert kzg.verify_blob_kzg_proof_batch([], [], [], settings)


def test_blob_proof_batch_fused_device_path(settings):
    """>= _DEVICE_EVAL_MIN blobs ride the fused one-dispatch plane
    (device barycentric eval + both MSMs + pairing in one jit): valid
    batch accepts, one tampered proof rejects, and a non-canonical blob
    field is caught by the vectorized validity check."""
    n = kzg._DEVICE_EVAL_MIN
    blobs = [_blob(settings, 30 + i) for i in range(n)]
    cs = [kzg.blob_to_kzg_commitment(b, settings) for b in blobs]
    proofs = [kzg.compute_blob_kzg_proof(b, c, settings)
              for b, c in zip(blobs, cs)]
    assert kzg.verify_blob_kzg_proof_batch(blobs, cs, proofs, settings)
    bad = list(proofs)
    bad[3] = proofs[2]
    assert not kzg.verify_blob_kzg_proof_batch(blobs, cs, bad, settings)
    # non-canonical field element (>= BLS_MODULUS) rejected up front
    evil = list(blobs)
    evil[1] = b"\xff" * 32 + blobs[1][32:]
    assert not kzg.verify_blob_kzg_proof_batch(evil, cs, proofs, settings)


def test_fused_check_counts_its_products(settings):
    """`kzg_fused_products_total` grows by `_fused_products` a dispatched
    check (the batch shape of the test above: 2·8+1 points in a bucket of
    32, 64 lanes, no new program), nearly all of it resident."""
    from lighthouse_tpu.common.metrics import REGISTRY

    def grown():
        fam = REGISTRY.counter(
            "kzg_fused_products_total",
            "Fp lane-products of the fused KZG checks dispatched, by "
            "multiply")
        return {k: fam.labels(multiply=k).value
                for k in ("resident", "materialized")}

    n = kzg._DEVICE_EVAL_MIN
    blobs = [_blob(settings, 30 + i) for i in range(n)]
    cs = [kzg.blob_to_kzg_commitment(b, settings) for b in blobs]
    proofs = [kzg.compute_blob_kzg_proof(b, c, settings)
              for b, c in zip(blobs, cs)]
    before = grown()
    assert kzg.verify_blob_kzg_proof_batch(blobs, cs, proofs, settings)
    after = grown()
    res, mat = kzg._fused_products(64, 64)
    assert after["resident"] - before["resident"] == res
    assert after["materialized"] - before["materialized"] == mat
    assert 100 * res / (res + mat) > 85


@pytest.mark.parametrize("lanes", [4, 16])
def test_fused_products_are_what_the_program_traces(monkeypatch, lanes):
    """`_fused_products` against a tally of the lanes each multiply of
    `_kzg_fused` is traced with, a scan's body counted once a step."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops import bigint as bi

    tally = {"resident": 0, "materialized": 0}
    steps = [1]
    lm, mm, scan = bi.FP.mont_mul_lm, bi.mont_mul, jax.lax.scan

    def count_lm(a, b):
        tally["resident"] += steps[-1] * int(np.prod(a.shape[1:]))
        return lm(a, b)

    def count_mm(a, b):
        tally["materialized"] += steps[-1] * int(np.prod(a.shape[:-1]))
        return mm(a, b)

    def count_scan(f, init, xs, *args, **kwargs):
        steps.append(steps[-1] * jax.tree_util.tree_leaves(xs)[0].shape[0])
        try:
            return scan(f, init, xs, *args, **kwargs)
        finally:
            steps.pop()

    monkeypatch.setattr(bi.FP, "mont_mul_lm", count_lm)
    monkeypatch.setattr(bi, "mont_mul", count_mm)
    monkeypatch.setattr(jax.lax, "scan", count_scan)
    rows = lambda n: jax.ShapeDtypeStruct((n, bi.L), jnp.uint32)  # noqa: E731
    jax.eval_shape(
        kzg._kzg_fused_program()._fn, rows(lanes), rows(lanes),
        jax.ShapeDtypeStruct((64, lanes), jnp.uint32), *[rows(2)] * 4)
    assert kzg._fused_products(lanes, 64) == (
        tally["resident"], tally["materialized"])
    # the cells' shape: 4,096 lanes of 256-bit scalars
    res, mat = kzg._fused_products(4096, 64)
    assert (res, mat) == (12_259_296, 29_680)
    assert 100 * res / (res + mat) > 99


@pytest.mark.parametrize("lanes", [4, 2048])
def test_subgroup_products_are_what_the_program_traces(monkeypatch, lanes):
    """`_subgroup_products` (what `g1_subgroup_products_total` grows by a
    dispatch) against a tally of the lanes each multiply of
    `_g1_subgroup_kernel` is traced with, a scan's body counted once a
    step; 2,048 lanes is both KZG cells' shape."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import bls_backend as bb

    tally = {"resident": 0, "materialized": 0}
    steps = [1]
    lm, mm, scan = bi.FP.mont_mul_lm, bi.mont_mul, jax.lax.scan

    def count_lm(a, b):
        tally["resident"] += steps[-1] * int(np.prod(a.shape[1:]))
        return lm(a, b)

    def count_mm(a, b):
        tally["materialized"] += steps[-1] * int(np.prod(a.shape[:-1]))
        return mm(a, b)

    def count_scan(f, init, xs, *args, **kwargs):
        steps.append(steps[-1] * jax.tree_util.tree_leaves(xs)[0].shape[0])
        try:
            return scan(f, init, xs, *args, **kwargs)
        finally:
            steps.pop()

    monkeypatch.setattr(bi.FP, "mont_mul_lm", count_lm)
    monkeypatch.setattr(bi, "mont_mul", count_mm)
    monkeypatch.setattr(jax.lax, "scan", count_scan)
    rows = jax.ShapeDtypeStruct((lanes, bi.L), jnp.uint32)
    # the function under the jit: a jit's cache would hide a shape
    # another test traced before
    jax.eval_shape(bb._g1_subgroup_kernel._fn.__wrapped__, rows, rows)
    assert kzg._subgroup_products(lanes) == (
        tally["resident"], tally["materialized"])
    assert kzg._subgroup_products(2048) == (9_414_656, 0)


def test_constant_blob_infinity_proof(settings):
    """Constant polynomial -> zero quotient -> infinity proof point."""
    vals = [42] * settings.width
    blob = b"".join(kzg.bls_field_to_bytes(v) for v in vals)
    commitment = kzg.blob_to_kzg_commitment(blob, settings)
    proof = kzg.compute_blob_kzg_proof(blob, commitment, settings)
    assert kzg.verify_blob_kzg_proof(blob, commitment, proof, settings)


class TestTrustedSetupLoading:
    def _ceremony_fixture(self, width=16, tau=0x123456789ABCDEF):
        """Ceremony-FORMAT fixture from the dev τ: g1_lagrange in natural
        order (loader applies the bit-reversal permutation, like c-kzg)."""
        from lighthouse_tpu.crypto.bls import curve as cv
        from lighthouse_tpu.crypto.kzg import (
            BLS_MODULUS,
            _compute_roots_of_unity,
        )

        roots = _compute_roots_of_unity(width)
        tau_pow = pow(tau, width, BLS_MODULUS)
        g1 = cv.g1_generator()
        lagrange_natural = []
        for w_i in roots:
            num = w_i * (tau_pow - 1) % BLS_MODULUS
            den = width * (tau - w_i) % BLS_MODULUS
            l_i = num * pow(den, -1, BLS_MODULUS) % BLS_MODULUS
            lagrange_natural.append(cv.g1_mul(g1, l_i))
        return {
            "g1_lagrange": ["0x" + cv.g1_to_bytes(p).hex()
                            for p in lagrange_natural],
            "g2_monomial": [
                "0x" + cv.g2_to_bytes(cv.g2_generator()).hex(),
                "0x" + cv.g2_to_bytes(
                    cv.g2_mul(cv.g2_generator(), tau)).hex(),
            ],
        }

    def test_load_matches_dev_setup(self, tmp_path):
        import json as _json

        from lighthouse_tpu.crypto import kzg

        fixture = self._ceremony_fixture()
        path = tmp_path / "trusted_setup.json"
        path.write_text(_json.dumps(fixture))
        loaded = kzg.KzgSettings.load_trusted_setup(path, validate=True)
        dev = kzg.KzgSettings.dev(width=16)
        assert loaded.width == dev.width
        assert loaded.g1_lagrange_brp == dev.g1_lagrange_brp
        assert loaded.g2_tau == dev.g2_tau

    def test_loaded_setup_verifies_blobs(self, tmp_path):
        import json as _json

        import numpy as np

        from lighthouse_tpu.crypto import kzg
        from lighthouse_tpu.crypto.bls.fields import R

        fixture = self._ceremony_fixture()
        path = tmp_path / "trusted_setup.json"
        path.write_text(_json.dumps(fixture))
        s = kzg.KzgSettings.load_trusted_setup(str(path), validate=False)
        rng = np.random.default_rng(3)
        blob = b"".join(kzg.bls_field_to_bytes(int(v) % R)
                        for v in rng.integers(0, 2**62, size=s.width))
        c = kzg.blob_to_kzg_commitment(blob, s)
        proof = kzg.compute_blob_kzg_proof(blob, c, s)
        assert kzg.verify_blob_kzg_proof(blob, c, proof, s)
        bad = bytearray(blob)
        bad[5] ^= 1
        assert not kzg.verify_blob_kzg_proof(bytes(bad), c, proof, s)

    def test_generator_check_rejects_forged_file(self, tmp_path):
        import json as _json

        import pytest

        from lighthouse_tpu.crypto import kzg
        from lighthouse_tpu.crypto.bls import curve as cv

        fixture = self._ceremony_fixture()
        fixture["g2_monomial"][0] = "0x" + cv.g2_to_bytes(
            cv.g2_mul(cv.g2_generator(), 7)).hex()
        path = tmp_path / "bad.json"
        path.write_text(_json.dumps(fixture))
        with pytest.raises(kzg.KzgError):
            kzg.KzgSettings.load_trusted_setup(str(path))

    def test_official_ceremony_file(self):
        """The real mainnet ceremony output (the file the reference
        embeds): lagrange basis must sum to G1 (Σ L_i(τ) = 1)."""
        import os

        import pytest

        from lighthouse_tpu.crypto import kzg
        from lighthouse_tpu.crypto.bls import curve as cv

        path = ("/root/reference/common/eth2_network_config/"
                "built_in_network_configs/trusted_setup.json")
        if not os.path.exists(path):
            pytest.skip("official ceremony file not available")
        # validate=False: the full 4096-lane device check is the TPU
        # path; the lagrange-sum identity below is the stronger oracle
        s = kzg.KzgSettings.load_trusted_setup(path, validate=False)
        assert s.width == 4096
        acc = cv.INF
        for p in s.g1_lagrange_brp:
            acc = cv.g1_add(acc, p)
        assert acc == cv.g1_generator()
        assert cv.g2_in_subgroup_fast(s.g2_tau)


# order-3 point on E(Fq) (NOT in G1; 3 | h1) — the adversarial case the
# [r-1]P membership test must reject fail-closed
G1_ORDER3_POINT = (
    0x0,
    0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAA9,
)


class TestDeviceG1SubgroupCheck:
    def test_members_pass_cofactor_fails(self):
        from lighthouse_tpu.crypto.bls import curve as cv
        from lighthouse_tpu.ops.bls_backend import batch_subgroup_check_g1

        g = cv.g1_generator()
        pts = [g, cv.g1_mul(g, 7), G1_ORDER3_POINT, cv.g1_mul(g, 12345)]
        assert cv.g1_is_on_curve(G1_ORDER3_POINT)
        assert not cv.g1_in_subgroup(G1_ORDER3_POINT)
        ok = batch_subgroup_check_g1(pts)
        assert list(ok) == [True, True, False, True]

    def test_dispatch_counts_its_products(self):
        """`g1_subgroup_products_total` grows by `_subgroup_products` of the
        padded shape a dispatch, by the multiply that runs the products."""
        from lighthouse_tpu.common.metrics import REGISTRY
        from lighthouse_tpu.crypto.bls import curve as cv
        from lighthouse_tpu.ops.bls_backend import batch_subgroup_check_g1

        def grown():
            fam = REGISTRY.counter(
                "g1_subgroup_products_total",
                "Fp lane-products of the G1 membership programs "
                "dispatched, by multiply")
            return {k: fam.labels(multiply=k).value
                    for k in ("resident", "materialized")}

        g = cv.g1_generator()
        before = grown()
        assert list(batch_subgroup_check_g1([g, G1_ORDER3_POINT, g])) == [
            True, False, True]
        after = grown()
        res, mat = kzg._subgroup_products(4)
        assert after["resident"] - before["resident"] == res
        assert after["materialized"] - before["materialized"] == mat

    def test_validate_rejects_corrupt_setup(self, tmp_path):
        import json as _json

        import pytest

        from lighthouse_tpu.crypto import kzg
        from lighthouse_tpu.crypto.bls import curve as cv

        fixture = TestTrustedSetupLoading()._ceremony_fixture()
        fixture["g1_lagrange"][5] = "0x" + cv.g1_to_bytes(
            G1_ORDER3_POINT).hex()
        path = tmp_path / "corrupt.json"
        path.write_text(_json.dumps(fixture))
        with pytest.raises(kzg.KzgError, match="subgroup"):
            kzg.KzgSettings.load_trusted_setup(str(path), validate=True)

    def test_truncated_setup_rejected(self, tmp_path):
        import json as _json

        import pytest

        from lighthouse_tpu.crypto import kzg

        fixture = TestTrustedSetupLoading()._ceremony_fixture()
        fixture["g1_lagrange"] = fixture["g1_lagrange"][:15]
        path = tmp_path / "trunc.json"
        path.write_text(_json.dumps(fixture))
        with pytest.raises(kzg.KzgError, match="power of two"):
            kzg.KzgSettings.load_trusted_setup(str(path))
