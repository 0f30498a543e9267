"""What is Fr's own in ops/fr.py — inversion, the blob byte layout, KZG
barycentric evaluation — against independent Python big-int oracles.
The field operations themselves are cases of tests/test_bigint.py."""

import secrets

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lighthouse_tpu.ops import fr

R = fr.R_INT


@pytest.fixture(scope="module")
def rand_pairs():
    a = [secrets.randbelow(R) for _ in range(16)]
    b = [secrets.randbelow(R) for _ in range(16)]
    return a, b, jnp.asarray(fr.to_mont_host(a)), jnp.asarray(
        fr.to_mont_host(b))


class TestInversionAndLayout:
    def test_batch_inverse_tree(self, rand_pairs):
        """Product-tree simultaneous inversion == per-lane Fermat ==
        python pow, over a [N, W] grid (the barycentric denominator
        shape)."""
        a, b, am, bm = rand_pairs
        vals = [(x * y + 1 + i) % R or 1
                for i, (x, y) in enumerate(zip(a * 2, b * 2))]
        grid = jnp.asarray(fr.to_mont_host(vals)).reshape(4, 8, fr.L)
        got = fr.from_mont_host(np.asarray(
            jax.jit(fr.batch_inv_mont)(grid)).reshape(32, fr.L))
        assert all(int(g) == pow(v, -1, R)
                   for g, v in zip(got, vals))

    def test_fermat_inverse(self, rand_pairs):
        a, _, am, _ = rand_pairs
        inv = fr.from_mont_host(np.asarray(jax.jit(fr.inv_mont)(am)))
        assert all(int(g) == pow(x, -1, R) for g, x in zip(inv, a))

    def test_bytes_to_limbs(self):
        raw = np.stack([
            np.frombuffer(secrets.randbelow(R).to_bytes(32, "big"), np.uint8)
            for _ in range(6)])
        limbs = fr.be32_bytes_to_limbs(raw)
        for row, lb in zip(raw, limbs):
            assert fr._limbs_to_int(lb) == int.from_bytes(
                row.tobytes(), "big")


class TestBarycentricEval:
    def test_matches_host_oracle_incl_root_hit(self):
        from lighthouse_tpu.crypto import kzg

        settings = kzg.KzgSettings.dev(width=8)
        N = 5
        polys = [[secrets.randbelow(R) for _ in range(8)] for _ in range(N)]
        zs = [secrets.randbelow(R) for _ in range(N - 1)]
        zs.append(settings.roots_brp[2])  # degenerate z == root case
        want = [kzg.evaluate_polynomial_in_evaluation_form(p, z, settings)
                for p, z in zip(polys, zs)]
        raw = np.stack(
            [np.stack([fr._int_to_limbs(v) for v in p]) for p in polys])
        got = fr.evaluate_polynomials_batch(raw, zs, settings.roots_brp)
        assert got == want

    def test_slice_fed_form_patches_a_root_hit_from_its_own_slice(self):
        """Five blobs fed in slices of two from their bytes, z == root in
        a blob of the second slice: its y is the host oracle's, taken
        from limbs that no one holds once the slice is dispatched."""
        from lighthouse_tpu.crypto import kzg

        settings = kzg.KzgSettings.dev(width=8)
        N = 5
        polys = [[secrets.randbelow(R) for _ in range(8)] for _ in range(N)]
        zs = [secrets.randbelow(R) for _ in range(N)]
        zs[3] = settings.roots_brp[6]
        want = [kzg.evaluate_polynomial_in_evaluation_form(p, z, settings)
                for p, z in zip(polys, zs)]
        assert want[3] == polys[3][6]
        blobs = [b"".join(v.to_bytes(32, "big") for v in p) for p in polys]
        asked = []

        def prepare(lo, hi):
            asked.append((lo, hi))
            raw = np.frombuffer(b"".join(blobs[lo:hi]), np.uint8)
            return (fr.be32_bytes_to_limbs(raw.reshape(hi - lo, 8, 32)),
                    zs[lo:hi])

        got = fr.evaluate_polynomial_slices(N, prepare, settings.roots_brp,
                                            max_blobs=2)
        assert got == (zs, want)
        assert asked == [(0, 2), (2, 4), (4, 5)]

    def test_what_prepare_raises_passes_through(self):
        from lighthouse_tpu.crypto import kzg

        settings = kzg.KzgSettings.dev(width=8)
        raw = np.zeros((3, 8, fr.L), np.uint32)

        def prepare(lo, hi):
            if lo == 2:
                raise kzg.KzgError("refused")
            return raw[lo:hi], [5] * (hi - lo)

        with pytest.raises(kzg.KzgError):
            fr.evaluate_polynomial_slices(3, prepare, settings.roots_brp,
                                          max_blobs=1)
