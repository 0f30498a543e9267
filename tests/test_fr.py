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

    @pytest.mark.parametrize("width", [2, 8, 64])
    def test_batch_inverse_pairs_halves(self, width):
        """Root w pairs with root w + width/2 (any pairing is a product
        tree; this one makes every level two contiguous halves): each of
        the four trees inverts its own row of the grid."""
        vals = [secrets.randbelow(R - 1) + 1 for _ in range(4 * width)]
        grid = jnp.asarray(fr.to_mont_host(vals)).reshape(4, width, fr.L)
        got = fr.from_mont_host(np.asarray(
            jax.jit(fr.batch_inv_mont)(grid)).reshape(4 * width, fr.L))
        assert [int(g) for g in got] == [pow(v, -1, R) for v in vals]

    def test_a_zero_poisons_its_own_tree_alone(self):
        vals = [secrets.randbelow(R - 1) + 1 for _ in range(16)]
        vals[5] = 0
        grid = jnp.asarray(fr.to_mont_host(vals)).reshape(2, 8, fr.L)
        got = fr.from_mont_host(np.asarray(
            jax.jit(fr.batch_inv_mont)(grid)).reshape(16, fr.L))
        assert [int(g) for g in got[8:]] == [pow(v, -1, R) for v in vals[8:]]
        assert all(int(g) == 0 for g in got[:8])

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

    @pytest.mark.parametrize("blobs,width", [(4, 16), (3, 8)])
    def test_eval_kernel_against_the_barycentric_formula(self, blobs, width):
        """_eval_kernel itself (its jit entry, its argument shapes) on a
        slice that holds a z == root blob: every other blob's y is the
        host formula's; three blobs are filled up to four inside."""
        from lighthouse_tpu.crypto import kzg

        settings = kzg.KzgSettings.dev(width=width)
        roots = settings.roots_brp
        polys = [[secrets.randbelow(R) for _ in range(width)]
                 for _ in range(blobs)]
        zs = [secrets.randbelow(R) for _ in range(blobs)]
        zs[1] = roots[width // 2 + 1]
        raw = jnp.asarray(np.stack(
            [np.stack([fr._int_to_limbs(v) for v in p]) for p in polys]))
        f = fr._to_mont_kernel(raw)
        assert f.shape == raw.shape
        assert [int(v) for v in fr.from_mont_host(np.asarray(f[0]))] == polys[0]
        y = fr._eval_kernel(
            f, jnp.asarray(fr.to_mont_host(zs)),
            jnp.asarray(fr.to_mont_host(roots)),
            jnp.asarray(fr.to_mont_host(pow(width, -1, R))))
        assert y.shape == (blobs, fr.L)
        got = [int(v) for v in fr.from_mont_host(np.asarray(y))]
        for i in (0, *range(2, blobs)):
            # the barycentric formula, term by term
            want = sum(p * w % R * pow(zs[i] - w, -1, R)
                       for p, w in zip(polys[i], roots)) % R
            want = want * (pow(zs[i], width, R) - 1) * pow(width, -1, R) % R
            assert got[i] == want
            assert want == kzg.evaluate_polynomial_in_evaluation_form(
                polys[i], zs[i], settings)

    def test_products_of_a_slice_are_what_the_programs_trace(self, monkeypatch):
        """`_slice_products` (what `kzg_eval_products_total` grows by a
        slice) against a tally of the lanes each multiply is traced
        with; the ladder's scan body is traced once and runs once a bit
        of the exponent."""
        tally = {"resident": 0, "materialized": 0}
        lm, mm = fr.mont_mul_lm, fr.mont_mul

        def count_lm(a, b):
            tally["resident"] += int(np.prod(a.shape[1:]))
            return lm(a, b)

        def count_mm(a, b):
            tally["materialized"] += int(np.prod(a.shape[:-1]))
            return mm(a, b)

        monkeypatch.setattr(fr, "mont_mul_lm", count_lm)
        monkeypatch.setattr(fr, "mont_mul", count_mm)
        rows = lambda *lead: jax.ShapeDtypeStruct(  # noqa: E731
            (*lead, fr.L), jnp.uint32)
        for n, w in ((2, 8), (5, 16)):
            tally.update(resident=0, materialized=0)
            jax.eval_shape(fr._to_mont_kernel._fn, rows(n, w))
            jax.eval_shape(fr._eval_kernel._fn, rows(n, w), rows(n),
                           rows(w), rows())
            assert fr._slice_products(n, w) == (
                tally["resident"],
                tally["materialized"] * len(fr._INV_EXP_BITS))
        # the cell's slice: 64 blobs of 4,096 field elements
        res, mat = fr._slice_products(64, 4096)
        assert (res, mat) == (1_573_568, 32_640)
        assert 100 * res / (res + mat) > 95

    def test_slices_count_their_products(self):
        from lighthouse_tpu.common.metrics import REGISTRY
        from lighthouse_tpu.crypto import kzg

        def grown():
            fam = REGISTRY.counter(
                "kzg_eval_products_total",
                "Fr lane-products of the evaluation slices, by multiply")
            return {k: fam.labels(multiply=k).value
                    for k in ("resident", "materialized")}

        settings = kzg.KzgSettings.dev(width=8)
        raw = np.zeros((5, 8, fr.L), np.uint32)
        before = grown()
        fr.evaluate_polynomials_batch(raw, [5] * 5, settings.roots_brp,
                                      max_blobs=2)
        after = grown()
        res, mat = fr._slice_products(2, 8)
        assert after["resident"] - before["resident"] == 3 * res
        assert after["materialized"] - before["materialized"] == 3 * mat

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
