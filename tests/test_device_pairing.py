"""Device pairing e2e tests.

The default suite exercises the full tpu BLS backend end-to-end on one
shared 4-lane compiled program (persistent compile cache in conftest keeps
repeat runs fast).  The per-lane scalar-oracle comparison compiles a
second program and stays behind LHTPU_SLOW=1.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

slow = pytest.mark.skipif(
    os.environ.get("LHTPU_SLOW") != "1",
    reason="extra compile shape; set LHTPU_SLOW=1")


@slow
def test_batch_miller_matches_scalar_oracle():
    from lighthouse_tpu.crypto.bls import curve as cv
    from lighthouse_tpu.crypto.bls.pairing_fast import miller_loop_fast
    from lighthouse_tpu.ops import bls12_381 as dev

    g1, g2 = cv.g1_generator(), cv.g2_generator()
    pairs = [(cv.g1_mul(g1, 7), cv.g2_mul(g2, 9)),
             (cv.g1_mul(g1, 1234567), cv.g2_mul(g2, 7654321))]
    cols, _ = dev.points_to_device(pairs)
    f = jax.jit(dev.batch_miller_loop)(*[jnp.asarray(c) for c in cols])
    f = jax.tree_util.tree_map(np.asarray, f)
    for lane in range(len(pairs)):
        fl = jax.tree_util.tree_map(lambda x: x[lane:lane + 1], f)
        assert dev.fq12_from_device(fl) == miller_loop_fast(*pairs[lane])


@slow
def test_jacobian_q_miller_matches_affine():
    """The zq path: Q lanes given in randomized Jacobian coordinates
    (X·Z², Y·Z³, Z) must produce the same FINAL-EXPONENTIATED value as
    the affine run — the Zq⁵ line factors must die in the final exp.
    This is the soundness base for the fused pipeline's inversion-free
    Σ r·sig lane."""
    from lighthouse_tpu.crypto.bls import curve as cv
    from lighthouse_tpu.crypto.bls.fields import P, final_exponentiation_fast
    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import bls12_381 as dev

    g1, g2 = cv.g1_generator(), cv.g2_generator()
    pairs = [(cv.g1_mul(g1, 7), cv.g2_mul(g2, 9)),
             (cv.g1_mul(g1, 31), cv.g2_mul(g2, 5)),
             (cv.g1_neg(cv.g1_mul(g1, 63)), g2),
             (cv.g1_neg(cv.g1_mul(g1, 155)), g2)]
    cols, _ = dev.points_to_device(pairs)
    n = len(pairs)

    # scale Q lanes into Jacobian form by per-lane Fq2 factors z_i
    zs = [cv.Fq2(3 + i, 11 * i + 1) for i in range(n)]
    xq = [p[1][0] * z * z for p, z in zip(pairs, zs)]
    yq = [p[1][1] * z * z * z for p, z in zip(pairs, zs)]

    def fq2_rows(vals):
        from lighthouse_tpu.ops import ec
        return (jnp.asarray(ec.ints_to_mont_limbs([v.a for v in vals])),
                jnp.asarray(ec.ints_to_mont_limbs([v.b for v in vals])))

    xqa, xqb = fq2_rows(xq)
    yqa, yqb = fq2_rows(yq)
    zqa, zqb = fq2_rows(zs)
    f_jac = jax.jit(lambda *a: dev.batch_miller_loop(*a[:6], zq=(a[6], a[7])))(
        jnp.asarray(cols[0]), jnp.asarray(cols[1]),
        xqa, xqb, yqa, yqb, zqa, zqb)
    f_aff = jax.jit(dev.batch_miller_loop)(*[jnp.asarray(c) for c in cols])
    # per-lane miller values differ by Fq2 factors; after the final exp
    # the products over any sub-batch must agree exactly
    mask = jnp.ones(n, bool)
    pj = dev.fq12_from_device(
        jax.tree_util.tree_map(np.asarray, dev.reduce_product(f_jac, mask)))
    pa = dev.fq12_from_device(
        jax.tree_util.tree_map(np.asarray, dev.reduce_product(f_aff, mask)))
    assert final_exponentiation_fast(pj) == final_exponentiation_fast(pa)
    # this specific product cancels: e(7G1,9G2)·e(-63G1,G2) != 1 but the
    # 4-lane set (7·9 + 31·5 - 63 - 155 = 0) is a valid cancellation
    assert final_exponentiation_fast(pj).is_one()


def test_multi_pairing_cancellation():
    from lighthouse_tpu.crypto.bls import curve as cv
    from lighthouse_tpu.ops import bls12_381 as dev

    g1, g2 = cv.g1_generator(), cv.g2_generator()
    pairs = [(cv.g1_mul(g1, 7), cv.g2_mul(g2, 9)),
             (cv.g1_neg(cv.g1_mul(g1, 63)), g2)]
    assert dev.multi_pairing_device(pairs).is_one()
    bad = [(cv.g1_mul(g1, 7), cv.g2_mul(g2, 9)),
           (cv.g1_neg(cv.g1_mul(g1, 64)), g2)]
    assert not dev.multi_pairing_device(bad).is_one()


def test_tpu_backend_verifies_real_signatures():
    from lighthouse_tpu.crypto import bls

    sks = [bls.SecretKey.from_bytes(bytes([0] * 31 + [i])) for i in (1, 2, 3)]
    msg = b"q" * 32
    sets = [bls.SignatureSet(sk.sign(msg), [sk.public_key()], msg)
            for sk in sks]
    assert bls.verify_signature_sets(sets, backend="tpu")
    # tampered signature fails
    sets[1] = bls.SignatureSet(
        sks[0].sign(b"other" + b"\x00" * 27), [sks[1].public_key()], msg)
    assert not bls.verify_signature_sets(sets, backend="tpu")


def test_tpu_backend_lazy_registration():
    """The round-1 regression: verify_signature_sets(backend='tpu') raised
    KeyError when the tpu backend had not been registered via set_backend
    yet (crypto/bls/api.py).  Simulate the fresh-process state by popping
    the registration."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import api

    api._BACKENDS.pop("tpu", None)
    sk = bls.SecretKey.from_bytes(bytes([0] * 31 + [9]))
    msg = b"z" * 32
    sets = [bls.SignatureSet(sk.sign(msg), [sk.public_key()], msg)]
    # must lazily register + verify without a prior set_backend call
    assert bls.verify_signature_sets(sets, backend="tpu")


class TestMessageGroupedPipeline:
    """The same-message merge (ops/bls_backend.py, msm._blinded_merge):
    sets sharing a message collapse to one Miller lane via
    e(Σ r_i·pk_i, H(m)).  Consensus-critical soundness: merged and flat
    runs must agree with each other and with the host oracle, on valid
    AND invalid batches."""

    def _sets(self, tamper: int | None = None):
        import numpy as np

        from lighthouse_tpu.crypto import bls

        rng = np.random.default_rng(3)
        msgs = [bytes(rng.integers(0, 256, 32, dtype=np.uint8))
                for _ in range(4)]
        sks = [bls.SecretKey.from_bytes(int(7 + i).to_bytes(32, "big"))
               for i in range(8)]
        pks = [sk.public_key() for sk in sks]
        sets = []
        for i in range(13):  # 13 sets over 4 messages -> the merge
            sk = sks[i % len(sks)]
            m = msgs[i % len(msgs)]
            sets.append(bls.SignatureSet(sk.sign(m), [pks[i % len(sks)]], m))
        if tamper is not None:
            # sign the right message with the WRONG key (signer sks[0],
            # claimed key pks[1]): only the merged G1 sums could hide
            # this if the layout were broken
            sets[tamper] = bls.SignatureSet(
                sks[0].sign(msgs[tamper % 4]), [pks[1]], msgs[tamper % 4])
        return sets

    def test_grouped_matches_flat_and_oracle_valid(self):
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.ops import bls_backend as bb

        sets = self._sets()
        assert bb.verify_sets_pipeline(sets)  # merged (dup messages)
        # flat fallback on the same sets: unique messages per set
        uniq = [s for i, s in enumerate(sets) if i < 4]
        assert bb.verify_sets_pipeline(uniq)
        # host reference oracle agrees
        assert bls.verify_signature_sets(sets, backend="reference")

    def test_grouped_rejects_wrong_key_in_group(self):
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.ops import bls_backend as bb

        sets = self._sets(tamper=5)
        assert not bb.verify_sets_pipeline(sets)
        assert not bls.verify_signature_sets(sets, backend="reference")

    def test_grouped_rejects_forged_signature(self):
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.ops import bls_backend as bb

        sets = self._sets()
        sets[7] = bls.SignatureSet(
            bls.SecretKey.from_bytes((99).to_bytes(32, "big")).sign(
                sets[7].message),
            sets[7].pubkeys, sets[7].message)
        assert not bb.verify_sets_pipeline(sets)

    def test_segment_sum_matches_host(self):
        """ec.g1_segment_sum against the host curve oracle."""
        import numpy as np

        import jax
        import jax.numpy as jnp

        from lighthouse_tpu.crypto.bls import curve as cv
        from lighthouse_tpu.crypto.bls.fields import P
        from lighthouse_tpu.ops import bigint as bi
        from lighthouse_tpu.ops import ec

        g1 = cv.g1_generator()
        pts = [cv.g1_mul(g1, 3 + i) for i in range(8)]
        # 2 groups of 4 (s-major layout: lane = s*G + g, G=2)
        xs = ec.ints_to_mont_limbs([p[0] for p in pts])
        ys = ec.ints_to_mont_limbs([p[1] for p in pts])
        # Jacobian lanes with Z = 1 (Montgomery form), then group-sum
        X, Y = jnp.asarray(xs), jnp.asarray(ys)
        Z = jnp.broadcast_to(bi._jconst("one_m"), X.shape)
        Xg, Yg, Zg = jax.jit(ec.g1_segment_sum, static_argnums=3)(
            X, Y, Z, 2)
        for g in range(2):
            x, y, z = (int(bi.from_mont(np.asarray(c)[g]))
                       for c in (Xg, Yg, Zg))
            zi = pow(z, -1, P)
            aff = (x * zi * zi % P, y * pow(zi, 3, P) % P)
            want = cv.INF
            for s in range(4):
                want = cv.g1_add(want, pts[s * 2 + g])
            assert aff == want, f"group {g} mismatch"


class TestDevicePubkeyAggregation:
    """aggregate_pubkeys_device vs the host per-set aggregation oracle."""

    def _keys(self, n=12):
        from lighthouse_tpu.crypto import bls

        sks = [bls.SecretKey.from_bytes(int(500 + i).to_bytes(32, "big"))
               for i in range(n)]
        return sks, [sk.public_key() for sk in sks]

    def test_matches_host_oracle_ragged(self):
        import numpy as np

        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.ops import bigint as bi
        from lighthouse_tpu.ops.bls_backend import aggregate_pubkeys_device

        sks, pks = self._keys()
        msg = b"\x11" * 32
        sig = sks[0].sign(msg)
        sets = [bls.SignatureSet(sig, pks[:k], msg) for k in (1, 5, 12, 3)]
        xa, ya, inf = aggregate_pubkeys_device(sets)
        assert not inf.any()
        for i, s in enumerate(sets):
            want = s.aggregate_pubkey()
            got = (int(bi.from_mont(xa[i])), int(bi.from_mont(ya[i])))
            assert got == want, i

    def test_lane_cap_slices_match_host_oracle(self, monkeypatch):
        """Over the lane cap the batch folds in equal-shaped slices of
        segments (the mainnet block's 131 x 512 keys does not fit one
        dispatch on a 16 GB chip); rows come back in set order."""
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.ops import bigint as bi
        from lighthouse_tpu.ops import bls_backend as bb

        sks, pks = self._keys()
        msg = b"\x12" * 32
        sig = sks[0].sign(msg)
        sets = [bls.SignatureSet(sig, pks[:k], msg)
                for k in (1, 5, 12, 3, 7)]
        # a cap of 64 lanes cuts a slice into four segments of 8 key + 8
        # blinding lanes: the 12-key set takes two, the single key none
        # (tests/test_electra_fold.py holds the policy at every width)
        monkeypatch.setattr(bb, "_AGG_MAX_LANES", 64)
        xa, ya, inf = bb.aggregate_pubkeys_device(sets)
        assert xa.shape[0] == len(sets) and not inf.any()
        for i, s in enumerate(sets):
            want = s.aggregate_pubkey()
            got = (int(bi.from_mont(xa[i])), int(bi.from_mont(ya[i])))
            assert got == want, i

    def test_identity_aggregate_flagged(self):
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.crypto.bls import curve as cv
        from lighthouse_tpu.ops.bls_backend import aggregate_pubkeys_device

        sks, pks = self._keys(4)
        msg = b"\x22" * 32
        sig = sks[0].sign(msg)
        neg = bls.PublicKey(cv.g1_to_bytes(cv.g1_neg(pks[1].point)))
        sets = [bls.SignatureSet(sig, pks[:3], msg),
                bls.SignatureSet(sig, [pks[1], neg] * 9, msg)]
        _, _, inf = aggregate_pubkeys_device(sets)
        assert list(inf) == [False, True]

    def test_pipeline_end_to_end_with_aggregation(self):
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.ops.bls_backend import verify_sets_pipeline

        sks, pks = self._keys()
        msg = b"\x33" * 32
        sets = []
        for lo, hi in ((0, 8), (1, 12), (2, 9)):
            sig = bls.Signature.aggregate(
                [sks[k].sign(msg) for k in range(lo, hi)])
            sets.append(bls.SignatureSet(
                bls.Signature(sig.to_bytes()), pks[lo:hi], msg))
        assert verify_sets_pipeline(sets)
        bad = list(sets)
        bad[1] = bls.SignatureSet(sets[0].signature, sets[1].pubkeys, msg)
        assert not verify_sets_pipeline(bad)

    def test_duplicate_keys_aggregate_correctly(self):
        # sync committees sample with replacement: duplicate member keys
        # are honest inputs and must not hit the incomplete H == 0 chord
        # (the blinding-lane design in aggregate_pubkeys_device)
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.ops import bigint as bi
        from lighthouse_tpu.ops.bls_backend import aggregate_pubkeys_device

        sks, pks = self._keys(8)
        msg = b"\x44" * 32
        sig = sks[0].sign(msg)
        sets = [
            bls.SignatureSet(sig, [pks[2], pks[2]], msg),
            bls.SignatureSet(sig, [pks[1]] * 8 + pks[3:7], msg),
        ]
        xa, ya, inf = aggregate_pubkeys_device(sets)
        assert not inf.any()
        for i, s in enumerate(sets):
            want = s.aggregate_pubkey()
            got = (int(bi.from_mont(xa[i])), int(bi.from_mont(ya[i])))
            assert got == want, i
