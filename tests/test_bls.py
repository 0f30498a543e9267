"""BLS12-381 reference implementation: algebra, vectors, batch semantics.

Ground truths used (all public test data):
- interop keypairs (sk -> pk) from the eth2 interop spec, as shipped in the
  reference's common/eth2_interop_keypairs/specs/keygen_10_validators.yaml
- a real staking-deposit-CLI signature (mainnet fork, validator_manager
  test vectors in the reference repo) — exercises the full chain:
  SSZ signing root + domain, hash-to-curve (SSWU + derived 3-isogeny +
  cofactor clearing), pairing, point (de)serialization.
"""

import pytest

from lighthouse_tpu import types as T
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls import curve as cv
from lighthouse_tpu.crypto.bls import hash_to_curve as h2c
from lighthouse_tpu.crypto.bls.fields import Fq2, R, P

INTEROP = [
    ("0x25295f0d1d592a90b333e26e85149708208e9f8e8bc18f6c77bd62f8ad7a6866",
     "a99a76ed7796f7be22d5b7e85deeb7c5677e88e511e0b337618f8c4eb61349b4bf2d153f649f7b53359fe8b94a38e44c"),
    ("0x51d0b65185db6989ab0b560d6deed19c7ead0e24b9b6372cbecb1f26bdfad000",
     "b89bebc699769726a318c8e9971bd3171297c61aea4a6578a7a4f94b547dcba5bac16a89108b6b6a1fe3695d1a874a0b"),
    ("0x315ed405fafe339603932eebe8dbfd650ce5dafa561f6928664c75db85f97857",
     "a3a32b0f8b4ddb83f1a0a853d81dd725dfe577d4f4c3db8ece52ce2b026eca84815c1a7e8e92a4de3d755733bf7e4a9b"),
]

# Real deposit (staking-deposit-cli 2.7.0, mainnet):
# reference validator_manager/test_vectors/.../deposit_data-1715584089.json
DEPOSIT_PK = "88b6b3a9b391fa5593e8bce8d06102df1a56248368086929709fbb4a8570dc6a560febeef8159b19789e9c1fd13572f0"
DEPOSIT_WC = "0049b6188ed20314309f617dd4030b8ddfac3c6e65759a03c226a13b2fe4cc72"
DEPOSIT_AMOUNT = 32000000000
DEPOSIT_SIG = (
    "8ac88247c1b431a2d1eb2c5f00e7b8467bc21d6dc267f1af9ef727a12e32b429"
    "9e3b289ae5734a328b3202478dd746a80bf9e15a2217240dca1fc1b91a6b7ff7"
    "a0f5830d9a2610c1c30f19912346271357c21bd9af35a74097ebbdda2ddaf491"
)
DEPOSIT_MSG_ROOT = "a9bc1d21cc009d9b10782a07213e37592c0d235463ed0117dec755758da90d51"


def _interop_sk(i):
    return bls.SecretKey.from_bytes(bytes.fromhex(INTEROP[i][0][2:]))


def test_generators_and_bilinearity():
    g1, g2 = cv.g1_generator(), cv.g2_generator()
    assert cv.g1_in_subgroup(g1) and cv.g2_in_subgroup(g2)
    e = cv.pairing(g1, g2)
    assert not e.is_one()
    assert e.pow(R).is_one()
    assert cv.pairing(cv.g1_mul(g1, 5), cv.g2_mul(g2, 3)) == e.pow(15)


@pytest.mark.parametrize("i", range(3))
def test_interop_pubkeys(i):
    sk = _interop_sk(i)
    assert sk.public_key().to_bytes().hex() == INTEROP[i][1]


def test_deposit_message_root_ssz():
    msg = T.DepositMessage(
        pubkey=bytes.fromhex(DEPOSIT_PK),
        withdrawal_credentials=bytes.fromhex(DEPOSIT_WC),
        amount=DEPOSIT_AMOUNT,
    )
    assert msg.hash_tree_root().hex() == DEPOSIT_MSG_ROOT


def _deposit_signing_root():
    fd = T.ForkData(current_version=b"\x00" * 4, genesis_validators_root=b"\x00" * 32)
    domain = b"\x03\x00\x00\x00" + fd.hash_tree_root()[:28]
    return T.SigningData(
        object_root=bytes.fromhex(DEPOSIT_MSG_ROOT), domain=domain
    ).hash_tree_root()


def test_real_deposit_signature_verifies():
    """End-to-end oracle: a real-world signature must verify."""
    pk = bls.PublicKey(bytes.fromhex(DEPOSIT_PK))
    sig = bls.Signature(bytes.fromhex(DEPOSIT_SIG))
    assert bls.verify(pk, _deposit_signing_root(), sig)


def test_real_deposit_signature_tamper_fails():
    pk = bls.PublicKey(bytes.fromhex(DEPOSIT_PK))
    sig = bls.Signature(bytes.fromhex(DEPOSIT_SIG))
    bad_root = bytearray(_deposit_signing_root())
    bad_root[0] ^= 1
    assert not bls.verify(pk, bytes(bad_root), sig)


def test_sign_verify_roundtrip():
    sk = _interop_sk(0)
    msg = b"\x11" * 32
    sig = sk.sign(msg)
    assert bls.verify(sk.public_key(), msg, sig)
    assert not bls.verify(sk.public_key(), b"\x22" * 32, sig)
    assert not bls.verify(_interop_sk(1).public_key(), msg, sig)


def test_fast_aggregate_verify():
    msg = b"\x33" * 32
    sks = [_interop_sk(i) for i in range(3)]
    sigs = [sk.sign(msg) for sk in sks]
    agg = bls.Signature.aggregate(sigs)
    pks = [sk.public_key() for sk in sks]
    assert bls.fast_aggregate_verify(pks, msg, agg)
    assert not bls.fast_aggregate_verify(pks[:2], msg, agg)
    assert not bls.fast_aggregate_verify([], msg, agg)


def test_verify_signature_sets_batch():
    m1, m2 = b"\x01" * 32, b"\x02" * 32
    sk0, sk1, sk2 = (_interop_sk(i) for i in range(3))
    agg = bls.Signature.aggregate([sk1.sign(m2), sk2.sign(m2)])
    sets = [
        bls.SignatureSet(sk0.sign(m1), [sk0.public_key()], m1),
        bls.SignatureSet(agg, [sk1.public_key(), sk2.public_key()], m2),
    ]
    assert bls.verify_signature_sets(sets)
    # tamper one message -> whole batch fails
    bad = [sets[0], bls.SignatureSet(agg, [sk1.public_key(), sk2.public_key()], m1)]
    assert not bls.verify_signature_sets(bad)
    assert not bls.verify_signature_sets([])


def test_fake_backend():
    sig = bls.Signature(b"\xc0" + b"\x00" * 95)
    s = bls.SignatureSet(sig, [bls.PublicKey(bytes.fromhex(DEPOSIT_PK))], b"\x00" * 32)
    assert bls.verify_signature_sets([s], backend="fake")
    assert not bls.verify_signature_sets([], backend="fake")


def test_infinity_signature_rejected():
    inf_sig = bls.Signature(b"\xc0" + b"\x00" * 95)
    pk = bls.PublicKey(bytes.fromhex(DEPOSIT_PK))
    assert not bls.verify(pk, b"\x00" * 32, inf_sig)
    assert not bls.verify_signature_sets(
        [bls.SignatureSet(inf_sig, [pk], b"\x00" * 32)]
    )


def test_infinity_pubkey_rejected():
    inf_pk = bls.PublicKey(b"\xc0" + b"\x00" * 47)
    sig = bls.Signature(bytes.fromhex(DEPOSIT_SIG))
    assert not bls.verify(inf_pk, b"\x00" * 32, sig)


def test_malformed_points_rejected():
    with pytest.raises(ValueError):
        cv.g1_from_bytes(b"\x00" * 48)  # no compression flag
    with pytest.raises(ValueError):
        cv.g1_from_bytes(b"\xff" * 48)  # x >= p
    with pytest.raises(ValueError):
        cv.g2_from_bytes(b"\x80" + b"\x11" * 95)  # not on curve (probably)


def test_g2_serialization_roundtrip():
    pt = cv.g2_mul(cv.g2_generator(), 987654321)
    assert cv.g2_from_bytes(cv.g2_to_bytes(pt)) == pt


def test_hash_to_g2_in_subgroup():
    pt = h2c.hash_to_g2(b"hello world")
    assert cv.g2_in_subgroup(pt)
    assert h2c.hash_to_g2(b"hello world") == pt  # deterministic
    assert h2c.hash_to_g2(b"hello worlds") != pt


def test_expand_message_xmd_properties():
    out = h2c.expand_message_xmd(b"msg", b"DST", 256)
    assert len(out) == 256
    assert h2c.expand_message_xmd(b"msg", b"DST", 256) == out
    assert h2c.expand_message_xmd(b"msg", b"DST2", 256) != out


def test_pinned_isogeny_matches_derivation():
    """The hardcoded iso map must be re-derivable from Vélu's formulas."""
    cands = h2c.derive_iso_candidates()
    pinned = h2c._ISO_MAP

    def eq(a, b):
        return len(a) == len(b) and all(x == y for x, y in zip(a, b))

    assert any(all(eq(c[i], pinned[i]) for i in range(4)) for c in cands)


def test_non_subgroup_point_rejected():
    """On-curve points outside the r-torsion subgroup must be rejected
    (invalid-point / small-subgroup attack defense)."""
    # find an on-curve G1 point that is NOT in the subgroup
    x = 1
    while True:
        y2 = (x * x * x + 4) % P
        y = pow(y2, (P + 1) // 4, P)
        if (y * y - y2) % P == 0:
            pt = (x, y)
            if not cv.g1_in_subgroup(pt):
                break
        x += 1
    assert cv.g1_is_on_curve(pt)
    raw = cv.g1_to_bytes(pt)
    with pytest.raises(ValueError, match="subgroup"):
        cv.g1_from_bytes(raw)
    # cofactor-cleared multiple IS accepted
    h1 = 0x396C8C005555E1568C00AAAB0000AAAB  # G1 cofactor
    cleared = cv.g1_mul(pt, h1)
    assert cv.g1_in_subgroup(cleared)


def test_fq2_sqrt_total():
    import random

    rng = random.Random(7)
    for _ in range(20):
        x = Fq2(rng.randrange(P), rng.randrange(P))
        s = x.sqrt()
        if s is None:
            # then x is a non-square: x^((q-1)/2) == -1 via norm criterion
            assert not x.legendre_is_square()
        else:
            assert s.square() == x


def test_fast_cofactor_clearing_matches_h_eff():
    import numpy as np

    from lighthouse_tpu.crypto.bls import curve as cv
    from lighthouse_tpu.crypto.bls import hash_to_curve as h2c
    from lighthouse_tpu.crypto.bls.fields import Fq2, P

    rng = np.random.default_rng(11)
    done = 0
    while done < 3:
        x = Fq2(int.from_bytes(rng.bytes(47), "big") % P,
                int.from_bytes(rng.bytes(47), "big") % P)
        y = (x.square() * x + cv.B2).sqrt()
        if y is None:
            continue
        assert h2c.clear_cofactor((x, y)) == h2c.clear_cofactor_slow((x, y))
        done += 1


def test_deferred_subgroup_check_semantics():
    # point_unchecked defers membership; .point completes it and raises
    # for a cofactor point
    import numpy as np
    import pytest

    from lighthouse_tpu.crypto.bls import curve as cv
    from lighthouse_tpu.crypto.bls.fields import Fq2, P

    rng = np.random.default_rng(13)
    while True:
        x = Fq2(int.from_bytes(rng.bytes(47), "big") % P,
                int.from_bytes(rng.bytes(47), "big") % P)
        y = (x.square() * x + cv.B2).sqrt()
        if y is not None and not cv.g2_in_subgroup((x, y)):
            break
    raw = cv.g2_to_bytes((x, y))
    sig = bls.Signature(raw)
    assert not sig.subgroup_checked()
    assert sig.point_unchecked() is not None  # decompresses fine
    with pytest.raises(bls.BlsError):
        _ = sig.point


def test_final_exp_without_native_is_the_host_python(monkeypatch):
    """The route behind the native library: _final_exp_is_one is
    final_exponentiation_fast, on a product that is one and on one that is
    not, and no device program is entered for it."""
    from lighthouse_tpu.common import device_telemetry as dtel
    from lighthouse_tpu.crypto.bls.fields import final_exponentiation_fast
    from lighthouse_tpu.crypto.bls.pairing_fast import multi_miller_fast
    from lighthouse_tpu.ops import bls_backend as bb
    from lighthouse_tpu.ops import native_bls

    g1, g2 = cv.g1_generator(), cv.g2_generator()
    is_one = multi_miller_fast([(g1, g2), (cv.g1_neg(g1), g2)])
    not_one = multi_miller_fast([(g1, g2), (g1, g2)])
    monkeypatch.setattr(native_bls, "available", lambda: False)
    dispatches = {e: st["dispatches"] for e, st in dtel.snapshot().items()}
    for f, want in ((is_one, True), (not_one, False)):
        assert final_exponentiation_fast(f).is_one() is want
        assert bb._final_exp_is_one(f) is want
    assert dispatches == {
        e: st["dispatches"] for e, st in dtel.snapshot().items()}


def test_grouped_layout_quantized():
    """jit shapes must not churn with batch composition: the grouped
    layout's lane total is exactly one or two flat layouts, seg stays a
    power of two (g1_segment_sum's contract), and unquantizable batches
    fall back to flat (seg None)."""
    from lighthouse_tpu.ops.bls_backend import _grouped_layout

    # the canonical ledger shape: 1024 sets over 64 messages, 16 each
    seg, g_pad, flat = _grouped_layout(1024, 64, 16)
    assert (seg, g_pad, flat) == (16, 64, 1024)
    # a skewed committee mix bumps seg to the 2x bucket, not to
    # next_pow2(max_sz)
    seg2, g_pad2, flat2 = _grouped_layout(2048, 64, 40)
    assert (seg2, g_pad2, flat2) == (64, 64, 2048)
    assert seg2 * g_pad2 == 2 * flat2
    # only two possible lane totals for any composition at this size
    totals = {
        _grouped_layout(2048, 64, m)[0] * 64
        for m in (1, 7, 20, 32, 33, 64)}
    assert totals <= {2048, 4096}
    # hopelessly skewed: one group holds nearly everything -> flat
    assert _grouped_layout(2048, 64, 100)[0] is None
    # degenerate: all distinct messages -> flat
    assert _grouped_layout(64, 64, 1)[0] is None
    # seg power-of-two invariant across a sweep
    for n in (8, 64, 512, 4096):
        for g in (2, 8, 32):
            for m in (1, 3, n // g if g < n else 1):
                seg_i, g_i, _ = _grouped_layout(n, min(g, n - 1), m)
                if seg_i is not None:
                    assert seg_i & (seg_i - 1) == 0
                    assert seg_i >= m
