"""The key-aggregation fold at every set width (ISSUE 35).

An electra aggregate (EIP-7549) carries the keys of up to 64 committees in
one signature set: wider than a segment of the fold's compiled shape, so
``aggregate_pubkeys_device`` cuts it into sub-segments and adds their
partial sums on the host.  The lane cap is monkeypatched to 64 lanes
(segments of 8 keys + 8 blinding lanes, four to a slice), as
``test_device_pairing``'s lane-cap test does, so one small program serves
every case.  Oracles: ``SignatureSet.aggregate_pubkey()`` and the plain
reference ``benchmarks/reference/bls_plain.py``.
"""

import random

import jax
import numpy as np
import pytest

from benchmarks.reference import bls_plain as ref
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import REGISTRY
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls import curve as cv
from lighthouse_tpu.ops import bigint as bi
from lighthouse_tpu.ops import bls_backend as bb

CAP = 64          # lanes a slice
SEG_KEYS = 8      # key lanes a segment at that cap
SLICE = 4         # segments a slice


@pytest.fixture(scope="module")
def keys():
    sks = [bls.SecretKey.from_bytes(int(900 + i).to_bytes(32, "big"))
           for i in range(24)]
    return sks, [sk.public_key() for sk in sks]


@pytest.fixture
def small_cap(monkeypatch):
    monkeypatch.setattr(bb, "_AGG_MAX_LANES", CAP)


@pytest.fixture
def dispatched(monkeypatch):
    """Shapes handed to ``blinded_fold_device``: [(lanes, segments)]."""
    seen = []
    real = bb._msm.blinded_fold_device

    def spy(X, Y, Z, ux, uy, n_segments):
        assert X.shape == Y.shape == Z.shape
        seen.append((X.shape[0], n_segments))
        return real(X, Y, Z, ux, uy, n_segments)

    monkeypatch.setattr(bb._msm, "blinded_fold_device", spy)
    return seen


def _neg(pk):
    return bls.PublicKey(cv.g1_to_bytes(cv.g1_neg(pk.point)))


def _sets(members):
    sig = bls.Signature(b"\xc0" + b"\x00" * 95)   # never read by the fold
    return [bls.SignatureSet(sig, list(m), b"\x35" * 32) for m in members]


def _aggregate(sets):
    """(rows as affine int points or None for a flagged set, attrs)."""
    with tracing.span("bls.aggregate") as sp:
        xa, ya, inf = bb.aggregate_pubkeys_device(sets)
    assert xa.shape == ya.shape == (len(sets), bi.L) and inf.shape == (len(sets),)
    return [None if inf[i] else (int(bi.from_mont(xa[i])), int(bi.from_mont(ya[i])))
            for i in range(len(sets))], sp.attrs


def _cases(pks):
    opposing = [pks[1], _neg(pks[1])]
    return {
        # 21 keys at 8 a segment: three sub-segments, the last of 5 keys
        "set_spanning_several_segments": [pks[:21], pks[:7], pks[3:6]],
        # wide, one-segment and single-key sets in one batch: rows come
        # back in set order
        "mixed_widths_in_set_order": [pks[:1], pks[:20], pks[2:7], pks[5:6],
                                      pks[4:21], pks[:8], pks[9:10]],
        # the first sub-segment sums to the identity, the set does not
        "identity_partial_sum_in_a_live_set": [opposing * 4 + pks[:8],
                                               pks[:5], pks[2:12]],
        # both sub-segments sum to the identity: the SET is flagged
        "identity_set_still_flagged": [opposing * 8, pks[:9], pks[:3]],
        # two equal partial sums: the second step doubles
        "two_equal_partial_sums": [pks[:8] + pks[:8], pks[:12]],
        # one key twenty times: duplicates inside and across segments
        "duplicates_across_segment_boundaries": [[pks[3]] * 20,
                                                 [pks[2], pks[2]], pks[:9]],
    }


CASES = ["set_spanning_several_segments", "mixed_widths_in_set_order",
         "identity_partial_sum_in_a_live_set", "identity_set_still_flagged",
         "two_equal_partial_sums", "duplicates_across_segment_boundaries"]


@pytest.mark.parametrize("case", CASES)
def test_fold_matches_both_oracles(keys, small_cap, dispatched, case):
    sets = _sets(_cases(keys[1])[case])
    got, attrs = _aggregate(sets)
    for i, s in enumerate(sets):
        want = s.aggregate_pubkey()
        assert got[i] == (None if want is cv.INF else want), (case, i)
        assert ref.g1_sum([pk.point for pk in s.pubkeys]) == want, (case, i)
    if case == "identity_set_still_flagged":
        assert [g is None for g in got] == [True, False, False]
    else:
        assert None not in got
    widths = [len(s.pubkeys) for s in sets]
    segments = sum(-(-k // SEG_KEYS) for k in widths if k > 1)
    assert attrs["sets"] == len(sets) and attrs["widest"] == max(widths)
    assert attrs["segments"] == segments
    assert attrs["slices"] == len(dispatched) == -(-segments // SLICE)
    assert set(dispatched) == {(CAP, SLICE)} and attrs["lanes"] == CAP


@pytest.mark.parametrize("keys_in_set", [
    256,    # 64 committees x 2,048 keys over a 32,768-lane cap, in small
    64,     # 64 committees x 512: an aggregate at 2^20 validators
    65,     # one key over: the last sub-segment holds one key
])
def test_no_dispatch_over_the_lane_cap(keys, small_cap, dispatched,
                                       keys_in_set):
    pks = keys[1]
    wide = [pks[i % len(pks)] for i in range(keys_in_set)]
    sets = _sets([wide, pks[:8], pks[:1]])
    got, attrs = _aggregate(sets)
    assert got == [s.aggregate_pubkey() for s in sets]
    assert attrs["lanes"] <= CAP and attrs["widest"] == keys_in_set
    assert all(lanes <= CAP for lanes, _ in dispatched)
    assert len(dispatched) == attrs["slices"] == -(
        -(-(-keys_in_set // SEG_KEYS) + 1) // SLICE)
    # the blinding lanes stay at the segment's width, laid out once a shape
    # and kept on the device: the half of a slice beside its key lanes
    X0, _, Z0 = bb._BLIND_LANES[SEG_KEYS, SLICE]
    assert X0.shape == Z0.shape == (CAP // 2, bi.L)
    assert isinstance(X0, jax.Array)
    assert int((np.asarray(Z0) != 0).any(axis=1).sum()) == SEG_KEYS * SLICE


def test_sets_no_wider_than_a_segment_dispatch_as_before(keys, small_cap,
                                                         dispatched):
    """``block-131`` in small: committee-wide sets, a sync set drawn with
    replacement and two single-key sets dispatch the slices the policy
    before ISSUE 35 gave them (every set a segment of the widest set's
    pow2 width, as many sets a slice as the cap holds)."""
    pks = keys[1]
    rng = random.Random(35)
    members = [rng.sample(pks, 8) for _ in range(8)]
    members.append([rng.choice(pks) for _ in range(8)])
    members += [pks[:1], pks[1:2]]
    sets = _sets(members)
    got, attrs = _aggregate(sets)
    assert got == [s.aggregate_pubkey() for s in sets]
    seg = 2 * bb._next_pow2(max(len(m) for m in members))
    n_pad = min(bb._next_pow2(len(sets)), CAP // seg)
    before = [(seg * n_pad, n_pad)] * -(-len(sets) // n_pad)
    assert dispatched == before == [(64, 4)] * 3
    assert (attrs["slices"], attrs["lanes"]) == (3, 64)


def test_mainnet_shapes_at_the_real_cap():
    """No fold runs: the policy alone, at the cap the chip runs."""
    assert bb._AGG_MAX_LANES == 1 << 15
    block_131 = [512] * 128 + [512, 1, 1]
    assert bb._fold_shape(block_131) == (512, 32)       # 5 slices of 32 x 1,024
    electra = [32768] * 8 + [512, 1, 1]
    assert bb._fold_shape(electra) == (512, 32)         # 513 segments, 17 slices
    assert bb._fold_shape([64 * 2048]) == (512, 32)     # the preset's widest set
    assert bb._fold_shape([8, 11, 7]) == (16, 4)        # small batches as before
    for widths in (block_131, electra, [64 * 2048], [3]):
        max_k, n_pad = bb._fold_shape(widths)
        assert 2 * max_k * n_pad <= bb._AGG_MAX_LANES


def _counter(family, label):
    out = {}
    for line in REGISTRY.render().splitlines():
        if line.startswith(family + "{"):
            out[line.split(f'{label}="')[1].split('"')[0]] = float(
                line.rsplit(" ", 1)[1])
    return out


def test_spans_and_lane_counter(keys, small_cap, dispatched):
    pks = keys[1]
    sets = _sets([pks[:20], pks[:7], pks[:1], pks[2:19]])
    roots = []

    def sink(root, _slot):
        roots.append(root.to_dict())

    before = _counter("bls_fold_lanes_total", "kind")
    tracing.TRACER.add_sink(sink)
    try:
        with tracing.span("bls.aggregate"):
            bb.aggregate_pubkeys_device(sets)
    finally:
        tracing.TRACER.remove_sink(sink)
    (aggregate,) = [r for r in roots if r["name"] == "bls.aggregate"]
    assert aggregate["attrs"] == {"slices": 2, "lanes": 64, "sets": 4,
                                  "segments": 7, "widest": 20}
    children = [c["name"] for c in aggregate["children"]]
    assert children == ["bls.aggregate.layout", "bls.aggregate.dispatch"] * 2 + [
        "bls.aggregate.fetch", "bls.aggregate.combine"]
    stages = {line.split('stage="')[1].split('"')[0]
              for line in REGISTRY.render().splitlines()
              if line.startswith("bls_verify_stage_seconds_count{")}
    assert "aggregate_combine" in stages
    grown = {k: v - before.get(k, 0.0)
             for k, v in _counter("bls_fold_lanes_total", "kind").items()}
    # 44 member keys in segments; a blinding lane beside every key lane of
    # both slices; the rest of the key lanes empty
    assert grown == {"key": 44.0, "blinding": 64.0, "padding": 20.0}
    assert sum(grown.values()) == sum(lanes for lanes, _ in dispatched) == 128


def _signed_set(sks, pks, members, message):
    sig = bls.SecretKey(sum(sks[i].k for i in members) % ref.R).sign(message)
    return bls.SignatureSet(bls.Signature(sig.to_bytes()),
                            [pks[i] for i in members], message)


def _reference_verdict(sets, seed=35):
    return ref.verify_batch(
        [([pk.point for pk in s.pubkeys], s.message, s.signature.to_bytes())
         for s in sets], random.Random(seed))


@pytest.mark.parametrize("rows", ["resident", "first_seen"])
@pytest.mark.parametrize("variant", ["good", "two_signatures_swapped"])
def test_pipeline_on_an_electra_shaped_batch(keys, small_cap, variant, rows):
    """Two aggregates wider than a segment, a sync set drawn with
    replacement and a single-key set, each its own message; with keys
    the fold's key table holds, or keys it meets in this batch."""
    sks, pks = keys
    if rows == "first_seen":
        base = 7600 + 100 * (variant == "good")
        sks = [bls.SecretKey.from_bytes(int(base + i).to_bytes(32, "big"))
               for i in range(24)]
        pks = [sk.public_key() for sk in sks]
        assert all(pk._fold_row == -1 for pk in pks)
    rng = random.Random(7549)
    members = [rng.sample(range(24), 20), rng.sample(range(24), 17),
               [rng.randrange(24) for _ in range(8)], [5]]
    sets = [_signed_set(sks, pks, m, bytes([0x40 + i]) * 32)
            for i, m in enumerate(members)]
    if variant == "two_signatures_swapped":
        a, b = sets[0], sets[1]
        sets[0] = bls.SignatureSet(b.signature, a.pubkeys, a.message)
        sets[1] = bls.SignatureSet(a.signature, b.pubkeys, b.message)
    want = variant == "good"
    assert bb.verify_sets_pipeline(sets) is want
    assert _reference_verdict(sets) is want


def test_electra_block_sets_are_the_unions_committee_bits_name(small_cap):
    """The traffic's shape is the model's: a minimal-preset electra block
    with multi-committee attestations, through ``include_all_signatures``,
    gives one set an on-chain aggregate holding every attester of the
    committees its ``committee_bits`` name, and the seam's verdict on the
    block's sets is the plain reference's."""
    from lighthouse_tpu.state_transition import (
        SignatureStrategy,
        misc,
        signature_sets,
        state_advance,
        state_transition,
    )
    from lighthouse_tpu.testing import Harness

    h = Harness(64, fork="electra", real_crypto=True)
    spec = h.spec
    assert misc.get_committee_count_per_slot(spec, 64) == 2

    def aggregate(slot, bits):
        """All of the slot's committees in one attestation; ``bits`` picks
        the members that took part."""
        one = h.attest(slot=slot, committee_index=0)
        committees = [misc.get_beacon_committee(h.state, spec, slot, c)
                      for c in range(2)]
        attesters = np.concatenate(committees)[np.asarray(bits, bool)]
        epoch = spec.compute_epoch_at_slot(slot)
        domain = misc.get_domain(h.state, spec, spec.domain_beacon_attester,
                                 epoch)
        root = misc.compute_signing_root(one.data.hash_tree_root(), domain)
        sig = h._aggregate_sign([h.sk(int(v)) for v in attesters], root)
        return h.t.AttestationElectra(
            aggregation_bits=list(bits), data=one.data,
            committee_bits=[True, True, False, False],
            signature=sig.to_bytes()), {int(v) for v in attesters}

    attestations, unions = [], []
    for bits in ([True] * 8, [True, True, False, True, True, True, False, True]):
        signed = h.produce_block()
        state_transition(h.state, spec, signed,
                         SignatureStrategy.NO_VERIFICATION)
        att, union = aggregate(int(h.state.slot), bits)
        attestations.append(att)
        unions.append(union)
    signed = h.produce_block(attestations=attestations)
    pre = h.state.copy()
    state_advance(pre, spec, int(signed.message.slot))
    sets = signature_sets.include_all_signatures(
        pre, spec, signed, include_proposal=False)
    # randao, the two aggregates, the sync aggregate
    assert [len(s.pubkeys) for s in sets] == [
        1, 8, 6, spec.preset.sync_committee_size]
    assert len(attestations) <= spec.preset.max_attestations_electra == 8
    pubkeys = pre.validators.pubkeys
    for s, union in zip(sets[1:3], unions):
        assert {pk.to_bytes() for pk in s.pubkeys} == {
            pubkeys[v].tobytes() for v in union}
    assert bb.verify_sets_pipeline(sets) is _reference_verdict(sets) is True
    swapped = list(sets)
    swapped[1] = bls.SignatureSet(sets[2].signature, sets[1].pubkeys,
                                  sets[1].message)
    swapped[2] = bls.SignatureSet(sets[1].signature, sets[2].pubkeys,
                                  sets[2].message)
    assert bb.verify_sets_pipeline(swapped) is _reference_verdict(swapped) is False
