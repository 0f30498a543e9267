"""Operation pool + naive aggregation + max-cover tests."""

import numpy as np
import pytest

from lighthouse_tpu.pool import (
    CoverItem,
    NaiveAggregationPool,
    OperationPool,
    maximum_cover,
)
from lighthouse_tpu.testing import Harness


class TestMaxCover:
    def test_greedy_picks_heaviest_first(self):
        items = [
            CoverItem("a", {1: 1, 2: 1}),
            CoverItem("b", {2: 1, 3: 1, 4: 1}),
            CoverItem("c", {5: 1}),
        ]
        got = maximum_cover(items, 2)
        assert [c.item for c in got] == ["b", "a"]
        # 'a' credited only with its fresh element
        assert set(got[1].covering) == {1}

    def test_rescoring_drops_fully_covered(self):
        items = [
            CoverItem("big", {1: 5, 2: 5}),
            CoverItem("dup", {1: 5, 2: 5}),
            CoverItem("tail", {3: 1}),
        ]
        got = maximum_cover(items, 3)
        assert [c.item for c in got] == ["big", "tail"]

    def test_limit_respected(self):
        items = [CoverItem(i, {i: 1}) for i in range(10)]
        assert len(maximum_cover(items, 4)) == 4


@pytest.fixture(scope="module")
def harness():
    h = Harness(n_validators=64, fork="altair", real_crypto=False)
    from lighthouse_tpu.state_transition import state_transition

    # advance a couple of slots so attestations exist
    for _ in range(4):
        atts = [h.attest()] if int(h.state.slot) > 0 else []
        signed = h.produce_block(attestations=atts)
        state_transition(h.state, h.spec, signed, h._verify_strategy())
    return h


class TestNaiveAggregation:
    def test_disjoint_bits_fold(self, harness):
        att = harness.attest()
        n = len(att.aggregation_bits)
        pool = NaiveAggregationPool()

        def single(i):
            bits = [False] * n
            bits[i] = True
            return type(att)(aggregation_bits=bits, data=att.data,
                             signature=bytes(att.signature))

        assert pool.insert(single(0))
        assert pool.insert(single(1))
        assert not pool.insert(single(0))  # no new bits
        got = pool.get_aggregate(att.data)
        assert got is not None
        _, bits, _ = got
        assert bits[0] and bits[1] and not bits[2:].any()

    def test_prune_below(self, harness):
        att = harness.attest()
        pool = NaiveAggregationPool()
        pool.insert(att)
        pool.prune_below(int(att.data.slot) + 1)
        assert pool.get_aggregate(att.data) is None


class TestOperationPool:
    def test_attestation_subsumption(self, harness):
        att = harness.attest()
        pool = OperationPool()
        full = np.asarray(att.aggregation_bits, bool)
        assert pool.insert_attestation(att.data, full, bytes(att.signature))
        # a subset aggregate is subsumed
        sub = full.copy()
        sub[np.argmax(sub)] = False
        assert not pool.insert_attestation(att.data, sub, bytes(att.signature))
        assert pool.num_attestations() == 1

    def test_packing_covers_fresh_validators(self, harness):
        h = harness
        att = h.attest()
        pool = OperationPool()
        pool.insert_attestation(
            att.data, np.asarray(att.aggregation_bits, bool),
            bytes(att.signature))
        packed = pool.get_attestations(
            h.state, h.spec,
            lambda e: None,  # shuffle computed internally when None
            t=h.t)
        # all committee members already have target flags set (the harness
        # includes attestations in blocks) OR packing returns the att
        assert isinstance(packed, list)

    def test_exit_dedup_and_filter(self, harness):
        h = harness
        pool = OperationPool()
        from lighthouse_tpu.types.containers import (
            SignedVoluntaryExit, VoluntaryExit)
        ve = SignedVoluntaryExit(message=VoluntaryExit(epoch=0, validator_index=3),
                      signature=b"\x00" * 96)
        assert pool.insert_voluntary_exit(ve)
        assert not pool.insert_voluntary_exit(ve)
        got = pool.get_voluntary_exits(h.state, h.spec)
        assert len(got) == 1

    def test_attester_slashing_subsumption(self, harness):
        h = harness
        sl_cls = h.t.AttesterSlashing
        ia = h.t.IndexedAttestation
        att = h.attest()

        def slashing(indices):
            a = ia(attesting_indices=indices, data=att.data,
                   signature=b"\x00" * 96)
            return sl_cls(attestation_1=a, attestation_2=a)

        pool = OperationPool()
        assert pool.insert_attester_slashing(slashing([1, 2, 3]))
        assert not pool.insert_attester_slashing(slashing([1, 2]))
        assert pool.insert_attester_slashing(slashing([4]))

    def test_prune_drops_stale_attestations(self, harness):
        h = harness
        att = h.attest()
        pool = OperationPool()
        pool.insert_attestation(
            att.data, np.asarray(att.aggregation_bits, bool),
            bytes(att.signature))
        # a state far in the future prunes everything
        future = h.state.copy()
        future.slot = int(h.state.slot) + 10 * h.spec.slots_per_epoch
        pool.prune(future, h.spec)
        assert pool.num_attestations() == 0


class TestPreAggregation:
    """Pre-BLS coalescing (pool/pre_aggregation): the blinded
    same-message merge (the reference rung's, crypto/bls/api
    ._merge_on_host) must verify iff ALL constituents verify — the
    soundness property the firehose's pairing savings rest on.  Real
    crypto, tiny set counts (one pairing call per assertion)."""

    @pytest.fixture(scope="class")
    def keys(self):
        from lighthouse_tpu.crypto import bls

        return [bls.SecretKey.from_bytes(int(101 + i).to_bytes(32, "big"))
                for i in range(4)]

    def _singles(self, keys, msg):
        from lighthouse_tpu.crypto import bls

        return [bls.SignatureSet(sk.sign(msg), [sk.public_key()], msg)
                for sk in keys]

    def test_dedup_collapses_exact_duplicates(self, keys):
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.pool.pre_aggregation import dedup_sets

        msg = b"\x11" * 32
        s = self._singles(keys[:1], msg)[0]
        copy = bls.SignatureSet(
            bls.Signature(s.signature.to_bytes()), list(s.pubkeys), msg)
        out, stats = dedup_sets([s, copy, s])
        assert len(out) == 1
        assert stats.deduped == 2
        assert stats.pairings_saved == 2

    def test_merged_verifies_when_all_constituents_valid(self, keys):
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.pool.pre_aggregation import merge_same_message

        msg = b"\x22" * 32
        out, stats = merge_same_message(self._singles(keys, msg))
        assert len(out) == 1 and stats.merged == len(keys)
        assert bls.verify_signature_sets(out)

    def test_merged_fails_when_any_constituent_invalid(self, keys):
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.pool.pre_aggregation import merge_same_message

        msg = b"\x33" * 32
        sets = self._singles(keys, msg)
        # one signer signed the WRONG message: a valid curve point, so
        # the fold proceeds — the merged verdict must still be False
        sets[2] = bls.SignatureSet(
            keys[2].sign(b"\x44" * 32), [keys[2].public_key()], msg)
        out, _ = merge_same_message(sets)
        assert len(out) == 1
        assert not bls.verify_signature_sets(out)

    def test_blinding_defeats_cancelling_pair(self, keys):
        """The adversarial case the blinders exist for: two invalid
        signatures crafted so their SUM equals the sum of two valid
        ones.  An unblinded fold would verify; the blinded merge must
        reject (up to 2^-64)."""
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.crypto.bls import curve as cv
        from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2
        from lighthouse_tpu.pool.pre_aggregation import merge_same_message

        msg = b"\x55" * 32
        good = [sk.sign(msg) for sk in keys[:2]]
        delta = hash_to_g2(b"adversarial offset")
        plus = cv.g2_add(good[0].point, delta)
        minus = cv.g2_add(good[1].point, cv.g2_neg(delta))
        forged = [bls.Signature(cv.g2_to_bytes(plus), plus),
                  bls.Signature(cv.g2_to_bytes(minus), minus)]
        sets = [bls.SignatureSet(sig, [sk.public_key()], msg)
                for sig, sk in zip(forged, keys[:2])]
        # sanity: the naive (unblinded) sum would have cancelled
        naive_sum = cv.g2_add(plus, minus)
        honest_sum = cv.g2_add(good[0].point, good[1].point)
        assert cv.g2_to_bytes(naive_sum) == cv.g2_to_bytes(honest_sum)
        out, stats = merge_same_message(sets)
        assert len(out) == 1 and stats.merged == 2
        assert not bls.verify_signature_sets(out)

    def test_overlapping_aggregate_bitfields_merge_as_multiset(self, keys):
        """Two committee aggregates with OVERLAPPING bitfields (a shared
        attester) merge as a pubkey multiset: valid pair verifies, one
        bad aggregate poisons the merged verdict."""
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.pool.pre_aggregation import merge_same_message

        msg = b"\x66" * 32
        sig_a = bls.Signature.aggregate([keys[0].sign(msg),
                                         keys[1].sign(msg)])
        sig_b = bls.Signature.aggregate([keys[1].sign(msg),
                                         keys[2].sign(msg)])
        set_a = bls.SignatureSet(
            sig_a, [keys[0].public_key(), keys[1].public_key()], msg)
        set_b = bls.SignatureSet(
            sig_b, [keys[1].public_key(), keys[2].public_key()], msg)
        out, stats = merge_same_message([set_a, set_b])
        assert len(out) == 1 and stats.merged == 2
        assert bls.verify_signature_sets(out)
        # same overlap, but aggregate B is missing a contribution
        bad_b = bls.SignatureSet(
            bls.Signature(keys[1].sign(msg).to_bytes()),
            [keys[1].public_key(), keys[2].public_key()], msg)
        out, _ = merge_same_message([set_a, bad_b])
        assert len(out) == 1
        assert not bls.verify_signature_sets(out)

    def test_unmergeable_fake_signatures_pass_through(self):
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.pool.pre_aggregation import merge_same_message

        msg = b"\x77" * 32
        fake = [bls.SignatureSet(bls.Signature(bytes([i]) * 96),
                                 [], msg) for i in range(2, 4)]
        out, stats = merge_same_message(fake)
        assert len(out) == 2 and stats.merged == 0
        assert stats.unmergeable == 2

    def test_distinct_messages_stay_separate(self, keys):
        from lighthouse_tpu.pool.pre_aggregation import merge_same_message

        sets = (self._singles(keys[:1], b"\x88" * 32)
                + self._singles(keys[1:2], b"\x99" * 32))
        out, stats = merge_same_message(sets)
        assert len(out) == 2 and stats.merged == 0

    def test_env_kill_switch(self, keys, monkeypatch):
        from lighthouse_tpu.pool.pre_aggregation import coalesce_sets

        monkeypatch.setenv("LHTPU_PRE_BLS", "0")
        sets = self._singles(keys, b"\xaa" * 32)
        out, stats = coalesce_sets(sets)
        assert out == sets and stats.pairings_saved == 0

    def test_reference_rung_merges_unless_switched_off(self, keys,
                                                       monkeypatch):
        """The reference rung folds same-message sets itself
        (crypto/bls/api._merge_on_host), counted under path="host";
        LHTPU_PRE_BLS=0 leaves them unmerged and the verdict unchanged."""
        from lighthouse_tpu.common.metrics import REGISTRY
        from lighthouse_tpu.crypto import bls

        host = REGISTRY.counter("pre_bls_merged_total").labels(path="host")
        sets = self._singles(keys, b"\xbb" * 32)
        before = host.value
        assert bls.verify_signature_sets(sets, backend="reference")
        assert host.value - before == len(keys)
        monkeypatch.setenv("LHTPU_PRE_BLS", "0")
        assert bls.verify_signature_sets(sets, backend="reference")
        assert host.value - before == len(keys)


def test_pool_prunes_are_accounted():
    """LH603 contract: pool evictions increment pool_dropped_total."""
    from lighthouse_tpu.common.metrics import REGISTRY

    fam = REGISTRY.counter(
        "pool_dropped_total",
        "items discarded from the aggregation/operation pools, by "
        "pool and reason")
    child = fam.labels(pool="naive_aggregation", reason="finalized")
    before = child.value
    h = Harness(n_validators=64, fork="altair", real_crypto=False)
    from lighthouse_tpu.state_transition import state_transition

    signed = h.produce_block()
    state_transition(h.state, h.spec, signed, h._verify_strategy())
    att = h.attest()
    pool = NaiveAggregationPool()
    pool.insert(att)
    pool.prune_below(int(att.data.slot) + 1)
    assert child.value == before + 1


def test_chain_packs_pool_attestations():
    """End-to-end: gossip attestations flow naive-pool -> op-pool ->
    produced block (VERDICT round-1 #7: produce_block_on must pack from
    the pool, not the caller)."""
    from lighthouse_tpu.chain.beacon_chain import BeaconChain
    from lighthouse_tpu.state_transition import state_transition

    h = Harness(n_validators=64, fork="altair", real_crypto=False)
    chain = BeaconChain(h.spec, h.state.copy(), verify_signatures=False)
    signed = h.produce_block()
    state_transition(h.state, h.spec, signed, h._verify_strategy())
    chain.slot_clock.set_slot(int(signed.message.slot))
    chain.process_block(signed)

    # committee members gossip single-bit attestations for the head block
    att = h.attest()
    n = len(att.aggregation_bits)
    singles = []
    for i in range(n):
        bits = [False] * n
        bits[i] = True
        singles.append(type(att)(aggregation_bits=bits, data=att.data,
                                 signature=bytes(att.signature)))
    chain.slot_clock.set_slot(int(att.data.slot) + 1)
    verified, rejects = chain.verify_attestations_for_gossip(singles)
    assert len(verified) == n, rejects

    epoch = h.spec.compute_epoch_at_slot(int(att.data.slot) + 1)
    randao = b"\x00" * 96
    block, proposer = chain.produce_block_on(
        int(att.data.slot) + 1, randao)
    packed = list(block.body.attestations)
    assert len(packed) >= 1
    got_bits = np.asarray(packed[0].aggregation_bits, bool)
    assert got_bits.all(), "pool aggregate should cover the whole committee"
