"""Overlapped BLS dispatch pipeline tests.

Consensus-critical equivalence: the chunked/double-buffered verify path
(ops/dispatch_pipeline + ops/bls_backend) must return verdicts identical
to the single-shot pipeline — same randomized-scalar semantics, same
fail-the-batch-then-bisect contract — across chunk boundaries, for
valid and invalid batches, flat and merged batches.  Plus the beacon
processor's non-blocking dispatch contract: the manager keeps draining
queues while a batch runs on the dedicated dispatch thread, and work
queued during the flight coalesces into one next sweep.

Shapes are chosen to reuse the persistently-cached compiled programs
(flat 4-lane chunks); only the tiny partial-combine program and the
same-message merge compile fresh on a cold cache.
"""

import asyncio
import os
import time

import numpy as np
import pytest

import jax

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.ops import bls_backend as bb
from lighthouse_tpu.ops import dispatch_pipeline as dp


def _sets(n, messages=None):
    """n signature sets; messages[i] picks each set's message (defaults
    to all-distinct, which keeps every chunk on the flat lane layout)."""
    sks = [bls.SecretKey.from_bytes(int(40 + i).to_bytes(32, "big"))
           for i in range(n)]
    if messages is None:
        messages = [bytes([0xA0 + i]) * 32 for i in range(n)]
    return sks, [bls.SignatureSet(sk.sign(messages[i]), [sk.public_key()],
                                  messages[i])
                 for i, sk in enumerate(sks)]


def _fresh(sets):
    """Re-wrap signatures so decompression/subgroup caches start cold."""
    return [bls.SignatureSet(bls.Signature(s.signature.to_bytes()),
                             s.pubkeys, s.message) for s in sets]


class TestPlanChunks:
    def test_single_chunk_below_threshold(self):
        assert dp.plan_chunks(4, 4) == [(0, 4)]
        assert dp.plan_chunks(3, 512) == [(0, 3)]

    def test_zero_disables(self):
        assert dp.plan_chunks(100, 0) == [(0, 100)]

    def test_fixed_pow2_chunks_with_tail(self):
        assert dp.plan_chunks(10, 4) == [(0, 4), (4, 8), (8, 10)]

    def test_non_pow2_rounds_down(self):
        assert dp.plan_chunks(9, 6) == [(0, 4), (4, 8), (8, 9)]

    def test_empty(self):
        assert dp.plan_chunks(0, 4) == []

    def test_chunk_size_resolution(self):
        assert dp.chunk_size(8) == 8
        old = os.environ.get("LHTPU_BLS_CHUNK")
        try:
            os.environ["LHTPU_BLS_CHUNK"] = "16"
            assert dp.chunk_size() == 16
            assert dp.chunk_size(4) == 4     # explicit beats env
        finally:
            if old is None:
                os.environ.pop("LHTPU_BLS_CHUNK", None)
            else:
                os.environ["LHTPU_BLS_CHUNK"] = old


class TestChunkedEquivalence:
    """Verdict identity between chunked and single-shot pipelines."""

    def test_valid_batch_across_chunk_boundary(self):
        _, sets = _sets(4)
        assert bb.verify_sets_pipeline(sets)                    # single-shot
        chunked = _fresh(sets)
        assert bb.verify_sets_pipeline(chunked, chunk_size=2)   # 2 chunks
        assert dp.LAST_BATCH["chunks"] == 2

    def test_randomized_verdict_identity(self):
        """Property: for seeded random batch compositions, the chunked
        verdict equals the single-shot verdict — valid AND tampered."""
        rng = np.random.default_rng(17)
        sks, sets = _sets(4)
        for trial in range(3):
            batch = _fresh(sets)
            tamper = rng.integers(0, len(batch) + 1)  # == len -> valid run
            if tamper < len(batch):
                wrong = sks[(tamper + 1) % len(sks)]
                batch[tamper] = bls.SignatureSet(
                    wrong.sign(batch[tamper].message),
                    batch[tamper].pubkeys, batch[tamper].message)
            single = bb.verify_sets_pipeline(_fresh(batch))
            chunked = bb.verify_sets_pipeline(_fresh(batch), chunk_size=2)
            assert single == chunked == (tamper == len(batch)), trial

    def test_bisection_attributes_across_chunks(self):
        """The fail-the-batch-then-bisect contract: with chunking forced
        on through the seam env var, bisection still attributes the one
        forged set, including when the failure sits at a chunk boundary."""
        from lighthouse_tpu.chain.attestation_verification import (
            verify_signature_sets_with_bisection,
        )

        sks, sets = _sets(4)
        bad = _fresh(sets)
        bad[2] = bls.SignatureSet(
            sks[0].sign(bad[2].message), bad[2].pubkeys, bad[2].message)
        old = os.environ.get("LHTPU_BLS_CHUNK")
        try:
            os.environ["LHTPU_BLS_CHUNK"] = "2"
            mask = verify_signature_sets_with_bisection(bad, backend="tpu")
        finally:
            if old is None:
                os.environ.pop("LHTPU_BLS_CHUNK", None)
            else:
                os.environ["LHTPU_BLS_CHUNK"] = old
        assert list(mask) == [True, True, False, True]

    def test_grouped_messages_across_chunks(self):
        """Messages repeating ACROSS chunk boundaries: the merge sums
        the whole batch a message before any chunk is laid out, so the
        chunked and single-shot runs agree."""
        msgs = [b"\x61" * 32, b"\x62" * 32] * 2          # A B A B
        _, sets = _sets(4, messages=msgs)
        assert bb.verify_sets_pipeline(sets)             # merged
        assert bb.verify_sets_pipeline(_fresh(sets), chunk_size=2)

    def test_empty_and_single_set(self):
        assert not bb.verify_signature_sets_device([])
        _, sets = _sets(1)
        assert bb.verify_sets_pipeline(sets, chunk_size=2)
        assert dp.LAST_BATCH["chunks"] == 1              # no split at n=1

    def test_async_subgroup_verdict_gates_commit(self):
        """A non-subgroup (on-curve) G2 signature fails the chunked batch
        at the deferred commit point; valid fresh signatures are only
        marked subgroup-checked when the whole verdict row passes."""
        from lighthouse_tpu.crypto.bls import curve as cv
        from lighthouse_tpu.crypto.bls.fields import P, Fq2

        rng = np.random.default_rng(13)
        while True:
            x = Fq2(int.from_bytes(rng.bytes(47), "big") % P,
                    int.from_bytes(rng.bytes(47), "big") % P)
            y = (x.square() * x + cv.B2).sqrt()
            if y is not None and not cv.g2_in_subgroup((x, y)):
                break
        _, sets = _sets(3)
        batch = _fresh(sets)
        batch[1] = bls.SignatureSet(
            bls.Signature(cv.g2_to_bytes((x, y))),
            batch[1].pubkeys, batch[1].message)
        assert not bb.verify_sets_pipeline(batch, chunk_size=2)
        assert not batch[1].signature.subgroup_checked()
        # a clean fresh batch marks its signatures after the verdict
        clean = _fresh(sets)
        assert not clean[0].signature.subgroup_checked()
        assert bb.verify_sets_pipeline(clean, chunk_size=2)
        assert all(s.signature.subgroup_checked() for s in clean)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 virtual devices")
def test_sharded_chunked_agrees_with_monolithic():
    """Mesh path: chunked double-buffered multi-pairing returns the same
    verdict as the one-dispatch sharded run (lane counts chosen so both
    reuse the cached per-device-2 compiled program)."""
    from lighthouse_tpu.parallel.bls_sharded import (
        verify_signature_sets_sharded,
    )

    sks, sets = _sets(6)
    assert verify_signature_sets_sharded(_fresh(sets), n_devices=2,
                                         chunk_size=4)
    assert dp.LAST_BATCH["chunks"] == 2                  # 7 pair lanes
    bad = _fresh(sets)
    bad[4] = bls.SignatureSet(sks[0].sign(bad[4].message),
                              bad[4].pubkeys, bad[4].message)
    assert not verify_signature_sets_sharded(bad, n_devices=2, chunk_size=4)


class TestProcessorDispatchThread:
    """The non-blocking integration: batches run on ONE dedicated
    dispatch thread while the manager keeps scheduling other work."""

    def _run(self, coro):
        return asyncio.run(coro)

    def test_manager_drains_while_batch_inflight(self):
        """Event-loop latency during a bulk batch is bounded by one
        dispatch, not by the batch: PROTECTED-lane work completes
        INSIDE the batch's tracing span window.

        The probe rides the protected lane (GOSSIP_BLOCK — the PR 8
        firehose drill's idiom) because priority isolation makes the
        unprotected lanes wait BY DESIGN here: with max_workers=2 the
        in-flight attestation batch occupies the single unprotected
        slot, so an unprotected probe (the old STATUS event)
        deterministically waited out the whole batch — that was the
        pre-existing "timing" failure, not flake."""
        from lighthouse_tpu.common import tracing
        from lighthouse_tpu.processor import (
            BeaconProcessor, WorkEvent, WorkType,
        )

        tracing.TRACER.clear()
        stamps = {}

        async def main():
            bp = BeaconProcessor(max_workers=2, batch_flush_ms=5)

            def batch_fn(ps):
                time.sleep(0.4)
                stamps["batch_done"] = time.monotonic()

            for i in range(2):
                bp.submit(WorkEvent(WorkType.GOSSIP_ATTESTATION, payload=i,
                                    process_batch=batch_fn))
            await bp.start()
            t0 = time.monotonic()
            while bp._dispatch_inflight == 0 and time.monotonic() - t0 < 2:
                await asyncio.sleep(0.005)
            assert bp._dispatch_inflight == 1
            submitted = time.monotonic()
            bp.submit(WorkEvent(
                WorkType.GOSSIP_BLOCK,
                process=lambda: stamps.__setitem__(
                    "probe_done", time.monotonic())))
            while "probe_done" not in stamps and \
                    time.monotonic() - submitted < 2:
                await asyncio.sleep(0.005)
            stamps["probe_latency"] = stamps["probe_done"] - submitted
            await bp.stop()

        self._run(main())
        # the protected-lane work finished while the device batch was in
        # flight, with latency far below the batch wall time
        assert stamps["probe_done"] < stamps["batch_done"]
        assert stamps["probe_latency"] < 0.2
        # the tracing timeline shows the same overlap: the work span sits
        # wholly inside the batch span's window
        tl = tracing.TRACER.timeline(tracing.UNSLOTTED)
        assert tl is not None
        spans = {s["name"]: s for s in tl["spans"]}
        batch = spans["beacon_processor.batch"]
        work = spans["beacon_processor.work"]
        assert work["attrs"]["work_type"] == "gossip_block"
        batch_end = batch["wall_start"] + batch["duration_ms"] / 1000.0
        work_end = work["wall_start"] + work["duration_ms"] / 1000.0
        assert batch["wall_start"] <= work["wall_start"]
        assert work_end < batch_end

    def test_events_during_flight_coalesce_into_one_sweep(self):
        """Batchable work arriving while the dispatch thread is busy
        merges into ONE next sweep instead of trickling out as several
        deadline-flushed mini batches."""
        from lighthouse_tpu.processor import (
            BeaconProcessor, WorkEvent, WorkType,
        )

        journal = []
        sweeps = []

        async def main():
            bp = BeaconProcessor(max_workers=2, batch_flush_ms=10,
                                 work_journal=journal.append)

            def batch_fn(ps):
                sweeps.append(len(ps))
                time.sleep(0.3)

            for i in range(2):
                bp.submit(WorkEvent(WorkType.GOSSIP_ATTESTATION, payload=i,
                                    process_batch=batch_fn))
            await bp.start()
            t0 = time.monotonic()
            while not sweeps and time.monotonic() - t0 < 2:
                await asyncio.sleep(0.005)
            # 5 more arrive spread over several flush deadlines, all
            # while sweep #1 occupies the dispatch thread
            for i in range(5):
                bp.submit(WorkEvent(WorkType.GOSSIP_ATTESTATION,
                                    payload=10 + i,
                                    process_batch=batch_fn))
                await asyncio.sleep(0.03)
            await bp.stop()

        self._run(main())
        assert sweeps == [2, 5]
        assert "GOSSIP_ATTESTATION_BATCH(5)" in journal

    def test_inflight_gauge_tracks_dispatch_thread(self):
        from lighthouse_tpu.common.metrics import REGISTRY
        from lighthouse_tpu.processor import (
            BeaconProcessor, WorkEvent, WorkType,
        )

        seen = []

        async def main():
            bp = BeaconProcessor(max_workers=2, batch_flush_ms=5)

            def batch_fn(ps):
                seen.append(REGISTRY.gauge(
                    "bls_pipeline_inflight_batches").value)
                time.sleep(0.05)

            for i in range(2):
                bp.submit(WorkEvent(WorkType.GOSSIP_ATTESTATION, payload=i,
                                    process_batch=batch_fn))
            await bp.start()
            await bp.stop()

        self._run(main())
        assert seen == [1.0]
        assert REGISTRY.gauge("bls_pipeline_inflight_batches").value == 0.0
