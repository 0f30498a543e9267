"""The same-message merge of a gossip flood on the device path (XLA:CPU).

A processor batch of single-key attestations over a slot's two head votes
(EIP-7549: one message a vote, whatever the committee) through
``attestation_verification.batch_verify``: with the "tpu" backend the sets
reach the seam unmerged and `msm._blinded_merge` sums them a message on the
device; with the reference backend its own merge folds them in Python
(`pool/pre_aggregation.merge_same_message`).  Every attestation's verdict is held to the reference backend's,
attestation by attestation, for a good batch and both bad variants of the
flood cell: two same-message signatures swapped (only the blinding rejects
them) and one signature over the other message.  The merge's counters and
spans, and its products reckoning against the traced program.
"""

import numpy as np
import pytest

from lighthouse_tpu.chain import attestation_verification as av
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import REGISTRY
from lighthouse_tpu.crypto import bls

N = 64
VOTES = [b"\x51" * 32, b"\x52" * 32]


@pytest.fixture(scope="module")
def flood():
    """64 attesters: 60 vote the slot's block, 4 its parent, interleaved."""
    sks = [bls.SecretKey.from_bytes(int(700 + i).to_bytes(32, "big"))
           for i in range(N)]
    votes = [1 if i % 16 == 5 else 0 for i in range(N)]
    sigs = [sk.sign(VOTES[v]).to_bytes() for sk, v in zip(sks, votes)]
    return sks, votes, sigs


def _batch(flood, variant):
    """Fresh candidates (fresh signature objects) and the verdicts the
    variant was built to have."""
    sks, votes, sigs = flood
    sigs = list(sigs)
    wrong = ()
    if variant == "swapped":
        sigs[2], sigs[9] = sigs[9], sigs[2]
        wrong = (2, 9)
    elif variant == "other_message":
        sigs[30] = sks[30].sign(VOTES[1 - votes[30]]).to_bytes()
        wrong = (30,)
    cands = [av.VerifiedAttestation(
        i, None, np.array([i]),
        [bls.SignatureSet(bls.Signature(sig), [sk.public_key()],
                          VOTES[v])])
        for i, (sk, v, sig) in enumerate(zip(sks, votes, sigs))]
    return cands, [i not in wrong for i in range(N)]


def _merged(path):
    return REGISTRY.counter("pre_bls_merged_total").labels(path=path).value


@pytest.fixture
def backend():
    was = bls.get_backend()
    yield bls.set_backend
    bls.set_backend(was)


@pytest.mark.parametrize("variant", ["good", "swapped", "other_message"])
def test_device_merge_verdicts_equal_the_reference(flood, backend, variant):
    backend("tpu")
    cands, want = _batch(flood, variant)
    device, host = _merged("device"), _merged("host")
    got = [c.ok for c in av.batch_verify(None, cands)]
    assert got == want
    # the same-message sets reached the seam unmerged: no Python fold
    assert _merged("host") == host
    assert _merged("device") - device >= N
    ref = [bls.verify_signature_sets(c.sets, backend="reference")
           for c in cands]
    assert got == ref


def test_host_backend_still_merges_on_the_host(flood, backend):
    backend("reference")
    cands, want = _batch(flood, "good")
    device, host = _merged("device"), _merged("host")
    assert [c.ok for c in av.batch_verify(None, cands)] == want
    assert _merged("host") - host == N
    assert _merged("device") == device


def test_merge_spans_and_counters_under_the_pipeline(flood):
    from lighthouse_tpu.ops import bls_backend as bb
    from lighthouse_tpu.ops import msm

    cands, _ = _batch(flood, "good")
    sets = [c.sets[0] for c in cands]
    lanes = REGISTRY.counter("bls_merge_lanes_total")
    products = REGISTRY.counter("bls_merge_products_total")
    live, pad, made = (lanes.labels(kind="live").value,
                       lanes.labels(kind="padding").value, products.value)
    roots = []

    def sink(root, _slot):
        roots.append(root.to_dict())

    tracing.TRACER.add_sink(sink)
    try:
        assert bb.verify_sets_pipeline(sets)
    finally:
        tracing.TRACER.remove_sink(sink)
    (pipeline,) = roots
    by_name = {c["name"]: c for c in pipeline["children"]}
    merge = by_name["bls.merge"]
    assert merge["attrs"] == {"sets": N, "messages": 2, "lanes": 256}
    assert by_name["bls.pipeline.dispatch"]["attrs"] == {"chunk": 0,
                                                         "lanes": 2}
    assert [c["name"] for c in merge["children"]] == [
        "bls.merge.layout", "bls.merge.dispatch", "bls.merge.wait",
        "bls.merge.combine"]
    assert lanes.labels(kind="live").value - live == N
    assert lanes.labels(kind="padding").value - pad == 256 - N
    assert products.value - made == msm.blinded_merge_products(256, 16)
    stages = {line.split('stage="')[1].split('"')[0]
              for line in REGISTRY.render().splitlines()
              if line.startswith("bls_verify_stage_seconds_count{")}
    assert {"merge", "merge_layout", "merge_dispatch", "merge_wait",
            "merge_combine"} <= stages


@pytest.mark.parametrize("lanes,groups", [(256, 16), (2048, 16), (64, 2)])
def test_merge_products_are_what_the_program_traces(monkeypatch, lanes,
                                                    groups):
    """`blinded_merge_products` (what `bls_merge_products_total` grows by
    a dispatch) against a tally of the lanes each `mont_mul_lm` of
    `_blinded_merge` is traced with, a scan's body counted once a step;
    no product runs on the materialized multiply."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import msm

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
    # the Python function under the jit: a shape another test traced is
    # not traced again through the jit's cache
    jax.eval_shape(
        lambda *a: msm._blinded_merge._fn.__wrapped__(*a, groups),
        *[rows] * 6,
        jax.ShapeDtypeStruct((16, lanes), jnp.uint32),
        jax.ShapeDtypeStruct((lanes,), jnp.int32))
    assert tally == {"resident": msm.blinded_merge_products(lanes, groups),
                     "materialized": 0}
    assert msm.blinded_merge_products(2048, 16) == 18620416
