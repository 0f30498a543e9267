"""The blob plane against its plain reference, at width 16 (ISSUE 29).

``benchmarks/reference/kzg_plain.py`` is the consensus-specs verifier on
Python integers; it imports nothing of the program.  Every variant a
``blob_sidecars_by_range`` batch can be wrong in gets the same verdict from
both.  Batches here are 8 blobs: the fused path's lane bucket
(``bucket(2n+1) = 32``) and the 16-lane membership program are the ones
``tests/test_kzg.py`` already compiles, so this file compiles no further
Miller program.  The slice cap of the evaluation is passed by argument;
the pipelined tests force it to 2 and 3 blobs, so that the same 8 blobs are
four whole slices or two and a ragged third.
"""

import random
from functools import partial

import numpy as np
import pytest

from benchmarks.reference import kzg_plain as ref
from benchmarks.reference.bls_py import curve as ref_cv
from lighthouse_tpu.chain.blob_verification import validate_blobs
from lighthouse_tpu.chain.data_availability import verify_kzg_for_rpc_blocks
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import REGISTRY
from lighthouse_tpu.crypto import kzg
from lighthouse_tpu.ops import fr

WIDTH, TAU = 16, 0x123456789ABCDEF
N = kzg._DEVICE_EVAL_MIN

# order-3 point on E(Fq): on the curve, outside G1
G1_ORDER3_POINT = (
    0x0,
    0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAA9,
)


@pytest.fixture(scope="module")
def settings():
    return kzg.KzgSettings.dev(width=WIDTH, tau=TAU)


@pytest.fixture(scope="module")
def setup():
    return ref.Setup(WIDTH, 32, TAU)


def _batch(setup, n, seed):
    """(blobs, commitments, proofs, zs, q_taus): n valid blobs of seeded
    canonical field elements, committed and proved in the scalar field."""
    rng = random.Random(seed)
    blobs, cs, zs, q_taus = [], [], [], []
    for _ in range(n):
        poly = [rng.randrange(ref.BLS_MODULUS) for _ in range(setup.width)]
        blob = b"".join(v.to_bytes(32, "big") for v in poly)
        p_tau, c = setup.commit(poly)
        z = ref.compute_challenge(blob, c, setup)
        y = ref.evaluate_polynomial_in_evaluation_form(poly, z, setup)
        blobs.append(blob)
        cs.append(c)
        zs.append(z)
        q_taus.append(setup.quotient_at_tau(p_tau, z, y))
    return blobs, cs, setup.g1_times(q_taus), zs, q_taus


def _changed_field_element(setup, blobs, cs, proofs, zs, q_taus):
    old = int.from_bytes(blobs[5][32 * 3:32 * 4], "big")
    changed = bytearray(blobs[5])
    changed[32 * 3:32 * 4] = ((old + 1) % ref.BLS_MODULUS).to_bytes(32, "big")
    return blobs[:5] + [bytes(changed)] + blobs[6:], cs, proofs


def _swapped_proofs(setup, blobs, cs, proofs, zs, q_taus):
    swapped = list(proofs)
    swapped[2], swapped[6] = proofs[6], proofs[2]
    return blobs, cs, swapped


def _cancelling_pair(setup, blobs, cs, proofs, zs, q_taus):
    """d_a (tau - z_a) + d_b (tau - z_b) = 0: the errors of the two
    forged proofs cancel in the unweighted sum."""
    a, b, d_a = 1, 4, 0xDEADBEEF
    d_b = (-d_a * (setup.tau - zs[a]) * pow(
        (setup.tau - zs[b]) % ref.BLS_MODULUS, -1, ref.BLS_MODULUS)
    ) % ref.BLS_MODULUS
    forged = list(proofs)
    forged[a], forged[b] = setup.g1_times([q_taus[a] + d_a, q_taus[b] + d_b])
    return blobs, cs, forged


def _non_canonical(setup, blobs, cs, proofs, zs, q_taus):
    evil = ref.BLS_MODULUS.to_bytes(32, "big") + blobs[0][32:]
    return [evil] + blobs[1:], cs, proofs


def _outside_subgroup(setup, blobs, cs, proofs, zs, q_taus):
    assert ref_cv.g1_is_on_curve(G1_ORDER3_POINT)
    return blobs, cs[:3] + [ref_cv.g1_to_bytes(G1_ORDER3_POINT)] + cs[4:], proofs


def _good(setup, blobs, cs, proofs, zs, q_taus):
    return blobs, cs, proofs


VARIANTS = {"good": (_good, True),
            "changed_field_element": (_changed_field_element, False),
            "swapped_proofs": (_swapped_proofs, False),
            "cancelling_forged_pair": (_cancelling_pair, False),
            "non_canonical_field_element": (_non_canonical, False),
            "commitment_outside_subgroup": (_outside_subgroup, False)}


@pytest.mark.parametrize("variant", VARIANTS)
def test_system_equals_plain_reference(settings, setup, variant):
    make, expected = VARIANTS[variant]
    blobs, cs, proofs = make(setup, *_batch(setup, N, seed=29))
    want = ref.verify_blob_kzg_proof_batch(blobs, cs, proofs, setup)
    assert want is expected
    assert validate_blobs(settings, cs, blobs, proofs) is want


def test_only_the_linear_combination_rejects_the_forged_pair(settings, setup):
    """The control: with every power of r at 1 the reference accepts the
    cancelling pair (and still rejects a plain swap); the system never
    does."""
    batch = _batch(setup, N, seed=31)
    blobs, cs, forged = _cancelling_pair(setup, *batch)
    assert ref.verify_blob_kzg_proof_batch(blobs, cs, forged, setup,
                                           blind=False) is True
    assert ref.verify_blob_kzg_proof_batch(blobs, cs, forged, setup) is False
    assert kzg.verify_blob_kzg_proof_batch(blobs, cs, forged, settings) is False
    _, _, swapped = _swapped_proofs(setup, *batch)
    assert ref.verify_blob_kzg_proof_batch(blobs, cs, swapped, setup,
                                           blind=False) is False


def test_reference_commitments_and_proofs_are_the_programs(settings, setup):
    """Scalar-field commitments and proofs (tau known) equal the program's
    multi-scalar multiplications over the Lagrange setup."""
    blobs, cs, proofs, _, _ = _batch(setup, 2, seed=37)
    for blob, c, proof in zip(blobs, cs, proofs):
        assert kzg.blob_to_kzg_commitment(blob, settings) == c
        assert kzg.compute_blob_kzg_proof(blob, c, settings) == proof
    assert setup.roots_brp == settings.roots_brp


def _counter(family, label):
    """{label value: count} of a labelled counter family, as scraped."""
    out = {}
    for line in REGISTRY.render().splitlines():
        if line.startswith(family + "{"):
            out[line.split(label + '="')[1].split('"')[0]] = float(
                line.rsplit(" ", 1)[1])
    return out


def _lanes():
    return {"live": 0.0, "padding": 0.0,
            **_counter("kzg_eval_lanes_total", "kind")}


def test_sliced_evaluation_equals_unsliced_and_host(settings, setup):
    """Ten blobs in slices of four (4 + 4 + 2 and two lanes of fill), a
    z == root blob on each side of the first slice boundary and one in the
    padded slice: the degenerate case is patched per blob, whatever slice
    it fell in."""
    rng = random.Random(41)
    n = 10
    polys = [[rng.randrange(ref.BLS_MODULUS) for _ in range(WIDTH)]
             for _ in range(n)]
    zs = [rng.randrange(ref.BLS_MODULUS) for _ in range(n)]
    zs[3], zs[4], zs[9] = (settings.roots_brp[5], settings.roots_brp[0],
                           settings.roots_brp[15])
    raw = np.frombuffer(b"".join(v.to_bytes(32, "big") for p in polys
                                 for v in p), np.uint8).reshape(n, WIDTH, 32)
    limbs = fr.be32_bytes_to_limbs(raw)
    want = [kzg.evaluate_polynomial_in_evaluation_form(p, z, settings)
            for p, z in zip(polys, zs)]
    assert want == [ref.evaluate_polynomial_in_evaluation_form(p, z, setup)
                    for p, z in zip(polys, zs)]
    assert (want[3], want[4], want[9]) == (polys[3][5], polys[4][0],
                                          polys[9][15])
    before = _lanes()
    sliced = fr.evaluate_polynomials_batch(limbs, zs, settings.roots_brp,
                                           max_blobs=4)
    after = _lanes()
    assert sliced == want
    assert after["live"] - before["live"] == n * WIDTH
    assert after["padding"] - before["padding"] == 2 * WIDTH
    unsliced = fr.evaluate_polynomials_batch(limbs, zs, settings.roots_brp)
    assert unsliced == want
    assert _lanes()["padding"] == after["padding"]


def test_limb_rows_of_big_endian_field_elements():
    rng = random.Random(43)
    values = [0, 1, ref.BLS_MODULUS - 1, (1 << 256) - 1] + [
        rng.randrange(1 << 256) for _ in range(60)]
    raw = np.frombuffer(b"".join(v.to_bytes(32, "big") for v in values),
                        np.uint8).reshape(4, 16, 32)
    limbs = fr.be32_bytes_to_limbs(raw)
    assert limbs.shape == (4, 16, fr.L) and limbs.dtype == np.uint32
    assert [fr._limbs_to_int(row) for row in limbs.reshape(-1, fr.L)] == values
    assert int(limbs.max()) <= fr.MASK


class _Sidecar:
    def __init__(self, blob, commitment, proof):
        self.blob, self.kzg_commitment, self.kzg_proof = blob, commitment, proof


@pytest.mark.parametrize("bad_block", [None, 2])
def test_segment_entry_equals_per_block_validation(settings, setup, bad_block):
    """Four blocks of two sidecars: one call for the segment, and its
    verdict is the conjunction of the per-block calls."""
    blobs, cs, proofs, _, _ = _batch(setup, 8, seed=47)
    if bad_block is not None:
        proofs[2 * bad_block] = proofs[2 * bad_block + 1]
    blocks = [[_Sidecar(blobs[i], cs[i], proofs[i]) for i in (2 * b, 2 * b + 1)]
              for b in range(4)]
    calls = []
    real = kzg.verify_blob_kzg_proof_batch

    def counted(*args):
        calls.append(len(args[0]))
        return real(*args)

    kzg.verify_blob_kzg_proof_batch = counted
    try:
        segment = verify_kzg_for_rpc_blocks(settings, blocks)
    finally:
        kzg.verify_blob_kzg_proof_batch = real
    assert calls == [8]
    per_block = [validate_blobs(settings,
                                [s.kzg_commitment for s in block],
                                [s.blob for s in block],
                                [s.kzg_proof for s in block])
                 for block in blocks]
    assert per_block == [b != bad_block for b in range(4)]
    assert segment is all(per_block)
    assert verify_kzg_for_rpc_blocks(settings, []) is True


KZG_PARENTS = {
    "kzg.decode": "kzg.verify_batch",
    "kzg.decode.verdict": "kzg.verify_batch",
    "kzg.canonical": "kzg.eval",
    "kzg.challenge": "kzg.eval",
    "kzg.limbs": "kzg.eval",
    "kzg.eval": "kzg.verify_batch",
    "kzg.rlc": "kzg.verify_batch",
    "kzg.pack": "kzg.verify_batch",
    "kzg.fused.dispatch": "kzg.verify_batch",
    "kzg.fused.wait": "kzg.verify_batch",
    "kzg.final_exp": "kzg.verify_batch",
    "kzg.eval.dispatch": "kzg.eval",
    "kzg.eval.fetch": "kzg.eval",
}
KZG_STAGES = {"verify_batch", "decode", "canonical", "challenge", "limbs",
              "eval", "eval_dispatch", "eval_fetch", "rlc", "pack",
              "fused_dispatch", "fused_wait", "final_exp"}


def _blobs_verified():
    return _counter("kzg_blobs_verified_total", "path")


def test_stage_spans_cover_the_batch_and_stamp_the_path(settings, setup):
    blobs, cs, proofs, _, _ = _batch(setup, N, seed=53)
    assert kzg.verify_blob_kzg_proof_batch(blobs, cs, proofs, settings)  # warm
    roots = []

    def sink(root, _slot):
        roots.append(root.to_dict())

    before = _blobs_verified()
    tracing.TRACER.add_sink(sink)
    try:
        assert kzg.verify_blob_kzg_proof_batch(blobs, cs, proofs, settings)
        assert kzg.verify_blob_kzg_proof_batch(blobs[:2], cs[:2], proofs[:2],
                                               settings)
    finally:
        tracing.TRACER.remove_sink(sink)
    batches = [r for r in roots if r["name"] == "kzg.verify_batch"]
    assert [b["attrs"] for b in batches] == [
        {"blobs": N, "path": "fused"}, {"blobs": 2, "path": "host"}]
    fused = batches[0]
    parents = {}

    def walk(d, parent):
        parents.setdefault(d["name"], set()).add(parent)
        for child in d.get("children", ()):
            walk(child, d["name"])

    walk(fused, None)
    for name, parent in KZG_PARENTS.items():
        assert parents.get(name) == {parent}, name
    (evaluation,) = [c for c in fused["children"] if c["name"] == "kzg.eval"]
    assert evaluation["attrs"] == {"slices": 1, "overlapped": 0}
    covered = sum(c["duration_ms"] for c in fused["children"])
    assert covered >= 0.95 * fused["duration_ms"]
    stages = {line.split('stage="')[1].split('"')[0]
              for line in REGISTRY.render().splitlines()
              if line.startswith("kzg_verify_stage_seconds_count{")}
    assert KZG_STAGES <= stages
    after = _blobs_verified()
    assert after["fused"] - before.get("fused", 0.0) == N
    assert after["host"] - before.get("host", 0.0) == 2


# --- the evaluation fed slice by slice (ISSUE 30) ---------------------------

@pytest.fixture
def slice_cap(monkeypatch):
    """Force the evaluation's slice cap on the fused route."""
    def force(max_blobs):
        monkeypatch.setattr(
            fr, "evaluate_polynomial_slices",
            partial(fr.evaluate_polynomial_slices, max_blobs=max_blobs))
    return force


def _flat(d, out):
    out.append(d)
    for child in d.get("children", ()):
        _flat(child, out)
    return out


def _traced_batch(blobs, cs, proofs, settings):
    """(verdict, the spans of the one kzg.verify_batch, in closing order)."""
    roots = []

    def sink(root, _slot):
        roots.append(root.to_dict())

    tracing.TRACER.add_sink(sink)
    try:
        verdict = kzg.verify_blob_kzg_proof_batch(blobs, cs, proofs, settings)
    finally:
        tracing.TRACER.remove_sink(sink)
    (batch,) = [r for r in roots if r["name"] == "kzg.verify_batch"]
    return verdict, _flat(batch, [])


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _slices():
    return {"exposed": 0.0, "overlapped": 0.0,
            **_counter("kzg_eval_slices_total", "prep")}


@pytest.mark.parametrize("max_blobs", [2, 3], ids=["whole", "ragged"])
@pytest.mark.parametrize("variant", ["good", "changed_field_element",
                                     "cancelling_forged_pair"])
def test_pipelined_verdict_equals_plain_reference(settings, setup, slice_cap,
                                                  variant, max_blobs):
    make, expected = VARIANTS[variant]
    blobs, cs, proofs = make(setup, *_batch(setup, N, seed=59))
    want = ref.verify_blob_kzg_proof_batch(blobs, cs, proofs, setup)
    assert want is expected
    slice_cap(max_blobs)
    assert kzg.verify_blob_kzg_proof_batch(blobs, cs, proofs, settings) is want


def test_non_canonical_in_the_last_slice_drops_the_slices_in_flight(
        settings, setup, slice_cap):
    blobs, cs, proofs, _, _ = _batch(setup, N, seed=61)
    blobs[N - 1] = blobs[N - 1][:-32] + ref.BLS_MODULUS.to_bytes(32, "big")
    assert ref.verify_blob_kzg_proof_batch(blobs, cs, proofs, setup) is False
    slice_cap(3)
    verdict, spans = _traced_batch(blobs, cs, proofs, settings)
    assert verdict is False
    # two slices went up, the third's check refused, nothing was fetched
    assert len(_named(spans, "kzg.eval.dispatch")) == 2
    assert len(_named(spans, "kzg.canonical")) == 3
    assert _named(spans, "kzg.canonical")[-1]["attrs"] == {"error": "KzgError"}
    for never in ("kzg.eval.fetch", "kzg.decode.verdict", "kzg.fused.dispatch"):
        assert not _named(spans, never), never


def test_point_outside_the_subgroup_never_reaches_the_fold(
        settings, setup, slice_cap, monkeypatch):
    blobs, cs, proofs = _outside_subgroup(setup, *_batch(setup, N, seed=67))
    assert ref.verify_blob_kzg_proof_batch(blobs, cs, proofs, setup) is False
    folded = []
    monkeypatch.setattr(kzg, "_kzg_fused_check",
                        lambda *a, **k: folded.append(a) or True)
    slice_cap(3)
    verdict, spans = _traced_batch(blobs, cs, proofs, settings)
    assert verdict is False and not folded
    # the verdict is read after the evaluation's fetch, not in kzg.decode
    names = [s["name"] for s in sorted(spans, key=lambda s: s["offset_ms"])]
    assert names.index("kzg.eval.fetch") < names.index("kzg.decode.verdict")
    assert not _named(spans, "kzg.rlc")


@pytest.mark.parametrize("max_blobs, slices", [(3, 3), (fr._EVAL_MAX_BLOBS, 1)],
                         ids=["three_slices", "one_slice"])
def test_host_prepares_a_slice_while_the_one_before_is_dispatched(
        settings, setup, slice_cap, max_blobs, slices):
    blobs, cs, proofs, _, _ = _batch(setup, N, seed=71)
    slice_cap(max_blobs)
    before = _slices()
    verdict, spans = _traced_batch(blobs, cs, proofs, settings)
    after = _slices()
    assert verdict is True
    assert after["exposed"] - before["exposed"] == 1
    assert after["overlapped"] - before["overlapped"] == slices - 1
    (evaluation,) = _named(spans, "kzg.eval")
    assert evaluation["attrs"] == {"slices": slices, "overlapped": slices - 1}
    dispatches = _named(spans, "kzg.eval.dispatch")
    limbs = _named(spans, "kzg.limbs")
    assert len(dispatches) == len(limbs) == slices
    assert len(_named(spans, "kzg.canonical")) == slices
    assert len(_named(spans, "kzg.challenge")) == slices
    # slice k is dispatched before slice k+1's limbs are made
    for k in range(slices - 1):
        assert (dispatches[k]["offset_ms"] + dispatches[k]["duration_ms"]
                <= limbs[k + 1]["offset_ms"])
    assert (dispatches[0]["offset_ms"] < limbs[-1]["offset_ms"]) is (slices > 1)
