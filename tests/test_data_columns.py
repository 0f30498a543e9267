"""The column plane (PeerDAS, fulu): data-column sidecars through
``verify_kzg_for_rpc_blocks`` -> ``validate_data_columns`` ->
``das.verify_cell_kzg_proof_batch`` against the plain reference
(``benchmarks/reference/das_plain.py``, the spec on Python integers), at a
small size on the CPU: width 128, so 128 columns of 2-element cells, two
blobs a block, the sidecars of 8 columns.  Seeded.
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import das_plain as ref
from benchmarks.reference.bls_py import curve as ref_cv
from lighthouse_tpu.chain import data_availability
from lighthouse_tpu.chain import data_column_verification as dcv
from lighthouse_tpu.chain.data_availability import verify_kzg_for_rpc_blocks
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import REGISTRY
from lighthouse_tpu.crypto import das, kzg
from lighthouse_tpu.crypto.bls import curve as cv
from lighthouse_tpu.ops import fr
from lighthouse_tpu.types.containers import (
    BeaconBlockHeader,
    DataColumnsByRootIdentifier,
    SignedBeaconBlockHeader,
    make_types,
)
from lighthouse_tpu.types.spec import MAINNET_PRESET, ChainSpec

WIDTH, TAU = 128, 0x123456789ABCDEF
BLOBS, COLUMNS = 2, 8
R = ref.BLS_MODULUS
SPEC = ChainSpec.mainnet()
TYPES = make_types(MAINNET_PRESET)
LAST_EPOCH = SPEC.blob_schedule[-1][0]

# order-3 point on E(Fq): on the curve, outside G1
G1_ORDER3_POINT = (
    0x0,
    0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAA9,
)


@pytest.fixture(scope="module")
def setup():
    return ref.Setup(WIDTH, 32, TAU, 128)


@pytest.fixture(scope="module")
def settings(setup):
    """What a node holds of its setup as far as cell verification reads
    it, from the reference's points."""
    n = setup.cell_size
    s = kzg.KzgSettings.from_setup_points([None] * WIDTH, None)
    s.g1_monomial = [cv.g1_from_bytes(ref_cv.g1_to_bytes(p))
                     for p in setup.g1_monomial]
    s.g2_monomial = [None] * n + [
        cv.g2_from_bytes(ref_cv.g2_to_bytes(setup.g2_tau_n))]
    return s


class _Sidecar:
    """What the checker reads of a DataColumnSidecar, with cells of the
    test's width (the SSZ type's are the preset's 2,048 bytes)."""

    def __init__(self, index, column, kzg_commitments, kzg_proofs,
                 signed_block_header, kzg_commitments_inclusion_proof):
        self.index = index
        self.column = list(column)
        self.kzg_commitments = list(kzg_commitments)
        self.kzg_proofs = list(kzg_proofs)
        self.signed_block_header = signed_block_header
        self.kzg_commitments_inclusion_proof = kzg_commitments_inclusion_proof


def _block(setup, seed, slot=LAST_EPOCH * 32, blobs=BLOBS, columns=COLUMNS):
    """(sidecars, quotients): the data-column sidecars of columns
    0 .. columns-1 of one block of seeded blobs, with header and
    inclusion proof, and q[blob][column] behind the proofs."""
    rng = random.Random(seed)
    cells, commitments, proofs, quotients = [], [], [], []
    for _ in range(blobs):
        blob = b"".join(rng.randrange(R).to_bytes(32, "big")
                        for _ in range(WIDTH))
        evals = ref.compute_cells(blob, setup)
        p_tau, c = setup.commit(ref.blob_to_polynomial(blob, setup))
        q = setup.quotients_at_tau(p_tau, evals)
        cells.append([ref.cell_to_bytes(e) for e in evals])
        commitments.append(c)
        quotients.append(q)
        proofs.append(setup.g1_times(q[:columns]))
    body = TYPES.beacon_block_body_class("electra")(
        blob_kzg_commitments=commitments)
    header = SignedBeaconBlockHeader(message=BeaconBlockHeader(
        slot=slot, body_root=body.hash_tree_root()))
    branch = dcv.compute_kzg_commitments_inclusion_proof(body)
    sidecars = [_Sidecar(
        index=c, column=[cells[i][c] for i in range(blobs)],
        kzg_commitments=commitments,
        kzg_proofs=[proofs[i][c] for i in range(blobs)],
        signed_block_header=header,
        kzg_commitments_inclusion_proof=branch) for c in range(columns)]
    return sidecars, quotients


@pytest.fixture(scope="module")
def block(setup):
    return _block(setup, seed=61)


def _flat(sidecars):
    """(commitments, cell indices, cells, proofs) a cell, a sidecar after
    the other: what validate_data_columns hands the batch verifier."""
    out = [], [], [], []
    for s in sidecars:
        out[0].extend(bytes(c) for c in s.kzg_commitments)
        out[1].extend([int(s.index)] * len(s.column))
        out[2].extend(bytes(c) for c in s.column)
        out[3].extend(bytes(p) for p in s.kzg_proofs)
    return out


def _reference(sidecars, setup, **kw):
    return ref.verify_cell_kzg_proof_batch(*_flat(sidecars), setup, **kw)


def _copy(sidecars):
    return [_Sidecar(s.index, s.column, s.kzg_commitments, s.kzg_proofs,
                     SignedBeaconBlockHeader.deserialize(
                         s.signed_block_header.serialize()),
                     s.kzg_commitments_inclusion_proof) for s in sidecars]


# --- variants of one good block: (sidecars, quotients, setup) -> sidecars ----

def _good(sidecars, q, setup):
    return sidecars


def _changed_field_element(sidecars, q, setup):
    cell = bytes(sidecars[3].column[1])
    v = (int.from_bytes(cell[32:64], "big") + 1) % R
    sidecars[3].column[1] = cell[:32] + v.to_bytes(32, "big")
    return sidecars


def _cancelling_pair(sidecars, q, setup):
    # two proofs of one column: the coset, so tau^n - h^n, is the same
    d = 0x1234567
    sidecars[5].kzg_proofs[0], sidecars[5].kzg_proofs[1] = setup.g1_times(
        [q[0][5] + d, q[1][5] - d])
    return sidecars


def _swapped_proofs(sidecars, q, setup):
    a, b = bytes(sidecars[2].kzg_proofs[0]), bytes(sidecars[2].kzg_proofs[1])
    sidecars[2].kzg_proofs[0], sidecars[2].kzg_proofs[1] = b, a
    return sidecars


def _wrong_column(sidecars, q, setup):
    sidecars[4].index = 99
    return sidecars


def _non_canonical(sidecars, q, setup):
    cell = bytes(sidecars[0].column[0])
    sidecars[0].column[0] = R.to_bytes(32, "big") + cell[32:]
    return sidecars


def _proof_outside_subgroup(sidecars, q, setup):
    assert ref_cv.g1_is_on_curve(G1_ORDER3_POINT)
    sidecars[6].kzg_proofs[1] = ref_cv.g1_to_bytes(G1_ORDER3_POINT)
    return sidecars


def _commitment_outside_subgroup(sidecars, q, setup):
    for s in sidecars:   # a block's commitments come with every sidecar
        s.kzg_commitments[0] = ref_cv.g1_to_bytes(G1_ORDER3_POINT)
    return sidecars


def _proof_no_point(sidecars, q, setup):
    sidecars[1].kzg_proofs[0] = b"\x80" + b"\x00" * 46 + b"\x05"
    return sidecars


VARIANTS = {"good": (_good, True),
            "changed_field_element": (_changed_field_element, False),
            "cancelling_forged_pair": (_cancelling_pair, False),
            "swapped_proofs": (_swapped_proofs, False),
            "wrong_column": (_wrong_column, False),
            "non_canonical_field_element": (_non_canonical, False),
            "proof_outside_subgroup": (_proof_outside_subgroup, False),
            "commitment_outside_subgroup": (_commitment_outside_subgroup,
                                            False),
            "proof_that_is_no_point": (_proof_no_point, False)}


@pytest.mark.parametrize("variant", VARIANTS)
def test_system_equals_plain_reference(settings, setup, block, variant):
    change, expected = VARIANTS[variant]
    sidecars, q = block
    sidecars = change(_copy(sidecars), q, setup)
    assert _reference(sidecars, setup) is expected
    assert verify_kzg_for_rpc_blocks(settings, [sidecars]) is expected


def test_only_the_powers_of_r_reject_the_forged_pair(settings, setup, block):
    """The control: the reference with every power of r at 1 accepts the
    pair whose errors cancel, and rejects every other bad variant."""
    sidecars, q = block
    forged = _cancelling_pair(_copy(sidecars), q, setup)
    assert _reference(forged, setup, blind=False) is True
    assert _reference(forged[5:6], setup, blind=False) is True
    assert _reference(forged[5:6], setup) is False
    changed = _changed_field_element(_copy(sidecars), q, setup)
    assert _reference(changed, setup, blind=False) is False


def test_a_segment_of_two_blocks_is_one_batch(settings, setup, block):
    """Two blocks' sidecars: one verify_cell_kzg_proof_batch call over
    the commitments of both, and one bad cell in the second fails it."""
    first, _ = block
    second, q2 = _block(setup, seed=67, slot=LAST_EPOCH * 32 + 1)
    calls = []
    real = das.verify_cell_kzg_proof_batch

    def counted(commitments, *rest):
        calls.append((len(commitments), len(set(commitments))))
        return real(commitments, *rest)

    das.verify_cell_kzg_proof_batch = counted
    try:
        assert verify_kzg_for_rpc_blocks(settings, [first, second]) is True
        bad = _changed_field_element(_copy(second), q2, setup)
        assert verify_kzg_for_rpc_blocks(settings, [first, bad]) is False
    finally:
        das.verify_cell_kzg_proof_batch = real
    assert calls == [(2 * BLOBS * COLUMNS, 2 * BLOBS)] * 2
    assert _reference(first + second, setup) is True


def test_the_challenge_is_the_specs(settings, setup, block):
    commitments, ids, cells, proofs = _flat(block[0])
    distinct = list(dict.fromkeys(commitments))
    idx = [distinct.index(c) for c in commitments]
    assert das.compute_verify_cell_kzg_proof_batch_challenge(
        distinct, idx, ids, cells, proofs, settings
    ) == ref.compute_verify_cell_kzg_proof_batch_challenge(
        distinct, idx, ids,
        [ref.cell_to_coset_evals(c, setup) for c in cells], proofs, setup)


def test_reference_cells_and_proofs_are_the_programs(setup):
    """The scalar-field prover of the reference against the program's
    own (the extension by FFT and the quotient commitments by MSM)."""
    s = kzg.KzgSettings.dev(width=WIDTH, tau=TAU)
    rng = random.Random(71)
    blob = b"".join(rng.randrange(R).to_bytes(32, "big")
                    for _ in range(WIDTH))
    evals = ref.compute_cells(blob, setup)
    p_tau, c = setup.commit(ref.blob_to_polynomial(blob, setup))
    cells, proofs = das.compute_cells_and_kzg_proofs(blob, s)
    assert [ref.cell_to_bytes(e) for e in evals] == cells
    assert setup.cell_proofs(p_tau, evals) == proofs
    assert c == kzg.blob_to_kzg_commitment(blob, s)


# --- groups ----------------------------------------------------------------------

def _full_block_ids(blobs=21, columns=128):
    return ([c for c in range(columns) for _ in range(blobs)],
            list(range(blobs)) * columns)


def test_a_full_block_is_two_groups_of_64_sidecars():
    ids, idx = _full_block_ids()
    assert das._split_groups(ids, idx, 64) == [(0, 1344), (1344, 2688)]
    # 21 + 1,344 + 64 lanes a group: one more blob a block would not fit two
    assert 21 + 1344 + 64 <= das._FUSED_MSM_LANES < 21 + 2688 + 64


@pytest.mark.parametrize("blobs, columns, blocks, groups", [
    (21, 128, 2, 3), (6, 128, 1, 1), (21, 8, 1, 1), (21, 128, 4, 6)])
def test_groups_are_whole_sidecars_equal_to_within_one(blobs, columns, blocks,
                                                      groups):
    ids, idx = [], []
    for b in range(blocks):
        i, x = _full_block_ids(blobs, columns)
        ids += i
        idx += [v + b * blobs for v in x]
    got = das._split_groups(ids, idx, 64)
    assert len(got) == groups
    assert got[0][0] == 0 and got[-1][1] == len(ids)
    assert all(a[1] == b[0] for a, b in zip(got, got[1:]))
    sidecars = [(hi - lo) / blobs for lo, hi in got]
    assert all(s == int(s) for s in sidecars)
    assert max(sidecars) - min(sidecars) <= 1
    for lo, hi in got:
        assert (len(set(idx[lo:hi])) + hi - lo + 64
                <= das._FUSED_MSM_LANES)
    if groups > 1:   # one group fewer does not fit
        fewer = -(-len(ids) // (groups - 1))
        assert fewer + 64 > das._FUSED_MSM_LANES - blobs * blocks


def test_a_stretch_of_one_column_too_long_to_fit_is_cut():
    n = 3000   # one column of 3,000 blocks, a commitment each
    got = das._split_groups([5] * n, list(range(n)), 64)
    assert got[0][0] == 0 and got[-1][1] == n
    assert all(2 * (hi - lo) + 64 <= das._FUSED_MSM_LANES for lo, hi in got)


@pytest.mark.parametrize("variant", ["good", "changed_field_element",
                                     "cancelling_forged_pair"])
def test_grouped_check_equals_the_ungrouped(settings, setup, block,
                                            monkeypatch, variant):
    """The same batch as two groups of four sidecars (the bucket made
    small), each its own check under the one transcript, with the
    interpolation in two dispatches, and as cells that each take a slot:
    the verdict is the ungrouped one."""
    change, expected = VARIANTS[variant]
    sidecars, q = block
    sidecars = change(_copy(sidecars), q, setup)
    assert verify_kzg_for_rpc_blocks(settings, [sidecars]) is expected
    groups = []
    real = das._split_groups

    def seen(*args):
        groups.append(real(*args))
        return groups[-1]

    monkeypatch.setattr(das, "_split_groups", seen)
    monkeypatch.setattr(das, "_FUSED_MSM_LANES", BLOBS + 8 + 2)
    assert verify_kzg_for_rpc_blocks(settings, [sidecars]) is expected
    assert groups[-1] == [(0, 8), (8, 16)]
    # one group a dispatch, then a lane cap under a group's columns
    for cap in (2 * 4 * 2, 2 * 4 * 2 - 1):
        monkeypatch.setattr(das, "_INTERP_MAX_LANES", cap)
        assert verify_kzg_for_rpc_blocks(settings, [sidecars]) is expected


def test_interpolation_layouts():
    dom = das._CellDomain(WIDTH)
    ids = [c for c in range(8) for _ in range(2)]
    (lo, shape, position, columns), = das._interp_layouts(
        [(0, 8), (8, 16)], ids, dom)
    assert (lo, shape) == (0, (2, 4, 2))
    # cell k of group g, slot s, row b sits at (b * 4 + s) * 2 + g
    assert position[:4] == [0, 8, 2, 10] and position[8:12] == [1, 9, 3, 11]
    assert columns == [[0, 4], [1, 5], [2, 6], [3, 7]]
    # a full block at mainnet width: one dispatch of 32 x 64 x 2 x 64
    ids, _ = _full_block_ids()
    (lo, shape, position, columns), = das._interp_layouts(
        [(0, 1344), (1344, 2688)], ids, das._CellDomain(4096))
    assert shape == (32, 64, 2) and len(set(position)) == 2688
    assert 32 * 64 * 2 * 64 == das._INTERP_MAX_LANES


# --- the device program ------------------------------------------------------------

@pytest.mark.parametrize("shape", [(2, 4, 2, 4), (1, 2, 1, 2), (4, 1, 2, 8)])
def test_cell_interp_kernel_against_python_integers(shape):
    b, c, g, s = shape
    rng = random.Random(sum(shape))
    v = [[[[rng.randrange(R) for _ in range(s)] for _ in range(g)]
          for _ in range(c)] for _ in range(b)]
    rk = [[[rng.randrange(R) for _ in range(g)] for _ in range(c)]
          for _ in range(b)]
    idft = [[rng.randrange(R) for _ in range(s)] for _ in range(s)]
    scale = [[[rng.randrange(R) for _ in range(s)] for _ in range(g)]
             for _ in range(c)]
    raw = np.array([fr._int_to_limbs(x) for x in np.ravel(
        np.array(v, object))]).reshape(b, c, g, s, fr.L)
    out, products = fr.interpolate_cells_dispatch(
        raw, fr.to_mont_host(rk), fr.to_mont_host(idft),
        fr.to_mont_host(scale))
    assert products == fr._interp_products(*shape)
    got = fr.interpolation_scalars(out)
    for gi in range(g):
        for m in range(s):
            want = sum(
                scale[ci][gi][m] * sum(
                    idft[j][m] * sum(rk[bi][ci][gi] * v[bi][ci][gi][j]
                                     for bi in range(b))
                    for j in range(s))
                for ci in range(c)) % R
            assert got[gi][m] == want


def test_products_of_a_dispatch_are_what_the_program_traces(monkeypatch):
    """`_interp_products` (what `kzg_interp_products_total` grows by a
    dispatch) against a tally of the lanes each multiply is traced with."""
    tally = []
    lm = fr.mont_mul_lm

    def count_lm(a, b):
        tally.append(int(np.prod(a.shape[1:])))
        return lm(a, b)

    monkeypatch.setattr(fr, "mont_mul_lm", count_lm)
    rows = lambda *lead: jax.ShapeDtypeStruct(  # noqa: E731
        (*lead, fr.L), jnp.uint32)
    # shapes no other test dispatches: a shape traced before is not traced
    # again (the tracing cache is the function's, whatever wraps it)
    for b, c, g, s in ((2, 2, 4, 4), (32, 64, 2, 64)):
        tally.clear()
        jax.eval_shape(fr._cell_interp_kernel._fn, rows(b, c, g, s),
                       rows(b, c, g), rows(s, s), rows(c, g, s))
        assert tally == [b * c * g * s, s * c * g * s, c * g * s]
        assert fr._interp_products(b, c, g, s) == sum(tally)
    # one full block: the padded weights, 128 transforms, the scaling
    assert fr._interp_products(32, 64, 2, 64) == 262_144 + 524_288 + 8_192


# --- spans and counters --------------------------------------------------------------

CELL_CHILDREN = {"kzg.decode", "kzg.decode.verdict", "kzg.canonical",
                 "kzg.challenge", "kzg.limbs", "kzg.interp.dispatch",
                 "kzg.interp.fetch", "kzg.rlc", "kzg.pack",
                 "kzg.fused.dispatch", "kzg.fused.wait", "kzg.final_exp"}
CELL_STAGES = {"verify_cell_batch", "validate", "decode", "canonical",
               "challenge", "limbs", "interp_dispatch", "interp_fetch", "rlc",
               "pack", "fused_dispatch", "fused_wait", "final_exp"}


def _counter(family, label):
    out = {}
    for line in REGISTRY.render().splitlines():
        if line.startswith(family + "{") or line.startswith(family + " "):
            key = (line.split(f'{label}="')[1].split('"')[0]
                   if label else "")
            out[key] = float(line.rsplit(" ", 1)[1])
    return out


def test_stage_spans_cover_the_batch_and_counters_count_it(settings, block):
    sidecars, _ = block
    assert verify_kzg_for_rpc_blocks(settings, [sidecars])   # warm
    roots = []

    def sink(root, _slot):
        roots.append(root.to_dict())

    before = {f: _counter(f, lab) for f, lab in (
        ("kzg_cells_verified_total", "path"),
        ("kzg_cell_lanes_total", "kind"),
        ("kzg_interp_products_total", None))}
    tracing.TRACER.add_sink(sink)
    try:
        assert verify_kzg_for_rpc_blocks(settings, [sidecars])
        assert verify_kzg_for_rpc_blocks(settings, [sidecars[:3]])
    finally:
        tracing.TRACER.remove_sink(sink)
    assert [r["attrs"] for r in roots if r["name"] == "das.validate"] == [
        {"sidecars": 8}, {"sidecars": 3}]
    batches = [r for r in roots if r["name"] == "kzg.verify_cell_batch"]
    assert [b["attrs"] for b in batches] == [
        {"cells": 16, "columns": 8, "commitments": 2, "groups": 1,
         "path": "fused"},
        {"cells": 6, "columns": 3, "path": "host"}]
    fused = batches[0]
    assert {c["name"] for c in fused["children"]} == CELL_CHILDREN
    covered = sum(c["duration_ms"] for c in fused["children"])
    assert covered >= 0.95 * fused["duration_ms"]
    stages = {line.split('stage="')[1].split('"')[0]
              for line in REGISTRY.render().splitlines()
              if line.startswith("kzg_verify_stage_seconds_count{")}
    assert CELL_STAGES <= stages
    grown = {f: {k: v - before[f].get(k, 0.0)
                 for k, v in _counter(f, lab).items()}
             for f, lab in (("kzg_cells_verified_total", "path"),
                            ("kzg_cell_lanes_total", "kind"),
                            ("kzg_interp_products_total", None))}
    assert grown["kzg_cells_verified_total"] == {"fused": 16, "host": 6}
    # 2 commitments + 16 proofs + 2 monomial points, and 16 proofs, in two
    # sums of bucket(20) = 32 lanes
    assert grown["kzg_cell_lanes_total"] == {"live": 36, "padding": 28}
    assert grown["kzg_interp_products_total"] == {
        "": fr._interp_products(2, 8, 1, 2)}


# --- structure and the inclusion proof --------------------------------------------------

def _structure_faults():
    def too_many(s):
        s.kzg_commitments = [bytes(s.kzg_commitments[0])] * 22
        s.column = [bytes(s.column[0])] * 22
        s.kzg_proofs = [bytes(s.kzg_proofs[0])] * 22

    def too_many_for_its_epoch(s):
        # 16 blobs at the schedule's first entry, whose maximum is 15
        s.signed_block_header.message.slot = SPEC.blob_schedule[0][0] * 32
        s.kzg_commitments = [bytes(s.kzg_commitments[0])] * 16
        s.column = [bytes(s.column[0])] * 16
        s.kzg_proofs = [bytes(s.kzg_proofs[0])] * 16

    def none(s):
        s.kzg_commitments, s.column, s.kzg_proofs = [], [], []

    return {
        "invalid_column_index": lambda s: setattr(s, "index", 128),
        "no_commitments": none,
        "too_many_commitments": too_many,
        "too_many_commitments@first_entry": too_many_for_its_epoch,
        "length_mismatch@column": lambda s: setattr(
            s, "column", [bytes(s.column[0])]),
        "length_mismatch@proofs": lambda s: setattr(
            s, "kzg_proofs", [bytes(p) for p in s.kzg_proofs] * 2)}


@pytest.mark.parametrize("fault", _structure_faults())
def test_a_sidecar_of_bad_structure_is_rejected_before_any_dispatch(
        settings, block, fault):
    sidecars = _copy(block[0])
    dcv.verify_data_column_sidecar(sidecars[2], SPEC)
    _structure_faults()[fault](sidecars[2])
    with pytest.raises(dcv.DataColumnError) as e:
        dcv.verify_data_column_sidecar(sidecars[2], SPEC)
    assert e.value.reason == fault.split("@")[0]
    before = _counter("kzg_cells_verified_total", "path")
    assert verify_kzg_for_rpc_blocks(settings, [sidecars]) is False
    assert _counter("kzg_cells_verified_total", "path") == before


@pytest.mark.parametrize("epoch, maximum", [
    (0, 6), (SPEC.blob_schedule[0][0] - 1, 6), (SPEC.blob_schedule[0][0], 15),
    (LAST_EPOCH - 1, 15), (LAST_EPOCH, 21), (LAST_EPOCH + 10**6, 21)])
def test_the_blob_maximum_is_a_schedule(epoch, maximum):
    assert SPEC.max_blobs_per_block_at(epoch) == maximum
    electra = SPEC.with_forks_at(0, through="electra")
    assert electra.max_blobs_per_block_at(0) == 9


@pytest.mark.parametrize("fork", ["deneb", "electra"])
def test_inclusion_proof_of_the_commitments(fork):
    commitments = [bytes([i]) * 48 for i in range(1, 22)]
    body = TYPES.beacon_block_body_class(fork)(
        blob_kzg_commitments=commitments, graffiti=b"\x07" * 32)
    header = SignedBeaconBlockHeader(message=BeaconBlockHeader(
        slot=LAST_EPOCH * 32, body_root=body.hash_tree_root()))
    branch = dcv.compute_kzg_commitments_inclusion_proof(body)
    assert len(branch) == MAINNET_PRESET.kzg_commitments_inclusion_proof_depth

    def sidecar(**changed):
        fields = dict(
            index=1, column=[b"\x00" * 2048] * 21,
            kzg_commitments=commitments, kzg_proofs=[b"\x00" * 48] * 21,
            signed_block_header=header,
            kzg_commitments_inclusion_proof=branch)
        return TYPES.DataColumnSidecar(**{**fields, **changed})

    assert dcv.verify_data_column_sidecar_inclusion_proof(sidecar(), SPEC)
    round_trip = TYPES.DataColumnSidecar.deserialize(sidecar().serialize())
    assert round_trip.hash_tree_root() == sidecar().hash_tree_root()
    assert dcv.verify_data_column_sidecar_inclusion_proof(round_trip, SPEC)
    # another block's commitments, one fewer, a sibling changed
    for bad in (sidecar(kzg_commitments=[b"\x09" * 48] + commitments[1:]),
                sidecar(kzg_commitments=commitments[:20]),
                sidecar(kzg_commitments_inclusion_proof=(
                    [b"\x01" * 32] + branch[1:]))):
        assert not dcv.verify_data_column_sidecar_inclusion_proof(bad, SPEC)


def test_by_root_identifier_round_trips():
    ident = DataColumnsByRootIdentifier(block_root=b"\x05" * 32,
                                        columns=[0, 64, 127])
    again = DataColumnsByRootIdentifier.deserialize(ident.serialize())
    assert [int(c) for c in again.columns] == [0, 64, 127]
    assert again.hash_tree_root() == ident.hash_tree_root()


# --- the segment entry's two branches ---------------------------------------------------

class _Blob:
    def __init__(self, i):
        self.blob, self.kzg_commitment, self.kzg_proof = (
            b"blob%d" % i, b"commitment%d" % i, b"proof%d" % i)


@pytest.mark.parametrize("kind", ["blobs", "columns", "empty"])
def test_segment_entry_routes_by_what_the_blocks_hold(monkeypatch, block,
                                                      kind):
    """A blob segment goes to validate_blobs with the arguments it always
    got, and nothing of it reaches the column plane; a column segment the
    other way round."""
    from lighthouse_tpu.chain import blob_verification

    calls = []
    monkeypatch.setattr(
        blob_verification, "validate_blobs",
        lambda *a: calls.append(("blobs", a)) or True)
    monkeypatch.setattr(
        dcv, "validate_data_columns",
        lambda *a: calls.append(("columns", a)) or True)
    blobs = [[_Blob(0), _Blob(1)], [], [_Blob(2)]]
    segment = {"blobs": blobs, "columns": [block[0][:2], block[0][2:]],
               "empty": []}[kind]
    assert data_availability.verify_kzg_for_rpc_blocks("settings", segment)
    if kind == "columns":
        assert calls == [("columns", ("settings", list(block[0])))]
    else:
        flat = [s for b in segment for s in b]
        assert calls == [("blobs", (
            "settings", [s.kzg_commitment for s in flat],
            [s.blob for s in flat], [s.kzg_proof for s in flat]))]
