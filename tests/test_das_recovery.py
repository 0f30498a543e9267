"""A supernode's reconstruction: `das.recover_cells_and_kzg_proofs_batch`
on the two device programs (ops/fr._recover_kernel, the spec's
recover_polynomialcoeff as radix-2 transforms; ops/msm._fk20_kernel, every
cell proof by FK20) against the host's Python rung and the plain
reference, at dev widths whose cells hold several field elements (256:
4 a cell, 512: 8), and `data_column_verification.reconstruct_data_columns`
above it.

The known-tau proofs come from `benchmarks/reference/das_plain.py`, in the
scalar field: no multi-scalar multiplication of the program is in them.
On XLA:CPU a FK20 shape compiles in about a minute and a call takes ten
to fifteen seconds: the tests share two blob counts at width 256 and one
at 512.
"""

import random

import numpy as np
import pytest

from benchmarks.reference import das_plain as ref
from lighthouse_tpu.chain import data_column_verification as dcv
from lighthouse_tpu.crypto import das, kzg
from lighthouse_tpu.crypto.bls.fields import R
from lighthouse_tpu.types.containers import (
    BeaconBlockHeader,
    SignedBeaconBlockHeader,
    make_types,
)
from lighthouse_tpu.types.spec import MAINNET_PRESET, ChainSpec

TAU = 0x123456789ABCDEF      # KzgSettings.dev's
SPEC = ChainSpec.mainnet()


class _Blobs:
    """Seeded blobs of one width with everything the tests compare: the
    settings, the reference's setup, each blob's cells and known-tau
    proofs."""

    def __init__(self, width, blobs, seed):
        self.width = width
        self.settings = kzg.KzgSettings.dev(width=width, tau=TAU)
        self.setup = ref.Setup(width, 32, TAU, 128)
        self.n_cells, self.size = das._cell_geometry(width)
        rng = random.Random(seed)
        self.blobs, self.cells, self.proofs, self.commitments = [], [], [], []
        for _ in range(blobs):
            blob = b"".join(rng.randrange(R).to_bytes(32, "big")
                            for _ in range(width))
            cells, proofs, c = self.known_tau(blob)
            self.blobs.append(blob)
            self.cells.append(cells)
            self.proofs.append(proofs)
            self.commitments.append(c)

    def known_tau(self, blob):
        """The reference's cells, known-tau proofs and commitment."""
        evals = ref.compute_cells(blob, self.setup)
        p_tau, c = self.setup.commit(ref.blob_to_polynomial(blob, self.setup))
        return ([ref.cell_to_bytes(e) for e in evals],
                self.setup.cell_proofs(p_tau, evals), c)

    def blob_of(self, coeffs):
        """The blob whose polynomial has these coefficients."""
        def at(w):
            acc = 0
            for c in reversed(coeffs):
                acc = (acc * w + c) % R
            return acc

        return b"".join(at(w).to_bytes(32, "big")
                        for w in self.settings.roots_brp)

    def held(self, ids):
        return [[cells[c] for c in ids] for cells in self.cells]


@pytest.fixture(scope="module")
def w256():
    return _Blobs(256, 2, seed=41)


@pytest.fixture(scope="module")
def w512():
    return _Blobs(512, 1, seed=43)


def _subsets(n_cells, rng):
    return {
        "random_half": sorted(rng.sample(range(n_cells), n_cells // 2)),
        "systematic_half": list(range(n_cells // 2)),
        "more_than_half": sorted(rng.sample(range(n_cells),
                                            3 * n_cells // 4)),
        "all_present": list(range(n_cells)),
    }


@pytest.mark.parametrize("kind", ["random_half", "systematic_half",
                                  "more_than_half", "all_present"])
def test_device_recovery_equals_the_host_rung(w256, kind):
    ids = _subsets(w256.n_cells, random.Random(7))[kind]
    held = w256.held(ids)
    cells, proofs = das.recover_cells_and_kzg_proofs_batch(
        ids, held, w256.settings)
    assert das.recovery_path(w256.settings) == "device"
    for r in range(2):
        assert cells[r] == das.recover_all_cells(ids, held[r], w256.settings)
        assert cells[r] == w256.cells[r]
        assert proofs[r] == w256.proofs[r]
        # a received cell comes back as the object given
        assert all(cells[r][c] is held[r][k] for k, c in enumerate(ids))


def test_what_the_spec_asserts_raises(w256):
    s, n = w256.settings, w256.n_cells
    few = list(range(n // 2 - 1))
    with pytest.raises(kzg.KzgError, match="need at least"):
        das.recover_cells_and_kzg_proofs_batch(few, w256.held(few), s)
    twice = [0] + list(range(n // 2))
    with pytest.raises(kzg.KzgError, match="duplicate"):
        das.recover_cells_and_kzg_proofs_batch(twice, w256.held(twice), s)
    outside = list(range(n // 2)) + [n]
    with pytest.raises(kzg.KzgError, match="out of range"):
        das.recover_cells_and_kzg_proofs_batch(
            outside, [row + [row[0]] for row in w256.held(outside[:-1])], s)
    ids = list(range(n // 2))
    short = w256.held(ids)
    short[1] = short[1][:-1]
    with pytest.raises(kzg.KzgError, match="length mismatch"):
        das.recover_cells_and_kzg_proofs_batch(ids, short, s)
    # more than half, one received cell off the codeword: the degree check
    ids = list(range(3 * n // 4))
    held = w256.held(ids)
    bad = bytearray(held[1][5])
    bad[40] ^= 1
    held[1][5] = bytes(bad)
    with pytest.raises(kzg.KzgError, match="inconsistent"):
        das.recover_cells_and_kzg_proofs_batch(ids, held, s)
    with pytest.raises(kzg.KzgError):
        das.recover_all_cells(ids, held[1], s)


def test_non_canonical_cells_raise(w256):
    ids = list(range(w256.n_cells // 2))
    held = w256.held(ids)
    held[0][3] = R.to_bytes(32, "big") + held[0][3][32:]
    with pytest.raises(kzg.KzgError, match="canonical"):
        das.recover_cells_and_kzg_proofs_batch(ids, held, w256.settings)


@pytest.fixture
def per_cell(monkeypatch):
    """The spec's per-cell path: each quotient committed on the monomial
    points by kzg.g1_lincomb, on the host's native lincomb (at width 512 a
    quotient's 504 points would route to the device fold)."""
    monkeypatch.setenv("LHTPU_MSM_DEVICE_MIN", str(1 << 30))

    def proofs(blob, settings):
        with monkeypatch.context() as m:
            m.setattr(das, "_DEVICE_MIN_WIDTH", 1 << 30)
            return das.compute_cells_and_kzg_proofs(blob, settings)

    return proofs


def _one_column(w256):
    """Coefficients non-zero in one column i = 1 of the l: every window
    of the fixed-base sum picks the identity in the other l - 1 columns."""
    rng = random.Random(5)
    return w256.blob_of([rng.randrange(1, R) if j % w256.size == 1 else 0
                         for j in range(w256.width)])


@pytest.mark.parametrize("kind", ["random", "one_column", "all_r_minus_1"])
def test_fk20_proofs_are_the_per_cell_and_known_tau_proofs(w256, per_cell,
                                                           kind):
    from lighthouse_tpu.crypto.bls import curve as cv

    blob = {"random": lambda: w256.blobs[0],
            "one_column": lambda: _one_column(w256),
            "all_r_minus_1": lambda: w256.blob_of([R - 1] * w256.width),
            }[kind]()
    want = ((w256.cells[0], w256.proofs[0]) if kind == "random"
            else w256.known_tau(blob)[:2])
    cells, proofs = das.compute_cells_and_kzg_proofs(blob, w256.settings)
    assert (cells, proofs) == tuple(want)
    assert per_cell(blob, w256.settings) == (cells, proofs)
    if kind != "random":
        return
    # a known answer: p(x) = x^l leaves the quotient 1 by every cell's
    # x^l - a, so every proof is the generator (ops/prewarm's gate)
    blob = b"".join(pow(w, w256.size, R).to_bytes(32, "big")
                    for w in w256.settings.roots_brp)
    _, proofs = das.compute_cells_and_kzg_proofs(blob, w256.settings)
    assert set(proofs) == {cv.g1_to_bytes(cv.g1_generator())}


def test_a_batch_is_its_blobs_one_at_a_time(w256):
    ids = _subsets(w256.n_cells, random.Random(9))["random_half"]
    both = das.recover_cells_and_kzg_proofs_batch(ids, w256.held(ids),
                                                  w256.settings)
    for r in range(2):
        one = das.recover_cells_and_kzg_proofs_batch(
            ids, [w256.held(ids)[r]], w256.settings)
        assert (one[0][0], one[1][0]) == (both[0][r], both[1][r])


def test_zero_and_constant_blobs_have_identity_proofs(w256):
    """No flag in the FK20 program: p = 0 and p = c have every quotient 0,
    so every proof is the identity, and recovery gives the blob back."""
    s = w256.settings
    inf = bytes([0xC0]) + b"\x00" * 47
    blobs = [b"\x00" * 32 * 256, (7).to_bytes(32, "big") * 256]
    cells = [das.compute_cells(b, s) for b in blobs]
    ids = list(range(64, 128))
    got, proofs = das.recover_cells_and_kzg_proofs_batch(
        ids, [[c[i] for i in ids] for c in cells], s)
    assert got == cells
    assert proofs == [[inf] * 128] * 2


def test_the_second_width(w512, per_cell):
    """Width 512: eight field elements a cell, one blob."""
    ids = _subsets(w512.n_cells, random.Random(11))["random_half"]
    held = w512.held(ids)
    cells, proofs = das.recover_cells_and_kzg_proofs_batch(
        ids, held, w512.settings)
    assert cells[0] == das.recover_all_cells(ids, held[0], w512.settings)
    assert cells[0] == w512.cells[0]
    assert proofs[0] == w512.proofs[0]
    assert per_cell(w512.blobs[0], w512.settings) == (
        w512.cells[0], w512.proofs[0])


def test_below_the_device_width_the_host_rung_serves():
    data = _Blobs(64, 1, seed=5)
    ids = list(range(1, 128, 2))
    assert das.recovery_path(data.settings) == "host"
    cells, proofs = das.recover_cells_and_kzg_proofs_batch(
        ids, data.held(ids), data.settings)
    assert cells == data.cells and proofs == data.proofs


# --- the chain entry ------------------------------------------------------------


class _Sidecar:
    """A DataColumnSidecar with cells of the test's width (the SSZ type's
    are the preset's 2,048 bytes)."""

    def __init__(self, index, column, kzg_commitments, kzg_proofs,
                 signed_block_header, kzg_commitments_inclusion_proof):
        self.index = index
        self.column = list(column)
        self.kzg_commitments = list(kzg_commitments)
        self.kzg_proofs = list(kzg_proofs)
        self.signed_block_header = signed_block_header
        self.kzg_commitments_inclusion_proof = kzg_commitments_inclusion_proof


def _sidecars(data, slot):
    body = make_types(MAINNET_PRESET).beacon_block_body_class("electra")(
        blob_kzg_commitments=data.commitments)
    header = SignedBeaconBlockHeader(message=BeaconBlockHeader(
        slot=slot, body_root=body.hash_tree_root()))
    branch = dcv.compute_kzg_commitments_inclusion_proof(body)
    return [_Sidecar(c, [cells[c] for cells in data.cells], data.commitments,
                     [proofs[c] for proofs in data.proofs], header, branch)
            for c in range(data.n_cells)]


def test_reconstruct_data_columns_rebuilds_valid_sidecars(w256):
    slot = SPEC.blob_schedule[-1][0] * 32
    full = _sidecars(w256, slot)
    rng = random.Random(13)
    held = sorted(rng.sample(range(128), 64), reverse=True)
    given = [full[c] for c in held]           # in no particular order
    out = dcv.reconstruct_data_columns(w256.settings, given, SPEC)
    missing = [c for c in range(128) if c not in held]
    assert [s.index for s in out] == missing
    for s in out:
        want = full[s.index]
        assert type(s) is _Sidecar
        assert (s.column, s.kzg_proofs, s.kzg_commitments) == (
            want.column, want.kzg_proofs, want.kzg_commitments)
        assert s.signed_block_header is want.signed_block_header
        assert s.kzg_commitments_inclusion_proof == \
            want.kzg_commitments_inclusion_proof
        assert dcv.verify_data_column_sidecar_inclusion_proof(s, SPEC)
    # four rebuilt sidecars, eight cells: the fused cell batch
    assert dcv.validate_data_columns(w256.settings, out[:4], SPEC)
    forged = out[:4]
    forged[2].kzg_proofs = forged[2].kzg_proofs[::-1]
    assert not dcv.validate_data_columns(w256.settings, forged, SPEC)
    # nothing missing: nothing to rebuild
    assert dcv.reconstruct_data_columns(w256.settings, full, SPEC) == []


def test_reconstruct_data_columns_refuses_what_it_cannot_rebuild(w256):
    slot = SPEC.blob_schedule[-1][0] * 32
    full = _sidecars(w256, slot)
    with pytest.raises(kzg.KzgError, match="need at least"):
        dcv.reconstruct_data_columns(w256.settings, full[:63], SPEC)
    other = _sidecars(w256, slot + 1)
    with pytest.raises(dcv.DataColumnError):
        dcv.reconstruct_data_columns(w256.settings,
                                     full[:64] + other[64:65], SPEC)


# --- the products reckoned beside each program ------------------------------------


def _tally(monkeypatch, trace):
    """Lane-products of every multiply traced by ``trace(fresh)``, a scan's
    body counted once a step; ``fresh(f)`` is a new function object over
    f, whose trace no cache holds (the device tests above traced the
    programs at the small shapes already)."""
    import jax

    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import fr

    tally = {"fr": 0, "fp": 0}
    steps = [1]
    fr_mul, fp_mul, scan = fr.mont_mul_lm, bi.FP.mont_mul_lm, jax.lax.scan

    def count(key, mul):
        def f(a, b):
            tally[key] += steps[-1] * int(np.prod(a.shape[1:]))
            return mul(a, b)
        return f

    def count_scan(f, init, xs, *args, **kwargs):
        steps.append(steps[-1] * jax.tree_util.tree_leaves(xs)[0].shape[0])
        try:
            return scan(f, init, xs, *args, **kwargs)
        finally:
            steps.pop()

    monkeypatch.setattr(fr, "mont_mul_lm", count("fr", fr_mul))
    monkeypatch.setattr(bi.FP, "mont_mul_lm", count("fp", fp_mul))
    monkeypatch.setattr(bi, "mont_mul", None)    # nothing is materialized
    monkeypatch.setattr(jax.lax, "scan", count_scan)
    trace(lambda f: lambda *a: f(*a))
    return tally


@pytest.mark.parametrize("rows,width", [(2, 256), (21, 4096)])
def test_ntt_products_are_what_the_program_traces(monkeypatch, rows, width):
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops import fr

    N, m = 2 * width, 128
    u32 = jnp.uint32
    args = (jax.ShapeDtypeStruct((rows, N, fr.L), u32),
            jax.ShapeDtypeStruct((m, fr.L), u32),
            jax.ShapeDtypeStruct((m, fr.L), u32),
            jax.ShapeDtypeStruct((N, fr.L), u32),
            jax.ShapeDtypeStruct((N, fr.L), u32),
            jax.ShapeDtypeStruct((N.bit_length() - 1, N // 2, fr.L), u32),
            jax.ShapeDtypeStruct((N.bit_length() - 1, N // 2, fr.L), u32))
    tally = _tally(monkeypatch, lambda fresh: jax.eval_shape(
        fresh(fr._recover_kernel._fn.__wrapped__), *args))
    assert (tally["fr"], tally["fp"]) == (fr._ntt_products(rows, N)[0], 0)
    assert fr._ntt_products(rows, N)[1] == 0
    if rows == 21:   # the cell's request: four transforms of 8,192
        assert tally["fr"] == 21 * (4 * 13 * 4096 + 5 * 8192 + 4096)


@pytest.mark.parametrize("rows,width", [(2, 256), (21, 4096)])
def test_fk20_products_are_what_the_program_traces(monkeypatch, rows, width):
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import ec, fr, msm

    m, ell = 128, 2 * width // 128
    point = jax.ShapeDtypeStruct((bi.L, ell, m), jnp.uint32)
    coeffs = jax.ShapeDtypeStruct((fr.L, width * rows), jnp.uint32)
    tally = _tally(monkeypatch, lambda fresh: jax.eval_shape(
        fresh(msm._fk20_kernel._fn.__wrapped__), coeffs, point, point,
        point))
    resident, materialized = msm.fk20_products(rows, ell, m)
    assert (tally["fr"] + tally["fp"], 0) == (resident, materialized)
    lanes = rows * m * ell
    assert tally["fr"] == 7 * 64 * ell * rows + lanes
    # the fixed-base stage: one table a setup point, then a window's four
    # doublings, l - 1 additions of the sum and one into it, a (cell, blob)
    assert ec.PROJ_MULTIPLES_PRODUCTS == 140
    assert ec.proj_straus_products(1, ell) == 4 * 8 + 12 + (ell - 1) * 12
    assert ec.proj_scalar_mul_products(64) == 140 + 64 * 44 == 2956
    assert ec.g1_fft_products(128) == 7 * 64 * (2956 + 24)
    if rows == 21:   # the cell's request
        assert ec.proj_straus_products(1, 64) == 800
        assert resident == 195_626_368
    columns = jax.ShapeDtypeStruct((bi.L, m, ell), jnp.uint32)
    tally = _tally(monkeypatch, lambda fresh: jax.eval_shape(
        fresh(msm._fk20_tables._fn.__wrapped__), columns, columns, columns))
    assert tally["fp"] == ell * ec.g1_fft_products(m)   # l transforms


@pytest.mark.slow
def test_a_full_block_at_the_preset_width():
    """The cell's own shape on XLA:CPU: 21 blobs of 4,096, half the
    columns (minutes: FK20 at 172,032 lanes)."""
    data = _Blobs(4096, 21, seed=47)
    ids = _subsets(128, random.Random(3))["random_half"]
    cells, proofs = das.recover_cells_and_kzg_proofs_batch(
        ids, data.held(ids), data.settings)
    assert cells == data.cells and proofs == data.proofs
