"""KZG polynomial commitments for EIP-4844 blobs (Deneb).

Rebuild of the reference's c-kzg-4844 wrapper
(/root/reference/crypto/kzg/src/lib.rs:105-131 verify_blob_kzg_proof_batch
et al.), math per the consensus specs' polynomial-commitments.md, riding
this repo's own BLS12-381 core:

- commitments / proofs are multi-scalar multiplications over the
  Lagrange-basis setup points — routed through the unified MSM plane
  (ops/msm.msm_g1: calibrated device threshold, native/pure-Python
  host seam for tiny dev setups);
- single-proof verification is ONE multi-pairing on the batched device
  Miller loop (ops/bls12_381.multi_pairing_device);
- `verify_blob_kzg_proof_batch` folds n proofs into a single 2-pairing
  check by a random linear combination (the verifier-local scalar r),
  and for production batch sizes rides the FUSED device plane: one
  membership dispatch for every decoded point (its verdict read when
  the fold needs it, not when it is dispatched), the blobs evaluated
  barycentrically in slices of ops/fr._EVAL_MAX_BLOBS (product-tree
  denominator inversion), each slice's canonicity check, challenges
  and limbs made while the device evaluates the slice before it, and
  one dispatch for both RLC MSMs + the pairing, with the folded points
  entering the Miller loop in Jacobian form (zp path) so no affine
  conversion or host crossing sits between MSM and pairing.  Host
  work: decompression, challenges, r-powers, limb packing, and the
  native final exponentiation.  Every stage is a span
  (`kzg.verify_batch` and its children) feeding
  ``kzg_verify_stage_seconds{stage}``.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from functools import lru_cache, partial

import numpy as np

from lighthouse_tpu.common import device_telemetry as _dtel
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import REGISTRY, record_swallowed
from lighthouse_tpu.crypto.bls import curve as cv
from lighthouse_tpu.ops import program_store as _pstore

# AOT program-store coverage (lhlint LH606): the fused verification
# program is prewarmed by the "kzg" driver in ops/prewarm; the plain
# MSM rides the unified plane's entry (ops/msm.py, "msm" driver)
_pstore.register_entry("crypto/kzg.py::_kzg_fused_program@_kzg_fused",
                       driver="kzg")
from lighthouse_tpu.crypto.bls.fields import R as BLS_MODULUS

BYTES_PER_FIELD_ELEMENT = 32
KZG_ENDIANNESS = "big"
FIAT_SHAMIR_PROTOCOL_DOMAIN = b"FSBLOBVERIFY_V1_"
RANDOM_CHALLENGE_KZG_BATCH_DOMAIN = b"RCKZGBATCH___V1_"
PRIMITIVE_ROOT_OF_UNITY = 7

class KzgError(ValueError):
    pass


def _bit_reversal_permutation(values: list) -> list:
    n = len(values)
    bits = n.bit_length() - 1
    assert 1 << bits == n, "length must be a power of two"
    return [values[int(format(i, f"0{bits}b")[::-1], 2)] for i in range(n)]


def _compute_roots_of_unity(order: int) -> list[int]:
    root = pow(PRIMITIVE_ROOT_OF_UNITY,
               (BLS_MODULUS - 1) // order, BLS_MODULUS)
    assert pow(root, order, BLS_MODULUS) == 1
    assert pow(root, order // 2, BLS_MODULUS) != 1
    out = [1]
    for _ in range(order - 1):
        out.append(out[-1] * root % BLS_MODULUS)
    return out


@dataclass
class KzgSettings:
    """Trusted setup in Lagrange form (bit-reversed order, like the spec).

    g1_lagrange_brp[i] = L_brp(i)(τ)·G1;  g2_tau = τ·G2.  The optional
    monomial halves (τ^i·G1 and τ^i·G2) power the PeerDAS cell proofs
    (crypto/das.py); the ceremony file carries both."""

    width: int
    g1_lagrange_brp: list          # affine G1 points (int pairs)
    g2_tau: object                 # τ·G2 (affine Fq2 point)
    roots_brp: list[int]
    g1_monomial: list | None = None    # τ^i·G1, i < width
    g2_monomial: list | None = None    # τ^i·G2, i <= 64

    @staticmethod
    @lru_cache(maxsize=4)
    def dev(width: int = 16, tau: int = 0x123456789ABCDEF) -> "KzgSettings":
        """INSECURE dev setup from a known τ — tests/benches only.

        Real deployments load the ceremony output via `from_setup_points`;
        the math downstream is identical.
        """
        roots = _compute_roots_of_unity(width)
        roots_brp = _bit_reversal_permutation(roots)
        inv_w = pow(width, -1, BLS_MODULUS)
        tau_pow = pow(tau, width, BLS_MODULUS)
        g1 = cv.g1_generator()
        lagrange = []
        for w_i in roots_brp:
            # L_i(τ) = w_i·(τ^n − 1) / (n·(τ − w_i))
            num = w_i * (tau_pow - 1) % BLS_MODULUS
            den = width * (tau - w_i) % BLS_MODULUS
            l_i = num * pow(den, -1, BLS_MODULUS) % BLS_MODULUS
            lagrange.append(cv.g1_mul(g1, l_i))
        g2_tau = cv.g2_mul(cv.g2_generator(), tau)
        # monomial halves for the cell-proof paths (τ^i·G1 / τ^i·G2)
        g1_monomial = []
        acc = 1
        for _ in range(width):
            g1_monomial.append(cv.g1_mul(g1, acc))
            acc = acc * tau % BLS_MODULUS
        g2_monomial = []
        acc = 1
        g2 = cv.g2_generator()
        for _ in range(min(width, 64) + 1):
            g2_monomial.append(cv.g2_mul(g2, acc))
            acc = acc * tau % BLS_MODULUS
        return KzgSettings(width, lagrange, g2_tau, roots_brp,
                           g1_monomial=g1_monomial, g2_monomial=g2_monomial)

    @staticmethod
    def from_setup_points(g1_lagrange_brp: list, g2_tau) -> "KzgSettings":
        """Wrap externally-loaded ceremony points (already bit-reversed)."""
        width = len(g1_lagrange_brp)
        roots = _compute_roots_of_unity(width)
        return KzgSettings(width, g1_lagrange_brp,
                           g2_tau, _bit_reversal_permutation(roots))

    @staticmethod
    def load_trusted_setup(source, validate: bool = True) -> "KzgSettings":
        """Load the ceremony output (consensus-specs
        trusted_setup_4096.json format: g1_lagrange in natural order +
        g2_monomial, compressed hex — the file the reference embeds at
        common/eth2_network_config/built_in_network_configs/trusted_setup.json
        and parses in crypto/kzg/src/trusted_setup.rs).

        The lagrange points are bit-reversal-permuted at load (c-kzg
        load_trusted_setup does the same).  With validate=True (the
        default, matching c-kzg) every G1 point passes the batched
        device membership test; validate=False skips that and only
        checks on-curve decompression + g1_lagrange[0]'s membership."""
        import json as _json

        if isinstance(source, dict):
            d = source
        else:
            with open(source) as f:        # str / bytes / os.PathLike
                d = _json.load(f)
        n = len(d.get("g1_lagrange", ()))
        if n == 0 or n & (n - 1):
            raise KzgError(
                f"g1_lagrange length {n} is not a power of two "
                "(truncated trusted-setup file?)")
        g1 = [cv.g1_from_bytes(bytes.fromhex(h.removeprefix("0x")),
                               subgroup_check=False)
              for h in d["g1_lagrange"]]
        g2_tau = cv.g2_from_bytes(
            bytes.fromhex(d["g2_monomial"][1].removeprefix("0x")))
        # monomial halves power the PeerDAS cell proofs; decompression is
        # deferred skip-checked like the lagrange points
        g1_monomial = None
        if "g1_monomial" in d:
            g1_monomial = [
                cv.g1_from_bytes(bytes.fromhex(h.removeprefix("0x")),
                                 subgroup_check=False)
                for h in d["g1_monomial"]]
        g2_monomial = [
            cv.g2_from_bytes(bytes.fromhex(h.removeprefix("0x")))
            for h in d["g2_monomial"]]
        # structural pins run in every mode: g2_monomial[0] must be THE
        # G2 generator, and at least one lagrange point must be a member
        if bytes.fromhex(d["g2_monomial"][0].removeprefix("0x")) != \
                cv.g2_to_bytes(cv.g2_generator()):
            raise KzgError("g2_monomial[0] is not the G2 generator")
        if validate:
            from lighthouse_tpu.ops.bls_backend import (
                batch_subgroup_check_g1,
            )

            pts = g1 if g1_monomial is None else g1 + g1_monomial
            ok = batch_subgroup_check_g1(pts)
            if not bool(ok.all()):
                bad = [i for i, v in enumerate(ok) if not v]
                raise KzgError(
                    f"{len(bad)} trusted-setup G1 points fail the subgroup "
                    f"check (first: index {bad[0]} of lagrange+monomial)")
        elif not cv.g1_in_subgroup(g1[0]):
            raise KzgError("g1_lagrange[0] fails the subgroup check")
        s = KzgSettings.from_setup_points(
            _bit_reversal_permutation(g1), g2_tau)
        s.g1_monomial = g1_monomial
        s.g2_monomial = g2_monomial
        return s


# --- field element / blob codecs -------------------------------------------

def bytes_to_bls_field(b: bytes) -> int:
    v = int.from_bytes(b, KZG_ENDIANNESS)
    if v >= BLS_MODULUS:
        raise KzgError("field element not canonical")
    return v


def bls_field_to_bytes(v: int) -> bytes:
    return int(v).to_bytes(BYTES_PER_FIELD_ELEMENT, KZG_ENDIANNESS)


def blob_to_polynomial(blob: bytes, settings: KzgSettings) -> list[int]:
    if len(blob) != settings.width * BYTES_PER_FIELD_ELEMENT:
        raise KzgError(f"blob must be {settings.width} field elements")
    return [bytes_to_bls_field(blob[i:i + 32]) for i in range(0, len(blob), 32)]


def hash_to_bls_field(data: bytes) -> int:
    return int.from_bytes(hashlib.sha256(data).digest(), "big") % BLS_MODULUS


def compute_challenge(blob: bytes, commitment: bytes, settings: KzgSettings) -> int:
    degree = settings.width.to_bytes(16, KZG_ENDIANNESS)
    return hash_to_bls_field(
        FIAT_SHAMIR_PROTOCOL_DOMAIN + degree + blob + commitment)


# --- MSM --------------------------------------------------------------------

def g1_lincomb(points, scalars, *, device: bool | None = None,
               pad_to: int | None = None):
    """Σ k_i·P_i (the c-kzg g1_lincomb seam), riding the unified MSM
    plane (ops/msm): device routing by the calibrated g1-track
    threshold, host fallback through the native lincomb seam.  `pad_to`
    rounds the lane count up so differently-sized MSMs share one
    compiled program."""
    from lighthouse_tpu.ops import msm as _msm

    return _msm.msm_g1(points, scalars, device=device, pad_to=pad_to)


# --- core KZG ---------------------------------------------------------------

def blob_to_kzg_commitment(blob: bytes, settings: KzgSettings) -> bytes:
    poly = blob_to_polynomial(blob, settings)
    return cv.g1_to_bytes(g1_lincomb(settings.g1_lagrange_brp, poly))


def _batch_inverse(vals: list[int]) -> list[int]:
    """Montgomery batch inversion: one modular inverse + 3(n-1) products."""
    prefix = [1] * (len(vals) + 1)
    for i, v in enumerate(vals):
        prefix[i + 1] = prefix[i] * v % BLS_MODULUS
    inv = pow(prefix[-1], -1, BLS_MODULUS)
    out = [0] * len(vals)
    for i in range(len(vals) - 1, -1, -1):
        out[i] = prefix[i] * inv % BLS_MODULUS
        inv = inv * vals[i] % BLS_MODULUS
    return out


def evaluate_polynomial_in_evaluation_form(
    poly: list[int], z: int, settings: KzgSettings
) -> int:
    """Barycentric evaluation over the bit-reversed evaluation domain."""
    width = settings.width
    roots = settings.roots_brp
    if z in roots:
        return poly[roots.index(z)]
    inv_width = pow(width, -1, BLS_MODULUS)
    invs = _batch_inverse([(z - w_i) % BLS_MODULUS for w_i in roots])
    total = 0
    for p_i, w_i, d_i in zip(poly, roots, invs):
        total += p_i * w_i % BLS_MODULUS * d_i
    total %= BLS_MODULUS
    return total * (pow(z, width, BLS_MODULUS) - 1) % BLS_MODULUS \
        * inv_width % BLS_MODULUS


def compute_kzg_proof_impl(
    poly: list[int], z: int, settings: KzgSettings
) -> tuple[bytes, int]:
    """Proof that p(z) = y: quotient commitment [q(τ)]G1 in Lagrange form."""
    y = evaluate_polynomial_in_evaluation_form(poly, z, settings)
    roots = settings.roots_brp
    q = [0] * settings.width
    if z in roots:
        m = roots.index(z)
        for i, (p_i, w_i) in enumerate(zip(poly, roots)):
            if i == m:
                continue
            # q_i = (p_i − y)/(w_i − z); q_m = Σ_i≠m (p_i − y)·w_i/(z·(z − w_i))
            q[i] = (p_i - y) * pow((w_i - z) % BLS_MODULUS, -1, BLS_MODULUS)
            q[i] %= BLS_MODULUS
            q[m] += (p_i - y) * w_i % BLS_MODULUS * pow(
                z * (z - w_i) % BLS_MODULUS, -1, BLS_MODULUS)
            q[m] %= BLS_MODULUS
    else:
        invs = _batch_inverse([(w_i - z) % BLS_MODULUS for w_i in roots])
        for i, (p_i, d_i) in enumerate(zip(poly, invs)):
            q[i] = (p_i - y) * d_i % BLS_MODULUS
    proof = cv.g1_to_bytes(g1_lincomb(settings.g1_lagrange_brp, q))
    return proof, y


def compute_kzg_proof(blob: bytes, z_bytes: bytes, settings: KzgSettings
                      ) -> tuple[bytes, bytes]:
    poly = blob_to_polynomial(blob, settings)
    proof, y = compute_kzg_proof_impl(poly, bytes_to_bls_field(z_bytes), settings)
    return proof, bls_field_to_bytes(y)


def compute_blob_kzg_proof(blob: bytes, commitment: bytes,
                           settings: KzgSettings) -> bytes:
    poly = blob_to_polynomial(blob, settings)
    z = compute_challenge(blob, commitment, settings)
    proof, _ = compute_kzg_proof_impl(poly, z, settings)
    return proof


def _pairing_check(pairs) -> bool:
    from lighthouse_tpu.ops.bls12_381 import multi_pairing_device

    return multi_pairing_device(pairs).is_one()


def verify_kzg_proof_impl(commitment, z: int, y: int, proof,
                          settings: KzgSettings) -> bool:
    """e(C − y·G1, −G2) · e(π, τ·G2 − z·G2) == 1."""
    g1, g2 = cv.g1_generator(), cv.g2_generator()
    p_minus_y = cv.g1_add(commitment, cv.g1_neg(cv.g1_mul(g1, y))) \
        if y else commitment
    tau_minus_z = cv.g2_add(settings.g2_tau, cv.g2_neg(cv.g2_mul(g2, z))) \
        if z else settings.g2_tau
    return _pairing_check([
        (p_minus_y, cv.g2_neg(g2)),
        (proof, tau_minus_z),
    ])


def verify_kzg_proof(commitment_bytes: bytes, z_bytes: bytes, y_bytes: bytes,
                     proof_bytes: bytes, settings: KzgSettings) -> bool:
    try:
        c = cv.g1_from_bytes(commitment_bytes)
        pi = cv.g1_from_bytes(proof_bytes)
        z = bytes_to_bls_field(z_bytes)
        y = bytes_to_bls_field(y_bytes)
    except (ValueError, KzgError):
        return False
    return verify_kzg_proof_impl(c, z, y, pi, settings)


def verify_blob_kzg_proof(blob: bytes, commitment_bytes: bytes,
                          proof_bytes: bytes, settings: KzgSettings) -> bool:
    try:
        c = cv.g1_from_bytes(commitment_bytes)
        pi = cv.g1_from_bytes(proof_bytes)
        poly = blob_to_polynomial(blob, settings)
    except (ValueError, KzgError):
        return False
    z = compute_challenge(blob, commitment_bytes, settings)
    y = evaluate_polynomial_in_evaluation_form(poly, z, settings)
    return verify_kzg_proof_impl(c, z, y, pi, settings)


# below this many blobs the device round-trip is not worth it
_DEVICE_EVAL_MIN = 8

_STAGE_BUCKETS = (0.0001, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0)


def record_stage(stage: str, seconds: float) -> None:
    """One stage of a blob or cell batch verification (sole registration
    site of the kzg_* families — lhlint LH501 FAMILY_OWNERS)."""
    try:
        REGISTRY.histogram(
            "kzg_verify_stage_seconds",
            "blob and cell batch verification wall time by stage "
            "(eval_dispatch, interp_dispatch and fused_dispatch time the "
            "enqueue, eval_fetch, interp_fetch and fused_wait the host "
            "blocked on the device)",
            buckets=_STAGE_BUCKETS,
        ).labels(stage=stage).observe(seconds)
    except Exception as e:
        record_swallowed("kzg.record_stage", e)


def stage_span(name: str, stage: str, **attrs):
    """A span whose duration also feeds
    ``kzg_verify_stage_seconds{stage}``."""
    return tracing.span(name, observe=partial(record_stage, stage), **attrs)


def count_eval_lanes(live: int, padding: int) -> None:
    """(blob, root) lanes the evaluation slices carried (ops/fr.py): the
    batch's own and the last slice's fill."""
    lanes = REGISTRY.counter(
        "kzg_eval_lanes_total",
        "barycentric evaluation lanes dispatched, by kind")
    lanes.labels(kind="live").inc(live)
    lanes.labels(kind="padding").inc(padding)


def _count_by_multiply(products, resident: int, materialized: int) -> None:
    products.labels(multiply="resident").inc(resident)
    products.labels(multiply="materialized").inc(materialized)


def count_eval_products(resident: int, materialized: int) -> None:
    """Fr lane-products the evaluation slices dispatched (ops/fr.py), by
    the multiply that ran them: ``resident`` is `MontField.mont_mul_lm`
    (partial products stay in the core), ``materialized`` `mont_mul`
    (they are arrays of the program)."""
    _count_by_multiply(REGISTRY.counter(
        "kzg_eval_products_total",
        "Fr lane-products of the evaluation slices, by multiply"),
        resident, materialized)


def count_fused_products(resident: int, materialized: int) -> None:
    """Fp lane-products of one dispatched `_kzg_fused` check, by the
    multiply that runs them: ``resident`` the G1 fold's (window tables,
    scan, segment sum: ops/msm.fold_segments_g1, on `mont_mul_lm`),
    ``materialized`` the two-lane Miller loop's and `reduce_product`'s
    (`mont_mul`)."""
    _count_by_multiply(REGISTRY.counter(
        "kzg_fused_products_total",
        "Fp lane-products of the fused KZG checks dispatched, by multiply"),
        resident, materialized)


def count_cells_verified(path: str, cells: int) -> None:
    """Cells through das.verify_cell_kzg_proof_batch, by the path that
    served the batch."""
    REGISTRY.counter(
        "kzg_cells_verified_total",
        "cells through verify_cell_kzg_proof_batch, by the path that "
        "served the batch").labels(path=path).inc(cells)


def count_cell_lanes(live: int, padding: int) -> None:
    """MSM lanes of one group of a cell batch through `_kzg_fused`: the
    points of both sums, and what fills their buckets."""
    lanes = REGISTRY.counter(
        "kzg_cell_lanes_total",
        "MSM lanes of the cell batch checks dispatched, by kind")
    lanes.labels(kind="live").inc(live)
    lanes.labels(kind="padding").inc(padding)


def count_interp_products(products: int) -> None:
    """Fr lane-products of the aggregated coset interpolation dispatched
    (ops/fr._cell_interp_kernel, all on `MontField.mont_mul_lm`)."""
    REGISTRY.counter(
        "kzg_interp_products_total",
        "Fr lane-products of the cell interpolation programs "
        "dispatched").inc(products)


def count_eval_slice(overlapped: bool) -> None:
    """One evaluation slice dispatched (ops/fr.py): ``exposed`` when the
    host prepared it with no evaluation slice of its batch in flight
    (the first), ``overlapped`` when the device had the slice before it
    to run meanwhile."""
    REGISTRY.counter(
        "kzg_eval_slices_total",
        "evaluation slices dispatched, by whether their host preparation "
        "ran under an earlier slice of the batch").labels(
            prep="overlapped" if overlapped else "exposed").inc()


def _blob_fields_canonical(raw: "np.ndarray") -> bool:
    """Vectorized canonicity check of [N, W, 32] big-endian field bytes
    (< BLS_MODULUS) — replaces per-element python parsing on the batch
    path (3.1M ints for a 768-blob batch)."""
    words = np.ascontiguousarray(raw).reshape(-1, 32).view(">u8")
    m = np.frombuffer(BLS_MODULUS.to_bytes(32, "big"), ">u8")
    lt = words < m
    eq = words == m
    ok = lt[:, 0] | (eq[:, 0] & (lt[:, 1] | (eq[:, 1] & (
        lt[:, 2] | (eq[:, 2] & lt[:, 3])))))
    return bool(ok.all())


def _decode_g1_batch(encodings: list[bytes]):
    """(points, membership): decompress every point, then ONE device
    membership dispatch for all of them
    (ops/bls_backend.dispatch_subgroup_check_g1) instead of a
    pure-Python [r]P a point: 4.1 ms each, 6.4 s for the 1,536 points of
    a 768-sidecar batch.  The dispatch is not waited for: ``membership``
    is an AsyncVerdict, and the caller commits it before any point
    enters a fold.  Raises ValueError as cv.g1_from_bytes does, before
    anything is dispatched."""
    from lighthouse_tpu.ops.bls_backend import dispatch_subgroup_check_g1

    pts = [cv.g1_from_bytes(b, subgroup_check=False) for b in encodings]
    return pts, dispatch_subgroup_check_g1(
        [p for p in pts if p is not cv.INF])


_KZG_FUSED_JIT = None


def _kzg_fused_program():
    """The fused verification program (built on first use: importing this
    module touches no jax)."""
    import jax

    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import msm as _msm
    from lighthouse_tpu.ops.bls12_381 import (
        batch_miller_loop,
        reduce_product,
    )

    global _KZG_FUSED_JIT
    if _KZG_FUSED_JIT is None:
        def _kzg_fused(xs, ys, digits, xqa, xqb, yqa, yqb):
            Xg, Yg, Zg = _msm.fold_segments_g1(xs, ys, digits, 2)
            ok = ~bi.is_zero_mod_p_device(Zg)
            f = batch_miller_loop(Xg, Yg, xqa, xqb, yqa, yqb, zp=Zg)
            return reduce_product(f, ok)

        _KZG_FUSED_JIT = jax.jit(_kzg_fused)
        _KZG_FUSED_JIT = _dtel.instrument(
            "crypto/kzg.py::_kzg_fused_program@_kzg_fused", _KZG_FUSED_JIT)
    return _KZG_FUSED_JIT


@lru_cache(maxsize=None)
def _fused_products(lanes: int, windows: int) -> tuple[int, int]:
    """(resident, materialized) Fp lane-products of one `_kzg_fused`
    dispatch at ``lanes`` MSM lanes of ``windows`` digits, as the program
    routes them: the fold of two segments on `mont_mul_lm`; on `mont_mul`
    the zero test of the two folded Z, the Miller loop of two Jacobian
    lanes (7 products a lane before its 63 steps of 235) and the Fq12
    product of the two lanes (54).  Static per shape, so reckoned once."""
    from lighthouse_tpu.ops import msm as _msm

    return (_msm.fold_products(lanes, 2, windows),
            2 + 2 * (7 + 63 * 235) + 54)


@lru_cache(maxsize=None)
def _subgroup_products(lanes: int) -> tuple[int, int]:
    """(resident, materialized) Fp lane-products of one dispatch of the
    G1 membership program (ops/bls_backend._g1_subgroup_kernel) at
    ``lanes`` lanes, all on `mont_mul_lm`: the [r-1]P scan, 255
    double-and-add steps of 18 products a lane, the four products of the
    residues and the zero tests of d1, d2 and Z; none on `mont_mul`."""
    return lanes * (255 * 18 + 4 + 3), 0


def count_subgroup_products(resident: int, materialized: int) -> None:
    """Fp lane-products of one dispatched G1 membership program, by the
    multiply that runs them (`_subgroup_products`)."""
    _count_by_multiply(REGISTRY.counter(
        "g1_subgroup_products_total",
        "Fp lane-products of the G1 membership programs dispatched, by "
        "multiply"), resident, materialized)


def _kzg_fused_check(lhs_points, lhs_scalars, pis, r_pows,
                     settings, tau_g2=None,
                     cache_attr: str = "_fused_g2_rows") -> bool:
    """`_kzg_fused_dispatch` and, at once, `_kzg_fused_verdict`: one check,
    nothing else in flight (the blob batch)."""
    return _kzg_fused_verdict(_kzg_fused_dispatch(
        lhs_points, lhs_scalars, pis, r_pows, settings, tau_g2, cache_attr))


def _kzg_fused_verdict(f) -> bool:
    """Wait for a dispatched check and read its verdict: the fetch of its
    Fq12 and the native final exponentiation."""
    import jax

    from lighthouse_tpu.ops.bls12_381 import fq12_from_device
    from lighthouse_tpu.ops.bls_backend import _final_exp_is_one

    with stage_span("kzg.fused.wait", "fused_wait"):
        f_host = fq12_from_device(jax.device_get(f))
    with stage_span("kzg.final_exp", "final_exp"):
        return _final_exp_is_one(f_host)


def _kzg_fused_dispatch(lhs_points, lhs_scalars, pis, r_pows,
                        settings, tau_g2=None,
                        cache_attr: str = "_fused_g2_rows"):
    """BOTH RLC MSMs and the 2-lane pairing as ONE device dispatch, not
    waited for: the device Fq12 `_kzg_fused_verdict` reads.  A caller with
    several checks (crypto/das.py: the groups of a cell batch) packs the
    next while the device runs this one.

    Lanes interleave s-major (even = lhs MSM, odd = proof MSM) through
    one windowed scalar-mul scan + a 2-segment sum; the two folded
    points feed the Miller loop DIRECTLY in Jacobian form (zp path), so
    no affine conversion — and no host crossing — exists between MSM
    and pairing.  Σ-lanes that legally fold to infinity (zero quotient
    polynomials) are masked on device: e(INF, ·) = 1.

    One dispatch at every batch size a node sees: at the 768 sidecars of
    a full blob_sidecars_by_range response (2n+1 = 1,537 -> 2,048 lanes
    an MSM, 4,096 in all) the TPU compiler reports 62 MB of temporaries
    for a described v5e (2.16 GB while the fold's products were arrays
    of the program), so no lane cap is needed."""
    import jax.numpy as jnp

    from lighthouse_tpu.ops import ec
    from lighthouse_tpu.ops import msm as _msm

    program = _kzg_fused_program()
    m = _msm.bucket(len(lhs_points))

    def lane_arrays(points, scalars):
        xs, ys, ks = [], [], []
        for p, k in zip(points, scalars):
            if p is cv.INF or k % BLS_MODULUS == 0:
                xs.append(0), ys.append(0), ks.append(0)
            else:
                xs.append(p[0]), ys.append(p[1]), ks.append(
                    k % BLS_MODULUS)
            if len(xs) > m:
                raise KzgError("lane overflow")
        pad = m - len(xs)
        return (ec.ints_to_mont_limbs(xs + [0] * pad),
                ec.ints_to_mont_limbs(ys + [0] * pad),
                ec.scalars_to_digits(ks + [0] * pad, n_bits=256))

    with stage_span("kzg.pack", "pack", lanes=2 * m):
        lx, ly, ld = lane_arrays(lhs_points, lhs_scalars)
        px_, py_, pd = lane_arrays(pis, r_pows)
        xs = np.empty((2 * m, lx.shape[-1]), np.uint32)
        ys = np.empty_like(xs)
        xs[0::2], xs[1::2] = lx, px_
        ys[0::2], ys[1::2] = ly, py_
        digits = np.empty((ld.shape[0], 2 * m), np.uint32)
        digits[:, 0::2], digits[:, 1::2] = ld, pd

        g2rows = getattr(settings, cache_attr, None)
        if g2rows is None:  # constants per settings: pack once, reuse
            neg_g2 = cv.g2_neg(cv.g2_generator())
            if tau_g2 is None:
                tau_g2 = settings.g2_tau
            g2rows = [jnp.asarray(ec.ints_to_mont_limbs(v)) for v in (
                [neg_g2[0].a, tau_g2[0].a], [neg_g2[0].b, tau_g2[0].b],
                [neg_g2[1].a, tau_g2[1].a], [neg_g2[1].b, tau_g2[1].b])]
            setattr(settings, cache_attr, g2rows)

    with stage_span("kzg.fused.dispatch", "fused_dispatch"):
        f = program(jnp.asarray(xs), jnp.asarray(ys),
                    jnp.asarray(digits), *g2rows)
    count_fused_products(*_fused_products(2 * m, digits.shape[0]))
    return f


def verify_blob_kzg_proof_batch(
    blobs: list[bytes], commitment_bytes_list: list[bytes],
    proof_bytes_list: list[bytes], settings: KzgSettings
) -> bool:
    """RLC-fold n blob proofs into one 2-pairing check (the BASELINE
    config #5 path; reference crypto/kzg/src/lib.rs:105-131).

    With challenges z_i, evaluations y_i and verifier powers r^i:
      e(Σ r^i(C_i − y_i·G1 + z_i·π_i), −G2) · e(Σ r^i·π_i, τ·G2) == 1.

    Batches of >= _DEVICE_EVAL_MIN blobs ride the fused device plane,
    in this order: every blob's length checked; commitments and proofs
    decompressed and ONE membership dispatch for all of them, not
    waited for; the barycentric evaluations in slices of blobs
    (product-tree denominator inversion, ops/fr.py), the host checking
    canonicity, hashing the challenges and laying out the limbs of
    slice k+1 while the device evaluates slice k; one fetch of every
    slice's evaluations; the membership verdict read (the device runs
    in order: it was ready before the first slice started), so no point
    outside the subgroup reaches a fold; and one dispatch for both MSMs
    + the pairing (_kzg_fused_check).  A batch of one slice goes the
    same way and overlaps nothing.  Smaller batches stay on the host.
    Which of the two served is the ``path`` of the ``kzg.verify_batch``
    span and of ``kzg_blobs_verified_total``."""
    n = len(blobs)
    if not (n == len(commitment_bytes_list) == len(proof_bytes_list)):
        return False
    if n == 0:
        return True
    fused = n >= _DEVICE_EVAL_MIN
    path = "fused" if fused else "host"
    with stage_span("kzg.verify_batch", "verify_batch", blobs=n, path=path):
        verdict = (_verify_batch_fused if fused else _verify_batch_host)(
            blobs, commitment_bytes_list, proof_bytes_list, settings)
    REGISTRY.counter(
        "kzg_blobs_verified_total",
        "blobs through verify_blob_kzg_proof_batch, by the path that "
        "served the batch").labels(path=path).inc(n)
    return verdict


def _rlc(zs, ys, cs, pis, commitment_bytes_list, proof_bytes_list,
         settings):
    """The verifier-local random linear combination: (r_pows, lhs_points,
    lhs_scalars) of Σ r^i·π_i and Σ r^i·(C_i − y_i·G1 + z_i·π_i).  r is a
    domain-separated hash of the batch plus per-run entropy: it need only
    be unpredictable to the prover."""
    import secrets

    n = len(zs)
    seed = hashlib.sha256(
        RANDOM_CHALLENGE_KZG_BATCH_DOMAIN
        + settings.width.to_bytes(16, KZG_ENDIANNESS)
        + n.to_bytes(16, KZG_ENDIANNESS)
        + b"".join(commitment_bytes_list) + b"".join(proof_bytes_list)
        + secrets.token_bytes(32)).digest()
    r = int.from_bytes(seed, "big") % BLS_MODULUS
    r_pows = [pow(r, i, BLS_MODULUS) for i in range(n)]
    lhs_points = cs + pis + [cv.g1_generator()]
    lhs_scalars = list(r_pows) + [ri * z % BLS_MODULUS
                                  for ri, z in zip(r_pows, zs)]
    y_comb = sum(ri * y % BLS_MODULUS for ri, y in zip(r_pows, ys)) % BLS_MODULUS
    lhs_scalars.append((-y_comb) % BLS_MODULUS)
    return r_pows, lhs_points, lhs_scalars


def _verify_batch_fused(blobs, commitment_bytes_list, proof_bytes_list,
                        settings) -> bool:
    from lighthouse_tpu.ops import fr

    n, width = len(blobs), settings.width
    if any(len(b) != width * BYTES_PER_FIELD_ELEMENT for b in blobs):
        return False
    with stage_span("kzg.decode", "decode", points=2 * n):
        try:
            pts, membership = _decode_g1_batch(
                list(commitment_bytes_list) + list(proof_bytes_list))
        except ValueError:
            return False
        cs, pis = pts[:n], pts[n:]

    def prepare(lo, hi):
        """Limb rows and challenges of blobs [lo, hi): called by the
        evaluation between two dispatches."""
        with stage_span("kzg.canonical", "canonical"):
            raw = np.frombuffer(b"".join(blobs[lo:hi]), np.uint8).reshape(
                hi - lo, width, BYTES_PER_FIELD_ELEMENT)
            if not _blob_fields_canonical(raw):
                raise KzgError("field element not canonical")
        with stage_span("kzg.challenge", "challenge"):
            challenges = [
                compute_challenge(blob, cb, settings) for blob, cb in zip(
                    blobs[lo:hi], commitment_bytes_list[lo:hi])]
        with stage_span("kzg.limbs", "limbs"):
            return fr.be32_bytes_to_limbs(raw), challenges

    try:
        # span kzg.eval, with its slices, is the evaluation's own (ops/fr.py)
        zs, ys = fr.evaluate_polynomial_slices(n, prepare,
                                               settings.roots_brp)
    except KzgError:  # slices in flight are dropped, nothing is fetched
        return False
    with stage_span("kzg.decode.verdict", "decode"):
        if not membership.commit():
            return False
    with stage_span("kzg.rlc", "rlc"):
        r_pows, lhs_points, lhs_scalars = _rlc(
            zs, ys, cs, pis, commitment_bytes_list, proof_bytes_list,
            settings)
    try:
        return _kzg_fused_check(lhs_points, lhs_scalars, pis, r_pows,
                                settings)
    except KzgError:  # defensive lane-overflow guard: bad input -> False
        return False


def _verify_batch_host(blobs, commitment_bytes_list, proof_bytes_list,
                       settings) -> bool:
    try:
        cs = [cv.g1_from_bytes(b) for b in commitment_bytes_list]
        pis = [cv.g1_from_bytes(b) for b in proof_bytes_list]
        polys = [blob_to_polynomial(b, settings) for b in blobs]
    except (ValueError, KzgError):
        return False
    zs = [compute_challenge(blob, cb, settings)
          for blob, cb in zip(blobs, commitment_bytes_list)]
    ys = [evaluate_polynomial_in_evaluation_form(p, z, settings)
          for p, z in zip(polys, zs)]
    r_pows, lhs_points, lhs_scalars = _rlc(
        zs, ys, cs, pis, commitment_bytes_list, proof_bytes_list, settings)
    # both MSMs padded to one lane count so the device compiles a single
    # program shape
    shared_pad = 1 << max(len(lhs_points) - 1, 0).bit_length()
    proof_comb = g1_lincomb(pis, r_pows, pad_to=shared_pad)
    lhs = g1_lincomb(lhs_points, lhs_scalars, pad_to=shared_pad)
    # INF combinations are legal (e.g. constant blobs give zero quotients):
    # e(INF, ·) = 1, which multi_pairing_device models by masking the lane
    return _pairing_check([
        (lhs, cv.g2_neg(cv.g2_generator())),
        (proof_comb, settings.g2_tau),
    ])
