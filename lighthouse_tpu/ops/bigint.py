"""Batched modular arithmetic for the BLS12-381 fields on TPU (jnp, uint32).

ONE Montgomery construction, ``MontField``, instantiated twice: the
381-bit BASE field Fp here (``FP``, whose operations this module binds
under the module-level names its callers use) and the 255-bit SCALAR
field Fr in ops/fr.py.  A bound, carry or layout change lands in the
class, once, for every device program built on it.

The machine has no wide integers (SURVEY.md §7 hard part #1), so an
element is L limbs x 15 bits in uint32 lanes (trailing axis), kept in a
REDUNDANT representation: limbs may slightly exceed 2^15 and values may
exceed the modulus N, inside a capacity of C = 15·L bits.  The
redundancy is what makes the arithmetic vectorize:

- products of two sub-2^16 limbs fit uint32 exactly;
- every product (in the limb-major multiply, below: every three) is split
  into 15-bit hi/lo halves before accumulation, so a full LxL schoolbook
  column sum stays < 2^24 — no carry chains in the hot path;
- ONE data-parallel carry pass (limb_k = (col_k & mask) + (col_{k-1}>>15))
  restores the limb bound.  The capacity margin makes the top limb tiny,
  so the pass never spills — no sequential ripple exists anywhere.

Montgomery multiplication uses the separated REDC (m = T·N' mod R;
out = (T + m·N)/R with R = 2^C).  The carry out of the low half — the
one place an exact carry chain seems unavoidable — is recovered from the
divisibility invariant instead: T + mN ≡ 0 (mod R) forces the low-half
value to be exactly 0 or R, so the carry is (S_{L-1} >> 15) + (1 iff any
low residue is nonzero), a vectorized reduction.

Subtraction adds a precomputed multiple of N whose limbs all dominate the
redundancy bound (so no borrows), with a small top limb (so values stay
bounded).  Values re-enter the canonical range only at the host boundary
(to_mont / from_mont).

Value-bound ledger.  n = bits of N, C = 15·L, F = C − 11 (bit 4 of the
top limb: where ``_fold_top`` cuts).  Worst cases, enforced for both
fields by the asserts in tests/test_bigint.py:

                                             Fp (n 381, L 27)   Fr (n 255, L 18)
    capacity C, fold bit F                   405, 394           270, 259
    add / sub / neg out   < 2^F + e·2^n      < 2^395            < 2^260
        (e = top limb >> 4 before the fold: <= 4 after add, <= 10 after
        sub and neg), top limb < 2^5
    scale_small(k <= 16) out, e < 2^5        < 2^395, top < 2^5  < 2^261, top < 2^6
    mul out   < 2^(2(F+1) − C) + N           < 2^386            < 2^256
        top limb 0 (Fp), <= 1 (Fr)
    sub's constant: limbs 0..L-2 in [2^15+2^10, 2^16+2^10), top limb in
        [2^6, 2^7) — above every top limb this table allows; pre-fold
        values < 2^(F+4)
    limbs     < 2^15 + 2^11 everywhere

The limb-major multiply (``mont_mul_lm`` with ``add_lm``, ``sub_lm``,
``scale_small_lm``: Fr's evaluation programs; of Fp, the G1 folds of
ops/msm.py, the same-message merge and the G1 and G2 membership
programs; the Miller loops and the unblinding ladder remain on
``mont_mul``).  The same construction for arrays
uint32[L, ...lanes] whose FIRST axis is the limb.  ``mont_mul`` makes
every schoolbook product an array of the program ([.., L, 2L], to HBM and
back: ~27 KB a Fr product-lane that needs 216 bytes); here the partial
products, the column sums and both REDC products of a block of lanes
live in vector registers and VMEM, so a product-lane costs HBM its two
operands and its result.  One text, ``_mont_mul_lm``; two launchers,
chosen by ``jax.default_backend()`` as ``_use_mxu_redc`` chooses: on a
TPU a Pallas kernel over blocks uint32[L, 8k, 128] (a limb of 1,024
lanes is one vector register; N and N' are scalars of the kernel),
elsewhere the text on the whole arrays (under plain XLA on a TPU it makes
arrays of its rows again: 7.8 GB a pass, PR 32).  Its lines of the ledger:

    mont_mul_lm in    limbs < 2^15 + 2^11 (THREE raw limb products are
        summed in uint32 before one 15-bit split: 3·(2^15+2^11-1)^2 <
        2^32; `mont_mul` splits each and takes limbs < 2^16), values
        < 2^(F+1) as every line above leaves them; a fixed multiplicand
        is one of the host limb tables, its limbs canonical
    mont_mul_lm out   the `mul out` line: < 2^(2(F+1) − C) + N, top limb
        0 (Fp), <= 1 (Fr), limbs < 2^15 + 2^11; congruent to `mont_mul`'s
        mod N, not limb for limb (the columns split differently, so m may
        differ by R and the product by N)
    columns           < 2^21: ceil(L/3) low halves < 2^15 and as many
        high halves < 2^17
    add_lm / sub_lm / scale_small_lm(k <= 16)   the `add / sub` and the
        `scale_small` lines, limb for limb: legal `mont_mul_lm in` (Fp)

Reference counterpart: the limb arithmetic inside blst
(/root/reference/crypto/bls/src/impls/blst.rs's FFI layer).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

P_INT = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB

B = 15                 # bits per limb
MASK = (1 << B) - 1


# --- limb-level pieces no modulus enters ------------------------------------

def _set_top(x: jax.Array, top: jax.Array) -> jax.Array:
    """Replace the last limb (concat of static slices; `.at[..., -1]`
    lowers to scatter — thousands of them blew up the trace)."""
    return jnp.concatenate([x[..., :-1], top], axis=-1)


def _carry(cols: jax.Array) -> jax.Array:
    """One vectorized carry pass; by the value-bound ledger the top limb's
    own carry is provably zero, so nothing spills."""
    hi = cols >> B
    lo = cols & MASK
    shifted = jnp.concatenate(
        [jnp.zeros_like(hi[..., :1]), hi[..., :-1]], axis=-1)
    out = lo + shifted
    # keep the top limb's high bits (tiny by the value bound) instead of
    # dropping them: top limb = col & mask + carry_in + (col >> B << B)
    return _set_top(out, out[..., -1:] + ((cols[..., -1:] >> B) << B))


def _shift_pad(x: jax.Array, off: int, width: int) -> jax.Array:
    pads = [(0, 0, 0)] * (x.ndim - 1) + [(off, width - off - x.shape[-1], 0)]
    return jax.lax.pad(x, jnp.uint32(0), pads)


def _mul_cols(a: jax.Array, b: jax.Array, out_cols: int) -> jax.Array:
    """Schoolbook column accumulation with 15-bit hi/lo split.

    a, b: uint32[..., L] with limbs < 2^16 → columns < 2^25.
    out_cols = 2L for the full product, L for the mod-R low product.

    Implemented as a stack of shifted-b rows reduced over the limb axis:
    row i holds b placed at columns [i, i+L), so a[..., i, None] * rows
    puts a_i·b_j at column i+j and ONE reduction accumulates all columns.
    (No scatters — scatter-add chains sent XLA's algebraic simplifier into
    a rewrite loop; and no per-term add chains — a 216-op chain per product
    made the Miller scan trace to ~300k StableHLO lines, VERDICT round-2.)
    """
    L = b.shape[-1]
    rows = min(L, out_cols)
    b_stack = jnp.stack(
        [_shift_pad(b[..., : min(L, out_cols - i)], i, out_cols)
         for i in range(rows)], axis=-2)          # [..., rows, out]
    p = a[..., :rows, None] * b_stack             # a_i·b_j at col i+j
    lo = p & MASK
    hi = p >> B                                   # belongs one column up
    hi = jnp.concatenate(
        [jnp.zeros_like(hi[..., :1]), hi[..., :-1]], axis=-1)
    return (lo + hi).sum(axis=-2, dtype=jnp.uint32)


# --- MXU constant-multiplicand products -------------------------------------
#
# Two of mont_mul's three big products have a FIXED multiplicand (N' and
# N, the separated REDC).  A fixed c turns the schoolbook column sum
# into a matmul:  col_k = Σ_i a_i·c_{k-i}  =  (a @ M_c)_k  with
# M_c[i, k] = c_{k-i} — which the TPU runs on the MXU instead of
# materializing the [.., L, 2L] schoolbook intermediate on the VPU
# (~20 KB of HBM traffic per Fp product-lane; the fused BLS pipeline is
# memory-bound on exactly this).  Exactness comes from int8 chunking:
# a limbs (< 2^16) split 6|6|4 bits, c limbs (< 2^15) split 5|5|5, so
# every dot product is ≤ 27·63·31 < 2^16 in an int32 accumulator.  The
# nine (i, j) chunk blocks recombine on the VPU with weight
# 2^(6i+5j) = 2^(15q + s): shift s bits and q columns — column sums stay
# < 9·2^28 < 2^32.  Env LHTPU_MXU_REDC=0/1 forces the path; default is
# on for TPU, off for CPU (XLA-CPU's int8 matmul is slower than its
# fused schoolbook).

_A_SHIFTS = (0, 6, 12)          # lhs chunk bit offsets (6|6|5 split:
_A_MASKS = (63, 63, 31)         # the top chunk covers limbs < 2^17 —
#                                 m's limbs after carrying ~2^31 columns
#                                 land just above 2^16)
_C_SHIFTS = (0, 5, 10)          # rhs chunk bit offsets (5|5|5 split)

_MXU_REDC: bool | None = None


def _use_mxu_redc() -> bool:
    global _MXU_REDC
    if _MXU_REDC is None:
        import os

        env = os.environ.get("LHTPU_MXU_REDC", "auto").lower()
        if env in ("0", "false"):
            _MXU_REDC = False
        elif env in ("1", "true"):
            _MXU_REDC = True
        else:
            try:
                _MXU_REDC = jax.default_backend() == "tpu"
            except Exception as e:
                from lighthouse_tpu.common.metrics import record_swallowed

                record_swallowed("bigint.mxu_probe", e)
                _MXU_REDC = False
    return _MXU_REDC


# --- limb-major pieces: the limb axis leads -----------------------------------
#
# The same carry and schoolbook as above for arrays uint32[n, ...lanes]
# whose FIRST axis is the limb: inside the resident kernel a block
# uint32[L, 8, 128] (a limb is one vector register), outside it the whole
# array.  Nothing here indexes along a lane, so one text serves both; a
# shift along the limb axis is a concatenation of whole limbs.

def _cat(*parts: jax.Array) -> jax.Array:
    """Concatenation along the limb axis of the parts that have a limb (a
    kernel body may hold no empty vector)."""
    return jnp.concatenate([p for p in parts if p.shape[0]])


def _zeros(n: int, like: jax.Array) -> jax.Array:
    return jnp.zeros((n,) + like.shape[1:], like.dtype)


def _splat_lm(limbs: list, like: jax.Array) -> jax.Array:
    """Constant limbs as limb-major splats over like's lanes, built from
    scalars (a kernel body may capture no array)."""
    return jnp.stack([jnp.full(like.shape[1:], c, jnp.uint32)
                      for c in limbs])


def _carry_lm(c: jax.Array) -> jax.Array:
    """`_carry` along the leading axis (the top limb keeps its high
    bits)."""
    hi = c[:-1] >> B
    return _cat(c[:1] & MASK, (c[1:-1] & MASK) + hi[:-1], c[-1:] + hi[-1:])


def _add_at(cols: jax.Array, x: jax.Array, off: int) -> jax.Array:
    """cols with x added to limbs [off, off + len(x)), cut at cols' end."""
    n = min(x.shape[0], cols.shape[0] - off)
    if n <= 0:
        return cols
    return _cat(cols[:off], cols[off:off + n] + x[:n], cols[off + n:])


def _mul_cols_lm(a: jax.Array, b: jax.Array, out_cols: int) -> jax.Array:
    """Schoolbook columns below `out_cols` of a·b, both uint32[L, ...].
    Limbs are < 2^15 + 2^11, so THREE raw products fit uint32: rows of
    the schoolbook go three at a time, each shifted one limb, summed and
    only then split into 15-bit halves.  A column takes ceil(L/3) low
    halves < 2^15 and as many high halves < 2^17, and stays < 2^21."""
    L = a.shape[0]
    cols = _zeros(out_cols, a)
    for i0 in range(0, L, 3):
        n = min(3, L - i0)
        s = None
        for r in range(n):
            p = _cat(_zeros(r, a), a[i0 + r][None] * b, _zeros(n - 1 - r, a))
            s = p if s is None else s + p
        cols = _add_at(cols, s & MASK, i0)
        cols = _add_at(cols, s >> B, i0 + 1)
    return cols


_RESIDENT: bool | None = None


def _use_resident_kernel() -> bool:
    """Whether `mont_mul_lm` launches its Pallas kernel: on a TPU, as
    `_use_mxu_redc` decides by default (and by nothing else)."""
    global _RESIDENT
    if _RESIDENT is None:
        _RESIDENT = jax.default_backend() == "tpu"
    return _RESIDENT


_BLOCK_LANES = 8 * 128        # the lanes of one [L, 8, 128] step of the kernel


def _to_blocks(x: jax.Array) -> jax.Array:
    """uint32[L, ...lanes] -> uint32[L, S, C], S a multiple of 8 and C of
    128: as it is when it has such a shape, else flattened to C = 128 and
    padded with zero lanes."""
    if x.ndim == 3 and x.shape[1] % 8 == 0 and x.shape[2] % 128 == 0:
        return x
    flat = x.reshape(x.shape[0], -1)
    fill = -flat.shape[1] % _BLOCK_LANES
    return jnp.pad(flat, ((0, 0), (0, fill))).reshape(x.shape[0], -1, 128)


def _from_blocks(x: jax.Array, shape: tuple) -> jax.Array:
    if x.shape == shape:
        return x
    lanes = int(np.prod(shape[1:]))
    return x.reshape(shape[0], -1)[:, :lanes].reshape(shape)


class MontField:
    """The construction of the module header for one modulus and limb
    count: its constant tables, the device operations on redundant
    Montgomery limb rows uint32[..., L], and the host boundary."""

    def __init__(self, modulus: int, limbs: int):
        self.P_INT = modulus
        self.L = limbs
        self.R_INT = 1 << (B * limbs)          # Montgomery R
        self.R_INV = pow(self.R_INT, -1, modulus)
        # -N^{-1} mod R, for the separated Montgomery reduction
        self.NPRIME_INT = (-pow(modulus, -1, self.R_INT)) % self.R_INT
        lim = self.int_to_limbs
        #: the host limb tables, by the names `jconst` serves them under
        self.tables: dict[str, np.ndarray] = {
            "p": lim(modulus),
            "nprime": lim(self.NPRIME_INT),
            # 2^F mod N: folds excess top-limb bits (>= bit 4 of the top
            # limb) back into range, pinning every value below ~2^(F+1)
            # with a single vectorized pass
            "foldq": lim((1 << (B * (limbs - 1) + 4)) % modulus),
            "neg": self._neg_const(),
            "one_m": lim(self.R_INT % modulus),          # Montgomery 1
            # R^2 mod N: host -> Montgomery form by one device multiply
            "r2": lim((self.R_INT * self.R_INT) % modulus),
            "one_plain": lim(1)}
        self._jconsts: dict[str, jax.Array] = {}
        self._const_rhs: dict[tuple[str, int], jax.Array] = {}

    # --- host boundary ------------------------------------------------------

    def int_to_limbs(self, v: int) -> np.ndarray:
        n = self.L
        out = np.zeros(n, np.uint32)
        for i in range(n):
            out[i] = (v >> (B * i)) & MASK
        assert v >> (B * n) == 0, "value does not fit"
        return out

    @staticmethod
    def limbs_to_int(limbs) -> int:
        """The value of ONE limb row (redundant limbs allowed)."""
        return sum(x << (B * i)
                   for i, x in enumerate(np.asarray(limbs).tolist()))

    def to_mont(self, v) -> np.ndarray:
        """int (or array / sequence of ints) -> Montgomery limb vector(s)."""
        if isinstance(v, (int, np.integer)):
            return self.int_to_limbs((int(v) * self.R_INT) % self.P_INT)
        flat = [(int(x) * self.R_INT) % self.P_INT
                for x in np.ravel(np.asarray(v, object))]
        out = np.stack([self.int_to_limbs(x) for x in flat])
        return out.reshape(np.shape(v) + (self.L,))

    def from_mont(self, limbs) -> int | np.ndarray:
        """Montgomery limb vector(s) -> canonical int(s)."""
        arr = np.asarray(limbs)
        rinv = self.R_INV
        if arr.ndim == 1:
            return (self.limbs_to_int(arr) * rinv) % self.P_INT
        flat = arr.reshape(-1, arr.shape[-1])
        vals = np.array(
            [(self.limbs_to_int(x) * rinv) % self.P_INT for x in flat],
            dtype=object)
        return vals.reshape(arr.shape[:-1])

    # --- constants ----------------------------------------------------------

    def _neg_const(self) -> np.ndarray:
        """A multiple of N decomposed so limbs 0..L-2 sit in
        [2^15+2^10, 2^16+2^10) — dominating any redundant operand limb, and
        a full 2^15 wide so the representable set is contiguous — while the
        top limb sits in [2^6, 2^7): above any top limb the ledger allows
        but small enough that values stay < 2^(F+4) pre-fold."""
        L, P = self.L, self.P_INT
        lo_limb = (1 << B) + (1 << 10)
        hi_limb = lo_limb + (1 << B)  # width exactly 2^15 → contiguous
        top_lo, top_hi = 1 << 6, 1 << 7
        lo = top_lo << (B * (L - 1))
        hi = (top_hi - 1) << (B * (L - 1))
        for i in range(L - 1):
            lo += lo_limb << (B * i)
            hi += (hi_limb - 1) << (B * i)
        k = lo // P + 1
        v = k * P
        assert lo <= v <= hi, "no representable multiple of N in range"
        out = np.zeros(L, np.uint32)
        rem = v
        for i in range(L - 1, -1, -1):
            unit = 1 << (B * i)
            lo_i, hi_i = (top_lo, top_hi - 1) if i == L - 1 else (
                lo_limb, hi_limb - 1)
            low_rest = sum(lo_limb << (B * j) for j in range(i))
            hi_rest = sum((hi_limb - 1) << (B * j) for j in range(i))
            # keep the remainder representable by the lower limbs' ranges
            d_max = min(hi_i, (rem - low_rest) // unit)
            d_min = max(lo_i, -((hi_rest - rem) // unit) if rem > hi_rest
                        else lo_i)
            d = max(d_min, min(d_max, (rem - low_rest) // unit))
            assert (lo_i <= d <= hi_i
                    and low_rest <= rem - d * unit <= hi_rest) or i == 0, (
                i, hex(d))
            out[i] = d
            rem -= d * unit
        assert rem == 0 and self.limbs_to_int(out) == v
        return out

    def jconst(self, name: str) -> jax.Array:
        """The device constant `name`: ONE object per field and name, so one
        jaxpr constvar.  jnp.asarray(np_const) at every use site emits a
        fresh `constant` op per trace reference (tens of thousands of lines
        in the Miller scan); caching the jnp array gives jaxpr constvar
        dedup by object identity."""
        c = self._jconsts.get(name)
        if c is None:
            # ensure_compile_time_eval: materialize a concrete array even
            # when the first call happens inside a jit trace (else a tracer
            # leaks into the cache and escapes its trace)
            with jax.ensure_compile_time_eval():
                c = self._jconsts[name] = jnp.asarray(
                    self.tables[name], jnp.uint32)
        return c

    # --- device primitives --------------------------------------------------

    def _fold_top(self, x: jax.Array) -> jax.Array:
        """Fold top-limb bits >= 4 down via 2^F ≡ `foldq` (mod N): one pass,
        no iteration — output value < 2^(F+1), top limb < 2^5."""
        e = x[..., -1:] >> 4
        x = _set_top(x, x[..., -1:] & 0xF)
        return _carry(x + e * self.jconst("foldq"))

    def add(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return self._fold_top(_carry(a + b))

    def sub(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """a - b + kN (the `neg` table's limbs dominate any redundant b
        limb)."""
        return self._fold_top(_carry(a + (self.jconst("neg") - b)))

    def neg(self, a: jax.Array) -> jax.Array:
        return self._fold_top(_carry(self.jconst("neg") - a))

    def scale_small(self, a: jax.Array, k: int) -> jax.Array:
        """a·k for small positive k (k <= 16 keeps values in fold range)."""
        assert 0 < k <= 16
        return self._fold_top(_carry(a * np.uint32(k)))

    def _const_mul_rhs(self, name: str, out_cols: int) -> jax.Array:
        key = (name, out_cols)
        m_dev = self._const_rhs.get(key)
        if m_dev is None:
            L, c = self.L, self.tables[name]
            m = np.zeros((L, 3 * out_cols), np.int8)
            for j, sh in enumerate(_C_SHIFTS):
                for i in range(L):
                    for k in range(i, min(i + L, out_cols)):
                        m[i, j * out_cols + k] = (int(c[k - i]) >> sh) & 31
            with jax.ensure_compile_time_eval():
                m_dev = self._const_rhs[key] = jnp.asarray(m)
        return m_dev

    def _const_mul_cols(self, a: jax.Array, name: str,
                        out_cols: int) -> jax.Array:
        """Fixed-multiplicand column product as int8 MXU matmuls (the
        construction above `_A_SHIFTS`).  a: uint32[..., L] with limbs
        < 2^17 -> uint32[..., out_cols] columns < 9·2^28 (callers must
        _carry before further multiplies; out_cols == L drops the k >= L
        columns — the mod-radix truncation the separated REDC needs).
        Exact because every int8 chunk product is ≤ 63·31 and a dot
        accumulates ≤ L of them in int32."""
        lhs = jnp.stack(
            [((a >> sh) & msk).astype(jnp.int8)
             for sh, msk in zip(_A_SHIFTS, _A_MASKS)],
            axis=-2)                            # [..., 3, L]
        out = jax.lax.dot_general(
            lhs, self._const_mul_rhs(name, out_cols),
            dimension_numbers=(((lhs.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)   # [..., 3, 3·out]
        out = out.astype(jnp.uint32).reshape(
            out.shape[:-2] + (3, 3, out_cols))  # [..., i, j, out]
        cols = jnp.zeros(out.shape[:-3] + (out_cols,), jnp.uint32)
        for i in range(3):
            for j in range(3):
                q, s = divmod(_A_SHIFTS[i] + _C_SHIFTS[j], B)
                blk = out[..., i, j, :] << s
                if q:           # one-column shift (2^B per column)
                    blk = jnp.concatenate(
                        [jnp.zeros_like(blk[..., :q]), blk[..., :-q]],
                        axis=-1)
                cols = cols + blk
        return cols

    def _redc(self, t: jax.Array, mxu: bool) -> jax.Array:
        """Separated Montgomery reduction of carried columns t (2L limbs,
        < 2^16): out = (t + (t·N' mod R)·N) / R."""
        L = self.L
        if mxu:
            m_cols = self._const_mul_cols(t[..., :L], "nprime", L)
        else:
            m_cols = _mul_cols(t[..., :L], self.jconst("nprime"), L)
        m = _carry(m_cols)                         # limbs < 2^16 (redundant)
        # mod R: mask ONLY the top limb (drops multiples of R, legal;
        # masking other limbs would change m mod R and break divisibility)
        m = _set_top(m, m[..., -1:] & MASK)
        if mxu:
            mn_cols = self._const_mul_cols(m, "p", 2 * L)
            # MXU columns reach ~2^31; one value-preserving carry pass brings
            # them under 2^17 so the 0-or-R low-half residual argument below
            # holds (residual < R + 2^(C-13) < 2R)
            s = _carry(mn_cols + t)
        else:
            s = _mul_cols(m, self.jconst("p"), 2 * L) + t  # < 2^25 ✓ uint32
        # low half of s has value ≡ 0 (mod R): carry into the high half is
        # (s_{L-1} >> B) + (1 iff any low residue bits remain)
        low_resid = jnp.concatenate(
            [s[..., :L - 1], (s[..., L - 1:L] & MASK)], axis=-1)
        delta = jnp.any(low_resid != 0, axis=-1,
                        keepdims=True).astype(jnp.uint32)
        c = (s[..., L - 1:L] >> B) + delta
        out_cols = s[..., L:]                      # L columns
        out_cols = jnp.concatenate(
            [out_cols[..., :1] + c, out_cols[..., 1:]], axis=-1)
        return _carry(out_cols)

    def mont_mul(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """Montgomery product a·b·R⁻¹ (mod N, redundant representation)."""
        t_cols = _mul_cols(a, b, 2 * self.L)       # 2L columns < 2^24
        t = _carry(t_cols)                         # 2L limbs < 2^16
        return self._redc(t, _use_mxu_redc())

    # --- the limb-major operations (header: "The limb-major multiply") ------

    def _const_lm(self, name: str, like: jax.Array) -> jax.Array:
        return _splat_lm(self.tables[name].tolist(), like)

    def _mont_mul_lm(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """The separated REDC of `mont_mul` with the limb axis leading
        (a, b uint32[L, ...lanes]); inputs and output by the header's
        ledger.  The body of the resident kernel, and the whole multiply
        where there is none."""
        L = self.L
        t = _carry_lm(_mul_cols_lm(a, b, 2 * L))
        m = _carry_lm(_mul_cols_lm(t[:L], self._const_lm("nprime", a), L))
        m = _cat(m[:-1], m[-1:] & MASK)            # mod R, as `_redc`
        s = _mul_cols_lm(m, self._const_lm("p", a), 2 * L) + t
        # the low half's value is 0 or R (the argument of `_redc`)
        resid = s[L - 1] & MASK
        for k in range(L - 1):
            resid = resid | s[k]
        c = (s[L - 1] >> B) + (resid != 0).astype(jnp.uint32)
        return _carry_lm(_cat((s[L] + c)[None], s[L + 1:]))

    def _fold_top_lm(self, x: jax.Array) -> jax.Array:
        e = x[-1:] >> 4
        x = _cat(x[:-1], x[-1:] & 0xF)
        return _carry_lm(x + e * self._const_lm("foldq", x))

    def _add_lm(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return self._fold_top_lm(_carry_lm(a + b))

    def _sub_lm(self, a: jax.Array, b: jax.Array) -> jax.Array:
        return self._fold_top_lm(
            _carry_lm(a + (self._const_lm("neg", a) - b)))

    def add_lm(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """`add` on limb-major arrays uint32[L, ...lanes]."""
        return self._launch(self._add_lm, 16 * self.L, a, b)

    def sub_lm(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """`sub` on limb-major arrays uint32[L, ...lanes]."""
        return self._launch(self._sub_lm, 16 * self.L, a, b)

    def mont_mul_lm(self, a: jax.Array, b) -> jax.Array:
        """Montgomery product of limb-major arrays uint32[L, ...lanes]; b
        is an array of a's shape, or one of the host limb tables (a fixed
        multiplicand, its limbs scalars of the program: `tables["r2"]` is
        the way into Montgomery form)."""
        ops_a_lane = 8 * self.L * self.L
        if not isinstance(b, np.ndarray):
            return self._launch(self._mont_mul_lm, ops_a_lane, a, b)
        limbs = b.tolist()
        return self._launch(
            lambda x: self._mont_mul_lm(x, _splat_lm(limbs, x)),
            ops_a_lane, a)

    def _launch(self, fn, ops_a_lane: int, *ops: jax.Array) -> jax.Array:
        """fn over limb-major arrays of one shape.  On a TPU their lanes
        run through the resident kernel in blocks of [L, 8k, 128], padded
        up to one where they are fewer or off a block's edge; elsewhere
        fn runs on the whole arrays."""
        assert all(x.shape == ops[0].shape for x in ops) and (
            ops[0].shape[0] == self.L), [x.shape for x in ops]
        if not _use_resident_kernel():
            return _jitted(fn)(*ops)
        out = self._resident_call(
            fn, ops_a_lane, [_to_blocks(x) for x in ops])
        return _from_blocks(out, ops[0].shape)

    def _resident_call(self, fn, ops_a_lane: int, ops: list,
                       interpret: bool = False) -> jax.Array:
        """pallas_call of fn over uint32[L, S, C] operands (S a multiple
        of 8, C of 128): a grid of [L, rows, 128] blocks, each walked
        eight sublanes at a time, so that a limb of the product in hand
        is one vector register and no partial product leaves the core."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        L, S, C = ops[0].shape
        rows = next(r for r in (64, 32, 16, 8) if S % r == 0)

        def body(*refs):
            def chunk(s, carry):
                at = pl.ds(pl.multiple_of(s * 8, 8), 8)
                refs[-1][:, at, :] = fn(*[r[:, at, :] for r in refs[:-1]])
                return carry

            jax.lax.fori_loop(0, rows // 8, chunk, 0)

        spec = pl.BlockSpec((L, rows, 128), lambda i, j: (0, i, j))
        return pl.pallas_call(
            body, grid=(S // rows, C // 128), in_specs=[spec] * len(ops),
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(ops[0].shape, jnp.uint32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            # what a lane costs HBM: its operands and its result
            cost_estimate=pl.CostEstimate(
                flops=ops_a_lane * S * C, transcendentals=0,
                bytes_accessed=4 * L * S * C * (len(ops) + 1)),
            interpret=interpret)(*ops)

    def _scale_small_lm(self, a: jax.Array, k: int) -> jax.Array:
        return self._fold_top_lm(_carry_lm(a * k))

    def scale_small_lm(self, a: jax.Array, k: int) -> jax.Array:
        """`scale_small` on limb-major arrays uint32[L, ...lanes]."""
        return self._launch(self._scale_fn(k), 16 * self.L, a)

    @functools.cache
    def _scale_fn(self, k: int):
        """One callable a factor, so that what `_launch` keeps by its
        function (`_jitted`) is found again."""
        assert 0 < k <= 16
        return lambda x: self._scale_small_lm(x, k)


# Off the TPU `_launch` runs a limb-major text through ONE jitted function
# an operation: a program that holds hundreds of them (the G1 fold: ~60
# multiplies, ~250 additions) then traces each once a shape and calls it,
# where tracing every use anew took XLA:CPU's tests four times as long.
_jitted = functools.lru_cache(maxsize=64)(jax.jit)


# --- the base field, under the names its callers use -------------------------

FP = MontField(P_INT, 27)      # 405 bits of capacity for 381-bit values

L = FP.L
R_INT = FP.R_INT
ONE_M = FP.tables["one_m"]

_int_to_limbs = FP.int_to_limbs
_limbs_to_int = FP.limbs_to_int
_jconst = FP.jconst
add = FP.add
sub = FP.sub
neg = FP.neg
scale_small = FP.scale_small
mont_mul = FP.mont_mul
to_mont = FP.to_mont
from_mont = FP.from_mont


# --- device-side canonical tests --------------------------------------------
#
# Redundant limbs can't be compared directly (one value, many encodings),
# which is why verdicts historically came home as residue limbs for host
# zero-tests — at one device->host fetch per leaf
# (BLS_LEDGER_TPU_r04.json's subgroup stage).  A value-preserving
# sequential carry pass makes the encoding unique, so the verdict itself
# can be computed on device and fetched as one bool row.


def canon_digits(x: jax.Array) -> jax.Array:
    """Value-preserving full carry propagation -> unique base-2^15 digits.

    Input: limbs < 2^16 with value < 2^405 (any _carry output qualifies);
    output limbs < 2^15, same value, one encoding per value — safe for
    equality against precomputed digit vectors."""
    xt = jnp.moveaxis(x, -1, 0)

    def step(c, limb):
        s = limb + c
        return s >> B, s & MASK

    _, digits = jax.lax.scan(step, jnp.zeros_like(xt[0]), xt)
    return jnp.moveaxis(digits, 0, -1)


@functools.cache
def _kp_digit_consts() -> jax.Array:
    """Digit vectors of {0, P, 2P, 3P, 4P}: every multiple of P a
    mont_mul by plain 1 can return (x·R⁻¹ + P < 2P for x < R·P; the
    rest is margin, which tests/test_bigint.py holds to < 5P)."""
    with jax.ensure_compile_time_eval():
        return jnp.asarray(
            np.stack([_int_to_limbs(k * P_INT) for k in range(5)]),
            jnp.uint32)


def is_zero_mod_p_device(x: jax.Array) -> jax.Array:
    """Per-lane x ≡ 0 (mod P) for redundant limb rows, ON DEVICE.

    Lowers x through one Montgomery mul by plain 1 (out ≡ x·R⁻¹ mod P,
    value < 5P), canonicalizes, and compares against every multiple of
    P below that.  x ≡ 0 ⟺ x·R⁻¹ ≡ 0 (R invertible).  Returns
    bool[...] (limb axis reduced)."""
    w = mont_mul(x, jnp.broadcast_to(_jconst("one_plain"), x.shape))
    d = canon_digits(w)
    return (d[..., None, :] == _kp_digit_consts()).all(-1).any(-1)


def is_zero_mod_p_lm(x: jax.Array) -> jax.Array:
    """`is_zero_mod_p_device` for limb-major arrays uint32[L, ...lanes]:
    the multiply by plain 1 on `mont_mul_lm` (its m < R·(1 + 2^-9), so
    the product of an x inside the ledger is < 2P, inside the multiples of
    P compared), the carry pass and the comparison on the digits turned
    back limbs last.  Returns bool[...lanes]."""
    w = FP.mont_mul_lm(x, FP.tables["one_plain"])
    d = canon_digits(jnp.moveaxis(w, 0, -1))
    return (d[..., None, :] == _kp_digit_consts()).all(-1).any(-1)


# --- the lane order of the constant-geometry transforms ----------------------
#
# ops/fr.py's NTT and ops/ec.py's G1 FFT lay a batch of ``rows`` transforms
# out limb-major with lane k·rows + r holding entry k of row r: the two
# halves of a stage's pairs are the two halves of the lanes, and a stage's
# outputs go back interleaved (Pease's constant geometry).


def interleave_lm(a: jax.Array, b: jax.Array, rows: int) -> jax.Array:
    """Lanes k·rows + r of a and b (uint32[L, lanes]) -> lanes
    (2k)·rows + r and (2k + 1)·rows + r of one array."""
    n_limbs, n = a.shape
    return jnp.stack([a.reshape(n_limbs, n // rows, rows),
                      b.reshape(n_limbs, n // rows, rows)],
                     axis=2).reshape(n_limbs, 2 * n)


def deinterleave_lm(x: jax.Array, rows: int) -> tuple:
    """`interleave_lm` undone: the even and the odd entries of every row."""
    n_limbs, n = x.shape
    y = x.reshape(n_limbs, n // (2 * rows), 2, rows)
    return (y[:, :, 0].reshape(n_limbs, n // 2),
            y[:, :, 1].reshape(n_limbs, n // 2))
