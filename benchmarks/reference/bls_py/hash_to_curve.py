"""Hash-to-curve for G2 per RFC 9380 (BLS12381G2_XMD:SHA-256_SSWU_RO_).

From-scratch: expand_message_xmd + hash_to_field + simplified SWU on the
3-isogenous curve E' + a 3-isogeny to E2 + cofactor clearing.

The 3-isogeny is DERIVED here via Vélu's formulas rather than transcribed
from the RFC's constant tables (none are available offline): `derive_iso()`
computes every candidate normalized 3-isogeny E' -> E2 (kernel choice x
sextic-twist scaling), and the unique candidate matching real-world
signatures (the deposit-CLI fixtures under
/root/reference/validator_manager/test_vectors) is pinned by
`_ISO_SELECTOR` below.  Cofactor clearing uses the effective-cofactor
scalar, cross-checked against the ψ-endomorphism (Budroni-Pintore) method.
"""

from __future__ import annotations

import hashlib

from benchmarks.reference.bls_py.fields import Fq2, P
from benchmarks.reference.bls_py import curve as cv

DST_G2 = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"

# SSWU target curve E': y² = x³ + A'x + B' (3-isogenous to E2)
A_PRIME = Fq2(0, 240)
B_PRIME = Fq2(1012, 1012)
Z_SSWU = Fq2(-2 % P, -1 % P)  # Z = -(2 + u)

# Effective cofactor for G2 cofactor clearing (RFC 9380 §8.8.2); validated
# at import against the ψ-endomorphism method in tests.
H_EFF = 0xBC69F08F2EE75B3584C6A0EA91B352888E2A8E9145AD7689986FF031508FFE1329C2F178731DB956D82BF015D1212B02EC0EC69D7477C1AE954CBC06689F6A359894C0ADEBBF6B4E8020005AAA95551


# ---------------------------------------------------------------------------
# expand_message_xmd + hash_to_field
# ---------------------------------------------------------------------------

def expand_message_xmd(msg: bytes, dst: bytes, len_in_bytes: int) -> bytes:
    if len(dst) > 255:
        dst = hashlib.sha256(b"H2C-OVERSIZE-DST-" + dst).digest()
    ell = (len_in_bytes + 31) // 32
    if ell > 255:
        raise ValueError("len_in_bytes too large")
    dst_prime = dst + len(dst).to_bytes(1, "big")
    z_pad = b"\x00" * 64
    l_i_b = len_in_bytes.to_bytes(2, "big")
    b0 = hashlib.sha256(z_pad + msg + l_i_b + b"\x00" + dst_prime).digest()
    bvals = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, ell + 1):
        xored = bytes(a ^ b for a, b in zip(b0, bvals[-1]))
        bvals.append(hashlib.sha256(xored + i.to_bytes(1, "big") + dst_prime).digest())
    return b"".join(bvals)[:len_in_bytes]


def hash_to_field_fq2(msg: bytes, count: int, dst: bytes = DST_G2) -> list[Fq2]:
    L = 64
    uniform = expand_message_xmd(msg, dst, count * 2 * L)
    out = []
    for i in range(count):
        comps = []
        for j in range(2):
            off = L * (j + i * 2)
            comps.append(int.from_bytes(uniform[off:off + L], "big") % P)
        out.append(Fq2(comps[0], comps[1]))
    return out


# ---------------------------------------------------------------------------
# Simplified SWU on E'
# ---------------------------------------------------------------------------

def sswu(u: Fq2) -> tuple[Fq2, Fq2]:
    """Map a field element to a point on E' (y² = x³ + A'x + B')."""
    A, B, Z = A_PRIME, B_PRIME, Z_SSWU
    u2 = u.square()
    zu2 = Z * u2
    tv1 = zu2.square() + zu2  # Z²u⁴ + Zu²
    if tv1.is_zero():
        x1 = B * (Z * A).inv()
    else:
        x1 = (-B) * A.inv() * (Fq2.ONE + tv1.inv())
    gx1 = (x1.square() + A) * x1 + B
    y1 = gx1.sqrt()
    if y1 is not None:
        x, y = x1, y1
    else:
        x2 = zu2 * x1
        gx2 = (x2.square() + A) * x2 + B
        y2 = gx2.sqrt()
        if y2 is None:  # impossible for valid SSWU parameters
            raise ArithmeticError("SSWU: neither gx1 nor gx2 is square")
        x, y = x2, y2
    if u.sgn0() != y.sgn0():
        y = -y
    return (x, y)


# ---------------------------------------------------------------------------
# 3-isogeny E' -> E2, derived via Vélu's formulas
# ---------------------------------------------------------------------------

def _poly_mulmod(a, b, mod):
    """Dense poly mult mod `mod` (lists of Fq2, low-to-high)."""
    res = [Fq2.ZERO] * (len(a) + len(b) - 1)
    for i, ai in enumerate(a):
        if ai.is_zero():
            continue
        for j, bj in enumerate(b):
            res[i + j] = res[i + j] + ai * bj
    return _poly_mod(res, mod)


def _poly_mod(a, mod):
    a = list(a)
    dm = len(mod) - 1
    inv_lead = mod[-1].inv()
    while len(a) > dm:
        c = a[-1] * inv_lead
        if not c.is_zero():
            for i in range(dm + 1):
                a[len(a) - 1 - dm + i] = a[len(a) - 1 - dm + i] - c * mod[i]
        a.pop()
    while len(a) > 1 and a[-1].is_zero():
        a.pop()
    return a or [Fq2.ZERO]


def _trim(a):
    a = list(a)
    while len(a) > 1 and a[-1].is_zero():
        a.pop()
    return a


def _is_zero_poly(a) -> bool:
    return len(a) == 1 and a[0].is_zero()


def _poly_gcd(a, b):
    a, b = _trim(a), _trim(b)
    while not _is_zero_poly(b):
        a, b = b, _poly_mod(a, b)
    lead = a[-1].inv()
    return [c * lead for c in a]


def _poly_powmod(base, e, mod):
    result = [Fq2.ONE]
    base = _poly_mod(base, mod)
    while e:
        if e & 1:
            result = _poly_mulmod(result, base, mod)
        base = _poly_mulmod(base, base, mod)
        e >>= 1
    return result


def _find_roots(poly):
    """All roots of `poly` (list of Fq2 coeffs, low-to-high) in Fq2."""
    q = P * P
    # g = gcd(x^q - x, poly): product of linear factors
    xq = _poly_powmod([Fq2.ZERO, Fq2.ONE], q, poly)
    xq_minus_x = list(xq) + [Fq2.ZERO] * (2 - len(xq))
    xq_minus_x[1] = xq_minus_x[1] - Fq2.ONE
    g = _poly_gcd(poly, xq_minus_x)
    roots: list[Fq2] = []

    import random

    rng = random.Random(0xB15)

    def split(f):
        deg = len(f) - 1
        if deg == 0:
            return
        if deg == 1:
            roots.append(-f[0] * f[1].inv())
            return
        while True:
            delta = Fq2(rng.randrange(P), rng.randrange(P))
            h = _poly_powmod([delta, Fq2.ONE], (q - 1) // 2, f)
            h = list(h) + [Fq2.ZERO] * (1 - len(h) + 0)
            h[0] = h[0] - Fq2.ONE
            d = _poly_gcd(f, h)
            if 0 < len(d) - 1 < deg:
                split(d)
                split(_poly_divexact(f, d))
                return

    split(g)
    return roots


def _poly_divexact(a, b):
    a = list(a)
    out = [Fq2.ZERO] * (len(a) - len(b) + 1)
    inv_lead = b[-1].inv()
    for i in range(len(out) - 1, -1, -1):
        c = a[i + len(b) - 1] * inv_lead
        out[i] = c
        for j in range(len(b)):
            a[i + j] = a[i + j] - c * b[j]
    return out


def derive_iso_candidates():
    """All normalized 3-isogenies E' -> E2 as rational-map coefficients.

    Returns a list of (x_num, x_den, y_num, y_den) polynomial coefficient
    lists (low-to-high degree, Fq2).  Exactly one candidate composes with
    SSWU/clear_cofactor into the standard hash-to-curve; it is selected by
    `_ISO_SELECTOR` (pinned by matching real deposit signatures).
    """
    A, B = A_PRIME, B_PRIME
    # 3-division polynomial of E': ψ₃(x) = 3x⁴ + 6Ax² + 12Bx − A²
    psi3 = [-(A * A), B.scale(12), A.scale(6), Fq2.ZERO, Fq2(3, 0)]
    kernels = _find_roots(psi3)
    candidates = []
    for x0 in kernels:
        # Vélu for the order-3 subgroup {O, (x0,±y0)}:
        gx = x0.square().scale(3) + A
        gy2 = (x0.square() + A) * x0 + B  # y0² (y0 itself may live in Fq4)
        v = gx.scale(2)
        w = gy2.scale(4) + x0 * v
        # φ_x = x + v/(x−x0) + u/(x−x0)² with u = 4y0²
        #     = [x(x−x0)² + v(x−x0) + u] / (x−x0)²
        u_ = gy2.scale(4)
        # numerator: x³ − 2x0x² + x0²x + vx − vx0 + u
        x_num = [
            u_ - v * x0,
            x0.square() + v,
            -(x0.scale(2)),
            Fq2.ONE,
        ]
        x_den = [x0.square(), -(x0.scale(2)), Fq2.ONE]
        # normalized: y' = y · dφ/dx.  φ' = [x_num' · x_den − x_num · x_den']/x_den²
        xn_d = [x_num[1], x_num[2].scale(2), x_num[3].scale(3)]  # derivative
        xd_d = [x_den[1], x_den[2].scale(2)]
        num = _poly_sub(
            _poly_mul(xn_d, x_den), _poly_mul(x_num, xd_d)
        )
        y_num = num
        y_den = _poly_mul(x_den, x_den)
        # image curve: A* = A − 5v, B* = B − 7w
        a_star = A - v.scale(5)
        b_star = B - w.scale(7)
        # isomorphism (x,y) → (c²x, c³y) taking (A*, B*) → (0, 4(1+u));
        # requires A* == 0 and c⁶ = B2/B*.
        if not a_star.is_zero():
            continue
        target = cv.B2 * b_star.inv()
        for c in _all_sixth_roots(target):
            c2, c3 = c.square(), c.square() * c
            cand = (
                [k * c2 for k in x_num],
                list(x_den),
                [k * c3 for k in y_num],
                list(y_den),
            )
            candidates.append(cand)
    return candidates


def _poly_mul(a, b):
    res = [Fq2.ZERO] * (len(a) + len(b) - 1)
    for i, ai in enumerate(a):
        for j, bj in enumerate(b):
            res[i + j] = res[i + j] + ai * bj
    return res


def _poly_sub(a, b):
    n = max(len(a), len(b))
    a = list(a) + [Fq2.ZERO] * (n - len(a))
    b = list(b) + [Fq2.ZERO] * (n - len(b))
    return [x - y for x, y in zip(a, b)]


def _all_sixth_roots(t: Fq2) -> list[Fq2]:
    """All c with c⁶ = t: roots of z⁶ − t via the generic root finder."""
    poly = [-t] + [Fq2.ZERO] * 5 + [Fq2.ONE]
    return _find_roots(poly)


# Pinned 3-isogeny E' -> E2: produced by derive_iso_candidates() and
# selected as the unique candidate under which real deposit-CLI signatures
# verify (see tests/test_bls.py::test_iso_map_matches_derivation).  These are
# OUR derived values (Vélu), not transcribed constants.
_ISO_MAP = (
    # x numerator (degree 3)
    [
        Fq2(0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6,
            0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6),
        Fq2(0x0,
            0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71A),
        Fq2(0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71E,
            0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38D),
        Fq2(0x171D6541FA38CCFAED6DEA691F5FB614CB14B4E7F4E810AA22D6108F142B85757098E38D0F671C7188E2AAAAAAAA5ED1,
            0x0),
    ],
    # x denominator (degree 2, monic)
    [
        Fq2(0x0,
            0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA63),
        Fq2(0xC,
            0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA9F),
        Fq2(0x1, 0x0),
    ],
    # y numerator (degree 4; y' = y · dφx/dx, unreduced — equals the RFC's
    # reduced deg-3 form after cancelling the common (x − x0) factor)
    [
        Fq2(0x1439B899BAF1B35B8FC02D1BFB73BF5231B21E4AF64B0E94DE7B4E7D31A614C6C285C71B6D7A38E357C6555555551445,
            0x0),
        Fq2(0x3DA3B8AFF09777F279251BC2FE54903772E1E26A8D1581C5B23AD6D2E0740E8E8197B422D3BDA12EC25C71C71C71024,
            0x3DA3B8AFF09777F279251BC2FE54903772E1E26A8D1581C5B23AD6D2E0740E8E8197B422D3BDA12EC25C71C71C71024),
        Fq2(0x0,
            0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97C6),
        Fq2(0x171D6541FA38CCFAED6DEA691F5FB614CB14B4E7F4E810AA22D6108F142B85757098E38D0F671C7188E2AAAAAAAA5ED0,
            0x2E3ACA83F47199F5DADBD4D23EBF6C29962969CFE9D0215445AC211E28570AEAE131C71A1ECE38E311C555555554BDB),
        Fq2(0x124C9AD43B6CF79BFBF7043DE3811AD0761B0F37A1E26286B0E977C69AA274524E79097A56DC4BD9E1B371C71C718B10,
            0x0),
    ],
    # y denominator (degree 4)
    [
        Fq2(0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFF966B,
            0x0),
        Fq2(0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA3EB,
            0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA3EB),
        Fq2(0x0,
            0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB),
        Fq2(0x18,
            0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA93),
        Fq2(0x1, 0x0),
    ],
)


def iso_map(x: Fq2, y: Fq2) -> tuple[Fq2, Fq2]:
    x_num, x_den, y_num, y_den = _ISO_MAP

    def ev(poly, at):
        acc = Fq2.ZERO
        for c in reversed(poly):
            acc = acc * at + c
        return acc

    xn, xd = ev(x_num, x), ev(x_den, x)
    yn, yd = ev(y_num, x), ev(y_den, x)
    return (xn * xd.inv(), y * yn * yd.inv())


def clear_cofactor_slow(pt):
    """Effective-cofactor multiplication (RFC 9380 §8.8.2) — the oracle."""
    return cv.g2_mul(pt, H_EFF)


def clear_cofactor(pt):
    """ψ-based fast clearing (Budroni–Pintore, the form RFC 9380 §8.8.2's
    h_eff was chosen to equal exactly):

        [h_eff]Q = [x²-x-1]Q + [x-1]ψ(Q) + ψ²([2]Q)

    Two short scalar muls (127- and 64-bit, x the signed parameter)
    instead of one 636-bit — ~3x less host work per fresh message;
    pinned bit-for-bit against clear_cofactor_slow in tests/test_bls.py."""
    from benchmarks.reference.bls_py.fields import BLS_X

    x = -BLS_X  # signed parameter
    t1 = cv.g2_mul(pt, x * x - x - 1)
    t2 = cv.g2_mul(cv.g2_psi(pt), x - 1)
    t3 = cv.g2_psi(cv.g2_psi(cv.g2_double(pt)))
    return cv.g2_add(cv.g2_add(t1, t2), t3)


def hash_to_g2(msg: bytes, dst: bytes = DST_G2):
    """Full hash_to_curve: two field elements, two SSWU points, iso, add,
    clear cofactor."""
    u0, u1 = hash_to_field_fq2(msg, 2, dst)
    q0 = iso_map(*sswu(u0))
    q1 = iso_map(*sswu(u1))
    return clear_cofactor(cv.g2_add(q0, q1))
