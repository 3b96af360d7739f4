"""BLS12-381 arithmetic for the benchmark: traffic generation and the
plain reference. Independent of the system under test: plain Python
integers, published constants only.

Points are Jacobian triples (X, Y, Z) over Fp (ints) or Fp2 (pairs
(c0, c1) meaning c0 + c1*u, u^2 = -1); Z == zero is the point at
infinity. Affine points are pairs (x, y) or None for infinity.

Sources: the curve parameters of BLS12-381 (Bowe, "BLS12-381: New
zk-SNARK Elliptic Curve Construction"); hash-to-G2 is RFC 9380 suite
BLS12381G2_XMD:SHA-256_SSWU_RO_ with the Ethereum consensus DST; the
formulas are dbl-2009-l, add-2007-bl and madd-2007-bl of the Explicit
Formulas Database, and the cofactor clearing of RFC 9380 section 8.8.2.
"""

from __future__ import annotations

import hashlib

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
X_PARAM = -0xD201000000010000  # the BLS parameter x (negative)

G1_GEN = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
)
B2 = (4, 4)
ETH_DST = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"


# ------------------------------------------------------------------ fields


class Fp:
    zero = 0
    one = 1

    @staticmethod
    def add(a, b):
        return (a + b) % P

    @staticmethod
    def sub(a, b):
        return (a - b) % P

    @staticmethod
    def neg(a):
        return -a % P

    @staticmethod
    def mul(a, b):
        return a * b % P

    @staticmethod
    def sqr(a):
        return a * a % P

    @staticmethod
    def small(a, k):
        return a * k % P

    @staticmethod
    def inv(a):
        return pow(a, -1, P)

    @staticmethod
    def is_zero(a):
        return a == 0


class Fp2:
    zero = (0, 0)
    one = (1, 0)

    @staticmethod
    def add(a, b):
        return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)

    @staticmethod
    def sub(a, b):
        return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)

    @staticmethod
    def neg(a):
        return (-a[0] % P, -a[1] % P)

    @staticmethod
    def mul(a, b):
        t0 = a[0] * b[0]
        t1 = a[1] * b[1]
        return ((t0 - t1) % P, ((a[0] + a[1]) * (b[0] + b[1]) - t0 - t1) % P)

    @staticmethod
    def sqr(a):
        return ((a[0] + a[1]) * (a[0] - a[1]) % P, 2 * a[0] * a[1] % P)

    @staticmethod
    def small(a, k):
        return (a[0] * k % P, a[1] * k % P)

    @staticmethod
    def inv(a):
        t = pow((a[0] * a[0] + a[1] * a[1]) % P, -1, P)
        return (a[0] * t % P, -a[1] * t % P)

    @staticmethod
    def is_zero(a):
        return a[0] == 0 and a[1] == 0


def fp_sqrt(a: int) -> int | None:
    r = pow(a, (P + 1) // 4, P)
    return r if r * r % P == a % P else None


def fp2_pow(a, e: int):
    out = Fp2.one
    for bit in bin(e)[2:]:
        out = Fp2.sqr(out)
        if bit == "1":
            out = Fp2.mul(out, a)
    return out


def fp2_sqrt(a):
    """A square root of ``a`` in Fp2, or None (the complex method)."""
    a0, a1 = a[0] % P, a[1] % P
    if a1 == 0:
        r = fp_sqrt(a0)
        if r is not None:
            return (r, 0)
        r = fp_sqrt(-a0 % P)
        return (0, r) if r is not None else None
    d = fp_sqrt((a0 * a0 + a1 * a1) % P)
    if d is None:
        return None
    half = pow(2, -1, P)
    for dd in (d, -d % P):
        x0 = fp_sqrt((a0 + dd) * half % P)
        if x0 is not None and x0 != 0:
            x1 = a1 * pow(2 * x0, -1, P) % P
            if Fp2.sqr((x0, x1)) == (a0, a1):
                return (x0, x1)
    return None


def fp2_sgn0(a) -> int:
    return (a[0] & 1) | (int(a[0] == 0) & (a[1] & 1))


# ------------------------------------------------------------ curve points


def jac(aff, F):
    """Affine (or None) to Jacobian."""
    if aff is None:
        return (F.one, F.one, F.zero)
    return (aff[0], aff[1], F.one)


def is_inf(p, F) -> bool:
    return F.is_zero(p[2])


def dbl(p, F):
    X, Y, Z = p
    if F.is_zero(Z) or F.is_zero(Y):
        return (F.one, F.one, F.zero)
    A = F.sqr(X)
    B = F.sqr(Y)
    C = F.sqr(B)
    D = F.small(F.sub(F.sub(F.sqr(F.add(X, B)), A), C), 2)
    E = F.small(A, 3)
    X3 = F.sub(F.sqr(E), F.small(D, 2))
    Y3 = F.sub(F.mul(E, F.sub(D, X3)), F.small(C, 8))
    Z3 = F.small(F.mul(Y, Z), 2)
    return (X3, Y3, Z3)


def add(p, q, F):
    """Jacobian + Jacobian (complete for equal and opposite inputs)."""
    if F.is_zero(p[2]):
        return q
    if F.is_zero(q[2]):
        return p
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    Z1Z1 = F.sqr(Z1)
    Z2Z2 = F.sqr(Z2)
    U1 = F.mul(X1, Z2Z2)
    U2 = F.mul(X2, Z1Z1)
    S1 = F.mul(F.mul(Y1, Z2), Z2Z2)
    S2 = F.mul(F.mul(Y2, Z1), Z1Z1)
    H = F.sub(U2, U1)
    r = F.small(F.sub(S2, S1), 2)
    if F.is_zero(H):
        if F.is_zero(r):
            return dbl(p, F)
        return (F.one, F.one, F.zero)
    I = F.sqr(F.small(H, 2))
    J = F.mul(H, I)
    V = F.mul(U1, I)
    X3 = F.sub(F.sub(F.sqr(r), J), F.small(V, 2))
    Y3 = F.sub(F.mul(r, F.sub(V, X3)), F.small(F.mul(S1, J), 2))
    Z3 = F.mul(F.sub(F.sub(F.sqr(F.add(Z1, Z2)), Z1Z1), Z2Z2), H)
    return (X3, Y3, Z3)


def madd(p, q, F):
    """Jacobian + affine (``q`` a pair, never infinity)."""
    X1, Y1, Z1 = p
    if F.is_zero(Z1):
        return (q[0], q[1], F.one)
    Z1Z1 = F.sqr(Z1)
    U2 = F.mul(q[0], Z1Z1)
    S2 = F.mul(F.mul(q[1], Z1), Z1Z1)
    H = F.sub(U2, X1)
    r = F.small(F.sub(S2, Y1), 2)
    if F.is_zero(H):
        if F.is_zero(r):
            return dbl(p, F)
        return (F.one, F.one, F.zero)
    HH = F.sqr(H)
    I = F.small(HH, 4)
    J = F.mul(H, I)
    V = F.mul(X1, I)
    X3 = F.sub(F.sub(F.sqr(r), J), F.small(V, 2))
    Y3 = F.sub(F.mul(r, F.sub(V, X3)), F.small(F.mul(Y1, J), 2))
    Z3 = F.sub(F.sub(F.sqr(F.add(Z1, H)), Z1Z1), HH)
    return (X3, Y3, Z3)


def neg(p, F):
    return (p[0], F.neg(p[1]), p[2])


def mul(p, k: int, F):
    """[k]p by double-and-add (k may be negative)."""
    if k < 0:
        return mul(neg(p, F), -k, F)
    acc = (F.one, F.one, F.zero)
    for bit in bin(k)[2:] if k else "":
        acc = dbl(acc, F)
        if bit == "1":
            acc = add(acc, p, F)
    return acc


def to_affine(p, F):
    if F.is_zero(p[2]):
        return None
    zi = F.inv(p[2])
    zi2 = F.sqr(zi)
    return (F.mul(p[0], zi2), F.mul(F.mul(p[1], zi2), zi))


def batch_affine(points, F):
    """Jacobian points to affine with one inversion (Montgomery's trick)."""
    out = [None] * len(points)
    live = [i for i, p in enumerate(points) if not F.is_zero(p[2])]
    if not live:
        return out
    prefix = []
    acc = F.one
    for i in live:
        prefix.append(acc)
        acc = F.mul(acc, points[i][2])
    inv = F.inv(acc)
    for j in range(len(live) - 1, -1, -1):
        i = live[j]
        zi = F.mul(inv, prefix[j])
        inv = F.mul(inv, points[i][2])
        zi2 = F.sqr(zi)
        X, Y, _ = points[i]
        out[i] = (F.mul(X, zi2), F.mul(F.mul(Y, zi2), zi))
    return out


def on_curve(aff, F, b) -> bool:
    if aff is None:
        return True
    x, y = aff
    return F.sqr(y) == F.add(F.mul(F.sqr(x), x), b)


def eq(p, q, F) -> bool:
    """Equality of Jacobian points without inversions."""
    pi, qi = F.is_zero(p[2]), F.is_zero(q[2])
    if pi or qi:
        return pi and qi
    z1z1, z2z2 = F.sqr(p[2]), F.sqr(q[2])
    if F.mul(p[0], z2z2) != F.mul(q[0], z1z1):
        return False
    return F.mul(F.mul(p[1], q[2]), z2z2) == F.mul(F.mul(q[1], p[2]), z1z1)


# ---------------------------------------------------------- G2 endomorphism

_XI = (1, 1)
_PSI_CX = Fp2.inv(fp2_pow(_XI, (P - 1) // 3))
_PSI_CY = Fp2.inv(fp2_pow(_XI, (P - 1) // 2))


def _conj(a):
    return (a[0], -a[1] % P)


def psi(p):
    """The untwist-Frobenius-twist endomorphism on Jacobian G2 points."""
    X, Y, Z = p
    return (Fp2.mul(_conj(X), _PSI_CX), Fp2.mul(_conj(Y), _PSI_CY), _conj(Z))


def clear_cofactor_g2(p):
    """[h_eff]p (RFC 9380 section 8.8.2, Budroni-Pintore):
    (x^2 - x - 1)p + (x - 1)psi(p) + psi^2(2p)."""
    x = X_PARAM
    t0 = mul(p, x * x - x - 1, Fp2)
    t1 = psi(mul(p, x - 1, Fp2))
    t2 = psi(psi(dbl(p, Fp2)))
    return add(add(t0, t1, Fp2), t2, Fp2)


# ------------------------------------------------------------- hash to G2

_ISO_XNUM = (
    (0x05C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6,
     0x05C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6),
    (0, 0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71A),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71E,
     0x08AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38D),
    (0x171D6541FA38CCFAED6DEA691F5FB614CB14B4E7F4E810AA22D6108F142B85757098E38D0F671C7188E2AAAAAAAA5ED1, 0),
)
_ISO_XDEN = (
    (0, 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA63),
    (0xC, 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA9F),
    (1, 0),
)
_ISO_YNUM = (
    (0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706,
     0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706),
    (0, 0x05C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97BE),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71C,
     0x08AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38F),
    (0x124C9AD43B6CF79BFBF7043DE3811AD0761B0F37A1E26286B0E977C69AA274524E79097A56DC4BD9E1B371C71C718B10, 0),
)
_ISO_YDEN = (
    (0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB),
    (0, 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA9D3),
    (0x12, 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA99),
    (1, 0),
)
_SSWU_A = (0, 240)
_SSWU_B = (1012, 1012)
_SSWU_Z = (P - 2, P - 1)


def expand_message_xmd(msg: bytes, dst: bytes, n: int) -> bytes:
    """RFC 9380 section 5.3.1 with SHA-256."""
    ell = (n + 31) // 32
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(bytes(64) + msg + n.to_bytes(2, "big") + b"\x00"
                        + dst_prime).digest()
    out = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, ell + 1):
        x = bytes(a ^ b for a, b in zip(b0, out[-1]))
        out.append(hashlib.sha256(x + bytes([i]) + dst_prime).digest())
    return b"".join(out)[:n]


def hash_to_field_fp2(msg: bytes, count: int, dst: bytes) -> list:
    u = expand_message_xmd(msg, dst, count * 128)
    return [(int.from_bytes(u[128 * i:128 * i + 64], "big") % P,
             int.from_bytes(u[128 * i + 64:128 * i + 128], "big") % P)
            for i in range(count)]


def _horner(coeffs, x):
    acc = coeffs[-1]
    for c in reversed(coeffs[:-1]):
        acc = Fp2.add(Fp2.mul(acc, x), c)
    return acc


def map_to_curve_g2(u):
    """Simplified SWU onto the isogenous curve, then the 3-isogeny to
    E2. Returns a Jacobian point of E2 (not yet in G2)."""
    u2 = Fp2.sqr(u)
    zu2 = Fp2.mul(_SSWU_Z, u2)
    tv1 = Fp2.add(Fp2.sqr(zu2), zu2)
    if Fp2.is_zero(tv1):
        x1 = Fp2.mul(_SSWU_B, Fp2.inv(Fp2.mul(_SSWU_Z, _SSWU_A)))
    else:
        x1 = Fp2.mul(Fp2.mul(Fp2.neg(_SSWU_B), Fp2.inv(_SSWU_A)),
                     Fp2.add(Fp2.one, Fp2.inv(tv1)))
    gx1 = Fp2.add(Fp2.mul(Fp2.add(Fp2.sqr(x1), _SSWU_A), x1), _SSWU_B)
    y = fp2_sqrt(gx1)
    x = x1
    if y is None:
        x = Fp2.mul(zu2, x1)
        gx2 = Fp2.add(Fp2.mul(Fp2.add(Fp2.sqr(x), _SSWU_A), x), _SSWU_B)
        y = fp2_sqrt(gx2)
    if fp2_sgn0(u) != fp2_sgn0(y):
        y = Fp2.neg(y)
    xn, xd = _horner(_ISO_XNUM, x), _horner(_ISO_XDEN, x)
    yn, yd = _horner(_ISO_YNUM, x), _horner(_ISO_YDEN, x)
    if Fp2.is_zero(xd) or Fp2.is_zero(yd):
        return (Fp2.one, Fp2.one, Fp2.zero)
    return (Fp2.mul(xn, Fp2.inv(xd)), Fp2.mul(y, Fp2.mul(yn, Fp2.inv(yd))),
            Fp2.one)


def hash_to_g2(msg: bytes, dst: bytes = ETH_DST):
    """RFC 9380 hash_to_curve for G2: an affine point of G2."""
    u0, u1 = hash_to_field_fp2(msg, 2, dst)
    q = add(map_to_curve_g2(u0), map_to_curve_g2(u1), Fp2)
    return to_affine(clear_cofactor_g2(q), Fp2)


# ----------------------------------------------------- fixed-base tables


class FixedBase:
    """[k]B for a fixed affine point B and k < 2**bits: a table of
    [d * 2**(w*i)]B for every window i and digit d, so each product is
    at most ceil(bits / w) - 1 mixed additions."""

    def __init__(self, base_affine, F, bits: int, w: int = 7):
        self.F, self.w = F, w
        self.windows = -(-bits // w)
        rows = []
        step = jac(base_affine, F)
        for _ in range(self.windows):
            acc = (F.one, F.one, F.zero)
            row = []
            for _ in range((1 << w) - 1):
                acc = add(acc, step, F)
                row.append(acc)
            rows.append(row)
            step = add(acc, step, F)
        flat = batch_affine([p for row in rows for p in row], F)
        n = (1 << w) - 1
        self.table = [flat[i * n:(i + 1) * n] for i in range(self.windows)]

    def mul(self, k: int):
        """[k]B as a Jacobian point (k >= 0, k < 2**bits)."""
        F, w, mask = self.F, self.w, (1 << self.w) - 1
        acc = (F.one, F.one, F.zero)
        i = 0
        while k:
            d = k & mask
            if d:
                acc = madd(acc, self.table[i][d - 1], F)
            k >>= w
            i += 1
        return acc


# ------------------------------------------------------- encodings


def fp_bytes(a: int) -> bytes:
    return a.to_bytes(48, "big")


def g1_bytes(aff) -> bytes:
    """x || y, 96 bytes big-endian (the uncompressed coordinates)."""
    return fp_bytes(aff[0]) + fp_bytes(aff[1])


def g2_bytes(aff) -> bytes:
    """x.c0 || x.c1 || y.c0 || y.c1, 192 bytes big-endian."""
    (x0, x1), (y0, y1) = aff
    return fp_bytes(x0) + fp_bytes(x1) + fp_bytes(y0) + fp_bytes(y1)


def g2_from_bytes(b: bytes):
    c = [int.from_bytes(b[i:i + 48], "big") for i in range(0, 192, 48)]
    return ((c[0], c[1]), (c[2], c[3]))
